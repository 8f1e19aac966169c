"""The deployment the serving cells run: ``LLMServer`` itself, with the
weights made by one jitted call and four methods for the benchmark.

Same engine, same pump, same replica and proxy path as
``build_llm_deployment``: the subclass adds no request handling. What it
adds runs before or after the window (``check``, ``counters``) or only in
a traced run (``start_trace``, ``stop_trace``).
"""

from __future__ import annotations

import time
from functools import partial

from ray_tpu.llm.serve_integration import LLMServer


class BenchLLMServer(LLMServer):
    def __init__(self, conf: dict, seed: int):
        self.first_line_at = time.time()
        import jax

        from benchmarks import modelcfg, train_loop
        from ray_tpu.models.llama import init_params

        self._compiles = train_loop.watch_compiles()
        self._conf = conf
        engine = dict(conf["engine"])
        cfg = modelcfg.llama_config(conf, max_seq=engine["max_seq"])
        # The eager initialiser compiles one program per weight shape
        # (PERF.md, PR 21); one jitted call makes the same tree.
        key = jax.random.fold_in(jax.random.key(seed % (2**31)), seed >> 31)
        params = jax.jit(partial(init_params, cfg=cfg))(key)
        super().__init__(
            cfg, {**engine, "params": params, "seed": seed % (2**31)}
        )

    # ------------------------------------------------------ before the window
    def check(self, seed: int, prompt_len: int = 508, decode: int = 4) -> dict:
        """Prefill, then ``decode`` steps through the paged cache, against
        the float32 reference's one full pass over the same tokens:
        largest absolute logit difference at the last prompt position
        and at each decoded one. Runs alone, before any request."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from benchmarks import reference
        from ray_tpu.llm.engine import SamplingParams

        eng = self.engine
        prefill_logits, decode_logits = [], []

        def tap(fn, pick, into):
            def tapped(*args, **kw):
                out = fn(*args, **kw)
                into.append(np.asarray(pick(out)))
                return out

            return tapped

        real_prefill, real_decode = eng._prefill_paged, eng._decode_paged
        n = prompt_len
        eng._prefill_paged = tap(
            real_prefill, lambda out: out[0][0, n - 1], prefill_logits
        )
        eng._decode_paged = tap(real_decode, lambda out: out[1][0], decode_logits)
        try:
            rng = np.random.default_rng(seed + 11)
            prompt = rng.integers(1, eng.cfg.vocab_size, n).tolist()
            (generated,) = eng.generate(
                [prompt], SamplingParams(max_tokens=decode + 1)
            )
        finally:
            eng._prefill_paged, eng._decode_paged = real_prefill, real_decode
        if len(prefill_logits) != 1 or len(decode_logits) != decode:
            raise RuntimeError(
                f"engine made {len(prefill_logits)} prefill and "
                f"{len(decode_logits)} decode calls for {decode + 1} tokens"
            )
        tokens = jnp.asarray([prompt + generated], jnp.int32)
        ref = np.asarray(
            jax.jit(
                partial(reference.forward, **reference.for_model(self._conf))
            )(eng.params, tokens)
        )[0]
        errs = [float(np.abs(prefill_logits[0] - ref[n - 1]).max())]
        for i, got in enumerate(decode_logits):
            errs.append(float(np.abs(got - ref[n + i]).max()))
        finite = bool(
            np.isfinite(prefill_logits[0]).all()
            and all(np.isfinite(d).all() for d in decode_logits)
        )
        return {
            "logit_max_abs_err": errs,
            "logit_scale": float(np.abs(ref[n - 1]).max()),
            "finite": finite,
            "tokens": n + decode,
            "paged_attn_kernel": bool(eng.paged_attn_kernel),
        }

    # ------------------------------------------------------ around the window
    def start_trace(self, trace_dir: str) -> float:
        import jax

        jax.profiler.start_trace(trace_dir)
        return time.time()

    def stop_trace(self) -> float:
        import jax

        jax.profiler.stop_trace()
        return time.time()

    def counters(self) -> dict:
        from benchmarks import train_loop

        return {
            "first_line_at": self.first_line_at,
            "compiles": list(self._compiles),
            "device": train_loop.device_record(),
            "engine": self.engine.stats(),
        }
