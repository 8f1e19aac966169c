"""The deployment that ``runners/serve_model.py`` runs: ``LLMServer``
itself around a model that is not Llama-shaped (``server.py`` builds
``BenchLLMServer`` from ``modelcfg.llama_config``), with the weights
made by the model's own initialiser in the dtypes they are held in, and
the same methods for the benchmark.

Same engine, same pump, same replica and proxy path as
``build_llm_deployment``: the subclass adds no request handling. What it
adds runs before or after the window (``check``, ``write_program_texts``,
``counters``) or only in a traced run (``start_trace``, ``stop_trace``).
The logits it checks are the timed programs' own, handed over by
``LLMEngine.on_logits``.
"""

from __future__ import annotations

import importlib
import os
import time

from benchmarks.server import BenchLLMServer
from ray_tpu.llm.serve_integration import LLMServer


class BenchModelServer(LLMServer):
    def __init__(self, conf: dict, seed: int):
        self.first_line_at = time.time()
        import jax

        from benchmarks import train_loop

        self._compiles = train_loop.watch_compiles()
        self._conf = conf
        self._model = importlib.import_module(f"benchmarks.models.{conf['model']}")
        engine = dict(conf["engine"])
        cfg = self._model.config(conf, max_seq=engine["max_seq"])
        key = jax.random.fold_in(jax.random.key(seed % (2**31)), seed >> 31)
        # As held, a block at a time: a float32 copy of the expert
        # stacks alone would be 21 GB.
        params = cfg.serving().init_weights(key)
        super().__init__(
            cfg, {**engine, "params": params, "seed": seed % (2**31)}
        )

    # ------------------------------------------------------ before the window
    def _run_tapped(self, prompt: list[int], decode: int) -> dict:
        """One request alone through the engine's own programs: the last
        prompt position's logits, each decode step's for the request's
        slot, every token's routes, and the tokens it generated."""
        import numpy as np

        from ray_tpu.llm.engine import SamplingParams

        eng = self.engine
        seen = []
        eng.on_logits = lambda phase, logits, record: seen.append(
            (phase, logits, record)
        )
        try:
            rid = eng.add_request(prompt, SamplingParams(max_tokens=decode + 1))
            slot, generated = None, None
            while generated is None:
                for fin in eng.step():
                    if fin["request_id"] == rid:
                        generated = fin["tokens"]
                if slot is None:
                    slot = eng.slot_of(rid)
        finally:
            eng.on_logits = None
        prefills = [s for s in seen if s[0].startswith("prefill")]
        decodes = [s for s in seen if s[0] == "decode"]
        if len(decodes) != decode or slot is None:
            raise RuntimeError(
                f"engine made {len(prefills)} prefill and {len(decodes)} "
                f"decode calls for {decode + 1} tokens (slot {slot})"
            )
        n = len(prompt)
        routes = np.concatenate(
            [np.asarray(s[2]["routes"]) for s in prefills], axis=1
        )[:, :n]
        routes = np.concatenate(
            [routes] + [np.asarray(s[2]["routes"])[:, slot: slot + 1]
                        for s in decodes], axis=1,
        )
        logits = [np.asarray(prefills[-1][1])[0, 0]] + [
            np.asarray(s[1])[slot] for s in decodes
        ]
        return {
            "tokens": prompt + generated[:-1], "routes": routes,
            "logits": np.stack(logits), "slot": slot,
            "prefill_calls": len(prefills),
            "states": np.asarray(eng.cache["ssm"][:, slot]),
        }

    def check(self, seed: int, whole_prompt_len: int = 508,
              chunked_prompt_len: int = 1000, decode: int = 4,
              lower: str | None = None) -> dict:
        """A prompt that is prefilled whole and one that goes in chunks,
        then ``decode`` steps each through the pages and the slot's
        state, against the float32 reference's one full pass over the
        same tokens, run block by block so that it fits beside the
        engine: with the system's routes forced on the reference, the
        largest absolute logit difference at the last prompt position
        and at each decoded one; each token's routes against the
        reference's own (how far below its cut the system's choice
        lies); and each Mamba block's state as the slot holds it after
        the last step against the scan's. Runs alone, before any
        request. ``lower`` computes the reference in a lower precision
        (``reference_nemotron_h``), for the reading a limit must fail."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        reference = importlib.import_module(
            f"benchmarks.reference_{self._conf['model']}"
        )
        eng = self.engine
        sizes = reference.for_model(self._conf) | {"lower": lower}
        rng = np.random.default_rng(seed + 11)
        out = {
            "logit_max_abs_err": [], "logit_scale": 0.0, "finite": True,
            "largest_slack": 0.0, "routes_beyond_epsilon": 0,
            "share_routed_otherwise": [], "state_rel_err": 0.0,
            "tokens": 0, "prefill_calls": [],
            "margin_epsilon": self._model.MARGIN_EPSILON,
            "paged_attn_kernel": bool(eng.paged_attn_kernel),
        }
        jitted = {}

        def block_fn(kind, fn):
            # One compiled program per kind of block and sequence length.
            return jitted.setdefault(kind, jax.jit(fn))

        for n in (whole_prompt_len, chunked_prompt_len):
            jitted.clear()
            got = self._run_tapped(
                rng.integers(1, eng.cfg.vocab_size, n).tolist(), decode
            )
            want, record = reference.forward_with_record(
                eng.params, jnp.asarray(got["tokens"], jnp.int32),
                routes=jnp.asarray(got["routes"]),
                rows=list(range(n - 1, n + decode)), block_fn=block_fn,
                **sizes,
            )
            want = np.asarray(want)
            out["logit_max_abs_err"] += [
                float(v) for v in np.abs(got["logits"] - want).max(-1)
            ]
            out["logit_scale"] = max(out["logit_scale"],
                                     float(np.abs(want).max()))
            out["finite"] &= bool(np.isfinite(got["logits"]).all())
            same = (
                np.sort(got["routes"], -1)
                == np.sort(np.asarray(record["routes"]), -1)
            ).all(-1)
            slack = np.asarray(record["slack"])
            out["largest_slack"] = max(out["largest_slack"], float(slack.max()))
            out["routes_beyond_epsilon"] += int(
                (slack > out["margin_epsilon"]).sum()
            )
            out["share_routed_otherwise"].append(float(1.0 - same.mean()))
            ref_states = np.asarray(record["states"])
            diff = np.linalg.norm(
                (got["states"] - ref_states).reshape(len(ref_states), -1),
                axis=-1,
            )
            norm = np.linalg.norm(
                ref_states.reshape(len(ref_states), -1), axis=-1
            )
            out["state_rel_err"] = max(out["state_rel_err"],
                                       float((diff / norm).max()))
            out["tokens"] += n + decode
            out["prefill_calls"].append(got["prefill_calls"])
        return out

    def write_program_texts(self, out_dir: str, traffic: dict) -> dict:
        """The compiled serving programs' text, one file a program, and
        the path of each under the name the trace gives the program: a
        TPU trace names an operation by its HLO instruction without the
        instruction's metadata, and the text has each one's named scope
        (``reducers/program_scope_share.py``). Compiled again from the
        shapes, which the persistent compile cache answers."""
        import jax

        from benchmarks import aot_fit_serve_model

        lowered = aot_fit_serve_model.lowered_programs(
            self._conf, traffic, jax.devices()[0],
            use_kernel=self.engine.paged_attn_kernel,
        )
        paths = {}
        os.makedirs(out_dir, exist_ok=True)
        for lowered_program in lowered.values():
            text = lowered_program.compile().as_text()
            name = text.split(None, 2)[1].rstrip(",")  # "HloModule <name>,"
            paths[name] = os.path.join(out_dir, f"{name}.txt")
            with open(paths[name], "w") as f:
                f.write(text)
        return paths

    # ------------------------------------------------------ around the window
    start_trace = BenchLLMServer.start_trace
    stop_trace = BenchLLMServer.stop_trace
    counters = BenchLLMServer.counters
