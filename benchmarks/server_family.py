"""The deployment that ``runners/serve_family.py`` runs:
``server_model.BenchModelServer`` with what is one family's asked of the
family's module (``benchmarks/models/<model>.py``): its ``check`` (what
to compare of a tapped request) and its ``lowered_programs``. A family is
then a module there and nothing here.

Same engine, same pump, same replica and proxy path; what this adds runs
before the window (``check``, ``write_program_texts``) or brackets a
traced run's trace: the engine's counters as they moved between
``start_trace`` and ``stop_trace`` go to ``counters()["engine"]["traced"]``,
so that a kernel's bytes and operations are those of the steps the trace
holds and not a replica's life (warm-up and check included) averaged.
"""

from __future__ import annotations

import os

from benchmarks.server import BenchLLMServer
from benchmarks.server_model import BenchModelServer


class BenchFamilyServer(BenchModelServer):
    def _run_tapped(self, prompt: list[int], decode: int) -> dict:
        """One request alone through the engine's own programs: the last
        prompt position's logits, each decode step's for the request's
        slot, every token's routes, the tokens it generated, and the
        pages it held (their contents outlive the request: a page is
        not cleared when it is freed)."""
        import numpy as np

        from ray_tpu.llm.engine import SamplingParams

        eng = self.engine
        seen = []
        eng.on_logits = lambda phase, logits, record: seen.append(
            (phase, logits, record)
        )
        try:
            rid = eng.add_request(prompt, SamplingParams(max_tokens=decode + 1))
            req = eng._queue[-1]
            slot, generated, pages = None, None, []
            while generated is None:
                for fin in eng.step():
                    if fin["request_id"] == rid:
                        generated = fin["tokens"]
                if slot is None:
                    slot = eng.slot_of(rid)
                pages = req.pages or pages
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
            "logits": np.stack(logits), "slot": slot, "pages": list(pages),
            "prefill_calls": len(prefills),
        }

    def check(self, seed: int, **kw) -> dict:
        """The family's comparison of tapped requests with its plain
        reference (``benchmarks/models/<model>.py``, ``check``). Runs
        alone, before any request."""
        return self._model.check(self, seed, **kw)

    def write_program_texts(self, out_dir: str, traffic: dict) -> dict:
        """As ``BenchModelServer``'s, of the programs the family's module
        lowers."""
        import jax

        lowered = self._model.lowered_programs(
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
    def start_trace(self, trace_dir: str) -> float:
        self._stats_at_trace = self.engine.stats()
        return BenchLLMServer.start_trace(self, trace_dir)

    def stop_trace(self) -> float:
        then, now = self._stats_at_trace, self.engine.stats()
        self._traced = {
            key: now[key] - then[key] for key in now
            if isinstance(now[key], (int, float))
            and not isinstance(now[key], bool)
        }
        return BenchLLMServer.stop_trace(self)

    def counters(self) -> dict:
        out = BenchLLMServer.counters(self)
        out["engine"]["traced"] = getattr(self, "_traced", None)
        return out
