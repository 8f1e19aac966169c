"""LongCat-Flash's double layer alone, on the chip, at the cell's widths
(longcat-flash-omni-serve1: two latent-attention sublayers of 64 heads,
two dense FFNs of 12,288, 16 of 512 experts behind a 768-wide router
with 256 identity outputs), one layer, a 2,048-token prompt prefilled
whole and four decode steps: which part of a program is how far from the
plain reference, and what it takes.

    chiprun -- python scripts/longcat_layer.py [seed [configuration file]]

Prints a JSON line a variant of the prefill program: the attention by
the kernel or by XLA's blockwise form, the experts by the kernels or by
XLA's forms (`moe.chip` told "cpu"), each against
`benchmarks/reference_longcat_flash.py` with the system's routes forced:
the largest |logit| difference at the last position, each attention
sublayer's cells against the reference's (sublayer 1's cells depend on
sublayer 0's attention and the first dense FFN and NOT on the experts:
the shortcut joins behind them), the share of tokens routed otherwise,
and the program's time. Then the decode program the same way.
"""

import json
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

from benchmarks import reference_longcat_flash as reference  # noqa: E402
from benchmarks.models import longcat_flash as family  # noqa: E402
from ray_tpu._private import chip  # noqa: E402
from ray_tpu.llm import latent_kv  # noqa: E402
from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.longcat_flash import init_params  # noqa: E402

TOKENS, PAGE, DECODE = 2048, 64, 4


def main():
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 61
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    path = (sys.argv[2] if len(sys.argv) > 2
            else "benchmarks/configs/longcat-flash-omni-serve1.json")
    with open(path) as f:
        conf = {**json.load(f), "num_layers": 1}
    cfg = family.config(conf, max_seq=TOKENS + PAGE)
    sizes = reference.for_model(conf)
    params = init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)
    n_pages = TOKENS // PAGE
    prompt = rng.integers(1, cfg.vocab_size, TOKENS - 7)
    n = len(prompt)
    tokens = np.zeros((1, TOKENS), np.int32)
    tokens[0, :n] = prompt
    pages = np.arange(1, n_pages + 1, dtype=np.int32)
    tables = np.full((1, n_pages + 1), -1, np.int32)
    tables[0, :n_pages] = pages
    tables[0, n_pages] = n_pages + 1

    def against(logits, cache, routes, all_tokens, rows):
        want, record = reference.forward_with_record(
            params, jnp.asarray(all_tokens, jnp.int32),
            routes=jnp.asarray(routes), rows=rows,
            block_fn=lambda kind, fn: jax.jit(fn), **sizes,
        )
        held = len(all_tokens)
        cells = np.asarray(
            cache["latent"][:, 1: n_pages + 2].astype(jnp.float32)
        ).reshape(2, -1, cfg.cell_width)[:, :held, : cfg.latent_dim]
        ref = np.asarray(record["latents"])
        same = (np.sort(np.asarray(routes), -1)
                == np.sort(np.asarray(record["routes"]), -1)).all(-1)
        return {
            "logit_max_abs_err": float(
                np.abs(np.asarray(logits) - np.asarray(want)).max()
            ),
            "logit_scale": float(np.abs(np.asarray(want)).max()),
            "latent_rel_err": [
                float(np.linalg.norm(cells[a] - ref[a]) / np.linalg.norm(ref[a]))
                for a in range(2)
            ],
            "routed_otherwise": float(1.0 - same.mean()),
            "largest_slack": float(np.asarray(record["slack"]).max()),
        }

    real = moe.chip
    for attend, experts in (("kernel", "kernels"), ("xla", "kernels"),
                            ("kernel", "xla"), ("xla", "xla")):
        moe.chip = real if experts == "kernels" else types.SimpleNamespace(
            platform=lambda: "cpu"
        )
        latent_kv.prefill_program.cache_clear()
        program = latent_kv.prefill_program(
            cfg, n_pages, n_pages, attend == "kernel"
        )

        def run():
            cache = latent_kv.init_latent_cache(cfg, n_pages + 2, PAGE)
            return program(params, tokens, cache, pages, np.int32(0), np.int32(n))

        jax.block_until_ready(run())
        began = time.perf_counter()
        logits, cache, record = jax.block_until_ready(run())
        ms = 1e3 * (time.perf_counter() - began)
        out = against(
            logits[0], cache, np.asarray(record["routes"])[:, :n], prompt, [n - 1]
        )
        print(json.dumps({"program": "prefill_2048", "attention": attend,
                          "experts": experts, "ms": round(ms, 2), **out}),
              flush=True)
    moe.chip = real

    # The decode program over the last variant's cache (the XLA prefill's
    # cells), by the kernels: one slot of 32 live.
    slots = 32
    block_tables = np.full((slots, n_pages + 1), -1, np.int32)
    block_tables[0] = tables[0]
    active = np.zeros(slots, bool)
    active[0] = True
    routes = [np.asarray(record["routes"])[:, :n]]
    generated, got = [int(np.argmax(np.asarray(logits[0, 0])))], []
    for step in range(DECODE):
        step_tokens = np.zeros((slots, 1), np.int32)
        step_tokens[0, 0] = generated[-1]
        positions = np.zeros(slots, np.int32)
        positions[0] = n + step
        _, step_logits, cache, record = latent_kv.latent_decode(
            params, step_tokens, cache, block_tables, positions, active,
            np.zeros(slots, np.float32), jax.random.key(0), cfg=cfg,
            use_kernel=chip.platform() == "tpu",
        )
        got.append(np.asarray(step_logits[0]))
        routes.append(np.asarray(record["routes"])[:, :1])
        generated.append(int(np.argmax(got[-1])))
    out = against(
        np.stack(got), cache, np.concatenate(routes, 1),
        list(prompt) + generated[:-1], list(range(n, n + DECODE)),
    )
    print(json.dumps({"program": "decode", **out}), flush=True)


if __name__ == "__main__":
    main()
