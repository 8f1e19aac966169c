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

    chiprun -- python scripts/longcat_layer.py expand [groups a step ...]

The expansion alone (`ops/pallas/latent_attention.py latent_expand`, what
a chunk program runs under `mla:expand`) beside the two einsums over the
whole table that it replaced: 128 heads (openPangu), 64 (LongCat) and 16
groups (Motif), tables of 4,096 to 65,536 cells, a 2,048-query chunk at
the table's start, its middle and its end; a JSON line each, ms a call
(calls sent back to back, a few in flight, and waited for together: a
call under ~0.2 ms reads the host's dispatch), the einsums' ms, their
ratio, what the live key blocks' share of the table would make it, and
the live blocks' largest difference from the einsums'. (The einsums
ALONE are no yardstick for the program: jitted by themselves they read
0.41 us a cell at 128 heads, inside `pangu-longdoc-16`'s chunk programs
0.175, where the kernel reads 0.183 with its dead steps: my chip runs,
PR 66, `PERF.md` §6. The kernel's own column is the one to keep.)
"""

import functools
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
EXPAND_GROUPS, EXPAND_TABLES = (128, 64, 16), (4096, 8192, 16384, 65536)


def expand_alone(blocks_of_groups):
    from ray_tpu.ops.pallas.latent_attention import keys_expanded, latent_expand

    rank, width, wide = 512, 640, 128
    key = jax.random.key(66)

    def bf16(k, *shape):
        return jax.random.normal(jax.random.fold_in(key, k), shape).astype(
            jnp.bfloat16
        )

    @jax.jit
    def einsums(cells, w_uk, w_uv):
        return (jnp.einsum("tc,hcd->htd", cells[:, :rank], w_uk),
                jnp.einsum("tc,hcd->htd", cells[:, :rank], w_uv))

    @functools.partial(jax.jit, static_argnames="live")
    def furthest(got, want, live):
        f32 = jnp.float32
        return jnp.abs(got.astype(f32) - want.astype(f32))[:, :live].max()

    def timed(fn, out_bytes):
        in_flight = int(max(1, min(8, 2**31 // out_bytes)))
        jax.block_until_ready(fn())
        began = time.perf_counter()
        for _ in range(3):
            out = None  # the last round's, freed before the next is sent
            out = jax.block_until_ready([fn() for _ in range(in_flight)])
        return 1e3 * (time.perf_counter() - began) / (3 * in_flight), out[-1]

    for groups in EXPAND_GROUPS:
        w_uk = bf16(1, groups, rank, wide) * rank**-0.5
        w_uv = bf16(2, groups, rank, wide) * rank**-0.5
        for table in EXPAND_TABLES:
            cells = bf16(3, table, width)
            out_bytes = 2 * groups * table * wide * 2
            whole_ms, want = timed(lambda: einsums(cells, w_uk, w_uv), out_bytes)
            for start in sorted({0, table // 2, table - TOKENS}):
                live = keys_expanded(start, TOKENS, table)
                at = jnp.int32(start)  # on the device before the clock runs
                for block_groups in blocks_of_groups:
                    ms, got = timed(
                        lambda: latent_expand(
                            cells, w_uk, w_uv, at, n_queries=TOKENS,
                            block_groups=block_groups,
                            interpret=chip.platform() != "tpu",
                        ), out_bytes,
                    )
                    err = max(
                        float(furthest(g, w, live)) for g, w in zip(got, want)
                    )
                    print(json.dumps({
                        "groups": groups, "table": table, "start": start,
                        "block_groups": block_groups, "ms": round(ms, 3),
                        "einsums_ms": round(whole_ms, 3),
                        "of_einsums": round(ms / whole_ms, 3),
                        "live_share": round(live / table, 3),
                        "max_abs_err": err,
                    }), flush=True)
                    del got
            del want


def main():
    device = jax.devices()[0]
    print(json.dumps({"device": device.device_kind,
                      "platform": device.platform}), flush=True)
    if sys.argv[1:2] == ["expand"]:
        return expand_alone([int(a) for a in sys.argv[2:]] or [8])
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 61
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
