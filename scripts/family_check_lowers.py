"""A serving family's check on the chip with the reference computed
otherwise: the readings a limit of `benchmarks/models/<model>.py` has to
fail (its `lower` switches), beside the reading it has to pass.

    chiprun --timeout 3000 -- python scripts/family_check_lowers.py \
        --config laguna-s21-serve1 --seed 7 --lower none weights_e4m3 ...

Builds the configuration's replica object in this process (no cluster:
the check runs alone before any request anyway), runs `check` once a
`lower`, and prints one JSON line each: the check's record and
`check_problems` of it. ``none`` is the check as a benchmark run makes
it. GLM-5.3-Flash's switches (`benchmarks/reference_glm5_next.py`):

    chiprun --timeout 3000 -- python scripts/family_check_lowers.py \
        --config glm53flash-serve1 --seed 7 --skip-whole --skip-long --lower none \
        weights_e4m3 state_bf16 one_decay_a_head unbounded_gate attend_all \
        recent_keys no_pooling no_tail static_h no_sinkhorn one_stream \
        no_clamp no_routed_scaling router_bf16

LongCat-Flash's (`benchmarks/reference_longcat_flash.py`), ~2 min a
switch:

    chiprun --timeout 3000 -- python scripts/family_check_lowers.py \
        --config longcat-flash-omni-serve1 --seed 7 --lower none weights_e4m3 \
        no_identity latent_unscaled shortcut_early router_bf16

Motif-3-Beta's (`benchmarks/reference_motif.py`), ~20 s a switch on the
9,000-token prompt alone:

    chiprun --timeout 3000 -- python scripts/family_check_lowers.py \
        --config motif3beta-serve1 --seed 7 --skip-whole --skip-long --lower none \
        weights_e4m3 no_noise lambda_const window_as_full polynorm_as_silu \
        static_h router_bf16

Phi-4-mini-flash-reasoning's (`benchmarks/reference_phi4flash.py`), the
9,000-token prompt alone:

    chiprun --timeout 3000 -- python scripts/family_check_lowers.py \
        --config phi4miniflash-serve1 --seed 7 --skip-whole --skip-long --lower none \
        weights_e4m3 state_bf16 lam_const window_511 memory_after_gate \
        cross_own_keys
"""

import argparse
import importlib
import json
import sys
import time

sys.path.insert(0, ".")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--lower", nargs="+", default=["none"])
    ap.add_argument(
        "--skip-whole", action="store_true",
        help="leave out the prompt that is prefilled whole (a family whose "
             "check takes whole_prompt_len 0 for that: glm5_next)",
    )
    ap.add_argument(
        "--skip-long", action="store_true",
        help="leave out the prompt past 32,768 tokens (long_prompt_len 0)",
    )
    args = ap.parse_args()

    from benchmarks.server_family import BenchFamilyServer

    with open(f"benchmarks/configs/{args.config}.json") as f:
        conf = json.load(f)
    family = importlib.import_module(f"benchmarks.models.{conf['model']}")
    server = BenchFamilyServer(conf, args.seed)
    sizes = conf.get("check", {})
    if args.skip_whole:
        sizes = {**sizes, "whole_prompt_len": 0}
    if args.skip_long:
        sizes = {**sizes, "long_prompt_len": 0}
    for lower in args.lower:
        began = time.time()
        record = server.check(
            args.seed, **sizes, lower=None if lower == "none" else lower,
        )
        print(json.dumps({
            "config": args.config, "seed": args.seed, "lower": lower,
            "seconds": round(time.time() - began, 1),
            "problems": family.check_problems(record), "check": record,
        }), flush=True)
    stats = server.engine.stats()
    print(json.dumps({key: stats[key] for key in (
        "param_bytes", "pool_bytes", "state_bytes") if key in stats}
        | {k: v for k, v in stats.items()
           if k.startswith(("window", "latent", "index"))}))


if __name__ == "__main__":
    main()
