"""`phi4flash-reasonctx-32`'s readings that have no per-layer entry yet
(the list is full: ROADMAP W13), by hand from one traced run on the chip:

    chiprun --timeout 3000 -- python scripts/phi4flash_by_hand.py \
        --seed 7 --trace-start 14

Runs the cell as `benchmarks/run.py --trace 1` does (the same runner,
replica, check, warm-up, load and window), then reads the trace with the
benchmark's own reducers under the arguments PERF.md section 7 gives for
each waiting reading: `shared_kv_hbm_pct`, `cross_attn_time_pct`,
`gmu_time_pct`, `attn_diff_time_pct`, `prefill_self_only_pct`; beside
them the shares of the other scopes the programs name, each program's
executions and median device time, and the engine's counters over the
traced steps. ``--trace-start`` moves the six traced seconds inside the
window (the benchmark traces seconds 2 to 8, where a closed loop's
callers have all just been admitted and nothing is prefilled); one JSON
line, the last one printed. No entry, reducer or file of the benchmark
is touched.
"""

import argparse
import importlib
import json
import os
import statistics
import sys
import time

T_START = time.time()
sys.path.insert(0, ".")

CELL = "phi4flash-reasonctx-32"
# name -> (reducer, arguments): section 7's waiting readings first.
READINGS = {
    "shared_kv_hbm_pct": ("hbm_share", {
        "scopes": ["attn:full"], "program": "hybrid_decode",
        "bytes_fn": "shared_kv_bytes_per_decode_step"}),
    "cross_attn_time_pct": ("program_scope_share", {
        "scopes": ["attn:full/cross"], "over": "busy"}),
    "gmu_time_pct": ("program_scope_share", {
        "scopes": ["gmu:gate", "gmu:out"], "over": "busy"}),
    "attn_diff_time_pct": ("program_scope_share", {
        "scopes": ["attn:diff"], "over": "busy"}),
    "prefill_self_only_pct": ("engine_ratio", {
        "num": ["prefill_self_only_chunks"], "den": ["prefill_programs"],
        "scale": 100.0}),
    "self_attn_time_pct": ("program_scope_share", {
        "scopes": ["attn:full/self"], "over": "busy"}),
    "dense_ffn_time_pct": ("program_scope_share", {
        "scopes": ["ffn:dense"], "over": "busy"}),
    "ssm_update_time_pct": ("program_scope_share", {
        "scopes": ["ssm:update"], "over": "busy"}),
    "ssm_scan_time_in_prefill_pct": ("program_scope_share", {
        "scopes": ["ssm:scan"], "over": "busy", "program": "hybrid_prefill"}),
    "window_attn_time_in_prefill_pct": ("program_scope_share", {
        "scopes": ["attn:window"], "over": "busy",
        "program": "hybrid_prefill"}),
    "full_attn_time_in_decode_pct": ("program_scope_share", {
        "scopes": ["attn:full"], "over": "busy", "program": "hybrid_decode"}),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace-start", type=float, default=14.0)
    ap.add_argument("--rehearse", help="a listing of benchmarks/tests, with "
                    "--workload: the same on the CPU, to try this script")
    ap.add_argument("--workload", default=CELL)
    args = ap.parse_args()
    args.trace = 1

    from benchmarks import run, traceread
    from benchmarks.runners import common

    plan = common.trace_plan

    def later(cell, run_args):
        return {**plan(cell, run_args), "start_s": args.trace_start}

    common.trace_plan = later
    listing, cell, conf, traffic = run.load_cell(args.workload, args.rehearse)
    runner = importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    measured = runner.run(cell, conf, traffic, args, T_START)
    path = traceread.find_trace_file(measured["trace_dir"])
    events = traceread.read_events(path)
    ctx = {"events": events, "counters": measured["counters"],
           "device": measured["device"], "config": conf, "traffic": traffic}
    out = {"correct": measured["correct"], "seed": args.seed,
           "trace_start_s": args.trace_start,
           "end_to_end_traced": measured["end_to_end"]}
    for name, (reducer, kw) in READINGS.items():
        module = importlib.import_module(f"benchmarks.reducers.{reducer}")
        out[name] = module.reduce(ctx, **kw)
    accepted, _, breakdown = run.per_layer_metrics(
        listing, cell, measured, conf, traffic
    )
    out["accepted"] = {k: v["value"] for k, v in accepted.items()}
    busy, window = traceread.busy_and_window(events)
    out["busy_s"], out["window_s"] = busy, window
    programs = {}
    for dev in traceread.devices(events):
        for p in traceread.select(events, dev, traceread.PROGRAMS):
            programs.setdefault(p.name, []).append(p.dur)
    out["programs"] = {
        name: {"n": len(durs), "median_ms": 1e3 * statistics.median(durs),
               "sum_s": sum(durs)}
        for name, durs in sorted(programs.items())
    }
    out["traced_counters"] = measured["counters"]["engine"].get("traced")
    out["device_ops"] = breakdown["device_ops"]
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/phi4flash_by_hand_{args.seed}.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
