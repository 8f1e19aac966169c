#!/usr/bin/env python3
"""Find an open-loop mix's knee: the highest rate the system sustains.

    python3 benchmarks/sweep.py --workload chat-open --rates 3.5 4 4.5 5 5.5 6 6.5 7 --seconds 20

One replica, started once; then the cell's own schedule offered at each
rate in turn (the same unit gaps and length pairs, scaled in time), each
run to its end before the next starts. A rate is sustained when the
requests completed keep pace with the requests sent: at the window's end no more are sent
and unfinished than the engine has slots (<= max_batch), so that none is
waiting behind a full batch. Prints one JSON line per rate; the cell's file then
gets ``rate_rps`` = 0.8 x the highest sustained rate, by hand, with the
sweep recorded beside it (README). Run once when a cell is defined, not
in every check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", metavar="LISTING")
    args = ap.parse_args()

    from benchmarks import loadgen, run as bench
    from benchmarks.runners import serve as runner

    _, cell, conf, traffic = bench.load_cell(args.workload, args.rehearse)
    if "rate_rps" not in traffic:
        sys.exit("only an open-loop mix has a rate to sweep")
    slots = conf["engine"]["max_batch"]
    vocab = conf["vocab_size"]
    with runner.replica(cell, conf, args.seed, bool(args.rehearse)) as (
        handle, port, _, _
    ):
        first = loadgen.build(traffic, args.seed, args.seconds)
        runner._warm(port, traffic, first, args.seed, vocab)
        for rate in args.rates:
            open_at = (time.time() + traffic["ramp_s"]
                       + runner.GENERATOR_START_S)
            requests = loadgen.offer_from_own_process(
                port, traffic, args.seed, vocab, args.seconds, open_at, rate
            )
            inside = [r for r in requests if r.in_window]
            done = [r for r in inside if r.ok and r.done_s is not None
                    and r.done_s < args.seconds]
            unfinished = sum(
                1 for r in requests if r.sent_s is not None
                and (r.done_s is None or r.done_s >= args.seconds)
            )
            ttft = loadgen.ttfts_ms(requests)
            gaps = loadgen.token_gaps_ms(requests, 0.0, args.seconds)
            print(json.dumps({
                "rate_rps": rate,
                "sent_in_window": len(inside),
                "completed_in_window": len(done),
                "unfinished_at_end": unfinished,
                "sustained": unfinished <= slots,
                "failed": sum(1 for r in requests if not r.ok),
                "ttft_p50_ms": loadgen.percentile(ttft, 50),
                "ttft_p90_ms": loadgen.percentile(ttft, 90),
                "itl_p50_ms": loadgen.percentile(gaps, 50),
                "itl_p90_ms": loadgen.percentile(gaps, 90),
                "tokens_per_s": loadgen.completed_tokens(
                    requests, 0.0, args.seconds) / args.seconds,
                "engine": runner.call(handle, "counters")["engine"],
            }), flush=True)
            time.sleep(1.0)


if __name__ == "__main__":
    main()
