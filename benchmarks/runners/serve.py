"""Runner for serving cells: ``serve.run`` of ``BenchLLMServer`` (the
program's ``LLMServer`` with jitted weights) on a replica that leases the
chip, ``serve.start_http()``, and the load generator in this process
against the proxy's port. This process never opens a JAX backend."""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import statistics
import threading
import time

# Engine logits (bf16 weights and activations at use; flash-free dense
# prefill, paged-attention kernel in decode) against the float32
# reference, largest absolute difference on logits of magnitude ~4. See
# runners/train.py for the reasoning; the decode path adds the cache's
# bf16 keys and values.
LOGIT_TOLERANCE = 0.12
# What the load generator's own process needs to start, import and build
# its requests before the schedule's ramp begins.
GENERATOR_START_S = 2.0
# The generator sends within 3-4 ms of the due instant in a quiet run;
# later than this and the host stalled under it (one run of twelve read
# 3.8 s, and a mean TTFT six times the others': my chip runs, PR 24).
LATE_GENERATOR_MS = 50.0


def _warm(port, traffic, requests, seed, vocab):
    """One short request per prompt shape the window will use, through
    the normal path, so that every program (prefill per shape, decode,
    the sampler's small ones) is compiled before the window."""
    from benchmarks import loadgen

    lengths = traffic.get("warm_prompt_lengths") or sorted(
        {r.prompt_len for r in requests}
    )
    warm = [loadgen.Request(10**6 + i, 0.0, n, 3) for i, n in enumerate(lengths)]
    one_at_a_time = {"kind": "closed_loop", "clients": 1, "ramp_s": 0.0,
                     "once": True}
    loadgen.offer(port, one_at_a_time, warm, seed, vocab, seconds=3600.0,
                  drain_s=600.0)
    bad = [r for r in warm if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0].error!r} "
                           f"({bad[0].tokens_out} tokens)")


@contextlib.contextmanager
def replica(cell: dict, conf: dict, seed: int, rehearsal: bool):
    """``serve.run`` of the cell's deployment and the HTTP proxy, up for
    the length of the block; yields the handle, the port and when
    ``serve.run`` was called and returned."""
    import ray_tpu
    from benchmarks.runners import common
    from benchmarks.server import BenchLLMServer
    from ray_tpu import serve

    ray_tpu.init()
    try:
        common.require_chips(cell["chips"], rehearsal)
        called_at = time.time()
        deployment = serve.deployment(
            BenchLLMServer,
            num_replicas=1,
            ray_actor_options={"num_tpus": cell["chips"]},
            max_ongoing_requests=conf["engine"]["max_batch"],
        )
        try:
            handle = serve.run(deployment.bind(conf, seed), timeout_s=1100)
            port = serve.start_http()
            yield handle, port, called_at, time.time()
        finally:
            serve.shutdown()
        common.wait_chip_free()
    finally:
        ray_tpu.shutdown()


def call(handle, method: str, *args, timeout: float = 60.0, **kw):
    return handle.options(method_name=method).remote(*args, **kw).result(
        timeout=timeout
    )


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    from benchmarks import loadgen
    from benchmarks.runners import common

    seconds = args.seconds
    vocab = conf["vocab_size"]
    trace = common.trace_plan(cell["name"], args) if args.trace else None
    with replica(cell, conf, args.seed, bool(args.rehearse)) as (
        handle, port, called_at, ready_at
    ):
        check = call(handle, "check", args.seed, timeout=600,
                     **conf.get("check", {}))
        _warm(port, traffic, loadgen.build(traffic, args.seed, seconds),
              args.seed, vocab)
        # The generator's process needs a moment to start and build.
        opened_at = time.time() + traffic["ramp_s"] + GENERATOR_START_S
        timers = []
        if trace is not None:
            # The replica's methods are called from timer threads; a few
            # seconds of the window are traced.
            begin = opened_at + trace["start_s"] - time.time()
            timers = [
                threading.Timer(begin, call,
                                (handle, "start_trace", trace["dir"])),
                threading.Timer(begin + trace["seconds"], call,
                                (handle, "stop_trace"), {"timeout": 120}),
            ]
            for t in timers:
                t.start()
        try:
            requests = loadgen.offer_from_own_process(
                port, traffic, args.seed, vocab, seconds, opened_at
            )
        finally:
            for t in timers:
                t.cancel()  # no effect on one that has fired
        for t in timers:
            t.join(timeout=180)
        counters = call(handle, "counters")
    # Every request's instants, for reading a run by hand.
    os.makedirs(os.path.join(common.OUT, cell["name"]), exist_ok=True)
    with open(os.path.join(common.OUT, cell["name"],
                           f"requests-{args.seed}.json"), "w") as f:
        json.dump([dataclasses.asdict(r) for r in requests], f)

    return summarize(
        conf, traffic, requests, seconds, check, counters,
        t_start=t_start, called_at=called_at, ready_at=ready_at,
        opened_at=opened_at, trace=trace,
    )


def summarize(conf, traffic, requests, seconds, check, counters, *, t_start,
              called_at, ready_at, opened_at, trace) -> dict:
    from benchmarks import loadgen
    from benchmarks.train_loop import compile_counters

    sent = [r for r in requests if r.sent_s is not None]
    # A mix with a rate has due instants, and its tails are taken over
    # the requests due inside the window. Every request that was sent,
    # in the ramp too, has to come back whole: one that failed, returned
    # another count than its max_tokens or never ended (the client gave
    # up at the drain) makes the run not correct.
    open_loop = "rate_rps" in traffic
    judged = [r for r in sent if r.in_window]
    failed = [r for r in sent if not r.ok]
    problems = []
    if failed:
        never = sum(1 for r in failed if r.error is None and r.done_s is None)
        problems.append(
            f"{len(failed)} of {len(sent)} requests failed, returned another "
            f"count than their max_tokens or never ended ({never}), e.g. "
            f"{failed[0].error!r} {failed[0].tokens_out}/{failed[0].max_tokens}"
        )
    if not check["finite"] or max(check["logit_max_abs_err"]) > LOGIT_TOLERANCE:
        problems.append(
            f"logits differ from the reference by {check['logit_max_abs_err']}"
            f" (tolerance {LOGIT_TOLERANCE})"
        )
    for p in problems:
        print(f"[bench] NOT CORRECT: {p}")

    end_to_end = {"setup_s": opened_at - t_start}
    extra = {}
    gaps = loadgen.token_gaps_ms(requests, 0.0, seconds)
    if open_loop:
        ttft = loadgen.ttfts_ms(requests)
        # Offered end to end too: BENCHMARK.json lists TTFT per-layer
        # while a 30 s window cannot carry its bound (PERF.md), and a
        # benchmark PR with a longer window can list it as data.
        end_to_end["ttft_mean_ms"] = statistics.fmean(ttft)
        end_to_end["ttft_p90_ms"] = loadgen.percentile(ttft, 90)
        end_to_end["itl_p90_ms"] = loadgen.percentile(gaps, 90)
        lags = [(r.sent_s - r.due_s) * 1e3 for r in sent]
        extra = {
            "loadgen_lag_p99_ms": loadgen.percentile(lags, 99),
            "ttft_p50_ms": loadgen.percentile(ttft, 50),
            "ttft_mean_ms": end_to_end["ttft_mean_ms"],
            "ttft_p90_ms": end_to_end["ttft_p90_ms"],
            "itl_p50_ms": loadgen.percentile(gaps, 50),
            "offered_rps": len(judged) / seconds,
            "in_flight_at_end": sum(
                1 for r in sent if r.done_s is None or r.done_s >= seconds
            ),
        }
        if extra["loadgen_lag_p99_ms"] > LATE_GENERATOR_MS:
            print(f"[bench] WARNING: the load generator sent late (p99 "
                  f"{extra['loadgen_lag_p99_ms']:.0f} ms): its host stalled, "
                  "and this run's tails are partly the generator's")
    # All the tokens the window processed, over all of the window.
    end_to_end["serve_tokens_per_s"] = (
        loadgen.window_tokens(requests, 0.0, seconds) / seconds
    )
    extra["completed_in_window"] = sum(
        1 for r in sent if r.ok and 0 <= r.done_s < seconds
    )
    extra["whole_requests_tokens_per_s"] = (
        loadgen.completed_tokens(requests, 0.0, seconds) / seconds
    )
    window = (opened_at, opened_at + seconds)
    compiles = [tuple(c) for c in counters["compiles"]]
    out_counters = {
        "entry_to_worker_s": counters["first_line_at"] - called_at,
        "ready_s": ready_at - called_at,
        **compile_counters(compiles, *window),
        **extra,
        "engine": counters["engine"],
    }
    print(
        f"[bench] requests={len(requests)} sent={len(sent)} "
        f"judged={len(judged)} "
        f"failed={len(failed)} check={check} "
        f"offered_tokens={sum(r.prompt_len + r.max_tokens for r in judged)} "
        + " ".join(f"{k}={v}" for k, v in extra.items())
        + f" serve_tokens_per_s={end_to_end['serve_tokens_per_s']}"
        + f" preemptions={counters['engine'].get('preemptions')}"
    )
    return {
        "correct": not problems,
        "attempted": len(sent),
        "failed": len(failed),
        "end_to_end": end_to_end,
        "device": counters["device"],
        "counters": out_counters,
        "trace_dir": trace["dir"] if trace else None,
    }
