"""Runner for serving cells of a model that is not Llama-shaped
(``"runner": "serve_model"``, ``"model": <module of benchmarks/models>``):
``serve.run`` of ``BenchModelServer`` on a replica that leases the chip,
``serve.start_http()``, and the load generator in its own process
against the proxy's port, as ``runners/serve.py`` does for the
Llama-shaped cells, whose warm-up, load offering and summary this
imports. The model's module decides ``correct`` from the server's
``check``. This process never opens a JAX backend."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import sys
import threading
import time

from benchmarks.runners import serve as serve_runner
from benchmarks.runners.serve import GENERATOR_START_S, call


@contextlib.contextmanager
def replica(cell: dict, conf: dict, seed: int, rehearsal: bool):
    """``serve.run`` of the cell's deployment and the HTTP proxy, up for
    the length of the block; yields the handle, the port and when
    ``serve.run`` was called and returned."""
    import ray_tpu
    from benchmarks.runners import common
    from benchmarks.server_model import BenchModelServer
    from ray_tpu import serve

    ray_tpu.init()
    try:
        common.require_chips(cell["chips"], rehearsal)
        called_at = time.time()
        deployment = serve.deployment(
            BenchModelServer,
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


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    from benchmarks import loadgen
    from benchmarks.runners import common

    model = importlib.import_module(f"benchmarks.models.{conf['model']}")
    missing = [
        name for name in model.PROGRAM_FILES
        if not os.path.exists(os.path.join(common.ROOT, "ray_tpu", name))
    ]
    if missing:
        sys.exit(
            f"benchmarks/run.py: this checkout's program has no ray_tpu/"
            f"{missing[0]}: it cannot run configuration {cell['config']}"
        )
    seconds = args.seconds
    vocab = conf["vocab_size"]
    trace = common.trace_plan(cell["name"], args) if args.trace else None
    with replica(cell, conf, args.seed, bool(args.rehearse)) as (
        handle, port, called_at, ready_at
    ):
        check = call(handle, "check", args.seed, timeout=900,
                     **conf.get("check", {}))
        serve_runner._warm(port, traffic, loadgen.build(traffic, args.seed, seconds),
                           args.seed, vocab)
        program_texts = {}
        if trace is not None:
            program_texts = call(handle, "write_program_texts", trace["dir"],
                                 traffic, timeout=900)
        # The generator's process needs a moment to start and build.
        opened_at = time.time() + traffic["ramp_s"] + GENERATOR_START_S
        timers = []
        if trace is not None:
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
    os.makedirs(os.path.join(common.OUT, cell["name"]), exist_ok=True)
    with open(os.path.join(common.OUT, cell["name"],
                           f"requests-{args.seed}.json"), "w") as f:
        json.dump([dataclasses.asdict(r) for r in requests], f)

    # The summary's own logit limit is the Llama cells'; this model's
    # module judges its check, so the summary sees one that passes.
    print(f"[bench] check={check}")
    measured = serve_runner.summarize(
        conf, traffic, requests, seconds,
        {"finite": True, "logit_max_abs_err": [0.0]}, counters,
        t_start=t_start, called_at=called_at, ready_at=ready_at,
        opened_at=opened_at, trace=trace,
    )
    problems = model.check_problems(check)
    for p in problems:
        print(f"[bench] NOT CORRECT: {p}")
    measured["correct"] = measured["correct"] and not problems
    engine = counters["engine"]
    held = engine.get("decode_steps", 0) * model.held_expert_slots(conf)
    measured["counters"].update(
        program_texts=program_texts,
        check=check,
        experts_touched_pct=(
            100.0 * engine["experts_touched"] / held if held else None
        ),
        moe_pairs_here_pct=(
            100.0 * engine["moe_pairs_here"] / engine["moe_pairs_routed"]
            if engine.get("moe_pairs_routed") else None
        ),
    )
    return measured
