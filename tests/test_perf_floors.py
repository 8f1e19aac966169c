"""Control-plane performance floors (reference: `ray microbenchmark`
ray_perf.py runs in release CI). The committed PERF.json records full-run
numbers; this test runs the quick suite and enforces conservative floors
so the control plane cannot silently regress by an order of magnitude.
"""

from ray_tpu._private import perf

# name-prefix → minimum ops/s. Set ~10x below measured dev-box rates
# (PERF.json) to absorb CI noise while still catching real regressions.
FLOORS = {
    "put (100 B)": 400.0,
    "get (100 B, cached owner)": 800.0,
    "put (1 MiB)": 80.0,
    "task submit+get (sync)": 80.0,
    "tasks async": 150.0,
    "actor call (sync)": 100.0,
    "actor calls async": 200.0,
    "queued burst": 100.0,
    "serve handle calls": 150.0,
    "serve http req": 200.0,
}

# Streaming time-to-first-byte ceiling (ms): measured p50 ~1.3ms on the
# dev box; 100ms catches a regression to buffered (non-streaming)
# delivery while absorbing CI noise.
SSE_TTFB_P99_CEILING_MS = 100.0


def test_microbench_floors():
    results = perf.main(quick=True)
    by_name = {r["name"]: r for r in results if "ops_per_s" in r}
    failures = []
    for prefix, floor in FLOORS.items():
        match = next(
            (r for name, r in by_name.items() if name.startswith(prefix)),
            None,
        )
        assert match is not None, f"benchmark {prefix!r} missing"
        if match["ops_per_s"] < floor:
            failures.append(
                f"{match['name']}: {match['ops_per_s']:.0f} < {floor} ops/s"
            )
    assert not failures, "control-plane regressions:\n" + "\n".join(failures)
    bcast = next(
        (r for r in results if r["name"].startswith("broadcast ")), None
    )
    assert bcast is not None, "benchmark 'broadcast' missing"
    # Aggregate store-to-store GB/s; conservative floor (the 1-core CI
    # VM is memcpy-bound and noisy — this catches large regressions
    # like a return to sequential single-holder pulls).
    assert bcast["agg_GB_s"] >= 0.035, (
        f"broadcast regressed: {bcast['agg_GB_s']} GB/s aggregate"
    )
    # Relay-tree depth is what the code actually controls and is
    # deterministic: 8 nodes through doubling waves (cap 4) is 1+2+4+1
    # = 4 waves; sequential pushes would be 8.
    assert bcast.get("waves", 99) <= 4, (
        f"broadcast relay degraded to {bcast.get('waves')} waves"
    )
    llm = next(
        (r for r in results if r["name"].startswith("llm paged decode")),
        None,
    )
    assert llm is not None, "benchmark 'llm paged decode' missing"
    # CPU CI floor: the tiny-model engine pumps well over 30 tok/s on
    # the dev box CPU; 5 catches structural regressions (per-step
    # recompiles, full-logits host transfers, allocator churn).
    assert llm["tokens_per_s"] >= 5.0, (
        f"paged decode regressed: {llm['tokens_per_s']} tok/s"
    )
    gloo = next(
        (r for r in results if r["name"].startswith("allreduce gloo")),
        None,
    )
    assert gloo is not None, "benchmark 'allreduce gloo' missing"
    # 2-process gloo over real process boundaries; measured 0.137 GB/s
    # bus at 64 MiB on the 1-core dev box (0.3+ at 8 MiB quick).
    assert gloo["bus_GB_s"] >= 0.01, (
        f"gloo allreduce regressed: {gloo['bus_GB_s']} GB/s bus"
    )
    ttfb = next(
        (r for r in results if r["name"] == "serve sse ttfb"), None
    )
    assert ttfb is not None, "benchmark 'serve sse ttfb' missing"
    assert ttfb["p99_ms"] < SSE_TTFB_P99_CEILING_MS, (
        f"serve sse ttfb p99 {ttfb['p99_ms']}ms >= "
        f"{SSE_TTFB_P99_CEILING_MS}ms (streaming regressed to buffering?)"
    )


# Disabled-path budget for train step telemetry: a no-op step_span +
# phase (outside a session / RAY_TPU_TRAIN_TELEMETRY=0) plus one tagged
# counter inc. Measured ~2µs/step on the dev box; 50µs catches a
# structural regression (allocation storms, config lookups per phase,
# span emission leaking into the disabled path) through CI noise.
STEP_TELEMETRY_DISABLED_CEILING_S = 50e-6


def test_compressed_allreduce_wire_floor():
    """Perf floor: the int8 codec's cpu-hub allreduce moves >= 1.9x
    fewer wire bytes than f32 at 4 MiB. Measured exactly as the backend
    measures it — the serialized RPC payload (contribution up + reply
    down), so envelope overhead and the per-block scales are priced in,
    not idealized away."""
    import numpy as np

    from ray_tpu.collective import codec
    from ray_tpu.collective.backends.cpu_group import (
        _compress,
        _pack,
        _packed_nbytes,
    )

    arr = np.linspace(-1.0, 1.0, (4 << 20) // 4, dtype=np.float32)  # 4 MiB
    f32_wire = 2 * _packed_nbytes(_pack(arr))  # up + down
    q8_wire = 2 * _packed_nbytes(_pack(_compress(arr, "int8")))
    ratio = f32_wire / q8_wire
    assert ratio >= 1.9, (
        f"compressed allreduce moves only {ratio:.2f}x fewer wire bytes "
        f"({q8_wire} vs {f32_wire}) — codec or serializer regressed"
    )
    # The codec's own accounting agrees with the serializer's within
    # the fixed envelope overhead.
    qt = codec.quantize(arr)
    assert abs(q8_wire / 2 - qt.wire_nbytes) < 2048


def test_step_telemetry_disabled_overhead():
    import time

    from ray_tpu.train import session
    from ray_tpu.util.metrics import Counter

    assert session._context is None  # outside a session → disabled path
    counter = Counter("perf_floor_steps_total", "d", tag_keys=("job",))
    n = 2000
    for _ in range(100):  # warmup (lazy imports, bytecode)
        with session.step_span() as s:
            with s.phase("compute"):
                pass
    t0 = time.perf_counter()
    for _ in range(n):
        with session.step_span() as s:
            with s.phase("compute"):
                pass
        counter.inc(tags={"job": "perf"})
    per_step = (time.perf_counter() - t0) / n
    assert per_step < STEP_TELEMETRY_DISABLED_CEILING_S, (
        f"disabled-path step telemetry costs {per_step * 1e6:.1f}µs/step "
        f"(budget {STEP_TELEMETRY_DISABLED_CEILING_S * 1e6:.0f}µs) — "
        "instrumentation is taxing the train loop"
    )


# The engine's host spans are jax.profiler.TraceAnnotation, always in
# the code: with no profiler session each is one flag test, and each
# phase of a step books its own seconds besides (`engine._Phase`: two
# clock reads, a stack of the open phases). Measured ~11µs on the CPU
# for a paged decode step's six phases (constructor, the keyword
# arguments, set_metadata, the clock reads and the booking: the
# annotations alone ~2µs of it), the step's two reads of its thread's
# CPU time and the one `is_ready()` in front of `launch:decode`; 50µs is
# 1.3% of the shortest decode step on the chip (3.84 ms,
# `qwen3next-longdoc-16`).
ENGINE_STEP_ANNOTATIONS_CEILING_S = 50e-6


def test_engine_step_annotations_cost_without_a_session(monkeypatch):
    """What one `LLMEngine.step()` spends on its phases when nobody
    traces: the step's own annotation calls are recorded once, then
    replayed on the real class through the engine's own helper, with the
    clock reads and the `is_ready()` a step makes beside them."""
    import time

    from jax.profiler import TraceAnnotation

    from ray_tpu.llm import engine as engine_mod

    calls: list[tuple] = []  # (name, keyword arguments, set_metadata's)

    class Recording(TraceAnnotation):
        def __init__(self, name, **kw):
            super().__init__(name, **kw)
            self.call = (name, kw, [])
            calls.append(self.call)

        def set_metadata(self, **kw):
            self.call[2].append(kw)
            super().set_metadata(**kw)

    eng = engine_mod.LLMEngine("tiny", max_batch=2, page_size=16)
    eng.add_request([1, 2, 3], engine_mod.SamplingParams(max_tokens=8))
    eng.add_request([4, 5, 6], engine_mod.SamplingParams(max_tokens=8))
    eng.step()  # admits both; the next step is a plain decode step
    monkeypatch.setattr(engine_mod, "TraceAnnotation", Recording)
    eng.step()
    monkeypatch.undo()
    names = [c[0] for c in calls]
    assert names == ["engine:step", "engine:grow_tables",
                     "engine:decode_dispatch", "launch:decode",
                     "engine:decode_sync", "engine:emit"], names
    assert not TraceAnnotation.is_enabled()
    in_flight = eng._in_flight.sampled

    def replay():
        # Nested as in the step: every phase inside `engine:step`, the
        # launch inside the dispatch.
        time.perf_counter(), time.thread_time()
        (name, kw, metadata), *children = calls
        with eng._phase(name, **kw) as step:
            for name, kw, metadata_of in children:
                if name == "launch:decode":
                    continue
                with eng._phase(name, **kw) as span:
                    if name == "engine:decode_dispatch":
                        in_flight.is_ready()
                        with eng._phase("launch:decode", ahead=1, starved=0):
                            pass
                    for more in metadata_of:
                        span.set_metadata(**more)
            time.thread_time()
            for more in metadata:
                step.set_metadata(**more)
        time.perf_counter()

    n = 2000
    for _ in range(100):
        replay()
    t0 = time.perf_counter()
    for _ in range(n):
        replay()
    per_step = (time.perf_counter() - t0) / n
    assert per_step < ENGINE_STEP_ANNOTATIONS_CEILING_S, (
        f"a step's annotations cost {per_step * 1e6:.1f}µs with no "
        f"profiler session (budget "
        f"{ENGINE_STEP_ANNOTATIONS_CEILING_S * 1e6:.0f}µs)"
    )
