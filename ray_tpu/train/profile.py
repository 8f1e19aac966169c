"""Compiled-program profiler: HLO roofline + in-program step attribution.

Everything the goodput plane measures today (comm_exposed_ratio,
host_sync_exposed_ratio, phase spans) stops at the jit boundary — the
compiled step itself is a black box. This module opens it, in two
halves that join into one MFU decomposition:

**Static (analytic)** — :func:`analyze_compiled` lowers a train step
once and walks its optimized HLO (``_private/xla_profile.py``), bucketing
every instruction into matmul / collective / elementwise_fusion /
layout and pricing each bucket against a per-chip roofline from the one
peak table (``_private/chip.py`` CHIP_SPECS): bf16 FLOP/s for math, HBM
bandwidth for bytes, ICI bandwidth (with standard algorithm wire
factors) for collectives.
The result is an *analytic ideal step time* and per-category floors.
Honesty caveat: these are cost-model numbers, not measurements —
``cost_analysis()``/HLO byte counts assume perfect fusion-boundary
traffic and peak sustained bandwidth.

**Empirical (measured)** — a capture request (head ``profile_capture``
fan-out, or :func:`request_capture` locally) arms the per-step hook that
``telemetry.finish_step`` calls. At the next step boundary the hook
wraps PROFILE_CAPTURE_STEPS steps in the hardened ``jax_profile``
tracer, parses the ``*.xplane.pb`` into per-category measured seconds,
and emits a ``profile:step`` span the head folds into the goodput
ledger (decomposition gauges + the regression-sentinel fingerprint).

**The join** — :func:`attribution_report` decomposes the measured step
wall into compute_floor / comm_in_program / hbm_bound / host_gap /
unattributed shares and names the dominant non-compute consumer: the
answer to "where does the missing MFU go".

Failure contract: nothing here may break a training step. The hook is a
two-branch no-op while disarmed (pinned <50µs by the perf-floor test),
and every capture-path failure degrades to one warning log.
"""

from __future__ import annotations

import glob
import hashlib
import json
import logging
import threading
import time

logger = logging.getLogger(__name__)

CATEGORIES = (
    "compute_floor", "comm_in_program", "hbm_bound", "host_gap",
    "unattributed",
)

def hbm_bandwidth_per_chip() -> float:
    """Peak HBM bytes/s of this host's chip (_private/chip.py)."""
    from ray_tpu._private import chip

    return chip.local_chip_spec().hbm_bps


def ici_bandwidth_per_chip() -> float:
    """Peak ICI bytes/s of this host's chip (_private/chip.py)."""
    from ray_tpu._private import chip

    return chip.local_chip_spec().ici_bps


def collective_wire_factor(op: str, group: int | None) -> float:
    """Wire-traffic multiple of the buffer size for one collective on a
    ring of ``group`` members: allreduce moves 2(n-1)/n of the buffer
    per chip, allgather/reduce-scatter (n-1)/n, permute 1."""
    n = group or 1
    if n <= 1:
        return 0.0
    base = op.replace("-start", "")
    if "all-reduce" in base or "allreduce" in base:
        return 2.0 * (n - 1) / n
    if ("all-gather" in base or "reduce-scatter" in base
            or "allgather" in base or "reducescatter" in base):
        return (n - 1) / n
    return 1.0


def price_categories(
    walk: dict,
    peak_flops: float | None = None,
    hbm_bps: float | None = None,
    ici_bps: float | None = None,
) -> dict:
    """Roofline-price the HLO walker's category table into per-category
    floor seconds. matmul takes max(flops-bound, bytes-bound); layout
    and elementwise are HBM-bound; collectives are ICI wire time."""
    from ray_tpu.train import telemetry

    peak = peak_flops or telemetry.peak_flops_per_chip()
    hbm = hbm_bps or hbm_bandwidth_per_chip()
    ici = ici_bps or ici_bandwidth_per_chip()
    cats = walk["categories"]
    floors = {}
    floors["matmul"] = max(
        cats["matmul"]["flops"] / peak, cats["matmul"]["bytes"] / hbm
    )
    floors["elementwise_fusion"] = cats["elementwise_fusion"]["bytes"] / hbm
    floors["layout"] = cats["layout"]["bytes"] / hbm
    wire = 0.0
    for op in walk["collective_ops"]:
        wire += op["bytes"] * collective_wire_factor(op["op"], op["group"])
    floors["collective"] = wire / ici
    return floors


def analyze_compiled(compiled) -> dict:
    """Static profile of one compiled executable: HLO category walk +
    roofline floors + the fingerprint signature the regression sentinel
    keys on. ``compiled`` is the result of ``jit(f).lower(...).
    compile()``."""
    text = compiled.as_text()
    walk = _analyze_text(text)
    # Cross-check against XLA's own aggregate (analytic too, but
    # independently derived). Counts each while body ONCE, so the
    # walker's trip-multiplied flops should be >= the aggregate.
    agg = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        agg = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
        }
    # tpulint: allow(broad-except reason=cost_analysis is a cross-check only; backends without it still get the HLO-walk profile)
    except Exception:  # noqa: BLE001
        pass
    return _finish_static(walk, agg)


def _analyze_text(text: str) -> dict:
    from ray_tpu._private import xla_profile

    return xla_profile.analyze_hlo_text(text)


def _finish_static(walk: dict, agg: dict) -> dict:
    floors = price_categories(walk)
    cats = walk["categories"]
    total_flops = sum(c["flops"] for c in cats.values())
    total_bytes = sum(c["bytes"] for c in cats.values())
    # Signature: the category shape of the program, stable across
    # processes (HLO text itself embeds unstable ids). Rounded so
    # float-noise in pricing can't fork fingerprints.
    sig_src = json.dumps(
        {
            k: [round(v["flops"]), round(v["bytes"]), v["ops"]]
            for k, v in sorted(cats.items())
        },
        sort_keys=True,
    )
    return {
        "sig": hashlib.sha1(sig_src.encode()).hexdigest()[:16],
        "categories": {
            k: {**v, "floor_s": floors[k]} for k, v in cats.items()
        },
        "ideal_step_s": sum(floors.values()),
        "flops_total": total_flops,
        "bytes_total": total_bytes,
        "cost_analysis": agg,
        "collective_ops": len(walk["collective_ops"]),
        "while_trips": walk["while_trips"],
    }


def analyze_train_step(
    cfg=None, batch_size: int = 8, seq: int | None = None
) -> dict:
    """Lower the flagship ``jit_train_step`` once (no execution) and
    statically profile it. Defaults to the bench preset at its bench
    shapes; pass ``cfg`` (e.g. PRESETS['tiny']) for fast tests."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import PRESETS
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train.step import (
        init_train_state,
        jit_train_step,
        make_optimizer,
    )

    if cfg is None:
        cfg = PRESETS["bench"]
    if seq is None:
        seq = min(2048, cfg.max_seq_len)
    opt = make_optimizer(total_steps=1000)
    mesh = make_mesh({"dp": len(jax.devices())})
    step = jit_train_step(cfg, opt, mesh)
    state = init_train_state(jax.random.key(0), cfg, opt)
    tokens = jnp.zeros((batch_size, seq + 1), dtype=jnp.int32)
    compiled = step.lower(state, {"tokens": tokens}).compile()
    static = analyze_compiled(compiled)
    static["batch_size"] = batch_size
    static["seq"] = seq
    static["model_flops_per_step"] = cfg.flops_per_token(seq) * (
        batch_size * seq
    )
    return static


# ------------------------------------------------------- attribution
def attribution_report(
    measured: dict,
    wall_s: float,
    steps: int,
    static: dict | None = None,
    model_flops_per_step: float | None = None,
) -> dict:
    """Join one capture's measured per-category seconds with the static
    roofline into the MFU decomposition.

    ``measured`` is ``xla_profile.measured_category_seconds`` output for
    ``steps`` steps totalling ``wall_s`` host seconds. Per-step
    decomposition (seconds, then shares of the step wall):

    - compute_floor: matmul time — the analytic floor when a static
      profile is supplied (what a perfect program would still pay),
      else the measured matmul seconds;
    - comm_in_program: measured collective time inside the program;
    - hbm_bound: measured elementwise/fusion + layout time (bandwidth,
      not math);
    - host_gap: step wall the device spent idle (wall − device busy);
    - unattributed: the remainder (tracer gaps, measured matmul above
      the floor, uncategorized ops).

    Multi-threaded CPU backends can sum concurrent leaf ops past the
    wall; measured seconds are normalized by min(1, wall/busy) so
    shares stay meaningful on every backend.
    """
    steps = max(1, steps)
    wall_step = wall_s / steps
    cats = {k: v / steps for k, v in measured["categories"].items()}
    busy_step = measured["device_busy_s"] / steps
    scale = 1.0
    if busy_step > 0 and wall_step > 0:
        scale = min(1.0, wall_step / busy_step)
    matmul_s = cats["matmul"] * scale
    comm_s = cats["collective"] * scale
    hbm_s = (cats["elementwise_fusion"] + cats["layout"]) * scale
    host_gap_s = max(0.0, wall_step - busy_step * scale)
    compute_s = matmul_s
    if static is not None:
        floor = static["categories"]["matmul"]["floor_s"]
        if 0.0 < floor <= matmul_s:
            compute_s = floor
    used = compute_s + comm_s + hbm_s + host_gap_s
    unattributed_s = max(0.0, wall_step - used)
    seconds = {
        "compute_floor": compute_s,
        "comm_in_program": comm_s,
        "hbm_bound": hbm_s,
        "host_gap": host_gap_s,
        "unattributed": unattributed_s,
    }
    shares = {
        k: (v / wall_step if wall_step > 0 else 0.0)
        for k, v in seconds.items()
    }
    gaps = {k: v for k, v in seconds.items() if k != "compute_floor"}
    dominant = max(gaps, key=gaps.get) if wall_step > 0 else "unattributed"
    report = {
        "step_s": wall_step,
        "steps": steps,
        "device_busy_s": busy_step * scale,
        "seconds": {k: round(v, 6) for k, v in seconds.items()},
        "shares": {k: round(v, 6) for k, v in shares.items()},
        "dominant_gap": dominant,
        "sig": (static or {}).get("sig", ""),
    }
    flops = model_flops_per_step or (static or {}).get(
        "model_flops_per_step"
    )
    if flops and wall_step > 0:
        from ray_tpu.train import telemetry

        try:
            import jax

            n_chips = max(1, len(jax.devices()))
        # tpulint: allow(broad-except reason=chip counting for an MFU denominator only; degrade to single-chip math)
        except Exception:  # noqa: BLE001
            n_chips = 1
        peak = telemetry.peak_flops_per_chip() * n_chips
        report["mfu"] = round(flops / (wall_step * peak), 6)
    return report


def _read_capture(path: str) -> dict | None:
    """Sum measured category seconds across every xplane.pb under one
    capture run directory; None when the tracer wrote nothing."""
    from ray_tpu._private import xla_profile

    files = sorted(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))
    if not files:
        return None
    total = None
    for f in files:
        with open(f, "rb") as fh:
            one = xla_profile.measured_category_seconds(fh.read())
        if total is None:
            total = one
        else:
            for k, v in one["categories"].items():
                total["categories"][k] += v
            total["device_busy_s"] += one["device_busy_s"]
            total["events"] += one["events"]
    return total


# -------------------------------------------------- capture machinery
# Module state machine, driven by the per-step hook telemetry calls.
# _armed is the single fast-path gate: False == hook returns in two
# branches (the pinned disabled path).
_armed = False
_lock = threading.Lock()
_pending_steps = 0
_active: dict | None = None
_statics: dict[str, dict] = {}  # job → static profile (register_static)
_last_reports: dict[str, dict] = {}  # job → last attribution report


def profiling_enabled() -> bool:
    from ray_tpu._private import config

    return config.get("PROFILE")


def register_static(job: str, static: dict) -> None:
    """Attach a static profile to a job so captures join against its
    analytic floors and fingerprint signature."""
    _statics[job] = static


def request_capture(steps: int | None = None) -> None:
    """Arm the step hook: the next step boundary starts an on-device
    trace of ``steps`` (default PROFILE_CAPTURE_STEPS) steps."""
    global _armed, _pending_steps
    if not profiling_enabled():
        logger.warning(
            "profile capture requested but RAY_TPU_PROFILE=0; ignoring"
        )
        return
    if steps is None:
        from ray_tpu._private import config

        steps = config.get("PROFILE_CAPTURE_STEPS")
    with _lock:
        _pending_steps = max(1, int(steps))
        _armed = True


def note_capture_request(msg: dict) -> None:
    """Pubsub fan-out entry point (head ``profile_capture`` event on the
    collective channel)."""
    request_capture(msg.get("steps"))


def last_report(job: str | None = None) -> dict | None:
    if job is not None:
        return _last_reports.get(job)
    for rep in _last_reports.values():
        return rep
    return None


def step_hook(ctx, step_s: float) -> None:
    """Per-step profiler hook, called by telemetry.finish_step on the
    step success path. MUST never raise and must cost nothing while
    disarmed (the perf-floor test pins this branch)."""
    global _armed, _active, _pending_steps
    if not _armed:
        return
    try:
        _step_hook_armed(ctx, step_s)
    # tpulint: allow(broad-except reason=capture failures must degrade to a warning, never an exception in the step loop — the acceptance contract of this plane)
    except Exception:  # noqa: BLE001
        logger.warning(
            "profile capture failed; disarming", exc_info=True
        )
        with _lock:
            _active = None
            _pending_steps = 0
            _armed = False


def _step_hook_armed(ctx, step_s: float) -> None:
    global _armed, _active, _pending_steps
    with _lock:
        if _active is None:
            if _pending_steps <= 0:
                _armed = False
                return
            steps = _pending_steps
            _pending_steps = 0
            from ray_tpu.util import tracing

            cm = tracing.jax_profile()
            cap = cm.__enter__()
            _active = {
                "cm": cm,
                "cap": cap,
                "left": steps,
                "steps": steps,
                "wall": 0.0,
                "t0": time.time(),
            }
            return
        act = _active
        act["left"] -= 1
        act["wall"] += step_s
        if act["left"] > 0:
            return
        _active = None
        if _pending_steps <= 0:
            _armed = False
    act["cm"].__exit__(None, None, None)
    _finish_capture(ctx, act)


def _finish_capture(ctx, act: dict) -> None:
    path = act["cap"].path
    measured = _read_capture(path) if path else None
    if measured is None:
        logger.warning(
            "profile capture wrote no parseable trace under %r", path
        )
        return
    job = ctx.experiment_name
    static = _statics.get(job)
    report = attribution_report(
        measured, act["wall"], act["steps"], static=static
    )
    if not report["sig"]:
        report["sig"] = job  # fingerprint key without a static profile
    report["path"] = path
    _last_reports[job] = report
    from ray_tpu.util import tracing

    tracing.emit_span(
        "profile:step",
        act["t0"],
        act["wall"],
        train_job=job,
        train_rank=ctx.rank,
        train_attempt=ctx.attempt,
        profile_sig=report["sig"],
        profile_steps=act["steps"],
        profile_step_s=round(report["step_s"], 6),
        profile_shares=report["shares"],
        profile_dominant=report["dominant_gap"],
        path=path or "",
    )
    logger.info(
        "profile capture %s: step %.4fs dominant_gap=%s shares=%s",
        job, report["step_s"], report["dominant_gap"], report["shares"],
    )


def _reset_for_tests() -> None:
    global _armed, _pending_steps, _active
    with _lock:
        _armed = False
        _pending_steps = 0
        _active = None
    _statics.clear()
    _last_reports.clear()


def profile_train_step(
    cfg=None, batch_size: int = 8, seq: int | None = None,
    steps: int | None = None,
) -> dict:
    """One-process convenience used by the CPU acceptance test:
    statically profile the flagship step, run ``steps`` of it under the
    tracer, and return the joined attribution report (with the static
    profile under ``"static"``)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import config
    from ray_tpu.models import PRESETS
    from ray_tpu.parallel import make_mesh
    from ray_tpu.train.step import (
        init_train_state,
        jit_train_step,
        make_optimizer,
    )
    from ray_tpu.util import tracing

    if cfg is None:
        cfg = PRESETS["bench"]
    if seq is None:
        seq = min(2048, cfg.max_seq_len)
    if steps is None:
        steps = config.get("PROFILE_CAPTURE_STEPS")
    opt = make_optimizer(total_steps=1000)
    mesh = make_mesh({"dp": len(jax.devices())})
    step = jit_train_step(cfg, opt, mesh)
    state = init_train_state(jax.random.key(0), cfg, opt)
    tokens = jax.random.randint(
        jax.random.key(1), (batch_size, seq + 1), 0, cfg.vocab_size
    )
    batch = {"tokens": tokens}
    compiled = step.lower(state, batch).compile()
    static = analyze_compiled(compiled)
    static["model_flops_per_step"] = cfg.flops_per_token(seq) * (
        batch_size * seq
    )
    # Warmup outside the trace (compile is done; first steps still run
    # cold caches), then capture.
    for _ in range(2):
        state, metrics = step(state, batch)
    jnp.asarray(metrics["loss"]).block_until_ready()
    with tracing.jax_profile() as cap:
        # Timer starts inside: the profiler's one-time start_trace
        # init (seconds on first use) must not read as host_gap.
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        jnp.asarray(metrics["loss"]).block_until_ready()
        wall = time.perf_counter() - t0
    measured = _read_capture(cap.path) if cap.path else None
    if measured is None:
        raise RuntimeError(
            f"profiler wrote no parseable trace under {cap.path!r}"
        )
    report = attribution_report(measured, wall, steps, static=static)
    report["path"] = cap.path
    report["static"] = static
    return report
