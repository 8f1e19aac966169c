"""Who holds the chip, and what the chip is.

One rule: a worker whose lease holds ``TPU > 0`` on real chips runs JAX
on the TPU; every other worker, the node daemon, the head and the
driver stay on the CPU. The node applies the rule at lease grant
(:func:`lease_platform`) and starts a worker whose lease holds chips,
real or fake, as a process of its own; where they are real that process
calls :func:`hold_chip` before any of its code can create a backend,
and either way it exits when the lease ends, because a process that has
opened the chip keeps it until it dies. Such a process also says how it
became useful (:func:`watch_startup`): a span around the backend's
creation and one a compile request.

The module also keeps the one table of published per-chip peaks, keyed
by the ``device_kind`` JAX reports.
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

from ray_tpu.util import tracing

# Set by hold_chip(): this process was promised a chip, so finding none
# (or another platform) is an error, never a CPU run.
_promised = False


class ChipUnavailableError(RuntimeError):
    """A process that was promised a TPU cannot run JAX on one."""


class UnknownChipError(LookupError):
    """A TPU reported a ``device_kind`` the peak table does not know."""


def lease_platform(held: dict, real_chips: int) -> str:
    """JAX platform for a worker whose lease holds ``held`` resources on
    a node with ``real_chips`` physical chips (0 where the ``TPU``
    resource is RAY_TPU_FAKE_CHIPS or a made-up count: such leases
    schedule like TPU leases and compute on the CPU)."""
    return "tpu" if held.get("TPU", 0) > 0 and real_chips > 0 else "cpu"


def compile_cache_dir() -> str:
    """JAX's persistent compile cache for this checkout:
    ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it, else
    ``<checkout>/.jax_cache``. The path is part of a cache entry's key,
    so it is never a temporary name, a pid or a time."""
    # tpulint: allow(TPU703 reason=JAX's own variable, read before jax is imported so that the environment's choice wins)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(root, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX at :func:`compile_cache_dir` before this process
    compiles anything. Exported so child processes inherit it."""
    path = compile_cache_dir()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def hold_chip() -> None:
    """Make this process run JAX on the TPU. Called before its first
    backend use: the environment alone is too late once ``jax`` is
    imported (it reads ``JAX_PLATFORMS`` at import), so the config is
    updated too. A process that already runs JAX elsewhere cannot
    switch, and raises."""
    global _promised
    enable_compile_cache()
    os.environ["JAX_PLATFORMS"] = "tpu"
    _promised = True
    if holds_backend():
        platform()  # raises unless the backend that exists is the TPU
    elif "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "tpu")
    watch_startup()


# ------------------------------------------------- start-up, by phase
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_watching = False
# What JAX has said of the compile request this thread is in the middle
# of: its trace and lowering intervals and whether the cache answered.
_request = threading.local()


def _on_compile_phase(event: str, start: float, end: float, **kw) -> None:
    """One ``compile:<fun_name>`` span a compile request. JAX reports a
    request's parts in order on the thread that makes it: the trace
    (an outer function's after, and around, those of the functions it
    calls, so the last one stands), the lowering, whether the
    persistent cache answered, and the backend compile (a cache read
    where it did)."""
    if event == _TRACE_EVENT:
        _request.trace = (start, end)
    elif event == _LOWER_EVENT:
        _request.lower = (start, end)
    elif event == _BACKEND_EVENT:
        parts = vars(_request)
        trace = parts.pop("trace", (start, start))
        lower = parts.pop("lower", (start, start))
        name = str(kw.get("fun_name", "?"))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        begin = min(trace[0], lower[0], start)
        tracing.emit_worker_span(
            f"compile:{name}", begin, end - begin,
            trace_s=trace[1] - trace[0], lower_s=lower[1] - lower[0],
            backend_s=end - start, cache_hit=parts.pop("hit", False),
        )


def _on_cache_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _request.hit = True


def watch_startup() -> None:
    """Make this process say how it became useful: a
    ``startup:chip_open`` span around the creation of the JAX backend,
    wherever that is triggered, and a ``compile:*`` span for every
    compile request of its life. For a process whose lease holds
    ``TPU > 0``: :func:`hold_chip` calls it where the chips are real,
    ``worker_main`` where they are fake and the backend that opens is
    the CPU's. Imports ``jax`` (which such a process exists to run)
    but creates no backend."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax.monitoring
    from jax._src import xla_bridge

    jax.monitoring.register_event_time_span_listener(_on_compile_phase)
    jax.monitoring.register_event_listener(_on_cache_event)
    if xla_bridge.backends_are_initialized():
        return
    # JAX reports nothing of its own about opening a backend; every
    # path to one (jax.devices, default_backend, the first array) goes
    # through xla_bridge.backends(), which this wraps until a backend
    # exists. A second thread that arrives meanwhile waits in
    # backends_are_initialized() for the first one's lock.
    unreported = threading.Lock()
    create = xla_bridge.backends

    def backends():
        if xla_bridge.backends_are_initialized():
            return create()
        start = time.time()
        found = create()
        if unreported.acquire(blocking=False):
            xla_bridge.backends = create
            devices = sys.modules["jax"].devices()
            tracing.emit_worker_span(
                "startup:chip_open", start, time.time() - start,
                platform=devices[0].platform,
                device_kind=devices[0].device_kind, count=len(devices),
            )
        return found

    xla_bridge.backends = backends


def holds_backend() -> bool:
    """True once this process has created a JAX backend (and with it,
    on a TPU platform, taken the chip). Never creates one."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def platform() -> str:
    """The platform JAX runs on in this process (creating the backend).
    Kernel and attention-path choices follow from this, so a process
    that was promised a chip never falls back to an interpreted or
    replaced kernel: it raises :class:`ChipUnavailableError`."""
    import jax

    try:
        found = jax.default_backend()
    except RuntimeError as e:
        if not _promised:
            raise
        raise ChipUnavailableError(
            "this process holds a TPU lease but JAX could not open the "
            "chip; its node saw the chip's device nodes open when it "
            "granted the lease (NodeManager._chips_let_go), so another "
            f"process has taken it since: {e}"
        ) from e
    if _promised and found != "tpu":
        raise ChipUnavailableError(
            "this process holds a TPU lease but a JAX backend for "
            f"{found!r} already existed when the lease was applied"
        )
    return found


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Published peaks of one chip."""

    bf16_flops: float  # FLOP/s
    hbm_bytes: int
    hbm_bps: float  # bytes/s
    ici_bps: float  # bytes/s, one direction, all links


# Keyed by jax.devices()[0].device_kind. Source: Google Cloud TPU
# documentation, system architecture pages "TPU v4", "TPU v5e",
# "TPU v5p", "TPU v6e" (per-chip peak compute, HBM capacity and
# bandwidth, inter-chip interconnect bandwidth). A v5e reports itself
# as "TPU v5 lite", a v5p as "TPU v5", a v6e as "TPU v6 lite".
CHIP_SPECS: dict[str, ChipSpec] = {
    "TPU v4": ChipSpec(275e12, 32 << 30, 1228e9, 300e9),
    "TPU v5 lite": ChipSpec(197e12, 16 << 30, 819e9, 200e9),
    "TPU v5": ChipSpec(459e12, 95 << 30, 2765e9, 600e9),
    "TPU v5p": ChipSpec(459e12, 95 << 30, 2765e9, 600e9),
    "TPU v6 lite": ChipSpec(918e12, 32 << 30, 1638e9, 448e9),
}
# What a CPU rig prices its dry runs against: the chip they rehearse.
_REHEARSED = CHIP_SPECS["TPU v5 lite"]


def chip_spec(platform: str, device_kind: str) -> ChipSpec:
    """Peaks for a device. On ``tpu`` an unknown kind raises — a default
    would put an invented denominator under every utilization printed.
    Other platforms get the v5e figures: their numbers are rehearsals,
    never device metrics."""
    if platform != "tpu":
        return _REHEARSED
    try:
        return CHIP_SPECS[device_kind]
    except KeyError:
        raise UnknownChipError(
            f"no published peaks for TPU device_kind {device_kind!r}; "
            f"add it to CHIP_SPECS (known: {sorted(CHIP_SPECS)})"
        ) from None


def local_chip_spec() -> ChipSpec:
    """:func:`chip_spec` of this process's first device."""
    import jax

    dev = jax.devices()[0]
    return chip_spec(dev.platform, dev.device_kind)
