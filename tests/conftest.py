"""Test harness: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's strategy of testing multi-node behavior on one
machine (reference: python/ray/cluster_utils.py:135 starts multiple raylets
in-process; python/ray/experimental/channel/conftest.py mocks NCCL) — here
multi-chip behavior runs on XLA's forced host-platform device count.
"""

import os

# Tests always run on the virtual CPU mesh, whatever platform the
# environment names: override both the env var (workers inherit it) and
# jax.config (a plugin may have imported jax already) before any backend
# initialization.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Wall-clock ceiling for collective tests: a hung collective (the exact
# failure mode the fault-tolerance layer exists to remove) must fail the
# one test, not wedge the whole suite until the CI timeout.
COLLECTIVE_WALLCLOCK_S = 60


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: kill-based fault-injection tests (worker/node processes "
        "are SIGKILLed mid-op)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running tests excluded from tier-1 (-m 'not slow')",
    )


# Fault-tolerance / chaos modules run under the runtime concurrency
# sanitizer: locks ray_tpu code allocates during these tests are
# instrumented, so a lock-order inversion raises LockOrderViolation at
# the acquisition instead of wedging the suite (see
# ray_tpu/_private/sanitize.py).
_SANITIZED_MODULES = (
    "test_collective_ft",
    "test_fault_tolerance",
    "test_head_ft",
    "test_node_drain",
    "test_chaos_and_bridges",
)


def _wants_sanitizer(item) -> bool:
    mod = getattr(getattr(item, "module", None), "__name__", "")
    return (
        any(mod.endswith(m) for m in _SANITIZED_MODULES)
        or item.get_closest_marker("chaos") is not None
    )


def pytest_runtest_setup(item):
    if _wants_sanitizer(item):
        from ray_tpu._private import sanitize

        sanitize.install()


def pytest_runtest_teardown(item, nextitem):
    if _wants_sanitizer(item):
        from ray_tpu._private import sanitize

        sanitize.uninstall()
        # One module's lock order must not poison the next test's graph
        # (different cluster topology, same lock names).
        sanitize.reset()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    import signal
    import threading

    guarded = (
        hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
        and (
            "collective" in getattr(getattr(item, "module", None),
                                    "__name__", "")
            or item.get_closest_marker("chaos") is not None
        )
    )
    if not guarded:
        yield
        return

    def _alarm(signum, frame):
        raise TimeoutError(
            f"collective test exceeded {COLLECTIVE_WALLCLOCK_S}s wall "
            "clock — a collective op hung instead of raising its typed "
            "deadline/abort error"
        )

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(COLLECTIVE_WALLCLOCK_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from ray_tpu.parallel import make_mesh

    assert len(jax.devices()) == 8
    return make_mesh({"dp": 2, "fsdp": 2, "tp": 2})


@pytest.fixture
def generate_at_lag0():
    """`LLMEngine.generate` with the engine held to lag 0: whatever a
    step() left in flight is read back before the next one, so that
    every decode step is dispatched from the host's tokens, in the
    order the engine had before it kept a step in flight. What the
    lag-1 tests compare with."""

    def generate(engine, prompts, sampling):
        """`sampling`: one for all prompts, or one a prompt."""
        if not isinstance(sampling, list):
            sampling = [sampling] * len(prompts)
        order = {
            engine.add_request(p, s): i
            for i, (p, s) in enumerate(zip(prompts, sampling, strict=True))
        }
        outs = [None] * len(prompts)
        while engine.has_unfinished():
            finished = engine.step()
            with engine._lock:
                engine._drain(finished, unlock=False)
            for fin in finished:
                outs[order[fin["request_id"]]] = fin["tokens"]
        return outs

    return generate


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e:2x2 for `test_tpu_aot_compile.py`
    (the kernels) and `test_tpu_aot_programs.py` (the serving programs),
    with the persistent compile cache off: such a compile is written to
    it but cannot be read back without a chip, and the next one would
    warn. Not `autouse`, and describing nothing until a test asks: a
    module that names no `v5e` never loads libtpu."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # libtpu lets one process a host load it, to protect an attached
    # chip. Nothing is attached here, and test processes run side by
    # side (xdist): read when libtpu loads, which is the call below.
    with pytest.MonkeyPatch.context() as env:
        env.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        # tpulint: allow(broad-except reason=whatever keeps the TPU compiler from describing a topology here (no libtpu, no compiler for this chip) skips these tests; they have no CPU meaning)
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
