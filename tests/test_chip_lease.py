"""Who holds the chip (ray_tpu/_private/chip.py), without a chip.

The lease->platform rule as a pure function, the hook a chip-holding
process applies, the one peak table, the node's handling of a worker
started for a chip lease (on a host that only *says* it has a chip), and
chip_smoke.py's control flow at a tiny size on the CPU.
"""

import asyncio
import errno
import json
import logging
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import chip
from ray_tpu._private import config as _config
from ray_tpu._private.accelerators import TPUAcceleratorManager

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def visible_chip(monkeypatch):
    """A host that says it has one real chip (it has none: JAX there
    cannot open it, which is what the typed error is for)."""
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


@pytest.fixture
def fake_chips(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "4")
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    _config._overrides.pop("FAKE_CHIPS", None)


@pytest.mark.parametrize(
    "held, real_chips, want",
    [
        ({"CPU": 1.0, "TPU": 1.0}, 1, "tpu"),
        ({"TPU": 4.0}, 4, "tpu"),
        ({"TPU": 1.0}, 0, "cpu"),  # FAKE_CHIPS or a made-up resource
        ({"CPU": 1.0}, 4, "cpu"),  # no TPU in the lease
        ({"CPU": 1.0, "TPU": 0.0}, 4, "cpu"),
    ],
)
def test_lease_platform_rule(held, real_chips, want):
    assert chip.lease_platform(held, real_chips) == want


def test_fake_chips_are_not_real(fake_chips):
    mgr = TPUAcceleratorManager()
    assert mgr.detect_count() == 4
    assert mgr.real_chips() == 0
    assert chip.lease_platform({"TPU": 1.0}, mgr.real_chips()) == "cpu"


def test_visible_chips_are_real(visible_chip):
    mgr = TPUAcceleratorManager()
    assert mgr.detect_count() == mgr.real_chips() == 1


def test_hold_chip_unpins_cpu_after_jax_import():
    """Regression for the trainer's old os.environ patch: jax reads
    JAX_PLATFORMS at import, and importing ray_tpu.train imports jax, so
    a worker started with JAX_PLATFORMS=cpu stayed on the CPU however
    the variable was changed afterwards. hold_chip() moves the config
    too, and creates no backend doing it."""
    code = (
        "import json, os\n"
        "import ray_tpu.train\n"
        "import jax\n"
        "from ray_tpu._private import chip\n"
        "before = jax.config.jax_platforms\n"
        "os.environ.pop('JAX_PLATFORMS')  # what trainer.py used to do\n"
        "popped = jax.config.jax_platforms\n"
        "chip.hold_chip()\n"
        "print(json.dumps({'before': before, 'popped': popped,\n"
        "  'after': jax.config.jax_platforms,\n"
        "  'env': os.environ['JAX_PLATFORMS'],\n"
        "  'backend': chip.holds_backend()}))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120, check=True,
    ).stdout
    got = json.loads(out.strip().splitlines()[-1])
    assert got == {"before": "cpu", "popped": "cpu", "after": "tpu",
                   "env": "tpu", "backend": False}


def test_compile_cache_yields_to_environment(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert chip.compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert chip.compile_cache_dir() == os.path.join(REPO, ".jax_cache")


def test_peak_table_knows_the_chip_and_refuses_to_guess():
    v5e = chip.chip_spec("tpu", "TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes, v5e.hbm_bps) == (
        197e12, 16 << 30, 819e9
    )
    with pytest.raises(chip.UnknownChipError, match="TPU v9 mega"):
        chip.chip_spec("tpu", "TPU v9 mega")
    # Off the TPU nothing is a device metric; the CPU rigs keep pricing
    # their dry runs against the chip they rehearse.
    assert chip.chip_spec("cpu", "cpu") == v5e
    from ray_tpu.train import profile, telemetry

    assert telemetry.peak_flops_per_chip() == 197e12
    assert profile.hbm_bandwidth_per_chip() == 819e9
    assert profile.ici_bandwidth_per_chip() == 200e9


def _pid_gone(
    pid: int, timeout_s: float = 10.0, zombie_ok: bool = True
) -> bool:
    """A zombie has let its chip go but is not reaped: enough for a
    lease's end, not for ``shutdown()``."""
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            with open(f"/proc/{pid}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
            if zombie_ok and state == "Z":
                return True
        except FileNotFoundError:
            return True
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.1)


def test_chip_lease_gets_its_own_process_and_the_process_ends_with_it(
    visible_chip,
):
    ray_tpu.init(num_cpus=2)
    assert ray_tpu.cluster_resources()["TPU"] == 1.0

    @ray_tpu.remote
    def where():
        return os.getpid(), os.environ["JAX_PLATFORMS"]

    @ray_tpu.remote
    def jax_platforms():
        import jax

        return jax.config.jax_platforms, chip._promised

    @ray_tpu.remote(num_tpus=1)
    def open_chip():
        return chip.platform()

    cpu_pid, cpu_env = ray_tpu.get(where.remote())
    assert cpu_env == "cpu"
    first_pid, first_env = ray_tpu.get(where.options(num_tpus=1).remote())
    assert first_env == "tpu" and first_pid != cpu_pid
    assert ray_tpu.get(jax_platforms.options(num_tpus=1).remote()) == (
        "tpu", True,
    )
    # The submitter returns an idle lease after a second; the worker
    # that held it must then exit, not rejoin the idle pool.
    assert _pid_gone(first_pid), "chip worker outlived its lease"
    second_pid, _ = ray_tpu.get(where.options(num_tpus=1).remote())
    assert second_pid != first_pid
    assert ray_tpu.get(where.remote())[1] == "cpu"
    # Promised a chip, finding none: typed, never a CPU run.
    with pytest.raises(Exception, match="ChipUnavailableError"):
        ray_tpu.get(open_chip.remote())


def test_fake_chip_lease_stays_on_the_cpu_in_a_process_of_its_own(fake_chips):
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote(num_tpus=1)
    def where():
        import jax

        return os.environ["JAX_PLATFORMS"], jax.default_backend(), os.getpid()

    @ray_tpu.remote
    def pooled_pid():
        return os.getpid()

    *ran_on, pid = ray_tpu.get(where.remote())
    assert ran_on == ["cpu", "cpu"]
    rt = ray_tpu.api._runtime
    workers = rt.run(rt.core.node.call("list_workers"))["workers"]
    assert {w["platform"] for w in workers} == {"cpu"}
    # Scheduled like a lease of real chips: started for it, ended with it.
    assert ray_tpu.get(pooled_pid.remote()) != pid
    assert _pid_gone(pid), "the fake-chip worker outlived its lease"


@ray_tpu.remote
def _where():
    return os.getpid(), os.environ["JAX_PLATFORMS"]


@ray_tpu.remote(num_tpus=1)
class ChipHolder:
    def pid(self):
        return os.getpid()


@pytest.mark.parametrize("lease", ["returned_just_before", "still_held"])
def test_shutdown_returns_with_the_chip_worker_reaped(fake_chips, lease):
    """After shutdown() the process that held the chip is gone: not
    killed and left to die, and not a zombie of this process."""
    ray_tpu.init(num_cpus=2)
    node = ray_tpu.api._runtime.node
    if lease == "still_held":
        holder = ChipHolder.remote()
        pid = ray_tpu.get(holder.pid.remote())
    else:
        # The submitter returns an idle lease after a second, and the
        # node kills the worker that held it.
        pid, _ = ray_tpu.get(_where.options(num_tpus=1).remote())
        deadline = time.monotonic() + 10
        while not node._dying_chip_procs and time.monotonic() < deadline:
            time.sleep(0.005)
        assert [p.pid for p in node._dying_chip_procs] == [pid]
    ray_tpu.shutdown()
    assert _pid_gone(pid, timeout_s=0, zombie_ok=False)
    assert node._dying_chip_procs == []


SLEEPER = (
    "import signal, sys, time\n"
    "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
    "held = [open(p) for p in sys.argv[1:]]\n"
    "print('ready', flush=True)\n"
    "time.sleep(120)\n"
)


def _sleeper(*paths) -> subprocess.Popen:
    """A process that ignores SIGTERM and holds ``paths`` open."""
    proc = subprocess.Popen(
        [sys.executable, "-c", SLEEPER, *map(str, paths)],
        stdout=subprocess.PIPE, text=True,
    )
    assert proc.stdout.readline() == "ready\n"
    return proc


def test_stop_gives_every_child_one_deadline_and_reaps_what_it_kills():
    from ray_tpu.runtime import node as node_mod

    ray_tpu.init(num_cpus=1)
    node = ray_tpu.api._runtime.node
    sleepers = [_sleeper() for _ in range(3)]
    for i, proc in enumerate(sleepers):
        node.workers[f"sleeper{i}"] = {"proc": proc, "state": "idle"}
    began = time.monotonic()
    ray_tpu.shutdown()
    took = time.monotonic() - began
    # SIGTERM, one wait for all of them, SIGKILL, reaped: not a wait each.
    assert node_mod.STOP_TERM_S <= took < 3 * node_mod.STOP_TERM_S - 1
    for proc in sleepers:
        assert proc.returncode == -9
        assert _pid_gone(proc.pid, timeout_s=0, zombie_ok=False)


def test_shutdown_says_so_when_the_teardown_overruns(monkeypatch, caplog):
    from ray_tpu import api
    from ray_tpu.runtime import node as node_mod

    ray_tpu.init(num_cpus=1)
    node = api._runtime.node
    procs = [w["proc"] for w in node.workers.values() if w.get("proc")]

    async def never_ends():
        await asyncio.sleep(60)

    monkeypatch.setattr(node, "stop", never_ends)
    monkeypatch.setattr(node_mod, "STOP_TERM_S", 0.1)
    monkeypatch.setattr(node_mod, "CHIP_FREE_TIMEOUT_S", 0.1)
    monkeypatch.setattr(api, "TEARDOWN_SLACK_S", 0.1)
    try:
        with caplog.at_level(logging.WARNING, logger="ray_tpu.api"):
            began = time.monotonic()
            ray_tpu.shutdown()
            took = time.monotonic() - began
        assert not ray_tpu.is_initialized() and took < 10
        (said,) = [r for r in caplog.records if r.name == "ray_tpu.api"]
        assert "did not end within 0 s" in said.getMessage()
        assert said.exc_info[0].__name__ == "TimeoutError"
    finally:  # what the teardown that never ran would have reaped
        for proc in procs:
            proc.kill()
            proc.wait()


@pytest.fixture
def chip_files(tmp_path, monkeypatch):
    """Two files where the host's chips would be, and one beside them."""
    from ray_tpu._private.accelerators import tpu

    monkeypatch.setattr(
        tpu, "_CHIP_NODE_GLOBS",
        (str(tmp_path / "accel*"), str(tmp_path / "vfio" / "*")),
    )
    (tmp_path / "vfio").mkdir()
    chips = [str(tmp_path / "vfio" / n) for n in "01"]
    for path in (*chips, tmp_path / "other"):
        open(path, "w").close()
    return chips


def _refusing(node: str, code: int | None):
    """An opener that refuses ``node`` with ``code`` and opens the rest."""
    def opener(path, flags):
        assert flags == os.O_RDWR
        if path == node and code is not None:
            raise OSError(code, os.strerror(code), path)
        return os.open(path, flags)

    return opener


@pytest.mark.parametrize(
    "code, busy",
    [(errno.EBUSY, True), (errno.ENOENT, False), (errno.EACCES, False),
     (errno.EPERM, False), (None, False)],
)
def test_a_chip_is_busy_while_its_open_says_ebusy(chip_files, code, busy):
    from ray_tpu._private.accelerators import tpu

    assert tpu.chip_nodes() == chip_files
    fds_before = len(os.listdir("/proc/self/fd"))
    got = tpu.busy_chips(opener=_refusing(chip_files[1], code))
    assert got == ({chip_files[1]: []} if busy else {})
    assert len(os.listdir("/proc/self/fd")) == fds_before  # closed at once


def test_busy_chips_names_the_live_holder_and_nothing_once_reaped(
    chip_files,
):
    """The pids tell a live holder from a group the kernel is closing;
    the verdict is the open's either way."""
    from ray_tpu._private.accelerators import tpu

    refused = _refusing(chip_files[1], errno.EBUSY)
    other = os.path.join(os.path.dirname(chip_files[0]), os.pardir, "other")
    bystander = _sleeper(other)
    holder = _sleeper(chip_files[1])
    try:
        assert tpu.busy_chips(opener=refused) == {
            chip_files[1]: [holder.pid]
        }
        # Open in a process, and it opens: a device that more than one
        # may hold, or a file. Not busy.
        assert tpu.busy_chips() == {}
    finally:
        for proc in (holder, bystander):
            proc.kill()
            proc.wait()
    assert tpu.busy_chips(opener=refused) == {chip_files[1]: []}
    assert tpu.busy_chips(nodes=[chip_files[0]], opener=refused) == {}


def test_no_chip_node_no_busy_chip(tmp_path, monkeypatch):
    from ray_tpu._private.accelerators import tpu

    monkeypatch.setattr(tpu, "_CHIP_NODE_GLOBS", (str(tmp_path / "accel*"),))
    assert tpu.chip_nodes() == [] and tpu.busy_chips() == {}
    assert TPUAcceleratorManager().real_chips() == 0


@pytest.mark.parametrize(
    "pids, named",
    [([4242, 4243], r"open in pids \[4242, 4243\]"),
     ([], "open in no process")],
)
def test_chip_lease_fails_typed_when_the_chip_is_never_let_go(
    visible_chip, monkeypatch, pids, named
):
    from ray_tpu._private.accelerators import tpu
    from ray_tpu.runtime import node as node_mod

    monkeypatch.setattr(tpu, "busy_chips", lambda: {"/dev/vfio/0": pids})
    monkeypatch.setattr(node_mod, "CHIP_FREE_TIMEOUT_S", 0.5)
    ray_tpu.init(num_cpus=2)
    node = ray_tpu.api._runtime.node
    with pytest.raises(
        Exception, match=rf"ChipUnavailableError.*/dev/vfio/0 \({named}"
    ):
        ray_tpu.get(_where.options(num_tpus=1).remote(), timeout=60)
    # The node gave the chip back to its pool, killed the worker it had
    # started for the lease, and serves on.
    assert node.available["TPU"] == 1.0
    assert not [w for w in node.workers.values() if w.get("chips")]
    assert ray_tpu.get(_where.remote(), timeout=60)[1] == "cpu"


def test_a_chip_this_nodes_own_worker_holds_is_not_waited_for(monkeypatch):
    """A one-chip worker opens every group of its host: the next chip
    lease of the same node finds them busy and held by a worker whose
    lease lasts, which is as it should be."""
    from ray_tpu._private.accelerators import tpu
    from ray_tpu.runtime import node as node_mod
    from ray_tpu.util import state

    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
    monkeypatch.setattr(node_mod, "CHIP_FREE_TIMEOUT_S", 2.0)
    ray_tpu.init(num_cpus=2)
    try:
        holder = ChipHolder.remote()
        held_by = ray_tpu.get(holder.pid.remote())
        monkeypatch.setattr(
            tpu, "busy_chips",
            lambda: {"/dev/vfio/0": [held_by], "/dev/vfio/1": [held_by]},
        )
        pid, env = ray_tpu.get(
            _where.options(num_tpus=1).remote(), timeout=60
        )
        assert env == "tpu" and pid != held_by
        rt = ray_tpu.api._runtime
        rt.run(rt.node.flush_spans(), timeout=10)
        (row,) = [w for w in state.startup_report()["workers"]
                  if w.get("pid") == pid]
        wait = row["spans"]["startup:chip_free_wait"]
        assert (wait["nodes"], wait["holders"]) == ([], [])
        assert wait["dur"] < 1.0
    finally:
        ray_tpu.shutdown()


def test_engine_stats_say_where_it_ran():
    from ray_tpu.llm.engine import LLMEngine

    stats = LLMEngine("tiny", max_batch=2, max_seq=64).stats()
    assert stats["platform"] == "cpu"
    assert stats["device_kind"] == "cpu"
    assert stats["paged_attn_kernel"] is False


def test_chip_smoke_refuses_to_run_without_a_chip():
    """No TPU resource: fail at once with the reason; neither wait on a
    lease that cannot be granted nor print a result."""
    env = {**os.environ, "PYTHONPATH": REPO}
    env.pop("RAY_TPU_FAKE_CHIPS", None)
    env.pop("TPU_VISIBLE_CHIPS", None)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode not in (0, 124)
    assert time.monotonic() - t0 < 30
    assert "registered TPU: 0" in proc.stderr
    assert '"ok"' not in proc.stdout


TINY = {
    "train": {
        "cfg": {"attn_impl": "flash", "remat": "full"}, "reduced": {},
        "batch": 2, "seq": 128, "steps": 3,
        "runs": [{"mesh": {"dp": 1}, "devices": 1}],
    },
    "fsdp": {
        "cfg": {"attn_impl": "flash", "remat": "full"}, "reduced": {},
        "batch": 4, "seq": 128, "steps": 3,
        "runs": [{"mesh": {"fsdp": 4}, "devices": 4},
                 {"mesh": {"dp": 1}, "devices": 1}],
    },
    "serve": {
        "cfg": {}, "reduced": {},
        "engine": {"max_batch": 2, "max_seq": 128, "page_size": 16},
        "max_tokens": 4,
        "requests": [(5, False), (20, True), (40, False)],
        "check_prompt": 12, "check_decode": 2, "check_pad": 32,
    },
    "hybrid": {
        "cfg": {}, "reduced": {},
        "engine": {"max_batch": 2, "max_seq": 128, "page_size": 16,
                   "prefill_chunk": 32},
        "check_prompt": 40, "check_decode": 2,
    },
    "latent": {
        "cfg": {}, "reduced": {},
        "engine": {"max_batch": 2, "max_seq": 128, "page_size": 16,
                   "prefill_chunk": 32},
        "check_prompt": 70, "check_decode": 2,
    },
}


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_phases_at_tiny_size_on_cpu(fake_chips, monkeypatch, chips):
    """chip_smoke's phases end to end through JaxTrainer, serve and a
    plain task, on fake chips: everything but the chip. The records come
    back whole, and verify() refuses them because they ran on the CPU."""
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "PRESET", "tiny")
    ray_tpu.init(num_cpus=4)
    records = chip_smoke.run_phases(chips, seed=0, sizes=TINY)
    assert [r["phase"] for r in records] == (
        ["fsdp"] if chips == 4
        else ["train", "serve", "engine_check", "hybrid_check",
              "latent_check"]
    )
    for run in records[0]["runs"]:
        assert run["losses"][-1] < run["losses"][0]
    if chips == 4:
        sharded, single = records[0]["runs"]
        assert sharded["param_shard_devices"] == [0, 1, 2, 3]
        assert sharded["param_shard_fraction"] == 0.25
        assert sharded["losses"] == pytest.approx(single["losses"], abs=1e-4)
    else:
        assert [r["tokens"] for r in records[1]["requests"]] == [4, 4, 4]
        assert max(records[2]["logit_max_abs_err"]) < 1e-4  # fp32 on CPU
        assert max(records[3]["logit_max_abs_err"]) < 2e-4
        assert records[3]["prefill_calls"] == 2  # 40 tokens, chunks of 32
        assert max(records[4]["logit_max_abs_err"]) < 2e-4
        assert records[4]["prefill_calls"] == 3  # 70 tokens, chunks of 32
        for check in records[3:]:
            # The touched-experts kernel, interpreted, on half the experts.
            assert check["expert_kernel_max_abs_err"] < 1e-4
            count, held = check["expert_kernel_touched"]
            assert 0 < count <= held // 2
    with pytest.raises(chip_smoke.SmokeFailure, match="platform 'cpu'"):
        chip_smoke.verify(records, chips)
