"""Who holds the chip (ray_tpu/_private/chip.py), without a chip.

The lease->platform rule as a pure function, the hook a chip-holding
process applies, the one peak table, the node's handling of a worker
started for a chip lease (on a host that only *says* it has a chip), and
chip_smoke.py's control flow at a tiny size on the CPU.
"""

import json
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


def _pid_gone(pid: int, timeout_s: float = 10.0) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with open(f"/proc/{pid}/stat") as f:
                if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                    return True
        except FileNotFoundError:
            return True
        time.sleep(0.1)
    return False


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
