"""Start-up by phase: every process says how it became useful.

``startup:*`` and ``compile:*`` spans from ``ray_tpu.init()`` to the
first line of the worker that holds a lease of (fake) chips, the head's
table of them, ``state.startup_report()`` and ``timeline()``. No
duration is asserted: only that each span has both ends and that they
lie in the order the program goes through them. Buffers are flushed by
hand where a test must see a span now.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import config as _config
from ray_tpu.util import state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# A worker that held a lease of chips says all of these; the node says
# the first three of it.
CHIP_WORKER_SPANS = (
    "startup:lease", "startup:chip_free_wait", "startup:spawn",
    "startup:boot", "startup:first_task", "startup:chip_open",
)


@pytest.fixture
def cluster(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_CHIPS", "4")
    ray_tpu.init(num_cpus=4)
    yield ray_tpu.api._runtime
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    _config._overrides.pop("FAKE_CHIPS", None)


def flush_worker():
    """Runs inside a worker: what its 1 Hz flusher would send later."""
    rt = ray_tpu.api._runtime
    rt.run(rt.core.flush_observability(), timeout=10)
    return os.getpid()


def report(rt) -> dict:
    rt.run(rt.core.flush_observability(), timeout=10)
    rt.run(rt.node.flush_spans(), timeout=10)
    return state.startup_report()


def end(span: dict) -> float:
    return span["ts"] + span["dur"]


def chip_rows(rep: dict) -> list[dict]:
    return [w for w in rep["workers"] if w.get("tpu")]


def check_chip_worker(row: dict) -> None:
    spans = row["spans"]
    assert set(CHIP_WORKER_SPANS) <= set(spans), sorted(spans)
    for name in CHIP_WORKER_SPANS:
        assert spans[name]["dur"] >= 0, name
        assert row["phases"][name.split(":")[1]] == spans[name]["dur"]
    lease, spawn = spans["startup:lease"], spans["startup:spawn"]
    wait, boot = spans["startup:chip_free_wait"], spans["startup:boot"]
    first, opened = spans["startup:first_task"], spans["startup:chip_open"]
    # The lease holds the wait for dying chip workers and the spawn; the
    # process boots inside the spawn; its first task begins where its
    # registration ended; the backend opens in or after that task.
    assert lease["ts"] <= wait["ts"] <= end(wait) <= spawn["ts"]
    assert end(spawn) <= end(lease)
    assert spawn["ts"] <= boot["ts"]
    assert end(boot) <= first["ts"] + 1e-6
    assert first["ts"] <= opened["ts"]
    assert lease["queued_s"] >= 0 and lease["platform"] == "cpu"
    assert boot["imports_s"] >= 0 and boot["core_start_s"] >= 0
    assert boot.get("exec_s", 0) >= 0
    assert (row["pid"], row["platform"]) == (spawn["pid"], "cpu")
    assert row["lease_id"] == lease["lease_id"] and row["same_host"]
    assert opened["device_kind"] == "cpu" and opened["count"] >= 1
    totals = row["compiles"]
    assert totals["requests"] == len(row["compile_spans"]) > 0
    assert 0 <= totals["cache_hits"] <= totals["requests"]
    for c in row["compile_spans"]:
        assert c["name"].startswith("compile:") and c["dur"] >= 0
        assert min(c["trace_s"], c["lower_s"], c["backend_s"]) >= 0
        # Tracing may begin before the backend is asked for; no compile
        # ends before there is one.
        assert end(c) >= end(opened)


@ray_tpu.remote(num_tpus=1)
class Doubler:
    def __init__(self):
        import jax
        import jax.numpy as jnp

        self.out = jax.jit(lambda x: x * 2, inline=False)(jnp.ones(3))

    def flush(self):
        return flush_worker()


def test_chip_lease_actor_leaves_every_span_in_order(cluster):
    actor = Doubler.remote()
    pid = ray_tpu.get(actor.flush.remote())
    rep = report(cluster)
    (row,) = chip_rows(rep)
    assert row["pid"] == pid
    check_chip_worker(row)
    assert row["spans"]["startup:first_task"]["task"] == "Doubler"
    # The driver's own phases: init holds its three parts.
    driver = rep["driver"]["spans"]
    init = driver["startup:init"]
    for part in ("startup:head", "startup:node", "startup:driver_core"):
        assert init["ts"] <= driver[part]["ts"]
        assert end(driver[part]) <= end(init)
    assert driver["startup:node"]["chips_found"] == 4
    # Pooled workers were spawned and booted too, for no lease.
    pooled = [w for w in rep["workers"] if not w.get("tpu")]
    assert pooled and all("boot" in w["phases"] for w in pooled)
    text = state.startup_table(rep)
    assert row["worker_id"][:8] in text and "chip_open" in text


@ray_tpu.remote(num_tpus=1)
def where_it_ran():
    return os.getpid(), os.environ["JAX_PLATFORMS"]


def test_chip_free_wait_covers_a_chip_that_is_being_let_go(monkeypatch):
    """The job before this one ended seconds ago: a process still has
    the chip's device node open, then nobody has and the kernel is
    closing it, and one open of it blocks. On a host that says its chip
    is real (it has none) the lease waits for all of that inside
    ``startup:chip_free_wait``, beside its worker's boot and off the
    node's event loop, and is granted once the node opens."""
    from ray_tpu._private.accelerators import tpu

    asked = []  # when the node asked, first to last

    def busy_chips():
        asked.append(time.monotonic())
        age = asked[-1] - asked[0]
        if len(asked) == 1:
            time.sleep(0.6)  # an open that blocks
        if age < 1:
            return {"/dev/vfio/0": [4242] if age < 0.5 else []}
        return {}

    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    monkeypatch.setattr(tpu, "busy_chips", busy_chips)
    ray_tpu.init(num_cpus=2)
    try:
        rt = ray_tpu.api._runtime
        ref = where_it_ran.remote()
        while not asked:
            time.sleep(0.005)
        # Inside the open that blocks: the node answers all the same.
        t = time.monotonic()
        workers = rt.run(rt.core.node.call("list_workers"), timeout=10)
        assert time.monotonic() - t < 0.3 and len(asked) == 1
        assert "tpu" in {w["platform"] for w in workers["workers"]}
        pid, env = ray_tpu.get(ref, timeout=60)
        assert env == "tpu"
        (row,) = chip_rows(report(rt))
        looks = len(asked)
    finally:
        ray_tpu.shutdown()
    # stop() looked once more, the node having leased a real chip.
    assert len(asked) == looks + 1
    del asked[looks:]
    wait, spawn = (row["spans"][k]
                   for k in ("startup:chip_free_wait", "startup:spawn"))
    assert row["pid"] == pid
    assert (wait["procs"], wait["holders"], wait["nodes"]) == (
        0, [4242], ["/dev/vfio/0"],
    )
    assert 1 <= asked[-1] - asked[0] <= wait["dur"] < 10
    assert wait["dur"] == row["phases"]["chip_free_wait"]
    # The process boots meanwhile: it opens no chip before its first task.
    assert wait["ts"] <= spawn["ts"] < end(wait)


def tiny_loop(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu import train

    loss = jax.jit(lambda w: (w * w).sum())(jnp.ones(4))
    train.report({"loss": float(loss)})


def test_fit_leaves_an_entry_and_its_workers_spans(cluster, tmp_path):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        tiny_loop,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, chips_per_worker=1
        ),
        run_config=RunConfig(name="startup_fit", storage_path=str(tmp_path)),
    ).fit()
    assert result.error is None
    rep = report(cluster)  # the attempt flushed its worker as it ended
    entry = rep["driver"]["spans"]["startup:entry/startup_fit"]
    assert (entry["kind"], entry["attempt"]) == ("train", 0)
    (row,) = chip_rows(rep)
    check_chip_worker(row)
    assert row["spans"]["startup:first_task"]["task"] == "TrainWorker"
    # fit() was called before the lease was asked for, and setup()
    # returned after the worker's first task had begun.
    assert entry["ts"] <= row["spans"]["startup:lease"]["ts"]
    assert row["spans"]["startup:first_task"]["ts"] <= end(entry)


class Echo:
    def __init__(self):
        import jax
        import jax.numpy as jnp

        self.one = float(jax.jit(lambda x: x + 1)(jnp.zeros(())))

    def __call__(self, request=None):
        return self.one

    def flush(self):
        return flush_worker()


def run_echo(name: str = "default"):
    deployment = serve.deployment(
        Echo, num_replicas=1, ray_actor_options={"num_tpus": 1}
    )
    return serve.run(deployment.bind(), name=name, timeout_s=120)


def test_serve_run_and_http_leave_entry_replica_init_and_http(cluster):
    try:
        handle = run_echo()
        port = serve.start_http()
        handle.options(method_name="flush").remote().result(timeout=30)
        rep = report(cluster)
    finally:
        serve.shutdown()
    driver = rep["driver"]["spans"]
    entry, http = driver["startup:entry/default"], driver["startup:http"]
    assert entry["kind"] == "serve" and http["port"] == port
    assert end(entry) <= http["ts"]
    (row,) = chip_rows(rep)
    check_chip_worker(row)
    replica_init = row["spans"]["startup:replica_init"]
    assert replica_init["deployment"] == "Echo"
    first = row["spans"]["startup:first_task"]
    assert first["task"] == "ReplicaActor"
    # serve.run -> lease -> ... -> first task -> the user's __init__,
    # which ends before serve.run sees the replica HEALTHY.
    assert entry["ts"] <= row["spans"]["startup:lease"]["ts"]
    assert end(first) <= replica_init["ts"]
    assert end(replica_init) <= end(entry)


def test_task_events_do_not_evict_startup_spans(cluster):
    actor = Doubler.remote()
    ray_tpu.get(actor.flush.remote())
    before = report(cluster)
    flood = [
        {"task_id": f"{i:032x}", "name": "noise", "state": "FINISHED",
         "ts": 0.0, "worker": "flood"}
        for i in range(25_000)
    ]
    for i in range(0, len(flood), 5_000):
        cluster.run(cluster.core.head.call(
            "add_task_events", events=flood[i:i + 5_000]
        ))
    raw = cluster.run(cluster.core.head.call(
        "list_task_events", limit=20_000, raw=True, state="SPAN"
    ))["events"]
    assert not [e for e in raw if e["name"].startswith("startup:")]
    after = report(cluster)
    assert chip_rows(after) == chip_rows(before)
    assert after["driver"] == before["driver"]


@pytest.mark.chaos
def test_a_replica_killed_and_restarted_has_two_rows(cluster):
    from ray_tpu._private.test_utils import kill_one_replica

    try:
        handle = run_echo("twice")
        first_pid = handle.options(method_name="flush").remote().result(
            timeout=30
        )
        kill_one_replica("Echo", "twice")
        deadline = time.monotonic() + 90
        second_pid = None
        while second_pid in (None, first_pid):
            assert time.monotonic() < deadline, "no replacement replica"
            try:
                second_pid = handle.options(
                    method_name="flush"
                ).remote().result(timeout=30)
            except Exception:  # noqa: BLE001 - the dead replica's address
                time.sleep(0.1)
        rep = report(cluster)
    finally:
        serve.shutdown()
    rows = [w for w in chip_rows(rep) if "replica_init" in w["phases"]]
    assert [w["pid"] for w in rows] == [first_pid, second_pid]
    for row in rows:
        check_chip_worker(row)
    # Time to recover, from the rows alone: the successor's lease was
    # asked for after the first replica was up.
    assert (
        end(rows[0]["spans"]["startup:replica_init"])
        <= rows[1]["spans"]["startup:lease"]["ts"]
    )


def test_last_startup_report_answers_after_shutdown(cluster):
    actor = Doubler.remote()
    pid = ray_tpu.get(actor.flush.remote())
    ray_tpu.shutdown()
    assert not ray_tpu.is_initialized()
    rep = state.last_startup_report()
    (row,) = chip_rows(rep)
    assert row["pid"] == pid
    check_chip_worker(row)
    assert "startup:init" in rep["driver"]["spans"]
    assert "lease" in state.startup_table(rep)


def test_timeline_carries_every_attribute_of_a_span(cluster):
    actor = Doubler.remote()
    ray_tpu.get(actor.flush.remote())
    report(cluster)
    slices = {e["name"]: e for e in state.timeline() if e["tid"] == "spans"}
    assert slices["startup:spawn"]["args"]["platform"] == "cpu"
    assert slices["startup:lease"]["args"]["tpu"] == 1.0
    compiles = [e for n, e in slices.items() if n.startswith("compile:")]
    assert compiles and all(
        e["args"]["cache_hit"] in (True, False) for e in compiles
    )
    assert "ts" not in slices["startup:boot"]["args"]


LISTENER = """
import json, sys
from ray_tpu._private import chip
from ray_tpu.util import tracing
import ray_tpu.api as api

spans = []
tracing.emit_span = lambda name, start, dur, **attrs: spans.append(
    {"name": name, "dur": dur, **attrs})
api._runtime.core = type("Core", (), {"worker_id": "w0"})()
chip.enable_compile_cache()
chip.watch_startup()
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

@jax.jit
def named_step(x):
    return (x * 3).sum()

named_step(jnp.arange(5.0)).block_until_ready()
print(json.dumps(spans))
"""


def test_compile_listener_reports_a_miss_then_a_hit_by_name(tmp_path):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
        "PYTHONPATH": REPO,
    }
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", LISTENER], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    cold, warm = (
        {s["name"]: s for s in spans} for spans in runs
    )
    for spans in (cold, warm):
        assert spans["startup:chip_open"]["platform"] == "cpu"
        assert spans["startup:chip_open"]["worker_id"] == "w0"
    assert cold["compile:named_step"]["cache_hit"] is False
    assert warm["compile:named_step"]["cache_hit"] is True
    for spans in (cold, warm):
        step = spans["compile:named_step"]
        assert min(step["trace_s"], step["lower_s"], step["backend_s"]) >= 0
        assert step["dur"] >= step["backend_s"]
