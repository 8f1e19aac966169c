"""The start-up phase metrics: the reducer on a made-up report (a phase
that is missing reads None and never 0; of two workers that were
started for chips, the one with the lease is read), and the tiny train
and serve cells of ``rehearsal-startup.json`` run whole on the CPU,
each reporting every new metric it is listed for."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.reducers import startup_phase
from ray_tpu.util import state

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NEW = (
    "cluster_init_s", "entry_to_lease_s", "worker_spawn_s",
    "worker_first_task_s", "chip_open_s", "trace_lower_s",
    "compile_cache_hit_pct", "startup_program_s", "replica_ready_lag_s",
    "http_start_s",
)


def span(ts, dur, **attrs):
    return {"ts": ts, "dur": dur, **attrs}


def worker(worker_id, spans, tpu=1.0, compiles=(0, 0, 0.0, 0.0, 0.0),
           compile_spans=()):
    keys = ("requests", "cache_hits", "trace_s", "lower_s", "backend_s")
    return {
        "worker_id": worker_id, "tpu": tpu, "same_host": True,
        "spans": spans, "phases": {},
        "compiles": dict(zip(keys, compiles)),
        "compile_spans": list(compile_spans),
    }


@pytest.fixture
def report(monkeypatch):
    """A driver that called serve.run at 100 s; a first chip worker that
    was spawned and never got its lease (the grant gave up on it), and
    the one that did, whose backend never opened."""
    made = {
        "driver": {"addr": "127.0.0.1:1", "spans": {
            "startup:init": span(90.0, 0.5),
            "startup:entry/old": span(50.0, 1.0, kind="serve"),
            "startup:entry/default": span(100.0, 20.0, kind="serve"),
            "startup:http": span(120.0, 0.25),
        }},
        "workers": [
            worker("pooled", {"startup:spawn": span(90.1, 0.7)}, tpu=0),
            worker("gave-up", {"startup:spawn": span(101.0, 30.0)}),
            worker("leased", {
                "startup:lease": span(102.0, 5.0, lease_id="n-2"),
                "startup:chip_free_wait": span(102.0, 1.5),
                "startup:spawn": span(103.5, 3.5),
                "startup:first_task": span(107.0, 0.5),
                "startup:replica_init": span(107.5, 12.0),
            }, compiles=(4, 3, 1.0, 2.0, 5.0), compile_spans=[
                {"name": "compile:a", **span(108.0, 2.0)},
                {"name": "compile:b", **span(121.0, 1.0)},
            ]),
        ],
    }
    monkeypatch.setattr(state, "_last_startup_report", made)
    return made


def test_a_phase_is_read_from_the_worker_that_held_the_lease(report):
    read = startup_phase.reduce
    assert read({}, span="startup:init", of="driver") == 0.5
    assert read({}, span="startup:http", of="driver") == 0.25
    assert read({}, span="startup:first_task") == 0.5
    # 3.5 s of spawn and the 1.5 s wait before it; not the 30 s of the
    # worker that never held the lease, nor the pooled worker's.
    assert read({}, span="startup:spawn",
                plus=["startup:chip_free_wait"]) == 5.0
    # The newest entry of the driver's two.
    assert read({}, begin=["driver", "startup:entry", "start"],
                until=["worker", "startup:lease", "start"]) == 2.0
    assert read({}, begin=["worker", "startup:replica_init", "end"],
                until=["driver", "startup:entry", "end"]) == 0.5
    assert read({}, compiles="trace_lower_s") == 3.0
    assert read({}, compiles="cache_hit_pct") == 75.0
    # Every process's spans: [50,51] [90,90.8] and, with the spawn that
    # was given up on (101 to 131) over entry, http and compiles, [100,131].
    assert read({}, union=True) == pytest.approx(1.0 + 0.8 + 31.0)


def test_a_missing_phase_is_none_and_not_zero(report, monkeypatch):
    read = startup_phase.reduce
    assert read({}, span="startup:chip_open") is None
    assert read({}, begin=["worker", "startup:chip_open", "end"],
                until=["driver", "startup:entry", "end"]) is None
    report["workers"][2]["compiles"]["requests"] = 0
    assert read({}, compiles="cache_hit_pct") is None
    del report["workers"][2]["spans"]["startup:chip_free_wait"]
    assert read({}, span="startup:spawn",
                plus=["startup:chip_free_wait"]) == 3.5
    report["workers"] = report["workers"][:2]  # no worker held a lease
    assert read({}, span="startup:spawn") is None
    assert read({}, compiles="trace_lower_s") is None
    # A program that keeps no report (the parent of the PR that added
    # the spans): nothing to read, nothing raised.
    monkeypatch.setattr(state, "_last_startup_report", None)
    assert read({}, union=True) is None
    monkeypatch.delattr(state, "last_startup_report")
    assert read({}, span="startup:init", of="driver") is None


def test_every_new_metric_has_its_file_and_its_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    serving = ["chat-open", "doc-prefill", "nemotron-reason-32",
               "pangu-longdoc-16"]
    for name in NEW:
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
            assert json.load(f)["reducer"] == "startup_phase"
        assert listed[name]["moves"] == "setup_s"
        assert listed[name].get("workloads") == (
            serving if name in NEW[-2:] else None
        )


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-open"])
def test_the_tiny_cells_report_every_new_metric_they_list(cell):
    listing = os.path.join(HERE, "rehearsal-startup.json")
    with open(listing) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"]
                  if cell in m.get("workloads", [cell])]
    assert len(set(listed) & set(NEW)) == (10 if cell == "tiny-open" else 8)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
         listing, "--workload", cell, "--seed", "2147483659",
         "--seconds", "8", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"]
    metrics = result["metrics"]
    assert set(listed) <= set(metrics), sorted(set(listed) - set(metrics))
    for name in set(listed) & set(NEW):
        assert metrics[name]["value"] >= 0, name
    assert metrics["compile_cache_hit_pct"]["value"] <= 100
    # What the node and the worker say of themselves adds up to what
    # the benchmark's two stamps see from outside.
    inside = sum(metrics[n]["value"] for n in (
        "entry_to_lease_s", "worker_spawn_s", "worker_first_task_s"))
    assert inside <= metrics["entry_to_worker_s"]["value"]
    assert metrics["startup_program_s"]["value"] >= inside
