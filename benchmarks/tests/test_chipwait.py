"""``benchmarks/chipwait.py`` against made-up device nodes, and the two
places ``benchmarks/run.py`` asks it: entering (the seconds waited come
off ``setup_s``; a chip that never frees gives no result) and leaving
(also when the runner raised). No chip and no cluster: the runner is
made up too."""

import errno
import json
import os
import sys
import types

import pytest

from benchmarks import chipwait
from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def no_sleep(monkeypatch):
    """Sleeps are counted, not slept."""
    slept = []
    monkeypatch.setattr(chipwait.time, "sleep", slept.append)
    return slept


class Nodes:
    """An ``opener`` over real files: ``refusals[node]`` is the errno it
    raises, for the node's first ``times[node]`` opens (always, where no
    count is given)."""

    def __init__(self, tmp_path, names, refusals=(), times=()):
        self.paths = []
        for name in names:
            path = tmp_path / name
            path.write_bytes(b"")
            self.paths.append(str(path))
        self.refusals = {str(tmp_path / k): v for k, v in dict(refusals).items()}
        self.times = {str(tmp_path / k): v for k, v in dict(times).items()}
        self.calls = {p: 0 for p in self.paths}
        self.opened = []

    def __call__(self, path, flags):
        assert flags == os.O_RDWR
        self.calls[path] += 1
        if path in self.refusals and (
            self.calls[path] <= self.times.get(path, sys.maxsize)
        ):
            raise OSError(self.refusals[path], os.strerror(self.refusals[path]))
        fd = os.open(path, flags)
        self.opened.append(fd)
        return fd

    def all_closed(self):
        for fd in self.opened:
            try:
                os.fstat(fd)
            except OSError:
                continue
            return False
        return True


def test_no_nodes_returns_at_once(no_sleep):
    assert chipwait.wait_chips_free(60.0, nodes=[]) == (0.0, [], [])
    assert no_sleep == []


def test_no_device_nodes_here_means_no_wait(monkeypatch, no_sleep):
    monkeypatch.setattr(chipwait.glob, "glob", lambda pattern: [])
    assert chipwait.wait_chips_free(60.0) == (0.0, [], [])
    assert no_sleep == []


def test_the_container_of_all_groups_is_not_a_chip(monkeypatch):
    found = {"/dev/vfio/*": ["/dev/vfio/vfio", "/dev/vfio/1", "/dev/vfio/0"],
             "/dev/accel*": ["/dev/accel0"]}
    monkeypatch.setattr(chipwait.glob, "glob", found.__getitem__)
    assert chipwait.chip_nodes() == ["/dev/accel0", "/dev/vfio/0", "/dev/vfio/1"]


def test_free_nodes_take_one_probe(tmp_path, no_sleep):
    nodes = Nodes(tmp_path, ["0", "1", "2", "3"])
    waited, seen, still = chipwait.wait_chips_free(
        60.0, nodes=nodes.paths, opener=nodes)
    assert (seen, still, no_sleep) == ([], [], [])
    assert waited < 1.0
    assert set(nodes.calls.values()) == {1} and nodes.all_closed()


def test_waits_out_a_busy_node_and_names_it(tmp_path, no_sleep):
    nodes = Nodes(tmp_path, ["0", "1", "2", "3"],
                  refusals={"3": errno.EBUSY}, times={"3": 3})
    waited, seen, still = chipwait.wait_chips_free(
        60.0, nodes=nodes.paths, opener=nodes)
    assert seen == [nodes.paths[3]] and still == []
    assert no_sleep == [chipwait.PROBE_EVERY_S] * 3
    # Every probe asks every node: the kernel frees them one by one.
    assert set(nodes.calls.values()) == {4}
    assert len(nodes.opened) == 4 * 4 - 3 and nodes.all_closed()


def test_a_node_whose_open_blocked_is_named(tmp_path, monkeypatch):
    """The kernel may hold ``open()`` until the group is let go instead
    of refusing it: those seconds are waited, and the node is named."""
    nodes = Nodes(tmp_path, ["0", "1"])
    clock = [100.0]

    def slow_open(path, flags):
        if path == nodes.paths[1]:
            clock[0] += 5.0
        return nodes(path, flags)

    monkeypatch.setattr(chipwait, "time", types.SimpleNamespace(
        monotonic=lambda: clock[0], sleep=None))
    assert chipwait.wait_chips_free(60.0, nodes=nodes.paths, opener=slow_open) \
        == (5.0, [nodes.paths[1]], [])
    assert nodes.all_closed()


def test_never_free_returns_at_the_bound(tmp_path):
    nodes = Nodes(tmp_path, ["0", "1"], refusals={"1": errno.EBUSY})
    waited, seen, still = chipwait.wait_chips_free(
        0.5, nodes=nodes.paths, opener=nodes)
    assert seen == still == [nodes.paths[1]]
    assert 0.5 <= waited < 0.5 + 3 * chipwait.PROBE_EVERY_S
    assert nodes.all_closed()


@pytest.mark.parametrize("code", [errno.ENOENT, errno.EACCES, errno.EPERM])
def test_any_other_error_is_not_busy(tmp_path, no_sleep, capsys, code):
    nodes = Nodes(tmp_path, ["0", "1"], refusals={"0": code})
    waited, seen, still = chipwait.wait_chips_free(
        60.0, nodes=nodes.paths, opener=nodes)
    assert (seen, still, no_sleep) == ([], [], [])
    said = capsys.readouterr().out
    assert said.count("not ours to wait for") == 1
    assert errno.errorcode[code] in said and nodes.paths[0] in said


def test_imports_neither_the_program_nor_jax():
    with open(chipwait.__file__) as f:
        source = f.read()
    imported = [line.split()[1].split(".")[0] for line in source.splitlines()
                if line.startswith(("import ", "from "))]
    assert set(imported) <= {"__future__", "errno", "glob", "os", "time"}


# ------------------------------------------- the two calls in run.py
MEASURED = {
    "correct": True, "attempted": 3, "failed": 0,
    "end_to_end": {"train_tokens_per_s": 5.0},
    "device": {"platform": "cpu", "kind": "cpu", "count": 4,
               "memory_peak_bytes": 1},
    "counters": {}, "trace_dir": None,
}


@pytest.fixture
def harness(monkeypatch):
    """``run.main`` on the tiny train cell with a made-up runner and
    made-up waits; what each was called with."""
    seen = types.SimpleNamespace(t_start=[], waits=[], answers=[])

    def fake_run(cell, conf, traffic, args, t_start):
        seen.t_start.append(t_start)
        measured = dict(MEASURED)
        measured["end_to_end"] = {**MEASURED["end_to_end"],
                                  "setup_s": 100.0 - (t_start - bench_run.T_START)}
        return measured

    seen.runner = types.SimpleNamespace(run=fake_run)

    def fake_wait(timeout_s):
        seen.waits.append(timeout_s)
        return seen.answers.pop(0)

    real_import = bench_run.importlib.import_module
    monkeypatch.setattr(
        bench_run.importlib, "import_module",
        lambda name: seen.runner if name.startswith("benchmarks.runners.")
        else real_import(name))
    monkeypatch.setattr(bench_run, "wait_chips_free", fake_wait)
    monkeypatch.setattr(bench_run, "rehearsal_environment", lambda: None)
    from ray_tpu._private import chip
    monkeypatch.setattr(chip, "holds_backend", lambda: False)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--rehearse", os.path.join(HERE, "rehearsal.json"),
        "--workload", "tiny-train", "--seed", "1", "--seconds", "1"])
    return seen


def test_seconds_waited_entering_are_not_setup(harness, capsys):
    harness.answers = [(7.0, ["/dev/vfio/3"], []), (0.02, [], [])]
    bench_run.main()
    assert harness.t_start == [bench_run.T_START + 7.0]
    assert harness.waits == [bench_run.ENTER_TIMEOUT_S, bench_run.LEAVE_TIMEOUT_S]
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[0] == ("[bench] chips free after 7.0 s "
                        "(entering; busy: /dev/vfio/3)")
    assert lines[-2] == "[bench] chips free after 0.0 s (leaving; busy: none)"
    result = json.loads(lines[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics", "device"]
    assert result["metrics"]["setup_s"]["value"] == 93.0


def test_a_chip_that_never_frees_gives_no_result(harness, capsys):
    harness.answers = [(60.0, ["/dev/vfio/3"], ["/dev/vfio/3"])]
    with pytest.raises(SystemExit) as e:
        bench_run.main()
    assert e.value.code not in (0, None) and "/dev/vfio/3" in str(e.value.code)
    assert harness.t_start == [] and len(harness.waits) == 1
    assert not any(line.startswith("{")
                   for line in capsys.readouterr().out.splitlines())


def test_a_run_that_failed_also_leaves_on_free_chips(harness, capsys):
    def boom(*a):
        raise RuntimeError("the worker died")

    harness.runner.run = boom
    harness.answers = [(0.0, [], []), (14.0, ["/dev/vfio/2"], [])]
    with pytest.raises(RuntimeError, match="the worker died"):
        bench_run.main()
    assert harness.waits == [bench_run.ENTER_TIMEOUT_S, bench_run.LEAVE_TIMEOUT_S]
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-1] == ("[bench] chips free after 14.0 s "
                         "(leaving; busy: /dev/vfio/2)")


def test_chips_still_busy_leaving_warn_and_the_result_stands(harness, capsys):
    harness.answers = [(0.0, [], []), (60.0, ["/dev/vfio/2"], ["/dev/vfio/2"])]
    bench_run.main()
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-2].startswith("[bench] WARNING: /dev/vfio/2 still busy")
    assert json.loads(lines[-1])["correct"] is True
