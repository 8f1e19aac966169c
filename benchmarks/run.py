#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration in
``benchmarks/configs/<config>.json`` and its traffic in
``benchmarks/traffic/<traffic>.json``; the configuration names its
runner (``benchmarks/runners/<runner>.py``). The runner drives the system
through its normal entry points and returns what it measured; with
``--trace 1`` each per-layer metric of the cell is then read by its own
file, ``benchmarks/layer_metrics/<metric>.json``, which names a reducer
(``benchmarks/reducers/<reducer>.py``). Adding a configuration, a mix, a
metric or a reducer is adding files and an entry in ``BENCHMARK.json``;
nothing here changes (``benchmarks/README.md``).

This process stays off JAX's backends: the chip belongs to the leased
worker or replica. No chip, no number: the exit code is then not 0 and
no result is printed. The last line of standard output is the one JSON
object of the contract; everything else is printed before it.

A run enters and leaves on free chips (``benchmarks/chipwait.py``). After
the cell is loaded and before the runner starts, and again after the
runner has shut the cluster down and before the result is printed, this
process asks the chip's device nodes themselves whether they open: the
kernel goes on closing a dead holder's chips for 14-23 s after its pid is
gone, ``open()`` is the only thing that answers, and a four-chip run
started into that fails at ``jax.devices()``. Each wait prints one
``[bench] chips free after <s> s`` line. The entering wait is at most
``ENTER_TIMEOUT_S``; when it runs out the run exits non-zero, naming the
busy nodes, and prints no result. Those seconds are somebody else's
chips, not this run's start-up: ``setup_s`` starts at ``T_START`` plus
the seconds waited entering (on free chips, one probe's ~20 ms), and
the runners get that as their ``t_start``. The leaving wait, at most
``LEAVE_TIMEOUT_S``, is made also when the run failed; when it runs out
a warning is printed and the result still is, since the measurement is
whole. It keeps the next run's entering wait near zero and protects a
next run that does not ask.

``--rehearse <file>`` runs a cell of another list (the tiny ones under
``benchmarks/tests/``) on fake chips on the CPU, to rehearse the harness.
It cannot name a cell of ``BENCHMARK.json``.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# Run as a script, sys.path[0] is this directory, whose file names could
# shadow the standard library's in every worker that inherits the path.
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

from benchmarks.chipwait import wait_chips_free  # noqa: E402

# How long a run waits for the chip's device nodes to open, at each end.
ENTER_TIMEOUT_S = 60.0
LEAVE_TIMEOUT_S = 60.0


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(listing: dict, name: str) -> dict:
    for cell in listing["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in listing["workloads"]]
    sys.exit(f"benchmarks/run.py: no workload {name!r}; known: {known}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_metrics(listing, cell, measured, conf, traffic):
    """Read each of the cell's per-layer metrics with its own reducer.
    A reader that finds nothing to read returns None and the metric is
    left out."""
    from benchmarks import traceread

    events = []
    if measured.get("trace_dir"):
        path = traceread.find_trace_file(measured["trace_dir"])
        if path is None:
            print("[bench] no trace file was written")
        else:
            events = traceread.read_events(path)
            print(f"[bench] trace {os.path.relpath(path, ROOT)}: "
                  f"{len(events)} device events")
    ctx = {
        "events": events,
        "counters": measured["counters"],
        "device": measured["device"],
        "config": conf,
        "traffic": traffic,
    }
    out = {}
    for metric in listing["per_layer"]:
        if not applies(metric, cell["name"]):
            continue
        spec = load_json(HERE, "layer_metrics", f"{metric['name']}.json")
        reducer = importlib.import_module(
            f"benchmarks.reducers.{spec['reducer']}"
        )
        value = reducer.reduce(ctx, **spec.get("args", {}))
        if value is None:
            print(f"[bench] {metric['name']}: nothing to read")
            continue
        out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    busy, window = traceread.busy_and_window(events)
    breakdown = {
        "device_ops": traceread.top_ops(events),
        "idle_gaps": traceread.idle_gaps(events),
    }
    return out, {"busy_s": busy, "window_s": window}, breakdown


def rehearsal_environment() -> None:
    """Leases of fake chips schedule like TPU leases and compute on the
    CPU (ray_tpu/_private/chip.py); four virtual devices for the
    sharded path."""
    os.environ["RAY_TPU_FAKE_CHIPS"] = "4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    )


def load_cell(workload: str, rehearse: str | None):
    """The listing, the cell and its configuration and traffic files.
    With ``rehearse``, from that listing and its directory, on the CPU."""
    if rehearse:
        listing = load_json(rehearse)
        data_root = os.path.dirname(os.path.abspath(rehearse))
        official = load_json(ROOT, "BENCHMARK.json")["workloads"]
        if workload in [c["name"] for c in official]:
            sys.exit("benchmarks/run.py: --rehearse cannot run a cell of "
                     "BENCHMARK.json")
        rehearsal_environment()
    else:
        listing = load_json(ROOT, "BENCHMARK.json")
        data_root = HERE
    cell = find_cell(listing, workload)
    conf = load_json(data_root, "configs", f"{cell['config']}.json")
    traffic = load_json(data_root, "traffic", f"{cell['traffic']}.json")
    if conf["chips"] != cell["chips"]:
        sys.exit(f"configuration {cell['config']} is laid out for "
                 f"{conf['chips']} chips, the cell asks for {cell['chips']}")
    return listing, cell, conf, traffic


def chips_free(end: str, timeout_s: float) -> tuple[float, str]:
    """Wait until the chip's device nodes open and say how long it took.
    The seconds waited, and the nodes still busy when the time ran out."""
    waited, seen, still = wait_chips_free(timeout_s)
    print(f"[bench] chips free after {waited:.1f} s ({end}; busy: "
          f"{' '.join(seen) or 'none'})", flush=True)
    return waited, " ".join(still)


def result_line(listing, cell, conf, traffic, args, measured) -> dict:
    """The one JSON object of the contract, from what the runner measured."""
    from ray_tpu._private import chip

    if chip.holds_backend():
        sys.exit("benchmarks/run.py: this process opened a JAX backend")
    device = dict(measured["device"])
    result = {
        "correct": measured["correct"],
        "attempted": measured["attempted"],
        "failed": measured["failed"],
    }
    if args.trace:
        metrics, traced, breakdown = per_layer_metrics(
            listing, cell, measured, conf, traffic
        )
        device.update(traced)
        result["breakdown"] = breakdown
    else:
        metrics = {}
        for metric in listing["end_to_end"]:
            value = measured["end_to_end"].get(metric["name"])
            if applies(metric, cell["name"]) and value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
    if measured["counters"].get("compiles_in_window"):
        print("[bench] WARNING: compiled inside the window, the warm-up "
              f"missed a shape: {measured['counters'].get('compiled_in_window')}")
    result["metrics"] = metrics
    result["device"] = device
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", metavar="LISTING",
                    help="a listing of tiny cells to run on the CPU")
    args = ap.parse_args()

    listing, cell, conf, traffic = load_cell(args.workload, args.rehearse)

    # Seconds spent waiting for somebody else's chips are not set-up.
    waited, busy = chips_free("entering", ENTER_TIMEOUT_S)
    if busy:
        sys.exit(f"benchmarks/run.py: {busy} still busy after {waited:.1f} s; "
                 "no free chip to measure on")
    runner = importlib.import_module(f"benchmarks.runners.{conf['runner']}")
    try:
        measured = runner.run(cell, conf, traffic, args, T_START + waited)
        result = result_line(listing, cell, conf, traffic, args, measured)
    finally:
        # The runner has shut the cluster down; a run that failed waits too.
        _, busy = chips_free("leaving", LEAVE_TIMEOUT_S)
        if busy:
            print(f"[bench] WARNING: {busy} still busy; the next run will "
                  "find them so", flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
