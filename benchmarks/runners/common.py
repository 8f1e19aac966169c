"""What both runners need of the cluster and the checkout."""

from __future__ import annotations

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# Traces and other run-time output; inside the checkout, git-ignored.
OUT = os.path.join(ROOT, ".bench_out")


def require_chips(chips: int, rehearsal: bool) -> None:
    """Exit non-zero, printing no result, unless the node found
    ``chips`` real chips. A rehearsal runs on fake chips, on the CPU."""
    import ray_tpu

    found = int(ray_tpu.cluster_resources().get("TPU", 0))
    if found < chips:
        ray_tpu.shutdown()
        sys.exit(
            f"benchmarks/run.py: this node registered TPU: {found} and the "
            f"cell needs {chips}; no accelerator to measure on"
        )
    if rehearsal:
        print("[bench] REHEARSAL on platform cpu: no number below is a "
              "device number")


def trace_plan(cell: str, args) -> dict:
    """Where the chip-holding process writes its trace, and which part
    of the window it traces: a few seconds after the start, so that the
    trace stays small and the window around it is undisturbed."""
    path = os.path.join(OUT, cell, "trace")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    seconds = min(6.0, max(args.seconds - 2.0, 1.0))
    start = min(2.0, max(args.seconds - seconds, 0.0))
    return {"dir": path, "start_s": start, "seconds": seconds}


def wait_chip_free(timeout_s: float = 60.0) -> None:
    """Until every chip-holding worker process is gone."""
    import ray_tpu

    rt = ray_tpu.api._runtime
    total = ray_tpu.cluster_resources().get("TPU", 0)
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        workers = rt.run(rt.core.node.call("list_workers"))["workers"]
        held = [w for w in workers if w["platform"] == "tpu"]
        if not held and ray_tpu.available_resources().get("TPU", 0) == total:
            return
        time.sleep(0.1)
    raise RuntimeError(f"chip-holding workers still alive: {held}")
