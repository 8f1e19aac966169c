"""Times of the executions of the programs whose name contains
``match``, from the device's ``XLA Modules`` line.

``stat``: ``median_device_ms`` (one execution's device time),
``median_start_to_start_ms`` (distance between consecutive executions'
starts, where the next follows within ``max_gap_ms``: a step's wall
time as the device sees it), or ``window_share_pct`` (all executions'
device time over the traced window)."""

import statistics

from benchmarks import traceread


def reduce(ctx, match: str, stat: str, max_gap_ms: float = 1000.0):
    runs = [r for r in traceread.program_runs(ctx["events"], match) if r]
    if not runs:
        return None
    if stat == "median_device_ms":
        value = statistics.median(p.dur for r in runs for p in r)
    elif stat == "median_start_to_start_ms":
        gaps = [
            b.start - a.start
            for r in runs for a, b in zip(r, r[1:])
            if (b.start - a.start) * 1e3 <= max_gap_ms
        ]
        value = statistics.median(gaps) if gaps else None
    elif stat == "window_share_pct":
        _, window = traceread.busy_and_window(ctx["events"])
        if window <= 0:
            return None
        total = sum(p.dur for r in runs for p in r) / len(runs)
        return 100.0 * total / window
    else:
        raise ValueError(f"unknown stat {stat!r}")
    return None if value is None else value * 1e3
