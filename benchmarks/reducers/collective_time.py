"""Seconds a collective was under way on a chip, as a share of the
traced window; with ``exposed``, only the seconds in which a collective
operation held the chip and nothing else ran on it."""

from benchmarks import traceread


def reduce(ctx, exposed: bool = False):
    _, window = traceread.busy_and_window(ctx["events"])
    if window <= 0:
        return None
    return 100.0 * traceread.collective_seconds(ctx["events"], exposed) / window
