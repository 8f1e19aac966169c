"""Share of the traced window in which no operation ran on the chip:
1 - union of the device operations' intervals over the window, averaged
over the chips."""

from benchmarks import traceread


def reduce(ctx):
    busy, window = traceread.busy_and_window(ctx["events"])
    if window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
