"""Own device time of the operations whose HLO text contains ``match``
(a string, or a list of strings that must all occur), as a
share of the time the chip was busy (``over: busy``) or of the traced
window (``over: window``)."""

from benchmarks import traceread


def reduce(ctx, match, over: str = "busy"):
    busy, window = traceread.busy_and_window(ctx["events"])
    base = {"busy": busy, "window": window}[over]
    if base <= 0:
        return None
    return 100.0 * traceread.op_seconds(ctx["events"], match) / base
