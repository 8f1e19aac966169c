"""Device-idle time by the host span it fell in (``benchmarks/hostspans.py``):
the instants in which no operation ran on the chip, intersected with the
innermost ``engine:*`` span of the step's thread, or with ``between``
(outside every ``engine:step``, between two steps of a running pump:
the later one found requests in hand).

``spans``: the names to sum, ``between`` among them, or ``*`` for all
that has a name. ``per``: ``step`` gives milliseconds per
``engine:step`` of the traced window, ``idle`` a percentage of all the
window's idle time."""

from benchmarks import hostspans


def reduce(ctx, spans, per: str = "step"):
    split = hostspans.idle_split(ctx)
    if split is None:
        return None
    seconds = split.seconds(spans)
    if per == "step":
        return 1e3 * seconds / split.steps
    if per == "idle":
        return 100.0 * seconds / split.idle if split.idle > 0 else None
    raise ValueError(f"unknown per {per!r}")
