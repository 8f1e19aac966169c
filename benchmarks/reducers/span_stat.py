"""A statistic over the host spans called ``span`` (a name or a list of
names; ``benchmarks/hostspans.py``): of their durations in milliseconds,
or of the attribute ``attr`` where they carry it, divided by the
attribute ``over`` if given and multiplied by ``scale``. ``holding``
keeps only the spans that hold a span of that name directly.

``stat``: ``median``, ``p90``, ``mean``, or ``sum_per_step`` (the sum
over the number of ``engine:step`` spans in the trace)."""

from benchmarks import hostspans


def reduce(ctx, span, stat: str, attr: str | None = None,
           over: str | None = None, holding: str | None = None,
           scale: float = 1.0):
    spans = hostspans.spans_of(ctx)
    chosen = hostspans.named(
        hostspans.with_child(spans, holding) if holding else spans, span
    )
    values = []
    for s in chosen:
        if attr is None:
            values.append(s.dur * 1e3 * scale)
        elif attr in s.attrs and (over is None or s.attrs.get(over)):
            value = float(s.attrs[attr])
            values.append(scale * (value / s.attrs[over] if over else value))
    if stat != "sum_per_step":
        return hostspans.statistic(values, stat)
    steps = len(hostspans.named(spans, hostspans.STEP))
    return sum(values) / steps if steps else None
