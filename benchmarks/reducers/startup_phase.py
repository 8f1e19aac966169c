"""A start-up phase in seconds, read from what the program recorded of
itself: ``ray_tpu.util.state.last_startup_report()``, the report that
``ray_tpu.shutdown()`` keeps of every process's ``startup:*`` and
``compile:*`` spans. The runner has shut the cluster down by the time a
reducer runs, in this same process, so nothing is measured again here.
A program that keeps no such report (the parent of the PR that added
the spans), or a report without the span asked for, gives None.

One of:

``span``, ``of`` ("driver", or "worker": the one that held the TPU
lease), ``plus`` (further spans of the same process, added where
present): the span's seconds.

``begin`` and ``until``, each ``[of, span, "start" | "end"]``: the
seconds from one instant to the other.

``compiles``: ``"trace_lower_s"``, the seconds the chip-holding worker
spent tracing and lowering, over every compile request of its life; or
``"cache_hit_pct"``, the share of those requests that the persistent
cache answered.

``union``: the length of the union of every ``startup:*`` and
``compile:*`` span of every process: the part of set-up during which
some part of the program was starting or compiling.
"""


def last_report():
    from ray_tpu.util import state

    read = getattr(state, "last_startup_report", None)
    return read() if read else None


def chip_worker(report: dict):
    """The worker whose lease held chips; of several, the newest lease."""
    held = [
        w for w in report["workers"]
        if w.get("tpu") and "startup:lease" in w["spans"]
    ]
    return max(
        held, key=lambda w: w["spans"]["startup:lease"]["ts"], default=None
    )


def find(report: dict, of: str, name: str):
    """The span called ``name`` of the driver or of the chip-holding
    worker; of a driver's several ``startup:entry/<entry>``, the
    newest."""
    process = report.get("driver") if of == "driver" else chip_worker(report)
    if not process:
        return None
    named = [
        s for key, s in process["spans"].items()
        if key == name or key.startswith(name + "/")
    ]
    return max(named, key=lambda s: s["ts"], default=None)


def instant(report: dict, of: str, name: str, which: str):
    span = find(report, of, name)
    if span is None:
        return None
    return span["ts"] + (span["dur"] if which == "end" else 0.0)


def union_s(report: dict) -> float | None:
    processes = [report.get("driver") or {"spans": {}}, *report["workers"]]
    spans = sorted(
        (s["ts"], s["ts"] + s["dur"])
        for p in processes
        for s in (*p["spans"].values(), *p.get("compile_spans", ()))
    )
    if not spans:
        return None
    total, (lo, hi) = 0.0, spans[0]
    for start, end in spans[1:]:
        if start > hi:
            total, lo, hi = total + hi - lo, start, end
        else:
            hi = max(hi, end)
    return total + hi - lo


def reduce(ctx, span: str | None = None, of: str = "worker",
           plus: tuple = (), begin=None, until=None,
           compiles: str | None = None, union: bool = False):
    report = last_report()
    if report is None:
        return None
    if union:
        return union_s(report)
    if compiles is not None:
        worker = chip_worker(report)
        totals = worker["compiles"] if worker else None
        if not totals or not totals["requests"]:
            return None
        if compiles == "cache_hit_pct":
            return 100.0 * totals["cache_hits"] / totals["requests"]
        return totals["trace_s"] + totals["lower_s"]
    if begin is not None:
        a, b = instant(report, *begin), instant(report, *until)
        return None if a is None or b is None else b - a
    found = find(report, of, span)
    if found is None:
        return None
    more = (find(report, of, name) for name in plus)
    return found["dur"] + sum(s["dur"] for s in more if s is not None)
