"""A ratio of the engine's own counters: the sum of the ``num`` keys of
``LLMEngine.stats()`` over the sum of the ``den`` keys, times ``scale``.

Read from ``counters["engine"]["traced"]`` where the server snapshots
the counters around the trace (``server_family.py``): what moved between
``start_trace`` and ``stop_trace``, so that a replica's compiles, which
lie in its first launches' seconds, are in no reading. Where the server
takes no snapshot: nothing with ``traced_only`` (a sum of seconds), the
replica's whole life without it (a ratio of counts, which a compile does
not spoil). Nothing either where a key is missing (a program that does
not count it) or the denominator is 0."""


def reduce(ctx, num: list[str], den: list[str], scale: float = 1.0,
           traced_only: bool = True):
    engine = ctx["counters"].get("engine") or {}
    counters = engine.get("traced")
    if counters is None:
        if traced_only:
            return None
        counters = engine
    if any(key not in counters for key in [*num, *den]):
        return None
    below = sum(counters[key] for key in den)
    if below <= 0:
        return None
    return scale * sum(counters[key] for key in num) / below
