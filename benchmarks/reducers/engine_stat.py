"""A counter the engine keeps itself: ``LLMEngine.stats()[key]``, which
the serving runner's record carries whole as ``counters["engine"]``."""


def reduce(ctx, key: str):
    return ctx["counters"].get("engine", {}).get(key)
