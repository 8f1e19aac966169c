"""A number the runner took on the host's clock or counted: its record's
``counters[key]``."""


def reduce(ctx, key: str):
    return ctx["counters"].get(key)
