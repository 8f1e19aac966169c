"""Open loop: requests are sent when they are due, whatever the server
is doing. Exponential gaps at ``rate_rps``; the schedule starts
``ramp_s`` before the window so that the window opens on a system
already under load. ``--seed`` permutes which length pair meets which
slot, among the slots due inside the window only."""

import asyncio

import numpy as np

from benchmarks import loadgen

# Gaps and lengths are drawn for this many slots whatever the window's
# length, so that a longer window extends a schedule and does not change it.
MAX_SLOTS = 8192


def build(traffic: dict, fixed, mixed, seconds: float,
          rate: float | None = None) -> list[loadgen.Request]:
    rate = rate or traffic["rate_rps"]
    gaps = fixed.exponential(1.0, MAX_SLOTS)
    prompts = loadgen.lengths(fixed, traffic["prompt"], MAX_SLOTS)
    outputs = loadgen.lengths(fixed, traffic["output"], MAX_SLOTS)
    due = np.cumsum(gaps) / rate - traffic["ramp_s"]
    n = int(np.searchsorted(due, seconds))
    if n >= MAX_SLOTS:
        raise ValueError("the window needs more slots than MAX_SLOTS")
    inside = [i for i in range(n) if due[i] >= 0]
    pair_of = list(range(n))
    moved = loadgen.permute_in_blocks(
        mixed, inside, traffic.get("permute_block", len(inside))
    )
    for slot, pair in zip(inside, moved, strict=True):
        pair_of[slot] = pair
    return [
        loadgen.Request(i, float(due[i]), int(prompts[pair_of[i]]),
                        int(outputs[pair_of[i]]), in_window=bool(due[i] >= 0))
        for i in range(n)
    ]


async def offer(port, traffic, requests, seed, vocab, clock, seconds,
                drain_s) -> None:
    bodies = [loadgen.body(r, seed, vocab) for r in requests]  # before any is due
    tasks = []
    for req, body in zip(requests, bodies, strict=True):
        wait = req.due_s - clock()
        if wait > 0:
            await asyncio.sleep(wait)
        tasks.append(
            asyncio.ensure_future(loadgen.stream(port, req, body, clock))
        )
    await loadgen.finish(tasks, seconds + drain_s - clock())
