"""Closed loop: ``clients`` callers each send their next request when
the one before is answered, taking requests in order from a list of
``requests`` (after the last, the list again, unless the mix says
``once``), from ``ramp_s`` before the window until it closes. ``--seed``
permutes the list's order within blocks of ``permute_block``."""

import asyncio

from benchmarks import loadgen


def build(traffic: dict, fixed, mixed, seconds: float,
          rate: float | None = None) -> list[loadgen.Request]:
    n = traffic["requests"]
    prompts = loadgen.lengths(fixed, traffic["prompt"], n)
    outputs = loadgen.lengths(fixed, traffic["output"], n)
    order = loadgen.permute_in_blocks(
        mixed, list(range(n)), traffic.get("permute_block", n)
    )
    return [
        loadgen.Request(i, 0.0, int(prompts[j]), int(outputs[j]))
        for i, j in enumerate(order)
    ]


async def offer(port, traffic, requests, seed, vocab, clock, seconds,
                drain_s) -> None:
    base = list(requests)
    once = traffic.get("once", False)
    taken = 0

    def take() -> loadgen.Request | None:
        """The next request of the list; after the last, the list again
        (appended to ``requests`` so that the summary sees it), or None
        where the mix says ``once``."""
        nonlocal taken
        if taken >= len(requests):
            if once:
                return None
            again = base[taken % len(base)]
            requests.append(
                loadgen.Request(taken, 0.0, again.prompt_len, again.max_tokens)
            )
        taken += 1
        return requests[taken - 1]

    async def client():
        # Stop taking requests when the window closes; finish the one
        # in hand.
        while clock() < seconds:
            req = take()
            if req is None:
                return
            await loadgen.stream(
                port, req, loadgen.body(req, seed, vocab), clock
            )

    tasks = [asyncio.ensure_future(client()) for _ in range(traffic["clients"])]
    await loadgen.finish(tasks, seconds + drain_s - clock())
