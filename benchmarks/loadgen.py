"""Traffic for the serving cells: one general builder that reads a mix's
parameters from its file, and one client that offers what it built over
HTTP streaming.

A mix is data (``benchmarks/traffic/<name>.json``). Its ``kind`` names
how requests arrive, ``benchmarks/arrivals/<kind>.py`` (``build`` makes
the run's requests, ``offer`` sends them); each of its ``prompt`` and
``output`` groups names a length distribution,
``benchmarks/lengths/<dist>.py`` (``draw``). Both are found by name, so
a new kind of arrival or of length is a new file.

Arrival gaps and the list of (prompt, output) lengths are drawn once
from ``schedule_seed``, a constant in the mix's file. ``--seed`` never
resamples a gap or a length: it permutes which length pair meets which
place (within blocks of ``permute_block`` consecutive places, where the
mix names one; a block of 1 keeps the order) and makes the prompts'
token ids. So every run of a cell offers the same tokens at the same
instants.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import math
import os
import time
from dataclasses import dataclass, field

import numpy as np


@dataclass
class Request:
    index: int
    due_s: float  # open loop: when to send, relative to the window's start
    prompt_len: int
    max_tokens: int
    in_window: bool = True  # open loop: due inside the window
    # filled by the client, seconds relative to the window's start
    sent_s: float | None = None
    frame_s: list = field(default_factory=list)  # arrival of each frame
    frame_tokens: list = field(default_factory=list)  # tokens in each
    done_s: float | None = None
    error: str | None = None

    @property
    def tokens_out(self) -> int:
        return sum(self.frame_tokens)

    @property
    def ok(self) -> bool:
        """Answered to the end, with exactly the tokens asked for."""
        return (self.error is None and self.done_s is not None
                and self.tokens_out == self.max_tokens)


def arrival(traffic: dict):
    return importlib.import_module(f"benchmarks.arrivals.{traffic['kind']}")


def lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from the distribution that ``spec`` names."""
    dist = importlib.import_module(f"benchmarks.lengths.{spec['dist']}")
    return dist.draw(rng, spec, n)


def permute_in_blocks(rng, items: list[int], block: int) -> list[int]:
    """``items`` with every run of ``block`` consecutive ones permuted
    among themselves: each stretch of a schedule keeps its work, in
    another order. A block of 1 keeps the order as it is."""
    out: list[int] = []
    for lo in range(0, len(items), max(block, 1)):
        out.extend(int(x) for x in rng.permutation(items[lo: lo + block]))
    return out


def build(traffic: dict, seed: int, seconds: float,
          rate: float | None = None) -> list[Request]:
    """The requests of one run, in the order they are offered."""
    fixed = np.random.default_rng(traffic["schedule_seed"])
    mixed = np.random.default_rng(seed)
    return arrival(traffic).build(traffic, fixed, mixed, seconds, rate)


def prompt_ids(seed: int, index: int, length: int, vocab: int) -> list[int]:
    """Token ids of one prompt: from the seed and the request's place,
    over the whole vocabulary, never 0."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(1, vocab, length).tolist()


# ------------------------------------------------------------------ client
async def stream(port: int, req: Request, body: bytes, clock) -> None:
    """POST one streaming request and note when each frame arrives."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        head = (
            "POST / HTTP/1.1\r\nHost: 127.0.0.1\r\n"
            "Content-Type: application/json\r\nAccept: text/event-stream\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n"
        ).encode()
        req.sent_s = clock()
        writer.write(head + body)
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            rest = await reader.read(400)
            req.error = f"{status.decode().strip()} {rest[-200:]!r}"
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        while True:
            size_line = await reader.readline()
            size = int(size_line.strip() or b"0", 16)
            if size == 0:
                break
            chunk = await reader.readexactly(size + 2)
            now = clock()
            for line in chunk.split(b"\n"):
                if not line.startswith(b"data: "):
                    continue
                payload = line[6:].strip()
                if payload == b"[DONE]":
                    continue
                frame = json.loads(payload)
                if "error" in frame and "tokens" not in frame:
                    req.error = str(frame["error"])[:200]
                    continue
                req.frame_s.append(now)
                req.frame_tokens.append(len(frame["tokens"]))
        req.done_s = clock()
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        req.error = f"{type(e).__name__}: {e}"
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except OSError:
            pass


def body(req: Request, seed: int, vocab: int) -> bytes:
    return json.dumps({
        "prompt": prompt_ids(seed, req.index, req.prompt_len, vocab),
        "max_tokens": req.max_tokens, "temperature": 0.0, "stream": True,
    }).encode()


async def finish(tasks, timeout_s: float) -> None:
    done, pending = await asyncio.wait(tasks, timeout=max(timeout_s, 0.1))
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


def offer(port: int, traffic: dict, requests: list[Request], seed: int,
          vocab: int, seconds: float, open_at: float | None = None,
          drain_s: float = 40.0) -> float:
    """Offer ``requests`` and fill in what came back. The window opens at
    ``open_at`` on ``time.time()`` (by default ``ramp_s`` from now); the
    schedule's ramp runs before it. Returns that instant."""
    ramp = traffic["ramp_s"]
    if open_at is None:
        open_at = time.time() + ramp
    t0 = time.perf_counter() + (open_at - time.time())

    def clock() -> float:
        return time.perf_counter() - t0

    async def main():
        await asyncio.sleep(max(-ramp - clock(), 0.0))
        await arrival(traffic).offer(port, traffic, requests, seed, vocab,
                                     clock, seconds, drain_s)

    asyncio.run(main())
    return open_at


def offer_from_own_process(port: int, traffic: dict, seed: int, vocab: int,
                           seconds: float, open_at: float,
                           rate: float | None = None) -> list[Request]:
    """Build and offer a run's requests from a process of its own: one
    thread, no collector pauses, nothing of the cluster's driver in it.
    Sharing the driver's process cost the generator stalls of 50-77 ms
    (``loadgen_lag_p99_ms``; my chip runs, PR 24)."""
    import subprocess
    import sys

    job = {"port": port, "traffic": traffic, "seed": seed, "vocab": vocab,
           "seconds": seconds, "open_at": open_at, "rate": rate}
    done = subprocess.run(
        [sys.executable, "-m", "benchmarks.loadgen"], input=json.dumps(job),
        capture_output=True, text=True, check=False,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if done.returncode != 0:
        raise RuntimeError(f"the load generator failed: {done.stderr[-2000:]}")
    return [Request(**r) for r in json.loads(done.stdout)]


def _own_process_main() -> None:
    import gc
    import sys
    from dataclasses import asdict

    job = json.load(sys.stdin)
    requests = build(job["traffic"], job["seed"], job["seconds"], job["rate"])
    gc.disable()
    offer(job["port"], job["traffic"], requests, job["seed"], job["vocab"],
          job["seconds"], open_at=job["open_at"])
    json.dump([asdict(r) for r in requests], sys.stdout)


# --------------------------------------------------------------- arithmetic
def percentile(values: list[float], q: float) -> float | None:
    """The smallest value with at least ``q`` percent of the sample at
    or below it (nearest rank)."""
    if not values:
        return None
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def ttfts_ms(requests, beyond_ms: float = 1e9) -> list[float]:
    """Time from when each window request was due to its first frame; a
    request that failed or never answered counts as beyond any limit."""
    out = []
    for r in requests:
        if not r.in_window:
            continue
        if r.error is not None or not r.frame_s:
            out.append(beyond_ms)
        else:
            out.append((r.frame_s[0] - r.due_s) * 1e3)
    return out


def token_gaps_ms(requests, lo_s: float, hi_s: float) -> list[float]:
    """Gaps between consecutive streamed tokens of a request, for every
    gap that ends inside [lo_s, hi_s). A frame that carries n tokens
    after a gap g is n gaps of g / n (the first frame of a request
    carries its first two tokens: the prefill's and the same step's
    decode)."""
    out = []
    for r in requests:
        for prev, now, n in zip(r.frame_s, r.frame_s[1:], r.frame_tokens[1:]):
            if lo_s <= now < hi_s and n > 0:
                out.extend([(now - prev) / n * 1e3] * n)
    return out


def completed_tokens(requests, lo_s: float, hi_s: float) -> int:
    """Prompt plus generated tokens of the requests that completed, whole
    and correct, inside [lo_s, hi_s)."""
    return sum(
        r.prompt_len + r.tokens_out for r in requests
        if r.ok and r.done_s is not None and lo_s <= r.done_s < hi_s
    )


def window_tokens(requests, lo_s: float, hi_s: float) -> float:
    """Prompt plus generated tokens that the system processed inside
    [lo_s, hi_s), as a client can tell, of the requests that came back
    whole. Generated tokens count when their frame arrives. A prompt's
    tokens count evenly over the time from the request's sending to its
    first frame, which is when its prefill ran: so a request that
    straddles either end of the window brings the part of its prompt
    that fell inside. (Counting a prompt whole at its first frame, or a
    request whole at its end, makes the sum step by 2-5% of a window of
    long prompts when one event falls just inside or outside.) A request
    that failed, or never ended, brings nothing: a hang shows here as
    the window's remaining time with no tokens."""
    total = 0.0
    for r in requests:
        if not r.ok or not r.frame_s:
            continue
        start, first = r.sent_s, r.frame_s[0]
        if first > start:
            inside = min(first, hi_s) - max(start, lo_s)
            total += r.prompt_len * max(inside, 0.0) / (first - start)
        elif lo_s <= first < hi_s:
            total += r.prompt_len
        total += sum(n for t, n in zip(r.frame_s, r.frame_tokens, strict=True)
                     if lo_s <= t < hi_s)
    return total


if __name__ == "__main__":
    # Through the package's module, so that the arrival kinds and this
    # process share one ``Request``.
    importlib.import_module("benchmarks.loadgen")._own_process_main()
