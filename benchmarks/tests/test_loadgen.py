"""The traffic builder and the latency arithmetic."""

import json
import os

import pytest

from benchmarks import loadgen
from benchmarks.loadgen import Request

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat-open", "doc-prefill"])
def test_two_seeds_offer_the_same_tokens_at_the_same_instants(name):
    traffic = mix(name)
    a = loadgen.build(traffic, 1, 30.0)
    b = loadgen.build(traffic, 3000000001, 30.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    pairs = lambda rs: sorted((r.prompt_len, r.max_tokens) for r in rs)  # noqa: E731
    assert pairs(a) == pairs(b)
    order = lambda rs: [(r.prompt_len, r.max_tokens) for r in rs]  # noqa: E731
    # A block of 1 keeps the order: the seed then changes ids and
    # weights only (doc-prefill; its file says why).
    assert (order(a) == order(b)) == (traffic.get("permute_block") == 1)
    assert loadgen.build(traffic, 1, 30.0) == a
    assert loadgen.prompt_ids(1, 0, 64, 32768) != loadgen.prompt_ids(
        3000000001, 0, 64, 32768
    )


def test_pairs_move_only_within_their_block():
    traffic = mix("chat-open")
    block = traffic["permute_block"]
    a = [r for r in loadgen.build(traffic, 1, 30.0) if r.in_window]
    b = [r for r in loadgen.build(traffic, 2, 30.0) if r.in_window]
    for lo in range(0, len(a), block):
        pairs = lambda rs: sorted((r.prompt_len, r.max_tokens) for r in rs[lo: lo + block])  # noqa: E731
        assert pairs(a) == pairs(b)
    whole = {**traffic, "permute_block": len(a)}
    c = [r for r in loadgen.build(whole, 2, 30.0) if r.in_window]
    assert [r.prompt_len for r in c[:block]] != [r.prompt_len for r in a[:block]]


def test_open_loop_permutes_only_inside_the_window():
    traffic = mix("chat-open")
    a = loadgen.build(traffic, 1, 30.0)
    b = loadgen.build(traffic, 2, 30.0)
    ramp = lambda rs: [(r.prompt_len, r.max_tokens) for r in rs if not r.in_window]  # noqa: E731
    assert ramp(a) == ramp(b) and ramp(a)
    assert all(r.due_s < 0 for r in a if not r.in_window)
    assert all(0 <= r.due_s < 30.0 for r in a if r.in_window)
    lo, hi = traffic["prompt"]["lo"], traffic["prompt"]["hi"]
    assert all(lo <= r.prompt_len <= hi for r in a)


def test_a_longer_window_extends_the_schedule():
    traffic = mix("chat-open")
    short = loadgen.build(traffic, 1, 10.0)
    long = loadgen.build(traffic, 1, 30.0)
    assert [r.due_s for r in long[: len(short)]] == [r.due_s for r in short]


def test_rate_scales_the_same_gaps():
    traffic = mix("chat-open")
    base = loadgen.build(traffic, 1, 30.0)
    fast = loadgen.build(traffic, 1, 30.0, rate=2 * traffic["rate_rps"])
    ramp = traffic["ramp_s"]
    assert (fast[5].due_s + ramp) == pytest.approx((base[5].due_s + ramp) / 2)


def test_closed_loop_lengths_are_snapped():
    traffic = mix("doc-prefill")
    snap = traffic["prompt"]["snap"]
    reqs = loadgen.build(traffic, 5, 30.0)
    assert len(reqs) == traffic["requests"]
    assert all(r.prompt_len % snap == 0 for r in reqs)
    assert {r.max_tokens for r in reqs} == {32}


def test_prompt_ids_follow_the_seed():
    a = loadgen.prompt_ids(3000000001, 4, 100, 32768)
    assert a == loadgen.prompt_ids(3000000001, 4, 100, 32768)
    assert a != loadgen.prompt_ids(3000000002, 4, 100, 32768)
    assert len(a) == 100 and min(a) >= 1 and max(a) < 32768


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert loadgen.percentile(values, 90) == 90
    assert loadgen.percentile(values, 50) == 50
    assert loadgen.percentile([5.0], 90) == 5.0
    assert loadgen.percentile([], 90) is None


def done(index, due, frames, tokens, **kw):
    r = Request(index, due, 10, sum(tokens), **kw)
    r.sent_s, r.frame_s, r.frame_tokens = due, frames, tokens
    r.done_s = frames[-1]
    return r


def test_ttft_is_timed_from_the_due_instant():
    late = done(0, 1.0, [1.5, 1.6], [2, 1])
    late.sent_s = 1.2  # the generator was late: the request still waited
    failed = Request(1, 2.0, 10, 3, error="boom")
    ramp = done(2, -1.0, [0.5], [3], in_window=False)
    assert loadgen.ttfts_ms([late, failed, ramp], beyond_ms=1e9) == [
        pytest.approx(500.0), 1e9,
    ]


def test_token_gaps_split_a_frame_of_several_tokens():
    r = done(0, 0.0, [1.0, 1.1, 1.4, 9.0], [2, 1, 3, 1])
    gaps = loadgen.token_gaps_ms([r], 0.0, 5.0)
    # 0.1 s for one token, then 0.3 s shared by three; the last frame
    # lands outside the window.
    assert gaps == pytest.approx([100.0, 100.0, 100.0, 100.0])


def test_window_tokens_count_what_was_processed_inside_the_window():
    # Sent at 0, first frame at 2: half of the 10 prompt tokens fall in
    # [1, 5); the frames at 2 and 4 are inside, the one at 6 is not.
    r = done(0, 0.0, [2.0, 4.0, 6.0], [2, 1, 1])
    assert loadgen.window_tokens([r], 1.0, 5.0) == pytest.approx(5 + 3)
    assert loadgen.window_tokens([r], 0.0, 7.0) == pytest.approx(10 + 4)
    # Everything before the window, or after it, brings nothing.
    assert loadgen.window_tokens([r], 6.5, 9.0) == 0.0
    assert loadgen.window_tokens([r], -3.0, 0.0) == 0.0


@pytest.mark.parametrize("how", ["error", "short", "never_ended"])
def test_a_request_that_did_not_come_back_whole_brings_no_tokens(how):
    r = done(0, 0.0, [1.0, 2.0], [1, 2])
    if how == "error":
        r.error = "boom"
    elif how == "short":
        r.max_tokens = 5
    else:
        r.done_s = None  # cancelled at the drain: the engine hung
    assert not r.ok
    assert loadgen.window_tokens([r], 0.0, 5.0) == 0.0


def test_a_hang_lowers_the_rate_over_the_whole_window():
    early = [done(i, float(i), [i + 0.5, i + 1.0], [1, 1]) for i in range(4)]
    hung = done(9, 4.0, [4.5], [1])
    hung.done_s, hung.max_tokens = None, 2
    rate = loadgen.window_tokens(early + [hung], 0.0, 30.0) / 30.0
    assert rate == pytest.approx(4 * 12 / 30.0)


def test_completed_tokens_counts_whole_requests_inside_the_window():
    inside = done(0, 0.0, [1.0, 2.0], [1, 2])
    outside = done(1, 0.0, [1.0, 6.0], [1, 2])
    short = done(2, 0.0, [1.0], [1])
    short.max_tokens = 3
    assert loadgen.completed_tokens([inside, outside, short], 0.0, 5.0) == 13
