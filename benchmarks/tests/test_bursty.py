"""The bursty arrival kind (``arrivals/bursty.py``) and the mix that
uses it, ``chat-burst``."""

import json
import os

import numpy as np
import pytest

from benchmarks import loadgen

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


def test_two_seeds_offer_the_same_tokens_at_the_same_instants():
    traffic = mix("chat-burst")
    a = loadgen.build(traffic, 1, 30.0)
    b = loadgen.build(traffic, 3000000001, 30.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    pairs = lambda rs: sorted((r.prompt_len, r.max_tokens) for r in rs)  # noqa: E731
    assert pairs(a) == pairs(b)
    order = lambda rs: [(r.prompt_len, r.max_tokens) for r in rs]  # noqa: E731
    assert order(a) != order(b)  # permuted, within blocks
    assert loadgen.build(traffic, 1, 30.0) == a
    block = traffic["permute_block"]
    wa = [r for r in a if r.in_window]
    wb = [r for r in b if r.in_window]
    for lo in range(0, len(wa), block):
        assert pairs(wa[lo: lo + block]) == pairs(wb[lo: lo + block])
    ramp = lambda rs: [(r.prompt_len, r.max_tokens) for r in rs if not r.in_window]  # noqa: E731
    assert ramp(a) == ramp(b) and ramp(a)


def test_chat_burst_is_chat_open_but_for_its_gaps():
    burst, steady = mix("chat-burst"), mix("chat-open")
    for key in ("rate_rps", "ramp_s", "prompt", "output", "schedule_seed",
                "warm_prompt_lengths", "permute_block"):
        assert burst[key] == steady[key], key
    assert burst["kind"] == "bursty" and burst["gap_cv"] == 3.0


@pytest.mark.parametrize("cv", [1.0, 3.0])
def test_gaps_have_the_stated_mean_and_spread(cv):
    traffic = {**mix("chat-burst"), "gap_cv": cv}
    due = np.array([r.due_s for r in loadgen.build(traffic, 1, 1500.0)])
    gaps = np.diff(due)
    assert len(gaps) > 4000
    assert gaps.mean() == pytest.approx(1 / traffic["rate_rps"], rel=0.1)
    assert gaps.std() / gaps.mean() == pytest.approx(cv, rel=0.15)


def test_a_longer_window_extends_the_schedule_and_rate_scales_it():
    traffic = mix("chat-burst")
    short = loadgen.build(traffic, 1, 10.0)
    long = loadgen.build(traffic, 1, 30.0)
    assert [r.due_s for r in long[: len(short)]] == [r.due_s for r in short]
    fast = loadgen.build(traffic, 1, 30.0, rate=2 * traffic["rate_rps"])
    ramp = traffic["ramp_s"]
    assert (fast[5].due_s + ramp) == pytest.approx((long[5].due_s + ramp) / 2)
