"""What the serving runner makes of a run's requests: which runs are
correct, and what a hang does to the numbers."""

import pytest

from benchmarks.loadgen import Request
from benchmarks.runners import serve

CHECK = {"finite": True, "logit_max_abs_err": [0.04]}
COUNTERS = {"compiles": [], "first_line_at": 5.0, "engine": {}, "device": {}}
CLOSED = {"kind": "closed_loop", "clients": 2, "ramp_s": 1.0}
OPEN = {"kind": "open_loop", "rate_rps": 1.0, "ramp_s": 1.0}


def answered(index, sent, frames, tokens=None, **kw):
    tokens = tokens or [1] * len(frames)
    r = Request(index, kw.pop("due", sent), 100, sum(tokens), **kw)
    r.sent_s, r.frame_s, r.frame_tokens = sent, frames, tokens
    r.done_s = frames[-1]
    return r


def summary(traffic, requests, seconds=10.0):
    return serve.summarize(
        {}, traffic, requests, seconds, CHECK, COUNTERS, t_start=0.0,
        called_at=1.0, ready_at=8.0, opened_at=20.0, trace=None,
    )


def test_a_closed_loop_run_whose_requests_all_came_back_is_correct():
    reqs = [answered(i, i * 2.0, [i * 2.0 + 1.0, i * 2.0 + 1.5])
            for i in range(5)]
    reqs.append(Request(5, 0.0, 100, 2))  # built, never taken: not sent
    out = summary(CLOSED, reqs)
    assert out["correct"] and out["attempted"] == 5 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] == pytest.approx(5 * 102 / 10)
    assert out["end_to_end"]["setup_s"] == 20.0
    assert "ttft_p90_ms" not in out["end_to_end"]


@pytest.mark.parametrize("traffic", [CLOSED, OPEN], ids=["closed", "open"])
def test_a_request_that_never_ended_makes_the_run_not_correct(traffic):
    """The engine hangs at 4 s: the request in hand gets a first frame
    and no end, and the client gives up at the drain."""
    reqs = [answered(0, 0.0, [1.0, 1.5]), answered(1, 2.0, [3.0, 3.5])]
    hung = Request(2, 4.0, 100, 2)
    hung.sent_s, hung.frame_s, hung.frame_tokens = 4.0, [4.5], [1]
    out = summary(traffic, reqs + [hung])
    assert not out["correct"]
    assert (out["attempted"], out["failed"]) == (3, 1)
    # The healthy 4 s are spread over the whole window of 10 s.
    assert out["end_to_end"]["serve_tokens_per_s"] == pytest.approx(2 * 102 / 10)


def test_a_short_answer_makes_the_run_not_correct():
    short = answered(0, 0.0, [1.0], [1])
    short.max_tokens = 3
    assert not summary(CLOSED, [short])["correct"]


def test_open_loop_tails_are_over_the_requests_due_in_the_window():
    ramp = answered(0, -0.5, [0.2, 0.3], in_window=False)
    a = answered(1, 1.0, [1.1, 1.2])
    b = answered(2, 2.1, [2.4, 2.5], due=2.0)  # sent late: timed from due
    out = summary(OPEN, [ramp, a, b])
    assert out["correct"] and out["attempted"] == 3
    assert out["end_to_end"]["ttft_mean_ms"] == pytest.approx(250.0)
    assert out["end_to_end"]["ttft_p90_ms"] == pytest.approx(400.0)
    assert out["counters"]["loadgen_lag_p99_ms"] == pytest.approx(100.0)
