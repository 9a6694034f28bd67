"""Window accounting: every request due in the window counts, a stall shows."""

import numpy as np
import pytest

from bench import harness


class R:
    def __init__(self, tokens=()):
        self.tokens = list(tokens)


def rec(due, stamps, done=None, submitted=None):
    return harness.Rec(req=R(stamps), due=due,
                       submitted=due if submitted is None else submitted,
                       stamps=list(stamps), done_at=done)


def test_ttft_counts_censored_requests_with_their_wait():
    recs = [rec(1.0, [1.5, 1.6]), rec(2.0, [4.0]), rec(8.0, []), rec(-1.0, [0.5])]
    s = harness.window_stats(recs, 0.0, 10.0)
    assert s["due"] == 3 and s["ttft_censored"] == 1
    # waits: 0.5, 2.0 and the stalled 10 - 8 = 2.0
    assert s["ttft_p50_ms"] == pytest.approx(2000.0)
    assert s["ttft_p90_ms"] == pytest.approx(np.percentile([500, 2000, 2000], 90))


def test_gaps_and_tokens_inside_the_window_only():
    recs = [rec(-5.0, [-1.0, 0.5, 1.0, 11.0]), rec(1.0, [2.0, 2.0, 3.5])]
    s = harness.window_stats(recs, 0.0, 10.0)
    assert s["tokens"] == 5  # 0.5, 1.0, 2.0, 2.0, 3.5
    assert s["gap_samples"] == 4  # 1.5, 0.5, 0.0, 1.5 -- not the one ending at 11
    assert s["output_tok_s"] == pytest.approx(0.5)
    assert s["itl_p99_ms"] == pytest.approx(np.percentile([1500, 500, 0, 1500], 99))


def test_generator_lateness_and_completion():
    recs = [rec(1.0, [2.0], done=2.0, submitted=1.25), rec(3.0, [3.5], done=11.0)]
    s = harness.window_stats(recs, 0.0, 10.0)
    assert s["generator_late_max_ms"] == pytest.approx(250.0)
    assert s["completed"] == 1


def test_sample_takes_the_longest_and_enough_tokens():
    recs = [harness.Rec(req=type("Q", (), {"tokens": [0] * n, "rid": i})(), due=0,
                        submitted=0, done_at=1.0) for i, n in enumerate([5, 50, 20, 30])]
    recs.append(harness.Rec(req=type("Q", (), {"tokens": [0] * 99, "rid": 9})(),
                            due=0, submitted=0, done_at=None))
    sample = harness.sample_finished(recs, 2.0, seed=3, want_tokens=60)
    assert sample[0].req.rid == 1 and sum(len(r.req.tokens) for r in sample) >= 60
    assert all(r.done_at is not None for r in sample)
    assert harness.sample_finished(recs, 0.5, seed=3) == []


def test_gap_stats():
    s = harness.gap_stats(np.array([0.0, 0.0, 0.5, 2.0]))
    assert s["logit_gap_max"] == 2.0 and s["logit_gap_mean"] == 0.625
    assert s["mismatch_share"] == 0.5 and s["tokens"] == 4
    assert harness.gap_stats(np.zeros(0))["logit_gap_max"] == float("inf")


def test_decide_needs_enough_scored_tokens():
    """A mean gap over a few tens of tokens is noise: the fp8 control read
    under the limit there on the chip, so such a run is not judged correct."""
    few = {"logit_gap_mean": 0.0, "tokens": 51}
    enough = {"logit_gap_mean": 0.05, "tokens": 200}
    config = {"limits": {"logit_gap_mean": 0.1}, "min_scored_tokens": 100}
    ok, check = harness.decide(few, config)
    assert not ok and check["scored_tokens"] == {"value": 51, "limit": 100, "at_least": True}
    assert harness.decide(enough, config)[0]
    assert not harness.decide(enough, config, compiles=1)[0]
    assert not harness.decide({**enough, "logit_gap_mean": 0.11}, config)[0]
