"""Estimators on hand-made records."""

import pytest

from benchmarks import estimators as E


def test_percentile_interpolates():
    assert E.percentile([1, 2, 3, 4], 50) == 2.5
    assert E.percentile([5], 90) == 5
    assert E.percentile(range(101), 90) == 90
    with pytest.raises(ValueError):
        E.percentile([], 50)


def test_ttft_is_timed_from_when_the_request_was_due():
    rec = {"due_t": 10.0, "sent_t": 10.4, "first_t": 10.9, "last_t": 12.9, "n_tokens": 5}
    assert E.ttft_ms(rec) == pytest.approx(900.0)
    assert E.tpot_ms(rec) == pytest.approx(500.0)
    assert E.tpot_ms({**rec, "n_tokens": 1}) is None
    assert E.ttft_ms({"due_t": 1.0, "first_t": None}) is None


def test_tokens_are_counted_by_arrival_not_by_finished_requests():
    recs = [
        {"phase": "lead", "token_ts": [9.0, 9.9, 10.0, 10.5]},      # began before the window
        {"phase": "window", "token_ts": [11.0, 12.0, 19.99]},
        {"phase": "window", "token_ts": [19.0, 20.0, 21.0]},        # ends after it
        {"phase": "tail", "token_ts": [20.5]},
    ]
    assert E.tokens_by_arrival(recs, 10.0, 20.0) == 2 + 3 + 1


def test_stratified_mean_does_not_swing_with_the_mix():
    code = [{"kind": "code", "seconds": 10.0}] * 9
    repo = [{"kind": "repo", "seconds": 30.0}]
    assert E.stratified_mean(code + repo, {"code": 1, "repo": 1}) == pytest.approx(20.0)
    assert E.stratified_mean(code[:1] + repo * 7, {"code": 1, "repo": 1}) == pytest.approx(20.0)
    assert E.stratified_mean(code + repo, {"code": 3, "repo": 1}) == pytest.approx(15.0)
    assert E.stratified_mean(code, {"code": 1, "repo": 1}) is None  # an empty stratum


def test_in_window_and_spread():
    recs = [{"phase": "lead"}, {"phase": "window"}, {"phase": "tail"}]
    assert E.in_window(recs) == [{"phase": "window"}]
    assert E.spread([100, 101, 102, 103, 104, 105]) == pytest.approx(3.5 / 102.5)
