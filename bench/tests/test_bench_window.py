"""Percentile, rate and failure arithmetic of a window."""

import _bench_path  # noqa: F401
import pytest

from mbench import window
from mbench.load import Record


def _rec(due, done=None, status=200, body=None, sent=None):
    r = Record({"spec": {}, "sql": "", "session": False, "tenant": "t0"},
               "query", due)
    r.sent = due if sent is None else sent
    r.done = done
    r.status = status if done is not None else None
    r.body = {"ids": []} if body is None else body
    return r


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert window.percentile(v, 50) == 50
    assert window.percentile(v, 95) == 95
    assert window.percentile([7.0], 95) == 7.0
    assert window.percentile([3, 1, 2], 50) == 2
    with pytest.raises(ValueError):
        window.percentile([], 50)


def test_failures_count_as_missing_every_limit():
    recs = [_rec(0.0, 0.1), _rec(1.0, 1.2),
            _rec(2.0, 2.05, status=429, body={"error": {"code": 429}}),
            _rec(3.0, 3.1, body={"error": {"code": 500}}),
            _rec(4.0)]                          # never answered
    s = window.summarize(recs, t0=0.0, end=10.0, cap_s=70.0)
    assert s["attempted"] == 5
    assert s["failed"] == 3
    assert s["completed"] == 2
    assert s["p95_ms"] == pytest.approx(70_000.0)
    assert s["p50_ms"] == pytest.approx(70_000.0)
    assert s["qps"] == pytest.approx(0.2)


def test_in_flight_at_close_counts_latency_not_rate():
    recs = [_rec(0.5, 1.0), _rec(9.0, 12.0), _rec(10.5, 10.6)]
    s = window.summarize(recs, t0=0.0, end=10.0, cap_s=70.0)
    assert s["attempted"] == 2            # the last fell due after the close
    assert s["failed"] == 0
    assert s["completed"] == 1            # answered after the close
    assert s["qps"] == pytest.approx(0.1)
    assert s["p95_ms"] == pytest.approx(3000.0)
    assert s["p50_ms"] == pytest.approx(500.0)


def test_generator_lateness():
    recs = [_rec(1.0, 2.0, sent=1.25), _rec(2.0, 3.0, sent=2.0)]
    s = window.summarize(recs, t0=0.0, end=10.0, cap_s=70.0)
    assert s["late_max_ms"] == pytest.approx(250.0)
