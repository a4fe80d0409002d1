import statistics

import pytest

from yardstick import arith


def test_percentile_matches_median_and_interpolates():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert arith.percentile(vals, 50) == statistics.median(vals) == 3.0
    assert arith.percentile(vals, 0) == 1.0
    assert arith.percentile(vals, 100) == 5.0
    assert arith.percentile(vals, 95) == pytest.approx(4.8)
    assert arith.percentile([1.0, 2.0], 50) == 1.5
    assert arith.percentile([7.0], 95) == 7.0


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        arith.percentile([], 50)
    with pytest.raises(ValueError):
        arith.percentile([1.0], 101)


def _req(t_send, t_reply, sigs=10, status="ok"):
    return {"t_send": t_send, "t_reply": t_reply, "sigs": sigs,
            "status": status}


REQS = [
    _req(9.9, 10.1),                       # sent before, answered inside
    _req(10.2, 10.5),
    _req(10.6, 11.0, status="mismatch"),   # answered wrongly
    _req(19.8, 20.3),                      # answered in the drain
    _req(19.9, None, status="unanswered"),
    _req(15.0, 15.1, status="refused"),
]


def test_latencies_are_of_replies_inside_the_window():
    lat = arith.latencies_ms(REQS, 10.0, 20.0)
    assert lat == pytest.approx([200.0, 300.0])


def test_rate_is_all_the_work_over_all_the_time():
    assert arith.sigs_per_s(REQS, 10.0, 20.0) == pytest.approx(2.0)
    with pytest.raises(ValueError):
        arith.sigs_per_s(REQS, 10.0, 10.0)


def test_attempted_are_sent_inside_failed_are_not_ok():
    assert arith.attempted_failed(REQS, 10.0, 20.0) == (5, 3)


def test_quartile_spread_is_statistics_quantiles():
    vals = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert arith.quartile_spread(vals) == pytest.approx(
        (q3 - q1) / statistics.median(vals))
