"""Metric arithmetic: percentiles, rates and the spread the bounds are set from."""

from __future__ import annotations

import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest ranks (numpy's default rule); q=50 equals
    ``statistics.median``.  Raises on an empty sample: a metric with
    nothing behind it is left out, never reported as 0."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def _answered_inside(requests, t_start: float, t_end: float) -> list:
    """Requests answered rightly whose reply arrived inside the window; a
    request is a mapping with ``t_send``, ``t_reply`` (None while
    unanswered), ``sigs`` and ``status``."""
    return [r for r in requests
            if r["status"] == "ok" and r["t_reply"] is not None
            and t_start <= r["t_reply"] <= t_end]


def latencies_ms(requests, t_start: float, t_end: float) -> list:
    """Send->reply milliseconds of every request completed in the window."""
    return [(r["t_reply"] - r["t_send"]) * 1e3
            for r in _answered_inside(requests, t_start, t_end)]


def sigs_per_s(requests, t_start: float, t_end: float) -> float:
    """Signatures in requests whose reply arrived inside the window, over
    the window's seconds: all the work over all the time."""
    if t_end <= t_start:
        raise ValueError("empty window")
    done = sum(r["sigs"] for r in _answered_inside(requests, t_start, t_end))
    return done / (t_end - t_start)


def attempted_failed(requests, t_start: float, t_end: float) -> tuple:
    """(attempted, failed): requests sent inside the window, and those of
    them refused, errored, answered wrongly or still unanswered after the
    drain (every status but ``ok``)."""
    sent = [r for r in requests if t_start <= r["t_send"] <= t_end]
    return len(sent), sum(r["status"] != "ok" for r in sent)


def quartile_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread a bound is set from (about five times the widest)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
