"""Reader kind ``tenants``: how unevenly one quantity is spread over the
tenants of the OP_STATS snapshot taken at the window's end — the worst
tenant's value over the median tenant's.

    "reader": {"kind": "tenants", "path": "queue_wait.bulk.p50_ms"}

``path`` is a key path inside each ``tenants.<name>`` record (one tenant
a connection's HELLO name), walked as reader kind ``stats`` walks the
whole snapshot.  A tenant whose record lacks the path is left out; with
fewer than two tenants left, or a median of zero, there is no spread to
report.  1.0 means every tenant reads alike.
"""

from __future__ import annotations

import statistics

from readers import stats


def read(source: dict, run: dict):
    tenants = (run.get("stats") or {}).get("tenants")
    if not isinstance(tenants, dict):
        return None
    values = [v for rec in tenants.values()
              if (v := stats.read(source, {"stats": rec})) is not None]
    if len(values) < 2:
        return None
    middle = statistics.median(values)
    if middle <= 0:
        return None
    return max(values) / middle
