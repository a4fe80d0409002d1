"""Reader kind ``span``: a percentile of one field over the window's
spans of one stage (the sidecar's ``obs/`` spans, host clock).

    "source": {"kind": "span", "stage": "pack", "field": "dur_ms",
               "percentile": 50}
"""

from __future__ import annotations

from yardstick import arith


def read(source: dict, run: dict):
    field = source.get("field", "dur_ms")
    values = [sp[field] for sp in run.get("spans") or ()
              if sp.get("stage") == source["stage"] and field in sp]
    if not values:
        return None
    return arith.percentile(values, float(source.get("percentile", 50)))
