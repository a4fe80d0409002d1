"""Reader kind ``stats``: a key path into the OP_STATS snapshot taken at
the window's end.

    "source": {"kind": "stats", "path": "queue_wait.latency.p50_ms"}
"""

from __future__ import annotations


def read(source: dict, run: dict):
    node = run.get("stats")
    for key in source["path"].split("."):
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return float(node)
