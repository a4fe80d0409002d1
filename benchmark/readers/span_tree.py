"""Reader kind ``span_tree``: a percentile, over the window's root spans,
of the root's time that none of its children covers (the sidecar's
``obs/`` spans, one clock: ``t0`` start, ``t`` end, epoch seconds).

    "reader": {"kind": "span_tree", "root": "request",
               "children": ["decode", "queue", "reply"],
               "launch": ["pack", "dispatch", "device"],
               "percentile": 50}

A root's children are the spans of the ``children`` stages whose
``parent`` is the root's ``id``, and the spans of the ``launch`` stages
that carry the ``lid`` its ``queue`` child names (a coalesced launch
serves every request it took).  Each child is clipped to the root, the
clipped intervals are merged, and what is left of the root is the value:
milliseconds no span accounts for.  A root whose launch is not in the
window's spans — no ``queue`` child, or no ``device`` span with its
``lid`` — is left out; a root answered from the verdict cache
(``cached``) has no launch and counts with its own children.
"""

from __future__ import annotations

from yardstick import arith, trace_reduce


def _interval(span: dict):
    return [span["t0"], span["t"]]


def unaccounted_ms(spans, source: dict) -> list:
    """One value a complete root, in milliseconds."""
    child_stages = set(source["children"])
    launch_stages = set(source["launch"])
    by_parent: dict = {}
    by_lid: dict = {}
    for sp in spans:
        if "t0" not in sp:
            continue
        if sp.get("stage") in child_stages and sp.get("parent") is not None:
            by_parent.setdefault(sp["parent"], []).append(sp)
        elif sp.get("stage") in launch_stages and sp.get("lid") is not None:
            by_lid.setdefault(sp["lid"], []).append(sp)
    out = []
    for root in spans:
        if root.get("stage") != source["root"] or "t0" not in root:
            continue
        mine = list(by_parent.get(root.get("id"), ()))
        if not root.get("cached"):
            lids = {sp["lid"] for sp in mine
                    if sp["stage"] == "queue" and sp.get("lid") is not None}
            launch = [sp for lid in lids for sp in by_lid.get(lid, ())]
            if not any(sp["stage"] == "device" for sp in launch):
                continue
            mine += launch
        lo, hi = _interval(root)
        covered = trace_reduce.merge(
            trace_reduce.clip((_interval(sp) for sp in mine), lo, hi))
        out.append(((hi - lo) - sum(e - s for s, e in covered)) * 1e3)
    return out


def read(source: dict, run: dict):
    values = unaccounted_ms(run.get("spans") or (), source)
    if not values:
        return None
    return arith.percentile(values, float(source.get("percentile", 50)))
