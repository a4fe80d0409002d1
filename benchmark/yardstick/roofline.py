"""The least time the chip could take for a verify launch: operations the
algorithm needs over the published peak, against bytes over bandwidth.

Counts and peaks are data beside this file (``field_muls.json``,
``peaks.json``); a device that is not in the table is an error."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str) -> dict:
    with open(os.path.join(_HERE, name), encoding="utf-8") as f:
        return json.load(f)


def peaks(device_kind: str) -> dict:
    table = _load("peaks.json")
    entry = table.get(device_kind)
    if not isinstance(entry, dict):
        raise KeyError(f"no published peak for device kind {device_kind!r} "
                       f"in yardstick/peaks.json")
    return entry


def bucket(n: int, lo: int = 8) -> int:
    """Power-of-two bucket a launch of n records pads to (8 at least)."""
    b = lo
    while b < n:
        b *= 2
    return b


def launch_work(route: str, sigs: float) -> dict:
    """FLOP and bytes one launch of ``sigs`` real signatures needs on
    ``route`` (padding rows are not work the algorithm needs)."""
    model = _load("field_muls.json")
    r = model["routes"].get(route)
    if r is None:
        raise KeyError(f"no operation count for route {route!r} in "
                       f"yardstick/field_muls.json")
    muls = r["muls_per_sig"] * sigs + r["muls_per_launch"]
    rows = bucket(int(round(sigs)))
    return {"flop": muls * model["flop_per_field_mul"],
            "bytes": rows * (r["bytes_in_per_row"] + r["bytes_out_per_row"])}


def least_time_s(route: str, sigs: float, device_kind: str) -> dict:
    """The larger of operations over peak FLOP/s and bytes over peak
    bytes/s, and which of the two binds."""
    work = launch_work(route, sigs)
    peak = peaks(device_kind)
    t_flop = work["flop"] / peak["flop_per_s"]
    t_bytes = work["bytes"] / peak["bytes_per_s"]
    return {"seconds": max(t_flop, t_bytes),
            "binds": "flop" if t_flop >= t_bytes else "bytes",
            "flop": work["flop"], "bytes": work["bytes"]}
