"""From a profiler trace and the sidecar's spans to numbers.

The reduction works on a plain form of the trace so that it can be
checked on a small recorded one (``recorded_trace.json`` beside this
file, ``benchmark/tests/test_trace_reduce.py``):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into that
form with nothing but JAX.  Times inside a trace are nanoseconds on the
trace's own clock; ``clock_offset_ns`` finds the offset to the wall clock
(the sidecar's spans carry wall-clock stamps) from a marker the runner
writes into the trace (``jax.profiler.TraceAnnotation``) whose name
carries the wall-clock nanosecond at which it was written.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# Lines of a device plane, as the TPU profiler names them: one event an
# executed HLO operation, and one event an executed program.
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MARK = "bench_clock:"


def load_xplane(path: str, keep=None) -> dict:
    """``.xplane.pb`` -> the plain form.  ``keep(plane_name) -> bool``
    selects planes (default: all; the reduction needs the device planes
    and the host plane that holds the clock marker)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        name = plane.name
        if keep is not None and not keep(name):
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def op_events(plane: dict) -> list:
    """The executed operations of a device plane: the ``XLA Ops`` line
    where the trace has one, else every event of the plane but those of
    whole programs and steps (which cover their own operations)."""
    line = _line(plane, OPS_LINE)
    if line is not None:
        return line["events"]
    return [e for ln in plane["lines"]
            if ln["name"] not in (MODULES_LINE, "Steps")
            for e in ln["events"]]


def module_events(plane: dict) -> list:
    line = _line(plane, MODULES_LINE)
    return line["events"] if line is not None else []


def clock_offset_ns(trace: dict):
    """wall-clock ns minus trace ns, from the runner's marker; None when
    the trace holds no marker."""
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue        # a host thread wrote it; skip the millions
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name.startswith(CLOCK_MARK):
                    return int(name[len(CLOCK_MARK):]) - start
    return None


def merge(intervals) -> list:
    """Union of [start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy(plane: dict, window) -> list:
    """Disjoint busy intervals of one device inside ``window``."""
    lo, hi = window
    return merge(clip(([s, s + d] for _, s, d in op_events(plane)), lo, hi))


def extent(trace: dict):
    """[first start, last end] over the device planes' operations."""
    starts, ends = [], []
    for plane in device_planes(trace):
        for _, s, d in op_events(plane):
            starts.append(s)
            ends.append(s + d)
    if not starts:
        return None
    return [min(starts), max(ends)]


def gaps(busy_intervals, window) -> list:
    """The idle intervals of ``window`` that ``busy_intervals`` leave."""
    lo, hi = window
    out, at = [], lo
    for s, e in busy_intervals:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def span_intervals(spans, offset_ns: int) -> list:
    """Sidecar spans -> (stage, start, end) on the trace's clock.  The
    sidecar writes a span when it ENDS: ``t`` is the wall-clock second
    of the end and ``dur_ms`` reaches back from it."""
    out = []
    for sp in spans:
        end = int(sp["t"] * 1e9) - offset_ns
        out.append((sp["stage"], end - int(sp.get("dur_ms", 0.0) * 1e6), end))
    return out


def stage_timeline(staged) -> list:
    """Cut the time the spans cover into stretches with one stage each:
    where spans overlap, the shortest one (the most specific: a pack
    inside a launch's dispatch->fetch) names the stretch.  Returns sorted
    (start, end, stage) with ``none`` between spans."""
    staged = [s for s in staged if s[2] > s[1]]
    points = sorted({s[1] for s in staged} | {s[2] for s in staged})
    out = []
    for a, b in zip(points, points[1:]):
        mid = (a + b) / 2
        covering = [(e - s, stage) for stage, s, e in staged if s <= mid < e]
        out.append((a, b, min(covering)[1] if covering else "none"))
    return out


def name_gaps(gap_list, staged) -> list:
    """Idle seconds by what the host was doing: every gap is cut along
    the stage timeline and each piece goes to its stage (``none`` where
    no span covers it), summed by stage, longest first, as
    [[name, seconds], ...]."""
    timeline = stage_timeline(staged)
    starts = [seg[0] for seg in timeline]
    totals: dict = {}
    for lo, hi in gap_list:
        left = hi - lo
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(timeline) and timeline[i][0] < hi:
            a, b, stage = timeline[i]
            piece = min(b, hi) - max(a, lo)
            if piece > 0:
                totals[stage] = totals.get(stage, 0) + piece
                left -= piece
            i += 1
        if left > 0:
            totals["none"] = totals.get("none", 0) + left
    return [[f"in_span:{k}", v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])]


_LAYOUT = re.compile(r"\{[^}]*\}|/\*[^*]*\*/")


def short_name(name: str, limit: int = 96) -> str:
    """An operation's name as the TPU trace gives it is its whole HLO
    text; keep its name and the head of its result type, without the
    layout annotations."""
    return " ".join(_LAYOUT.sub("", name).split())[:limit]


def top_ops(trace: dict, window, limit: int = 10) -> list:
    """Device operations by total seconds inside the window, averaged
    over the devices: [[name, seconds], ...], longest first.  A loop
    and the operations of its body both count: the list names where the
    time is, it does not add up."""
    lo, hi = window
    planes = device_planes(trace)
    totals: dict = {}
    for plane in planes:
        for name, s, d in op_events(plane):
            o = min(s + d, hi) - max(s, lo)
            if o > 0:
                totals[name] = totals.get(name, 0) + o
    n = max(1, len(planes))
    return [[short_name(k), v / n / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:limit]]


def program_runs(trace: dict, window, pattern: str) -> dict:
    """The verify programs' executions that lie wholly inside the window,
    on the first device: how many, their mean device seconds, and how
    they were found — by program name (events of the ``XLA Modules`` line
    that ``pattern`` matches), else every program of that line.  A trace
    without that line gives no executions (``by`` says ``nothing``)."""
    lo, hi = window
    plane = device_planes(trace)[0]
    rx = re.compile(pattern)
    modules = [e for e in module_events(plane)
               if lo <= e[1] and e[1] + e[2] <= hi]
    for how, picked in (
            ("modules_matching", [e for e in modules if rx.search(e[0])]),
            ("all_modules", modules)):
        if picked:
            return {"runs": len(picked),
                    "mean_s": sum(d for _, _, d in picked) / len(picked) / 1e9,
                    "by": how,
                    "names": sorted({short_name(e[0]) for e in picked})[:8]}
    return {"runs": 0, "mean_s": None, "by": "nothing", "names": []}


def reduce_trace(trace: dict, spans: list, wall_window_ns,
                 pattern: str) -> dict:
    """Everything the profile readers and the result line take from one
    traced slice.

    wall_window_ns  [start, stop] of the slice on the wall clock (taken by
                    the runner around the profiler's start and stop)
    spans           the sidecar's spans (``t`` wall-clock seconds)
    pattern         regular expression for the verify programs' names
    """
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane "
                         f"({[p['name'] for p in trace['planes']]})")
    offset = clock_offset_ns(trace)
    ext = extent(trace)
    if ext is None:
        raise ValueError("no operation ran on the device in the trace")
    window, window_by = ext, "device_extent"
    if offset is not None:
        marked = [wall_window_ns[0] - offset, wall_window_ns[1] - offset]
        # The marker's clock is trusted only if the device ran inside
        # the window it gives.
        if marked[0] - 1e8 <= ext[0] and ext[1] <= marked[1] + 1e8:
            window, window_by = marked, "clock_marker"
    per_device = [busy(p, window) for p in planes]
    busy_s = sum(e - s for b in per_device for s, e in b) / len(planes) / 1e9
    window_s = (window[1] - window[0]) / 1e9
    staged, launch_sigs = [], []
    if offset is not None:
        for sp, iv in zip(spans, span_intervals(spans, offset)):
            if iv[2] < window[0] or iv[1] > window[1]:
                continue
            staged.append(iv)
            if iv[0] == "device" and window[0] <= iv[2] <= window[1]:
                launch_sigs.append(sp.get("sigs", 0))
    launches = len(launch_sigs)
    program = program_runs(trace, window, pattern)
    # Gaps are named on the fullest-looking device: the first.
    idle = name_gaps(gaps(per_device[0], window), staged)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "window_by": window_by,
        "devices": len(planes),
        "launches": launches,
        "sigs_per_launch": (sum(launch_sigs) / len(launch_sigs))
        if launch_sigs else None,
        "program_runs": program["runs"],
        "program_mean_s": program["mean_s"],
        "program_by": program["by"],
        "program_names": program["names"],
        "device_ops": top_ops(trace, window),
        "idle_gaps": idle[:10],
    }
