import json
import os

import pytest

from yardstick import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "yardstick",
                       "recorded_trace.json"), encoding="utf-8") as f:
    TRACE = json.load(f)

OFFSET = 10**18                       # wall ns - trace ns, by the marker
MS = 1_000_000


def _wall_s(trace_ns):
    return (trace_ns + OFFSET) / 1e9


# The sidecar's spans of the two launches: written at their END.
SPANS = [
    {"stage": "pack", "t": _wall_s(1 * MS), "dur_ms": 0.5},
    {"stage": "device", "t": _wall_s(32 * MS), "dur_ms": 31.0, "sigs": 67},
    {"stage": "reply", "t": _wall_s(32 * MS), "dur_ms": 0.0},
    {"stage": "pack", "t": _wall_s(41 * MS), "dur_ms": 1.0},
    {"stage": "device", "t": _wall_s(72 * MS), "dur_ms": 31.0, "sigs": 67},
    {"stage": "device", "t": _wall_s(500 * MS), "dur_ms": 31.0, "sigs": 67},
]
WALL_WINDOW = [OFFSET + 500_000, OFFSET + 80 * MS]


def test_merge_clip_gaps():
    assert tr.merge([[5, 7], [1, 3], [2, 4], [7, 8], [9, 9]]) == \
        [[1, 4], [5, 8]]
    assert tr.clip([[1, 4], [5, 8]], 3, 6) == [[3, 4], [5, 6]]
    assert tr.gaps([[1, 4], [5, 8]], [0, 10]) == [[0, 1], [4, 5], [8, 10]]


def test_only_tpu_chips_are_device_planes():
    assert [p["name"] for p in tr.device_planes(TRACE)] == ["/device:TPU:0"]


def test_clock_offset_from_the_marker():
    assert tr.clock_offset_ns(TRACE) == OFFSET
    assert tr.clock_offset_ns({"planes": []}) is None


def test_busy_is_the_union_nested_operations_count_once():
    plane = tr.device_planes(TRACE)[0]
    b = tr.busy(plane, [0, 80 * MS])
    # per launch 10 + 8 + 10 ms (the nested fusion.9 adds nothing); the
    # small program's 1 ms in between
    assert sum(e - s for s, e in b) == (28 + 28 + 1) * MS


def test_spans_end_at_t_and_reach_back():
    (stage, start, end), = tr.span_intervals(SPANS[1:2], OFFSET)
    assert (stage, start, end) == ("device", 1 * MS, 32 * MS)


def test_stage_timeline_prefers_the_shortest_span():
    staged = [("device", 0, 100), ("pack", 40, 50)]
    assert tr.stage_timeline(staged) == [
        (0, 40, "device"), (40, 50, "pack"), (50, 100, "device")]


def test_reduce_trace_on_the_recorded_trace():
    out = tr.reduce_trace(TRACE, SPANS, WALL_WINDOW, "verify_rlc")
    assert out["window_by"] == "clock_marker"
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.0795)
    assert out["busy_s"] == pytest.approx(0.057)
    assert out["launches"] == 2                 # the third ended outside
    assert out["sigs_per_launch"] == 67
    # by name: the two verify programs, not the small conversion
    assert out["program_by"] == "modules_matching"
    assert out["program_runs"] == 2
    assert out["program_mean_s"] == pytest.approx(0.030)
    assert out["program_names"] == [
        "jit_verify_rlc_packed(1432102161886162504)"]
    # the longest operations, named without their layouts
    assert out["device_ops"][0] == [
        "%fusion.5322 = (s32[63], s32[1,1,63]) fusion(f32[1,1,32] %p)",
        pytest.approx(0.020)]
    assert out["device_ops"][1] == [
        "%while.1487 = (s32[], s32[4,32], s32[64,4,32], s32[]) "
        "while(%tuple.10582), condition=%region_141", pytest.approx(0.020)]
    gaps = dict(out["idle_gaps"])
    # idle, cut along the spans: 0.5 ms before the first operation (the
    # first pack); 2 ms inside each launch, 1 ms after each before its
    # `device` span ends at the fetch; 1 ms of the second pack; the rest
    # (3 + 4 + 8 ms) under no span
    assert sum(gaps.values()) == pytest.approx(0.0795 - 0.057)
    # (a span's ``t`` is a float of wall-clock seconds: ~0.1 us exact)
    assert gaps["in_span:device"] == pytest.approx(0.006, abs=1e-6)
    assert gaps["in_span:none"] == pytest.approx(0.015, abs=1e-6)
    assert gaps["in_span:pack"] == pytest.approx(0.0015, abs=1e-6)


def test_program_time_falls_back_when_no_name_matches():
    out = tr.reduce_trace(TRACE, SPANS, WALL_WINDOW, "no_such_program")
    assert out["program_by"] == "all_modules"
    assert out["program_runs"] == 3
    assert out["program_mean_s"] == pytest.approx(0.061 / 3)
    stripped = json.loads(json.dumps(TRACE))
    for p in stripped["planes"]:
        p["lines"] = [ln for ln in p["lines"] if ln["name"] != "XLA Modules"]
    out = tr.reduce_trace(stripped, SPANS, WALL_WINDOW, "verify_rlc")
    assert out["program_by"] == "nothing" and out["program_runs"] == 0
    assert out["program_mean_s"] is None


def test_an_execution_cut_by_the_window_is_not_a_whole_one():
    late = [WALL_WINDOW[0] + 1 * MS, WALL_WINDOW[1]]   # cuts the first
    out = tr.reduce_trace(TRACE, SPANS, late, "verify_rlc")
    assert out["program_runs"] == 1
    assert out["program_mean_s"] == pytest.approx(0.030)


def test_without_a_marker_the_window_is_the_devices_extent():
    bare = {"planes": [p for p in TRACE["planes"]
                       if p["name"].startswith("/device")]}
    out = tr.reduce_trace(bare, SPANS, WALL_WINDOW, "verify_rlc")
    assert out["window_by"] == "device_extent"
    assert out["window_s"] == pytest.approx(0.070)
    assert out["launches"] == 0 and out["idle_gaps"][0][0] == "in_span:none"


def test_a_trace_without_device_work_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace({"planes": TRACE["planes"][:1]}, SPANS, WALL_WINDOW,
                        "x")
