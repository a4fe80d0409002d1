import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reader(kind):
    spec = importlib.util.spec_from_file_location(
        f"readers_{kind}", os.path.join(BENCH, "readers", f"{kind}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_stats_reader_walks_a_key_path_or_returns_nothing():
    read = _reader("stats").read
    run = {"stats": {"queue_wait": {"latency": {"p50_ms": 0.25}},
                     "paths": {"rlc": 3}, "ok": True}}
    assert read({"path": "queue_wait.latency.p50_ms"}, run) == 0.25
    assert read({"path": "paths.rlc"}, run) == 3.0
    assert read({"path": "queue_wait.bulk.p50_ms"}, run) is None
    assert read({"path": "queue_wait"}, run) is None
    assert read({"path": "ok"}, run) is None
    assert read({"path": "x"}, {}) is None


def test_span_reader_takes_a_percentile_of_one_stage():
    read = _reader("span").read
    spans = [{"stage": "pack", "dur_ms": d} for d in (1.0, 2.0, 9.0)] + \
        [{"stage": "device", "dur_ms": 40.0}]
    run = {"spans": spans}
    assert read({"stage": "pack", "percentile": 50}, run) == 2.0
    assert read({"stage": "device", "field": "dur_ms"}, run) == 40.0
    assert read({"stage": "queue"}, run) is None
    assert read({"stage": "pack"}, {"spans": []}) is None


PROFILE = {"busy_s": 1.5, "window_s": 2.0, "launches": 40,
           "program_runs": 44, "program_mean_s": 0.030,
           "sigs_per_launch": 67.0}
RUN = {"profile": PROFILE, "config": {"route": "rlc"},
       "device": {"kind": "TPU v5 lite"}}


def test_profile_reader_reductions():
    read = _reader("profile").read
    assert read({"reduction": "device_idle_share"}, RUN) == pytest.approx(25)
    assert read({"reduction": "kernel_ms"}, RUN) == pytest.approx(30.0)
    share = read({"reduction": "kernel_roofline_share"}, RUN)
    # (67 * 2322 + 2692) field muls * 2048 FLOP / 197e12 FLOP/s over 30 ms
    want = 100 * ((67 * 2322 + 2692) * 2048 / 197e12) / 0.030
    assert share == pytest.approx(want) and share < 0.01
    with pytest.raises(ValueError):
        read({"reduction": "nope"}, RUN)


def test_profile_reader_returns_nothing_without_a_profile_or_launches():
    read = _reader("profile").read
    assert read({"reduction": "kernel_ms"}, {"profile": None}) is None
    none = dict(RUN, profile=dict(PROFILE, program_runs=0,
                                  program_mean_s=None))
    assert read({"reduction": "kernel_ms"}, none) is None
    assert read({"reduction": "kernel_roofline_share"}, none) is None


def test_roofline_names_the_bound_and_refuses_unknown_devices():
    from yardstick import roofline

    least = roofline.least_time_s("per_sig", 1024, "TPU v5 lite")
    assert least["binds"] == "flop"
    assert least["flop"] == 1024 * 3295 * 2048
    assert least["bytes"] == 1024 * 129
    assert roofline.bucket(67) == 128 and roofline.bucket(3) == 8
    with pytest.raises(KeyError):
        roofline.least_time_s("rlc", 67, "TPU v9")
    with pytest.raises(KeyError):
        roofline.least_time_s("pairing", 67, "TPU v5 lite")
