"""The runner's own logic that needs no device: finding a cell's files by
name, and the OP_STATS conditions behind ``correct``."""

import copy
import json
import os

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
MAIN = os.path.join(REPO, "BENCHMARK.json")
REHEARSAL = os.path.join(BENCH, "rehearsal", "BENCHMARK.json")


def _cells(path):
    with open(path, encoding="utf-8") as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize(
    "manifest,name",
    [(m, n) for m in (MAIN, REHEARSAL) for n in _cells(m)])
def test_every_cell_resolves_from_data_alone(manifest, name):
    cell = run.resolve_cell(manifest, name)
    assert os.path.isfile(cell["config_path"])
    assert os.path.isfile(cell["mix_path"])
    assert cell["mix_data"]["name"] == cell["traffic"]
    assert os.path.isfile(os.path.join(
        BENCH, "drivers", cell["mix_data"]["driver"] + ".py"))
    e2e = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in cell["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(cell["layers_dir"],
                                           m["name"] + ".json"))
    # serve() has every argument the configuration gives
    import inspect

    from hotstuff_tpu.sidecar import service
    assert set(cell["config_data"]["sidecar"]) <= \
        set(inspect.signature(service.serve).parameters)


def test_a_cell_that_is_not_listed_is_refused():
    with pytest.raises(run.Refused):
        run.resolve_cell(MAIN, "no.such.cell")
    with pytest.raises(run.Refused):
        run.load_module("drivers", "no_such_driver")


GOOD = {
    "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    "paths": {"rlc": 460, "rlc_bisect": 5, "per_sig": 5},
    "guard": {"wedges": 0, "host_fallback_records": 0},
    "dedup": {"cache_hits": 0},
}
BEFORE = {"paths": {"rlc": 2, "per_sig": 5}, "dedup": {"cache_hits": 0}}
CONFIG = {"route": "rlc"}


def test_a_run_the_device_answered_has_no_problem():
    assert run.check_served_by_device(GOOD, BEFORE, CONFIG, 1) == []


@pytest.mark.parametrize("edit,word", [
    (lambda s: s.pop("device"), "no `device` section"),
    (lambda s: s["device"].update(platform="cpu"), "platform"),
    (lambda s: s["device"].update(count=4), "count"),
    (lambda s: s["paths"].update(host=1), "host entry"),
    (lambda s: s["paths"].update(rlc=2), "configured route"),
    (lambda s: s["guard"].update(wedges=1), "wedges"),
    (lambda s: s["guard"].update(host_fallback_records=3), "host_fallback"),
    (lambda s: s["dedup"].update(cache_hits=67), "verdict cache"),
])
def test_each_way_the_device_did_not_answer_is_named(edit, word):
    stats = copy.deepcopy(GOOD)
    edit(stats)
    problems = run.check_served_by_device(stats, BEFORE, CONFIG, 1)
    assert len(problems) == 1 and word in problems[0]


POOL = {"sample": {"checked": 258, "forged": 2, "disagreements": []}}
WINDOW = {"unmeasured_wrong": 0}
REQUESTS = [{"status": "ok", "index": 0}, {"status": "ok", "index": 1}]


def test_a_sound_run_keeps_every_compared_number_to_its_limit():
    checks = run.compared_numbers(GOOD, BEFORE, CONFIG, POOL, WINDOW,
                                  REQUESTS)
    assert all(run.within(c) for c in checks.values())
    assert checks["route_launches"] == {"value": 458, "limit": ">=1"}
    assert {c["limit"] for k, c in checks.items()
            if k != "route_launches"} == {0}


@pytest.mark.parametrize("name,edit", [
    ("replies_wrong", lambda s, p, w, r: r[1].update(status="mismatch")),
    ("never_answered", lambda s, p, w, r: r[0].update(status="unanswered")),
    ("never_answered", lambda s, p, w, r: r[0].update(status="error")),
    ("unmeasured_wrong", lambda s, p, w, r: w.update(unmeasured_wrong=1)),
    ("sample_disagreements",
     lambda s, p, w, r: p["sample"]["disagreements"].append([3, 4])),
    ("cache_hits", lambda s, p, w, r: s["dedup"].update(cache_hits=67)),
    ("host_path_launches", lambda s, p, w, r: s["paths"].update(host=2)),
    ("wedges", lambda s, p, w, r: s["guard"].update(wedges=1)),
    ("host_fallback_records",
     lambda s, p, w, r: s["guard"].update(host_fallback_records=67)),
    ("route_launches", lambda s, p, w, r: s["paths"].update(rlc=2)),
])
def test_each_compared_number_leaves_its_limit_alone(name, edit):
    args = copy.deepcopy((GOOD, POOL, WINDOW, REQUESTS))
    edit(*args)
    stats, pool, window, requests = args
    checks = run.compared_numbers(stats, BEFORE, CONFIG, pool, window,
                                  requests)
    assert [k for k, c in checks.items() if not run.within(c)] == [name]


def test_end_to_end_values_leave_out_what_has_no_sample():
    reqs = [{"t_send": 1.0, "t_reply": 1.5, "sigs": 8, "status": "ok"}]
    full = run.end_to_end_values(reqs, 0.0, 2.0, 12.5)
    assert full == {"setup_s": 12.5, "verify_sigs_per_s": 4.0,
                    "verify_p50_ms": 500.0, "verify_p95_ms": 500.0}
    none = run.end_to_end_values([], 0.0, 2.0, 12.5)
    assert "verify_p50_ms" not in none and none["verify_sigs_per_s"] == 0.0
