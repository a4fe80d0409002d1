"""The ``span_tree`` reader on a hand-written span list, and the layer
files this reader and the request-path spans brought: each loads, names
a reader kind that exists and agrees with its ``BENCHMARK.json`` entry."""

import glob
import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
SOURCE = {"kind": "span_tree", "root": "request",
          "children": ["decode", "queue", "reply"],
          "launch": ["pack", "dispatch", "device"], "percentile": 50}
NEW = ("request_ms", "decode_ms", "reply_ms", "unaccounted_ms", "h2d_ms",
       "dispatch_ms", "guard_hop_ms", "d2h_ms", "warm_lower_s",
       "warm_backend_s")


def _reader():
    spec = importlib.util.spec_from_file_location(
        "readers_span_tree", os.path.join(BENCH, "readers", "span_tree.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _span(stage, t0_ms, t_ms, **tags):
    return dict(stage=stage, t0=100.0 + t0_ms / 1e3, t=100.0 + t_ms / 1e3,
                dur_ms=t_ms - t0_ms, **tags)


def _request(rid, id_, t0, t, lid, decode=0.2, reply=0.3):
    """A request [t0, t] ms: decode at its start, queue until 0.5 ms in,
    reply over its last ``reply`` ms."""
    return [
        _span("request", t0, t, id=id_, rid=rid, parent=None, ok=True),
        _span("decode", t0, t0 + decode, id=id_ + 1, rid=rid, parent=id_),
        _span("queue", t0 + decode, t0 + 0.5, id=id_ + 2, rid=rid,
              parent=id_, lid=lid),
        _span("reply", t - reply, t, id=id_ + 3, rid=rid, parent=id_),
    ]


def _launch(lid, id_, t0, packed, dispatched, fetched):
    return [
        _span("pack", t0, packed, id=id_, lid=lid, parent=None),
        _span("dispatch", packed, dispatched, id=id_ + 1, lid=lid,
              parent=None),
        _span("device", dispatched, fetched, id=id_ + 2, lid=lid,
              parent=None),
        # children of the device span are not the root's: ignored
        _span("d2h", fetched - 0.1, fetched, id=id_ + 3, lid=lid,
              parent=id_ + 2),
    ]


def test_one_request_leaves_what_no_child_covers():
    reader = _reader()
    # root 0..42: decode 0..0.2, queue 0.2..0.5, pack 0.5..2, dispatch
    # 2.4..3 (0.4 uncovered before it), device 3..41.5, reply 41.7..42
    # (0.2 uncovered before it).
    spans = _request(7, 10, 0.0, 42.0, lid=1) + \
        _launch(1, 20, 0.5, 2.0, 3.0, 41.5)
    spans[5]["t0"] += 0.4e-3    # the dispatch span starts 0.4 ms late
    values = reader.unaccounted_ms(spans, SOURCE)
    assert values == [pytest.approx(0.6, abs=1e-6)]
    assert reader.read(SOURCE, {"spans": spans}) == pytest.approx(0.6,
                                                                  abs=1e-6)


def test_a_coalesced_pair_shares_one_launch_and_children_are_clipped():
    reader = _reader()
    # Two requests ride launch 2.  The second arrives while the launch is
    # already packing: the launch's spans are clipped to each root.
    spans = _request(1, 10, 0.0, 40.0, lid=2) + \
        _request(1, 30, 1.0, 40.5, lid=2) + \
        _launch(2, 50, 0.5, 2.0, 2.5, 39.5)
    values = reader.unaccounted_ms(spans, SOURCE)
    # first: 39.5..39.7 uncovered (reply 39.7..40) = 0.2
    # second: 39.5..40.2 uncovered (reply 40.2..40.5) = 0.7
    assert values == [pytest.approx(0.2, abs=1e-6),
                      pytest.approx(0.7, abs=1e-6)]
    assert reader.read(dict(SOURCE, percentile=100), {"spans": spans}) == \
        pytest.approx(0.7, abs=1e-6)


def test_a_request_whose_launch_is_outside_the_window_is_left_out():
    reader = _reader()
    inside = _request(1, 10, 0.0, 42.0, lid=1) + \
        _launch(1, 20, 0.5, 2.0, 3.0, 41.7)
    # Request 2's launch (lid 9) ended before the window: only the
    # request's own spans are in the list.
    orphan = _request(2, 30, 50.0, 51.0, lid=9)
    # A verdict-cache answer has no launch and counts with its children.
    cached = [_span("request", 60.0, 60.5, id=40, rid=3, parent=None,
                    ok=True, cached=True),
              _span("decode", 60.0, 60.1, id=41, rid=3, parent=40),
              _span("reply", 60.3, 60.5, id=42, rid=3, parent=40)]
    values = reader.unaccounted_ms(inside + orphan + cached, SOURCE)
    assert values == [pytest.approx(0.0, abs=1e-6),
                      pytest.approx(0.2, abs=1e-6)]
    assert reader.read(SOURCE, {"spans": orphan}) is None
    assert reader.read(SOURCE, {"spans": []}) is None
    # the parent commit's spans carry no t0, id or parent: nothing, no raise
    old = [{"stage": "device", "t": 100.0, "dur_ms": 38.0},
           {"stage": "reply", "t": 100.0, "dur_ms": 0.0, "rid": 1}]
    assert reader.read(SOURCE, {"spans": old}) is None


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["solo", "flood"])
@pytest.mark.parametrize("metric", NEW)
def test_new_layer_files_load_and_agree_with_the_manifests(metric, mix):
    path = os.path.join(BENCH, "layers", f"{metric}.{mix}.json")
    if mix == "flood" and metric.startswith("warm_"):
        assert not os.path.exists(path)   # set-up: no .flood twin
        return
    layer = _load(path)
    assert layer["name"] == f"{metric}.{mix}"
    assert os.path.isfile(os.path.join(
        BENCH, "readers", layer["reader"]["kind"] + ".py"))
    assert layer["moves"] == ("setup_s" if metric.startswith("warm_") else
                              {"solo": "verify_p50_ms",
                               "flood": "verify_sigs_per_s"}[mix])
    manifests = {"benchmark": os.path.join(REPO, "BENCHMARK.json"),
                 "tracing": os.path.join(BENCH, "rehearsal",
                                         "BENCHMARK.tracing.json")}
    for which, manifest in manifests.items():
        entries = {e["name"]: e for e in _load(manifest)["per_layer"]}
        if mix == "flood" and which == "tracing":
            assert layer["name"] not in entries   # that manifest is solo's
            continue
        entry = entries[layer["name"]]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert layer[key] == entry[key], (which, key)
        assert len(entry["workloads"]) == 1
        if mix == "flood":
            assert entry["workloads"] == ["eddsa1024.flood"]


def test_every_flood_layer_file_has_its_entry():
    """The cell has landed: every ``*.flood.json`` is in BENCHMARK.json."""
    names = {os.path.basename(p)[:-5] for p in
             glob.glob(os.path.join(BENCH, "layers", "*.flood.json"))}
    entries = {e["name"]: e for e in
               _load(os.path.join(REPO, "BENCHMARK.json"))["per_layer"]}
    assert len(names) == 15 and names <= set(entries)
    for name in names:
        assert entries[name]["workloads"] == ["eddsa1024.flood"]
        assert entries[name]["moves"] == "verify_sigs_per_s"


def test_the_tracing_rehearsal_manifest_is_whole():
    """The qc24.solo17 cell with what the old rehearsal manifest gives it
    plus the new metrics; every metric has its layer file."""
    old = _load(os.path.join(BENCH, "rehearsal", "BENCHMARK.json"))
    new = _load(os.path.join(BENCH, "rehearsal", "BENCHMARK.tracing.json"))
    assert [w["name"] for w in new["workloads"]] == ["qc24.solo17"]
    assert [c["name"] for c in new["configs"]] == ["qc24"]
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    kept = [e for e in old["per_layer"] if "qc24.solo17" in e["workloads"]]
    assert new["per_layer"][:len(kept)] == kept
    assert [e["name"] for e in new["per_layer"][len(kept):]] == \
        [f"{m}.solo" for m in NEW]
    names = {os.path.basename(p)[:-5] for p in
             glob.glob(os.path.join(BENCH, "layers", "*.json"))}
    assert {e["name"] for e in new["per_layer"]} <= names
