"""The cell ``ingress20.gate`` as data: its mix against the plain
reference and its connections, the ``tenants`` reader on a recorded
OP_STATS snapshot, and its rehearsal manifest against the contract's
mechanical limits (``test_manifest.py``'s own checks, on one more
manifest).  The whole command at rehearsal size is
``test_gate_rehearsal.py``."""

import json
import os

import pytest

import run
import test_manifest
from test_readers import _reader
from yardstick import ref_ed25519 as ref
from yardstick import streams

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
REHEARSAL = os.path.join(BENCH, "rehearsal", "BENCHMARK.gate.json")
CELL = "ingress6.gate8"


def _load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


GATE = _load("traffic", "gate.json")
INGRESS20 = _load("configs", "ingress20.json")


def test_the_pool_is_105_requests_of_64_for_each_of_twenty_gates():
    votes = streams.votes_per_request(GATE, INGRESS20)
    blocks = streams.pool_blocks(GATE, votes)
    assert (votes, blocks) == (64, 84)
    requests = blocks * sum(GATE["block"].values())
    assert requests == 2100 and requests * votes == 134400 > 2 * 65536
    # Connection k replays requests k, k+20, ...: its own 105, in order,
    # so a record comes back only after every other one of the pool.
    assert GATE["connections"] == INGRESS20["sidecar"]["committee"] == 20
    assert requests % GATE["connections"] == 0
    # 1,280 records in flight: more than one launch holds, far under
    # the bulk cap even at the surge controller's full derate.
    in_flight = GATE["connections"] * votes
    assert 1024 < in_flight == 1280 < 2 * INGRESS20["sidecar"]["client_rate"] / 4


def test_one_signature_in_a_hundred_is_forged_in_every_block():
    per_block = sum(GATE["block"].values())
    forged = sum(n * GATE["kinds"][k]["forged"]
                 for k, n in GATE["block"].items())
    assert forged / (per_block * 64) == 0.01
    for seed in (1, 2**31 + 11):
        kinds = streams.schedule(GATE, seed, 84)
        assert len(kinds) == 2100
        for i in range(84):
            block = kinds[per_block * i:per_block * (i + 1)]
            assert {k: block.count(k) for k in set(block)} == GATE["block"]


@pytest.mark.parametrize("kind", sorted(GATE["kinds"]))
def test_a_gate_request_is_64_distinct_keys_the_reference_judges(kind):
    gen = streams.Generator(GATE, INGRESS20, 2**31 + 5)
    r = gen.request("pool", 0, kind)
    assert len(r["msgs"]) == len(r["pks"]) == len(r["sigs"]) == 64
    assert len(set(r["pks"])) == len(set(r["msgs"])) == 64
    assert len(r["bad"]) == GATE["kinds"][kind]["forged"]
    mask = [bool(ref.verify(pk, m, s)) for m, pk, s in
            zip(r["msgs"], r["pks"], r["sigs"])]
    assert mask == streams.expected_mask(r)


# -- the ``tenants`` reader --------------------------------------------------

def _wait(p50_ms, n=100):
    return {"admitted": {"bulk": n}, "shed": {},
            "queue_wait": {"bulk": {"n": n, "p50_ms": p50_ms,
                                    "p99_ms": 2 * p50_ms}}}


# OP_STATS as ``sched/stats.py`` writes it: one record a HELLO name, a
# class absent from ``queue_wait`` until it has a sample.
SNAPSHOT = {"launches": 7, "tenants": {
    "gate-0": _wait(10.0), "gate-1": _wait(12.0), "gate-2": _wait(11.0),
    "gate-3": _wait(33.0),
    "watcher": {"admitted": {}, "shed": {}, "queue_wait": {}}}}
SOURCE = _load("layers", "tenant_wait_skew.gate.json")["reader"]


def test_tenants_reader_gives_the_worst_tenant_over_the_median_one():
    read = _reader("tenants").read
    assert SOURCE == {"kind": "tenants", "path": "queue_wait.bulk.p50_ms"}
    # median of 10, 11, 12, 33 is 11.5; the tenant without a bulk
    # sample is left out
    assert read(SOURCE, {"stats": SNAPSHOT}) == pytest.approx(33.0 / 11.5)
    even = {"tenants": {f"gate-{k}": _wait(31.2) for k in range(20)}}
    assert read(SOURCE, {"stats": even}) == 1.0


@pytest.mark.parametrize("stats", [
    None, {}, {"tenants": {}}, {"tenants": {"gate-0": _wait(10.0)}},
    {"tenants": {"a": _wait(0.0), "b": _wait(0.0)}},
    {"tenants": {"a": {"queue_wait": {"bulk": {"p50_ms": True}}},
                 "b": {"queue_wait": {"bulk": {"p50_ms": "1"}}}}},
    {"tenants": ["gate-0"]}], ids=repr)
def test_tenants_reader_returns_nothing_where_there_is_no_spread(stats):
    assert _reader("tenants").read(SOURCE, {"stats": stats}) is None
    assert _reader("tenants").read(SOURCE, {}) is None


# -- the rehearsal manifest: test_manifest.py's checks on one more ---------

@pytest.fixture(scope="module")
def manifest():
    with open(REHEARSAL, encoding="utf-8") as f:
        return REHEARSAL, json.load(f)


@pytest.mark.parametrize("check", [
    test_manifest.test_keys_and_limits,
    test_manifest.test_names_units_and_entries,
    test_manifest.test_cells_configs_and_metrics_fit_together,
    test_manifest.test_every_layer_metric_has_its_file_and_they_agree],
    ids=lambda f: f.__name__)
def test_rehearsal_manifest_keeps_the_contract(manifest, check):
    check(manifest)


def test_the_rehearsal_lists_the_cells_own_layer_metrics(manifest):
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        main = json.load(f)

    def of(m, cell):
        return sorted((e["name"], e["unit"], e["layer"], e["moves"])
                      for e in m["per_layer"] if cell in e["workloads"])

    assert of(manifest[1], CELL) == of(main, "ingress20.gate")
    assert len(of(main, "ingress20.gate")) == 18
    cell = run.resolve_cell(REHEARSAL, CELL)
    mix, config = cell["mix_data"], cell["config_data"]
    assert mix["block"] == GATE["block"] and mix["kinds"] == GATE["kinds"]
    assert (mix["class"], mix["keys"], mix["ctx"]) == \
        (GATE["class"], GATE["keys"], GATE["ctx"])
    assert config["guarantees"] == INGRESS20["guarantees"]
    # The rehearsal keeps the cell's shape (a gate a committee member,
    # the same count of requests a connection) but not its carry-over:
    # all six requests of 8 fit one launch of the warmed 64.
    votes = streams.votes_per_request(mix, config)
    requests = streams.pool_blocks(mix, votes) * sum(mix["block"].values())
    assert mix["connections"] == config["sidecar"]["committee"] == 6
    assert requests % mix["connections"] == 0
    assert mix["connections"] * votes <= config["sidecar"]["warm_max"]
