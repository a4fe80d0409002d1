"""The BLS cell's yardstick: the plain reference against the program's
host BLS, the certificate generator, the driver's never-replay loop and
its refusal of a program without the VOTES client, the pairing's count
of Fq products, and the cell's per-layer metrics."""

import importlib.util
import json
import os

import pytest

from yardstick import bls_streams, ref_bls12381 as ref

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def _module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}", os.path.join(BENCH, kind, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


VOTES = _load("traffic", "votes.json")
QC100BLS = _load("configs", "qc100bls.json")
SMALL = {"sidecar": {"committee": 4}}
SMALL_MIX = dict(VOTES, block={"qc": 4, "qc_forged": 1}, pool_blocks=2)


def test_the_cell_sends_quorum_certificates_of_its_committee():
    gen = bls_streams.Generator(VOTES, QC100BLS, 3900000123)
    assert gen.votes == 67 and len(gen.pks) == 100
    assert len(set(gen.pks)) == 100
    assert all(len(pk) == 96 for pk in gen.pks)
    plan = gen.plan()
    assert len(plan) == 100 * VOTES["pool_blocks"]
    kinds = [kind for _, _, kind in plan]
    assert kinds.count("qc_forged") == VOTES["pool_blocks"]


def test_the_pool_never_repeats_a_digest_and_its_forgeries_are_planted():
    gen = bls_streams.Generator(SMALL_MIX, SMALL, 2**31 + 39)
    pool = gen.build(gen.plan(), 2)
    warm = gen.build(gen.warmup(), 1)
    digests = [r["msg"] for r in pool + warm]
    assert len(set(digests)) == len(digests) == 12
    assert [bool(r["bad"]) for r in pool].count(True) == 2
    for r in pool + warm:
        assert len(r["pks"]) == len(r["sigs"]) == 3
        assert all(len(s) == 192 for s in r["sigs"])
        assert bls_streams.expected(r) == (r["kind"] == "qc")
    # the same seed builds the same bytes, in one process or several
    again = gen.build(gen.plan()[:3], 1)
    assert again == pool[:3]
    sample = bls_streams.check_sample(pool, 7, 2, 2)
    assert sample == {"checked": 4, "forged": 2, "disagreements": []}


def test_the_reference_agrees_with_the_programs_host_bls():
    """Valid, forged-vote and wrong-digest certificates: the yardstick's
    copy and the program's ``offchain/bls12381.py`` give one verdict."""
    from hotstuff_tpu.offchain import bls12381 as program

    gen = bls_streams.Generator(SMALL_MIX, SMALL, 17)
    valid, forged = gen.build([("t", 0, "qc"), ("t", 1, "qc_forged")], 1)
    wrong = dict(valid, msg=b"\x01" * 32)
    for cert, want in ((valid, True), (forged, False), (wrong, False)):
        keys = [program.g1_decode(p) for p in cert["pks"]]
        agg = program.aggregate([program.g2_decode(s) for s in cert["sigs"]])
        assert program.verify_aggregate_common(keys, cert["msg"], agg) \
            is want
        assert ref.verify_votes(cert["msg"], cert["pks"], cert["sigs"]) \
            is want
    off_curve = list(valid["sigs"])
    off_curve[0] = off_curve[0][:-1] + bytes([off_curve[0][-1] ^ 1])
    assert ref.verify_votes(valid["msg"], valid["pks"], off_curve) is False


class _Client:
    def __init__(self):
        self.sent = []

    def bls_verify_votes(self, msg, pks, sigs, *, ctx=None):
        self.sent.append(msg)
        return True


def test_the_driver_sends_each_certificate_once_and_reports_running_out():
    driver = _module("drivers", "bls_votes")
    pool = [{"kind": "qc", "msg": bytes([i]) * 32, "pks": [b""],
             "sigs": [b""], "bad": []} for i in range(3)]
    client, records = _Client(), []
    ran_out = driver._loop(client, pool, VOTES, float("inf"), records)
    assert ran_out is True
    assert client.sent == [r["msg"] for r in pool]
    assert [r["status"] for r in records] == ["ok"] * 3
    client, records = _Client(), []
    assert driver._loop(client, pool, VOTES, 0.0, records) is False
    assert client.sent == [] and records == []


def test_the_driver_refuses_a_program_without_the_votes_client(
        monkeypatch, tmp_path):
    from hotstuff_tpu.sidecar import client

    driver = _module("drivers", "bls_votes")
    monkeypatch.delattr(client.SidecarClient, "bls_verify_votes")
    with pytest.raises(driver.DriverError, match="bls_verify_votes"):
        driver.start({"config_path": "", "mix_path": ""}, 1, str(tmp_path))


def test_the_pairing_count_adds_up():
    model = _load("yardstick", "pairing_muls.json")
    assert sum(model["fq_muls"].values()) == model["fq_muls_total"]
    assert model["bytes_in"] == 2 * 63 * 2 * 12 * 48 * 4


MANIFEST = _load("..", "BENCHMARK.json")
VOTES_METRICS = [m for m in MANIFEST["per_layer"]
                 if "qc100bls.votes" in m.get("workloads", ())]
# What one traced run of the cell hands the readers: a BLS launch's spans,
# OP_STATS' compile counters, the profile's busy time.
STAGES = {"request": 640.0, "device": 639.0, "bls_prep": 19.0,
          "hash_to_g2": 26.0, "miller_lines": 115.0, "pairing": 470.0,
          "d2h": 0.7}
RUN = {"spans": [{"stage": k, "dur_ms": v} for k, v in STAGES.items()],
       "stats": {"compile": {"lower_s": 88.9, "backend_s": 128.6}},
       "profile": {"busy_s": 0.812, "window_s": 1.0},
       "config": QC100BLS, "mix": VOTES, "device": {"kind": "TPU v5 lite"}}


@pytest.mark.parametrize("metric", VOTES_METRICS, ids=lambda m: m["name"])
def test_each_votes_metric_reads_its_layer(metric):
    """Every per-layer metric of the cell has its layer file, the same
    entry in both places, and a reader that finds a number in a run of
    the cell."""
    layer = _load("layers", f"{metric['name']}.json")
    for key in ("layer", "unit", "better", "source", "moves"):
        assert layer[key] == metric[key], key
    assert metric["workloads"] == ["qc100bls.votes"]
    reader = _module("readers", layer["reader"]["kind"])
    value = reader.read(layer["reader"], RUN)
    assert isinstance(value, float) and value > 0
    if layer["reader"]["kind"] == "span":
        assert value == STAGES[layer["reader"]["stage"]]

