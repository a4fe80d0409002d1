"""The cell ``qc100f33.byz`` as data: its mix against the plain
reference, its rehearsal manifest against the contract's mechanical
limits (``test_manifest.py``'s own checks, on one more manifest), and
the whole command on the CPU at rehearsal size (a ``run.py`` process,
~3 minutes, most of it the sidecar's own warm-up)."""

import json
import os
import subprocess
import sys

import pytest

import run
import test_manifest
from yardstick import ref_ed25519 as ref
from yardstick import streams

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
REHEARSAL = os.path.join(BENCH, "rehearsal", "BENCHMARK.byz.json")
CELL = "qc24f7.byz17"


def _load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


BYZ = _load("traffic", "byz.json")
QC100F33 = _load("configs", "qc100f33.json")
SMALL = {"sidecar": {"committee": 10}}


def test_every_block_holds_a_byzantine_third():
    assert streams.votes_per_request(BYZ, QC100F33) == 67
    blocks = streams.pool_blocks(BYZ, 67)
    assert blocks == 20 and 20 * 100 * 67 > 2 * 65536
    for seed in (1, 2**31 + 11):
        kinds = streams.schedule(BYZ, seed, blocks)
        assert len(kinds) == 2000
        for i in range(blocks):
            block = kinds[100 * i:100 * (i + 1)]
            assert {k: block.count(k) for k in set(block)} == \
                {"qc": 62, "tc": 5, "qc_forged": 30, "tc_forged": 3}
    assert streams.schedule(BYZ, 1, 2) != streams.schedule(BYZ, 2, 2)


@pytest.mark.parametrize("kind", sorted(BYZ["kinds"]))
def test_a_forged_request_has_one_bad_row_the_reference_rejects(kind):
    gen = streams.Generator(BYZ, SMALL, 2**31 + 5)
    r = gen.request("pool", 0, kind)
    assert len(r["msgs"]) == len(r["pks"]) == len(r["sigs"]) == 7
    assert len(set(r["pks"])) == 7                      # distinct validators
    assert (len(set(r["msgs"])) == 1) == kind.startswith("qc")
    assert len(r["bad"]) == kind.endswith("_forged")
    mask = [bool(ref.verify(pk, m, s)) for m, pk, s in
            zip(r["msgs"], r["pks"], r["sigs"])]
    assert mask == streams.expected_mask(r)
    assert mask.count(False) == len(r["bad"])


def test_the_sample_check_holds_every_planted_forgery():
    mix = dict(BYZ, pool_min_records=0)                 # one block
    pool = streams.Generator(mix, {"sidecar": {"committee": 4}}, 3).pool()
    got = streams.check_sample(pool, 3, sample=8)
    assert got == {"checked": 33 + 8, "forged": 33, "disagreements": []}


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

    assert of(manifest[1], CELL) == of(main, "qc100f33.byz") != []
    cell = run.resolve_cell(REHEARSAL, CELL)
    assert cell["mix_data"]["block"] == BYZ["block"]
    assert cell["mix_data"]["kinds"] == BYZ["kinds"]
    assert cell["config_data"]["guarantees"] == QC100F33["guarantees"]


# -- the whole command, on the CPU ------------------------------------------

def test_the_whole_command_runs_and_bisects_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         REHEARSAL, "--workload", CELL, "--seed", str(2**31 + 13),
         "--seconds", "3", "--trace", "1"],
        capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
    out = got.stdout.strip().splitlines()
    assert got.returncode == 1, got.stdout[-2000:] + got.stderr[-2000:]
    assert out[-1].startswith("bench: REFUSED, no result: platform is 'cpu'")
    assert out[-1].endswith("problems besides the platform: none")
    notes = json.loads(out[-2][len("bench: notes: "):])
    assert set(notes["by_status"]) == {"ok"}
    # a 3 s window on the CPU holds a handful of requests: the seed puts
    # forged ones among them
    assert set(notes["by_kind"]) <= set(BYZ["kinds"])
    assert notes["paths"]["rlc_bisect"] > 0 and "host" not in notes["paths"]
    assert notes["compiles_in_window"] == []

    # What the cell's span readers will read on the chip is there.
    work = os.path.join(BENCH, ".work", CELL)
    with open(os.path.join(work, "notes.json"), encoding="utf-8") as f:
        full = json.load(f)
    assert "reader_errors" not in full
    with open(os.path.join(work, "spans.jsonl"), encoding="utf-8") as f:
        spans = [json.loads(line) for line in f]
    bisects = [s for s in spans if s["stage"] == "bisect"]
    steps = [s for s in spans if s["stage"] == "bisect_step"]
    forged = sum(n for k, n in notes["by_kind"].items()
                 if k.endswith("_forged"))
    assert 1 <= forged == notes["paths"]["rlc_bisect"] - \
        full["paths_before"]["rlc_bisect"]
    # the spans reach back to the boot: the two forged kinds' unmeasured
    # requests bisected too
    assert len(bisects) == notes["paths"]["rlc_bisect"] == forged + 2
    # one forged vote at quorum 17: six programs (tests/test_byz.py)
    assert {s["launches"] for s in bisects} == {6}
    assert {(s["bad"], s["n"]) for s in bisects} == {(1, 17)}
    assert len(steps) == sum(s["launches"] for s in bisects)
    assert {s["parent"] for s in steps} == {s["id"] for s in bisects}
