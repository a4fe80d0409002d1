import json
import os

import pytest

from yardstick import ref_ed25519 as ref
from yardstick import streams

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


SOLO = _load("traffic", "solo.json")
FLOOD = _load("traffic", "flood.json")
QC100 = _load("configs", "qc100.json")
EDDSA = _load("configs", "eddsa1024.json")
SMALL = {"sidecar": {"committee": 10}}


def test_quorum_is_the_nodes_formula():
    assert [streams.quorum(n) for n in (4, 10, 100, 1000)] == [3, 7, 67, 667]
    assert streams.votes_per_request(SOLO, QC100) == 67
    assert streams.votes_per_request(FLOOD, EDDSA) == 1024


def test_pool_exceeds_twice_the_verdict_cache_in_whole_blocks():
    assert streams.pool_blocks(SOLO, 67) == 20          # 2,000 certificates
    assert 20 * 100 * 67 > 2 * 65536
    assert streams.pool_blocks(FLOOD, 1024) == 2        # 128 requests
    assert 2 * 64 * 1024 == 2 * 65536
    assert streams.pool_blocks(SOLO, 667) == 2          # qc1000 unchanged


def test_every_block_holds_the_same_work_in_a_seeded_order():
    a = streams.schedule(SOLO, 1, 3)
    b = streams.schedule(SOLO, 2, 3)
    assert a != b and a == streams.schedule(SOLO, 1, 3)
    for kinds in (a, b):
        for i in range(3):
            block = kinds[100 * i:100 * (i + 1)]
            assert {k: block.count(k) for k in set(block)} == SOLO["block"]


def test_requests_are_a_function_of_the_seed_even_a_large_one():
    seed = 2**31 + 11
    g1 = streams.Generator(SOLO, SMALL, seed)
    g2 = streams.Generator(SOLO, SMALL, seed)
    r1, r2 = g1.request("pool", 3, "qc"), g2.request("pool", 3, "qc")
    assert r1 == r2
    assert r1 != streams.Generator(SOLO, SMALL, seed + 1).request(
        "pool", 3, "qc")


@pytest.mark.parametrize("kind", ["qc", "tc", "qc_forged"])
def test_ground_truth_is_the_plain_reference(kind):
    gen = streams.Generator(SOLO, SMALL, 5)
    r = gen.request("pool", 0, kind)
    assert len(r["msgs"]) == len(r["pks"]) == len(r["sigs"]) == 7
    assert len(set(r["pks"])) == 7                      # distinct validators
    assert (len(set(r["msgs"])) == 1) == (kind != "tc")
    assert len(r["bad"]) == (kind == "qc_forged")
    mask = [ref.verify(pk, m, s) for m, pk, s in
            zip(r["msgs"], r["pks"], r["sigs"])]
    assert mask == streams.expected_mask(r)


def test_signer_gives_the_reference_signature():
    secret = bytes(range(32))
    s = streams.Signer(secret)
    assert s.pk == ref.generate_keypair(secret)[1]
    assert s.sign(b"m" * 32) == ref.sign(secret, b"m" * 32)


def test_distinct_keys_and_the_sample_check():
    mix = dict(FLOOD, votes=4, block={"batch": 3, "batch_forged": 1},
               pool_min_records=15)
    gen = streams.Generator(mix, {"sidecar": {}}, 9)
    pool = gen.pool()
    assert len(pool) == 4
    assert len({pk for r in pool for pk in r["pks"]}) == 16
    ok = streams.check_sample(pool, 9, sample=8)
    assert ok == {"checked": 9, "forged": 1, "disagreements": []}
    # a generator that lied about a forged row is caught
    liar = [dict(r, bad=[]) for r in pool]
    assert streams.check_sample(liar, 9, sample=64)["disagreements"]
