"""Device BLS12-381 engine tests: Fq Montgomery arithmetic, the Fq12
tower, Frobenius/inversion, and (behind HOTSTUFF_TPU_SLOW_TESTS=1, ~4 min
of XLA compile on CPU) the full aggregate pairing check against the host
reference (offchain/bls12381.py).
"""


import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from hotstuff_tpu.offchain import bls12381 as host
from hotstuff_tpu.ops import bls381 as D
from hotstuff_tpu.ops import field381 as F

RNG = np.random.default_rng(11)


def rand_fq() -> int:
    return int.from_bytes(RNG.bytes(48), "little") % F.Q


def rand_fq12():
    return tuple(rand_fq() for _ in range(12))


def to_dev(x):
    return jnp.asarray(D.host_fq12_to_mont_limbs(x))[None]


def from_dev(d):
    return tuple(F.from_limbs(r) for r in np.asarray(F.from_mont(d))[0])


def test_field381_mont_roundtrip_and_ops():
    F.mul_selfcheck()
    xs = [rand_fq() for _ in range(8)]
    ys = [rand_fq() for _ in range(8)]
    a = jnp.asarray(np.stack([F.to_limbs(x * F.R % F.Q) for x in xs]))
    b = jnp.asarray(np.stack([F.to_limbs(y * F.R % F.Q) for y in ys]))
    assert [F.from_limbs(v) for v in np.asarray(F.from_mont(F.add(a, b)))] \
        == [(x + y) % F.Q for x, y in zip(xs, ys)]
    assert [F.from_limbs(v) for v in np.asarray(F.from_mont(F.sub(a, b)))] \
        == [(x - y) % F.Q for x, y in zip(xs, ys)]
    assert [F.from_limbs(v) for v in np.asarray(F.from_mont(F.inv(a)))] \
        == [pow(x, F.Q - 2, F.Q) for x in xs]


def test_field381_mul_chain_stability():
    """Digit bounds must hold over arbitrarily long mul/sub chains."""
    x, y = rand_fq(), rand_fq()
    a = jnp.asarray(F.to_limbs(x * F.R % F.Q))[None]
    b = jnp.asarray(F.to_limbs(y * F.R % F.Q))[None]
    acc, want = a, x
    for _ in range(50):
        acc = F.mont_mul(F.sub(acc, b), b)
        want = (want - y) * y % F.Q
    assert F.from_limbs(np.asarray(F.from_mont(acc))[0]) == want


def test_inverse_of_a_weak_input_near_2_385():
    """A weak element near 2^385 (reduce_sum's output reaches it): the
    Fermat chain's table of its powers grew past the conv exactness
    bound and came back wrong (on the chip, 5 of 64 valid certificates'
    final exponentiations).  ``F.inv`` brings it under R first."""
    xs = [rand_fq() for _ in range(4)]
    rows = []
    for x in xs:
        r = x * F.R % F.Q
        v = r + (2 ** 385 - 1 - r) // F.Q * F.Q   # same residue, top value
        assert 1.8 * F.R < v < 2 ** 385
        limbs = [(v >> (8 * i)) & 0xFF for i in range(F.NLIMBS - 1)]
        rows.append(limbs + [v >> (8 * (F.NLIMBS - 1))])
    got = np.asarray(F.from_mont(F.inv(jnp.asarray(rows, jnp.int32))))
    assert [F.from_limbs(g) for g in got] == \
        [pow(x, F.Q - 2, F.Q) for x in xs]


def test_fq12_mul_matches_host():
    x, y = rand_fq12(), rand_fq12()
    assert from_dev(D.fq12_mul(to_dev(x), to_dev(y))) == host.fq12_mul(x, y)


def test_fq12_mul_deep_chain():
    """The reduce_sum invariant: 20 chained tower muls stay exact (without
    it the top limb creeps past the f32 conv bound and results corrupt
    silently)."""
    x, y = rand_fq12(), rand_fq12()
    acc, hacc = to_dev(x), x
    for _ in range(20):
        acc = D.fq12_mul(acc, to_dev(y))
        hacc = host.fq12_mul(hacc, y)
    assert from_dev(acc) == hacc


def test_fq12_frobenius_and_inverse():
    x = rand_fq12()
    dx = to_dev(x)
    assert from_dev(D.fq12_frobenius(dx, 1)) == host.fq12_pow(x, host.Q)
    assert from_dev(D.fq12_frobenius(dx, 6)) == host.fq12_pow(x, host.Q ** 6)
    assert from_dev(D.fq12_inv(dx)) == host.fq12_inv(x)


def test_miller_lines_match_host_miller():
    """Accumulating the host-precomputed lines reproduces the host Miller
    value (up to the BLS_X-sign inversion the device skips)."""
    sk, pk = host.key_gen(b"\x07" * 32)
    sig = host.sign(sk, b"m")
    lines = D.miller_lines(pk, sig)
    f_dev = from_dev(D.miller_accumulate(jnp.asarray(lines)[None]))
    f_host = host.miller_loop(host._twist(sig), host._cast_g1_fq12(pk))
    assert f_dev == host.fq12_inv(f_host)  # host returns the inverse


@pytest.mark.slow  # ~4 min XLA compile
def test_aggregate_verify_device_end_to_end():
    msg = b"quorum certificate digest"
    sks, pks = zip(*[host.key_gen(bytes([i]) * 32) for i in range(1, 5)])
    sigs = [host.sign(s, msg) for s in sks]
    agg = host.aggregate(sigs)
    assert D.verify_aggregate_common(list(pks), msg, agg)
    bad = host.aggregate(sigs[:3] + [host.sign(sks[0], b"other")])
    assert not D.verify_aggregate_common(list(pks), msg, bad)
    # A 67-of-100 QC as a scheme=bls replica ships it (OP_BLS_VERIFY_VOTES:
    # encoded keys and votes), decoded and summed as the sidecar does.
    committee = [host.key_gen(b"validator %d" % i) for i in range(100)]
    signers = RNG.choice(100, 67, replace=False)
    digest = bytes(RNG.bytes(32))
    h = host.hash_to_g2(digest)
    pk_enc = [host.g1_encode(committee[i][1]) for i in signers]
    votes = [host.g2_encode(host.g2_mul(h, committee[i][0]))
             for i in signers]
    forged = list(votes)
    forged[13] = host.g2_encode(host.sign(committee[signers[13]][0],
                                          b"another digest"))
    for enc, want in ((votes, True), (forged, False)):
        keys = [host.g1_decode(p) for p in pk_enc]
        agg = host.aggregate([host.g2_decode_lax(v) for v in enc])
        assert host.g2_in_subgroup(agg)
        assert D.verify_common_apk(D.aggregate_keys(keys), digest, agg) \
            is want


@pytest.mark.slow  # ~4 min XLA compile
def test_aggregate_verify_multi_device_end_to_end():
    """Distinct-digest product-of-pairings (the TC verify shape)."""
    sks, pks = zip(*[host.key_gen(bytes([i]) * 32) for i in range(1, 4)])
    msgs = [bytes([i]) * 32 for i in range(3)]
    sigs = [host.sign(s, m) for s, m in zip(sks, msgs)]
    agg = host.aggregate(sigs)
    assert D.verify_aggregate_multi(list(pks), msgs, agg)
    # wrong digest on one vote breaks the product
    bad = host.aggregate(sigs[:2] + [host.sign(sks[2], b"x" * 32)])
    assert not D.verify_aggregate_multi(list(pks), msgs, bad)
    # mismatched lengths and empty input reject without device work
    assert not D.verify_aggregate_multi(list(pks), msgs[:2], agg)
    assert not D.verify_aggregate_multi([], [], agg)


def test_pairing_muls_json_counts_the_traced_program():
    """``benchmark/yardstick/pairing_muls.json`` (the roofline's count of
    one pairing check) against the program: Fq multiplications counted
    in one traced lowering of ``pairings_check`` — every convolution is
    a third of a Montgomery multiply over its feature groups, times the
    trips of the scans around it."""
    import json
    import os

    import jax

    def fq_muls(jaxpr, trips=1):
        total = 0
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "conv_general_dilated":
                total += trips * eqn.params["feature_group_count"]
            elif name == "scan":
                total += fq_muls(eqn.params["jaxpr"].jaxpr,
                                 trips * eqn.params["length"])
            elif name in ("pjit", "jit", "closed_call"):
                total += fq_muls(eqn.params["jaxpr"].jaxpr, trips)
            else:
                assert name not in ("while", "cond"), name
        return total

    lines = jax.ShapeDtypeStruct((2, D.N_STEPS, 2, 12, F.NLIMBS), jnp.int32)
    convs = fq_muls(jax.make_jaxpr(D.pairings_check)(lines).jaxpr)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "yardstick",
        "pairing_muls.json")
    with open(path, encoding="utf-8") as f:
        model = json.load(f)
    assert convs == 3 * model["fq_muls_total"]
    assert sum(model["fq_muls"].values()) == model["fq_muls_total"]
    assert model["bytes_in"] == 2 * D.N_STEPS * 2 * 12 * F.NLIMBS * 4
