"""Device BLS12-381 engine tests: Fq Montgomery arithmetic, the Fq12
tower, Frobenius/inversion, and (behind HOTSTUFF_TPU_SLOW_TESTS=1, ~4 min
of XLA compile on CPU) the full aggregate pairing check against the host
reference (offchain/bls12381.py).
"""


import contextlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
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


def cyclotomic_fq12():
    """A random Fq12 value after the easy part of the final
    exponentiation, f^((q^6 - 1)(q^2 + 1)): an element of the cyclotomic
    subgroup, where the hard part's chain runs."""
    x = rand_fq12()
    f1 = host.fq12_mul(host.fq12_pow(x, host.Q ** 6), host.fq12_inv(x))
    return host.fq12_mul(host.fq12_pow(f1, host.Q ** 2), f1)


def test_hard_exponent_factors_through_the_curve_parameter():
    """(q^4 - q^2 + 1)/r = ((x-1)^2/3)(x+q)(x^2+q^2-1) + 1 for the
    curve's x = -BLS_X, in Python integers."""
    q, x = host.Q, -host.BLS_X
    assert (x - 1) ** 2 % 3 == 0
    assert D._HARD_LAMBDA == (x - 1) ** 2 // 3
    assert D._HARD_LAMBDA.bit_length() == 126
    assert (q ** 4 - q ** 2 + 1) % host.R == 0
    assert (q ** 4 - q ** 2 + 1) // host.R == \
        D._HARD_LAMBDA * (x + q) * (x ** 2 + q ** 2 - 1) + 1


def test_cyclotomic_exp_x_matches_host():
    y = cyclotomic_fq12()
    got = from_dev(jax.jit(D._cyclotomic_exp_x)(to_dev(y)))
    assert got == host.fq12_pow(y, host.BLS_X)


def test_conj_is_frobenius_6_and_the_cyclotomic_inverse():
    y = cyclotomic_fq12()
    conj = from_dev(D._conj(to_dev(y)))
    assert conj == from_dev(D.fq12_frobenius(to_dev(y), 6))
    assert conj == host.fq12_inv(y)
    # a negation's value reaches 64q: the output is back in weak form
    # and a product of it stays exact
    assert from_dev(D.fq12_mul(D._conj(to_dev(y)), to_dev(y))) == \
        host.FQ12_ONE


@pytest.mark.slow  # ~3 min XLA compile on the CPU
def test_final_exponentiate_matches_host():
    x = rand_fq12()
    got = from_dev(jax.jit(D.final_exponentiate)(to_dev(x)))
    assert got == host.final_exponentiate(x)


def test_miller_lines_match_host_miller():
    """Accumulating the host-precomputed lines reproduces the host Miller
    value (up to the BLS_X-sign inversion the device skips)."""
    sk, pk = host.key_gen(b"\x07" * 32)
    sig = host.sign(sk, b"m")
    lines = D.miller_lines(pk, sig)
    f_dev = from_dev(D.miller_accumulate(jnp.asarray(lines)[None]))
    f_host = host.miller_loop(host._twist(sig), host._cast_g1_fq12(pk))
    assert f_dev == host.fq12_inv(f_host)  # host returns the inverse


def fq12_miller_lines(p_g1, q_g2) -> np.ndarray:
    """The oracle: the Miller lines by the host reference's loop on the
    untwisted point over Fq12 (two Fq12 inversions a step), each line's
    coefficients in Montgomery limbs."""
    qt = host._twist(q_g2)
    pf = host._cast_g1_fq12(p_g1)
    rpt = qt
    out = np.zeros((D.N_STEPS, 2, 12, F.NLIMBS), np.int32)
    for i, bit in enumerate(D._BITS):
        out[i, 0] = D.host_fq12_to_mont_limbs(host._linefunc(rpt, rpt, pf))
        rpt = host._add(rpt, rpt, host._fq12)
        if bit:
            out[i, 1] = D.host_fq12_to_mont_limbs(
                host._linefunc(rpt, qt, pf))
            rpt = host._add(rpt, qt, host._fq12)
        else:
            out[i, 1] = D.host_fq12_to_mont_limbs(host.FQ12_ONE)
    return out


@pytest.fixture(scope="module")
def qc67():
    """A 67-vote certificate of a seeded 100-validator committee: the
    aggregate key, the digest, its hash to G2 and the votes' aggregate."""
    rng = np.random.default_rng(67)
    committee = [host.key_gen(b"validator %d" % i) for i in range(100)]
    signers = rng.choice(100, 67, replace=False)
    digest = bytes(rng.bytes(32))
    h = host.hash_to_g2(digest)
    apk = D.aggregate_keys([committee[i][1] for i in signers])
    agg = host.aggregate([host.g2_mul(h, committee[i][0]) for i in signers])
    return apk, digest, h, agg


def line_pair(case, qc67):
    apk, _, h, agg = qc67
    if case == "apk_h":
        return apk, h
    if case == "neg_g1_agg":
        return host.g1_neg(host.g1_generator()), agg
    if case == "generators":
        return host.g1_generator(), host.g2_generator()
    rng = np.random.default_rng(4242)
    k, k2 = (int.from_bytes(rng.bytes(32), "little") for _ in range(2))
    return (host.g1_mul(host.g1_generator(), k),
            host.g2_mul(host.g2_generator(), k2))


@pytest.mark.parametrize(
    "case", ["apk_h", "neg_g1_agg", "generators", "random"])
def test_twist_lines_are_the_fq12_lines(case, qc67):
    """The lines computed on the twist over Fq2 are the reference loop's
    over Fq12, limb for limb: alone, and in a lockstep pass beside the
    certificate's other pairing."""
    p, q = line_pair(case, qc67)
    want = fq12_miller_lines(p, q)
    got = D.miller_lines(p, q)
    assert got.dtype == want.dtype and np.array_equal(got, want)
    partner = (host.g1_neg(host.g1_generator()), qc67[3])
    both = D.miller_lines_many([(p, q), partner])
    assert np.array_equal(both[0], want)
    assert np.array_equal(both[1], D.miller_lines(*partner))


@contextlib.contextmanager
def counted_inversions():
    """Count the Fq inversions (``pow(x, -1, Q)``) the module takes."""
    calls = []

    def counting(base, exp, mod=None):
        calls.append(exp)
        return pow(base, exp, mod)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(D, "pow", counting, raising=False)
        yield calls


def test_the_lockstep_pass_halves_the_inversions(qc67):
    """One Fq inversion a step for both pairings of a certificate (63
    doublings + 5 additions = 68), twice that in two passes; and
    ``verify_common_apk`` stages the lockstep pass's lines."""
    apk, digest, h, agg = qc67
    pairs = [(apk, h), (host.g1_neg(host.g1_generator()), agg)]
    with counted_inversions() as shared:
        lines = D.miller_lines_many(pairs)
    with counted_inversions() as alone:
        for pair in pairs:
            D.miller_lines_many([pair])
    assert set(shared) == set(alone) == {-1}
    assert len(shared) == D.N_STEPS + sum(D._BITS) == 68
    assert len(alone) == 2 * len(shared) == 136

    class Stages:
        tags = {}

        @contextlib.contextmanager
        def stage(self, name):
            yield self.tags.setdefault(name, {})

    staged = []

    def program(x):
        staged.append(np.asarray(x))
        return np.True_

    trace = Stages()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(D, "pairings_check_jit", program)
        with counted_inversions() as taken:
            assert D.verify_common_apk(apk, digest, agg, trace=trace)
    assert trace.tags["miller_lines"] == {"pairings": 2}
    assert taken == [-1] * 68
    assert np.array_equal(staged[0], lines)


def twist_step(p1, p2, p_g1):
    """One ``_lines_step`` of one pair: (the line's 12 Montgomery
    coefficients, the new point, the inversions taken)."""
    pts, vals = [p1], [0] * 12
    with counted_inversions() as taken:
        D._lines_step(pts, [p2], [p_g1], vals, [0])
    return vals, pts[0], len(taken)


@pytest.mark.parametrize("case", ["distinct", "tangent", "vertical"])
def test_twist_step_branches_match_the_fq12_step(case):
    """Each of host._linefunc's cases on hand-made affine points: the
    twist step's line is the Fq12 line and its point, untwisted, is
    host._add's (the identity for a vertical line)."""
    q = host.g2_mul(host.g2_generator(), 5)
    other = {"distinct": host.g2_mul(host.g2_generator(), 7),
             "tangent": q, "vertical": host.g2_neg(q)}[case]
    p = host.g1_mul(host.g1_generator(), 11)
    vals, new, taken = twist_step(q, other, p)
    qt, ot, pf = host._twist(q), host._twist(other), host._cast_g1_fq12(p)
    assert vals == [c * F.R % F.Q for c in host._linefunc(qt, ot, pf)]
    assert host._twist(new) == host._add(qt, ot, host._fq12)
    assert taken == (0 if case == "vertical" else 1)
    assert (new is None) == (case == "vertical")


def test_twist_step_shares_one_inversion_beside_a_vertical_line():
    """Three pairs in one step: a vertical line takes no inversion, the
    other two share one, and each pair's line is what it reads alone."""
    g2 = host.g2_generator()
    q, p = host.g2_mul(g2, 5), host.g1_mul(host.g1_generator(), 11)
    pts = [q, q, q]
    others = [host.g2_neg(q), q, host.g2_mul(g2, 7)]
    vals = [0] * 36
    with counted_inversions() as taken:
        D._lines_step(pts, others, [p] * 3, vals, [0, 12, 24])
    assert len(taken) == 1
    for j in range(3):
        line, new, _ = twist_step(q, others[j], p)
        assert vals[12 * j:12 * j + 12] == line and pts[j] == new


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
    trips of the scans around it.  The yardstick's ``hard_part`` counts
    the windowed power by the 1,268-bit exponent; the program runs the
    chain through the curve parameter, counted here from its constants."""
    import json
    import os

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

    def pow_products(exponent, window):   # F.pow_windowed's Fq12 products
        steps = -(-exponent.bit_length() // window)
        return 2 ** window - 2 + steps * (window + 1)

    # the 126-bit power, three powers by |x|, four products and two
    # Frobenius maps (a matrix product each); _conj multiplies nothing
    chain = 144 * (pow_products(D._HARD_LAMBDA, D._LAMBDA_WINDOW)
                   + 3 * pow_products(D.BLS_X, 1) + 4 + 2)
    assert chain == 144 * 564
    assert convs == 3 * (model["fq_muls_total"]
                         - model["fq_muls"]["hard_part"] + chain)
    assert sum(model["fq_muls"].values()) == model["fq_muls_total"]
    assert model["bytes_in"] == 2 * D.N_STEPS * 2 * 12 * F.NLIMBS * 4
