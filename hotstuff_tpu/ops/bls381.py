"""BLS12-381 pairing verification on TPU: batched Fq12 arithmetic + the
final exponentiation, with host-precomputed Miller line values.

Work split (mirrors the Ed25519 engine's host/device boundary):
* HOST (python bigints): point decode/validation, hash-to-G2, public-key
  aggregation, and the Miller loop's line values — O(64) affine
  operations over Fq12 a pairing, tens of ms of Python each (~44 ms a
  pairing on a CPU core); PERF.md §5 has what they cost on the chip's
  host beside the device program (``qc100bls.votes``).
* DEVICE (the FLOPs): the Miller accumulation f <- f^2 * l_i over the 63
  BLS_X bits and the final exponentiation (its hard part by the curve
  parameter: ~560 chained Fq12 products), all as batched Fq12 arithmetic
  on the Montgomery conv engine (field381.py).

An Fq12 element is a (..., 12, 48) int32 array — a flat degree-12
polynomial over Fq (modulus w^12 - 2w^6 + 2, matching the host reference
offchain/bls12381.py) with Montgomery-form coefficient limbs. Products
ride ONE grouped conv per 144-coefficient multiply; Frobenius maps are
precomputed 12x12 Fq matrices, so f^(q^k) is one more conv round — which
also powers an inversion-free path everywhere (the BLS_X sign conjugation
cancels in the == 1 check, and the one true inversion in the easy part of
the final exponentiation uses the field-norm trick).

Reference parity: the aggregate-verification capability of
off-chain-benchmarking/bls.py:20-32 and the production bench's
filecoin-style BLS aggregate path, re-designed TPU-first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import field381 as F
from ..obs.spans import NO_LAUNCH
from ..offchain import bls12381 as host

Q = host.Q
BLS_X = host.BLS_X

# Miller schedule: per bit of BLS_X (after the leading 1), a doubling line
# and, on set bits, an addition line. Fixed at import time.
_BITS = [int(b) for b in bin(BLS_X)[3:]]
N_STEPS = len(_BITS)


# ---------------------------------------------------------------------------
# Host-side preparation
# ---------------------------------------------------------------------------

def host_fq12_to_mont_limbs(x) -> np.ndarray:
    """Host Fq12 tuple (12 ints) -> (12, 48) Montgomery limb array."""
    return np.stack([F.to_limbs(c * F.R % Q) for c in x])


def miller_lines(p_g1, q_g2) -> np.ndarray:
    """Run the host Miller loop recording line values: (N_STEPS, 2, 12, 48)
    Montgomery limbs. Slot 0 is the doubling line, slot 1 the addition
    line (identity 1 on clear bits so the device body is uniform)."""
    qt = host._twist(q_g2)
    pf = host._cast_g1_fq12(p_g1)
    one = host.FQ12_ONE
    rpt = qt
    out = np.zeros((N_STEPS, 2, 12, F.NLIMBS), np.int32)
    for i, bit in enumerate(_BITS):
        out[i, 0] = host_fq12_to_mont_limbs(host._linefunc(rpt, rpt, pf))
        rpt = host._add(rpt, rpt, host._fq12)
        if bit:
            out[i, 1] = host_fq12_to_mont_limbs(host._linefunc(rpt, qt, pf))
            rpt = host._add(rpt, qt, host._fq12)
        else:
            out[i, 1] = host_fq12_to_mont_limbs(one)
    return out


# Frobenius matrices: FROB[k][i] = (w^i)^(q^k) as a host Fq12 element, so
# f^(q^k) = sum_i f_i * FROB[k][i] (coefficients of Fq are Frobenius-fixed).
def _frob_matrices():
    w = tuple(1 if i == 1 else 0 for i in range(12))
    w_q = host.fq12_pow(w, Q)  # one 381-bit host exponentiation
    mats = {}
    basis = [w]
    for i in range(2, 12):
        basis.append(host.fq12_mul(basis[-1], w))
    basis = [tuple(1 if j == 0 else 0 for j in range(12))] + basis  # w^0..w^11

    def apply_frob(x, wq_pows):
        acc = tuple(0 for _ in range(12))
        for i, c in enumerate(x):
            if c:
                acc = host.fq12_add(acc, host.fq12_scalar(wq_pows[i], c))
        return acc

    wq_pows = [tuple(1 if j == 0 else 0 for j in range(12))]
    for i in range(1, 12):
        wq_pows.append(host.fq12_mul(wq_pows[-1], w_q))

    cur = basis
    for k in range(1, 12):
        cur = [apply_frob(b, wq_pows) for b in cur]
        mats[k] = np.stack([host_fq12_to_mont_limbs(row) for row in cur])
    return mats  # mats[k]: (12, 12, 48) — row i = (w^i)^(q^k)


_FROB = _frob_matrices()

# Final-exponentiation hard part: (q^4 - q^2 + 1) / r, factored through
# the curve parameter x = -BLS_X as ((x-1)^2/3)(x+q)(x^2+q^2-1) + 1: a
# 126-bit power, three powers by |x| and Frobenius maps.
_HARD_EXP = (Q ** 4 - Q ** 2 + 1) // host.R
_HARD_LAMBDA = (BLS_X + 1) ** 2 // 3      # (x-1)^2/3, exact: x = 1 mod 3
# Window 3 costs the 126-bit power what window 4 does (6 + 42 x 4 = 174
# products against 14 + 32 x 5) with half the product bodies in the
# program.
_LAMBDA_WINDOW = 3
assert (Q ** 12 - 1) % host.R == 0
assert (Q ** 6 - 1) * (Q ** 2 + 1) * _HARD_EXP == (Q ** 12 - 1) // host.R
assert (BLS_X + 1) ** 2 % 3 == 0
assert _HARD_EXP == \
    _HARD_LAMBDA * (Q - BLS_X) * (BLS_X ** 2 + Q ** 2 - 1) + 1


# ---------------------------------------------------------------------------
# Device Fq12 arithmetic
# ---------------------------------------------------------------------------

def fq12_one(batch_shape=()) -> jnp.ndarray:
    one = np.zeros((12, F.NLIMBS), np.int32)
    one[0] = F.to_limbs(F.R_MOD_Q)
    return jnp.broadcast_to(jnp.asarray(one), (*batch_shape, 12, F.NLIMBS))


def fq12_mul(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """(..., 12, 48) x (..., 12, 48): all 144 coefficient products in one
    grouped conv, anti-diagonal accumulation, w^12 = 2w^6 - 2 fold."""
    prod = F.mont_mul(x[..., :, None, :], y[..., None, :, :])
    # coeff[k] = sum_{i+j=k} prod[i, j]; <= 12 weak terms -> limbs < 2^13,
    # value < 2^389: reduce_sum brings each back to weak form (anything
    # less lets the top limb creep past the conv exactness bound).
    coeffs = []
    for k in range(23):
        terms = [prod[..., i, k - i, :]
                 for i in range(max(0, k - 11), min(12, k + 1))]
        coeffs.append(F.reduce_sum(sum(terms)))
    # fold degrees 22..12 down (top-first so cascades resolve)
    for d in range(22, 11, -1):
        c2 = F.add(coeffs[d], coeffs[d])
        coeffs[d - 6] = F.add(coeffs[d - 6], c2)
        coeffs[d - 12] = F.sub(coeffs[d - 12], c2)
    # the folded coefficients carry one add + one biased sub on top of a
    # weak element; one more reduce_sum restores the invariant
    return jnp.stack([F.reduce_sum(c) for c in coeffs[:12]], axis=-2)


def fq12_sqr(x: jnp.ndarray) -> jnp.ndarray:
    return fq12_mul(x, x)


def fq12_frobenius(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """f^(q^k) via the precomputed basis-image matrix: one conv round."""
    mat = jnp.asarray(_FROB[k])  # (12i, 12j, 48)
    prod = F.mont_mul(x[..., :, None, :], mat)  # (..., 12i, 12j, 48)
    return F.reduce_sum(jnp.sum(prod, axis=-3))


def fq12_inv(x: jnp.ndarray) -> jnp.ndarray:
    """Field-norm inversion: g = prod_{k=1..11} f^(q^k); N = f*g lies in
    Fq (its 0-coefficient), so f^{-1} = g * N^{-1}."""
    g = fq12_frobenius(x, 1)
    for k in range(2, 12):
        g = fq12_mul(g, fq12_frobenius(x, k))
    n = fq12_mul(x, g)
    n0_inv = F.inv(n[..., 0, :])
    return F.mont_mul(g, n0_inv[..., None, :])


def fq12_pow_const(x: jnp.ndarray, exponent: int,
                   window: int = 4) -> jnp.ndarray:
    """x^exponent, static exponent (field381.pow_windowed over Fq12)."""
    return F.pow_windowed(x, exponent, fq12_mul, fq12_one(x.shape[:-2]),
                          window)


_ODD = np.arange(12)[:, None] % 2 == 1


def _conj(y: jnp.ndarray) -> jnp.ndarray:
    """y^(q^6) with no product: w^(q^6) = -w negates the odd
    coefficients.  In the cyclotomic subgroup, where the final
    exponentiation's hard part runs, that is y^-1.  A negation's value
    reaches 64q; reduce_sum brings it back to weak form."""
    return jnp.where(_ODD, F.reduce_sum(F.neg(y)), y)


def _cyclotomic_exp_x(y: jnp.ndarray) -> jnp.ndarray:
    """y^|x| = y^BLS_X: one scan of 64 square-and-multiply steps
    (window 1: no table outside the scan), 128 products."""
    return fq12_pow_const(y, BLS_X, window=1)


# ---------------------------------------------------------------------------
# Pairing pieces
# ---------------------------------------------------------------------------

def miller_accumulate(lines: jnp.ndarray) -> jnp.ndarray:
    """lines (..., N_STEPS, 2, 12, 48) -> Miller value (without the BLS_X
    sign conjugation — it cancels in the == 1 check after final exp)."""
    batch_shape = lines.shape[:-4]
    f0 = fq12_one(batch_shape)
    steps = jnp.moveaxis(lines, -4, 0)

    def body(f, step):
        f = fq12_mul(fq12_sqr(f), step[..., 0, :, :])
        f = fq12_mul(f, step[..., 1, :, :])
        return f, None

    f, _ = jax.lax.scan(body, f0, steps)
    return f


def final_exponentiate(f: jnp.ndarray) -> jnp.ndarray:
    """f^((q^12-1)/r): easy part via Frobenius + norm-inversion, hard part
    f2^((q^4 - q^2 + 1)/r) by the curve parameter (_HARD_LAMBDA's
    identity). f2 lies in the cyclotomic subgroup, so y^x = conj(y^|x|)."""
    f1 = fq12_mul(fq12_frobenius(f, 6), fq12_inv(f))      # f^(q^6 - 1)
    f2 = fq12_mul(fq12_frobenius(f1, 2), f1)              # ^(q^2 + 1)
    a = fq12_pow_const(f2, _HARD_LAMBDA, _LAMBDA_WINDOW)  # ^((x-1)^2/3)
    b = fq12_mul(_conj(_cyclotomic_exp_x(a)),
                 fq12_frobenius(a, 1))                    # ^(x + q)
    c = fq12_mul(fq12_mul(_cyclotomic_exp_x(_cyclotomic_exp_x(b)),
                          fq12_frobenius(b, 2)),
                 _conj(b))                                # ^(x^2 + q^2 - 1)
    return fq12_mul(c, f2)


def is_one(f: jnp.ndarray) -> jnp.ndarray:
    """(..., 12, 48) Montgomery Fq12 -> (...,) bool: f == 1."""
    canon = F.from_mont(f)
    one = jnp.zeros_like(canon).at[..., 0, 0].set(1)
    return jnp.all(canon == one, axis=(-1, -2))


def pairings_check(lines: jnp.ndarray) -> jnp.ndarray:
    """lines (..., P, N_STEPS, 2, 12, 48): P pairings multiplied under ONE
    final exponentiation -> (...,) bool (product == 1)."""
    fs = miller_accumulate(jnp.moveaxis(lines, -5, 0))  # (P, ..., 12, 48)
    f = fs[0]
    for i in range(1, fs.shape[0]):
        f = fq12_mul(f, fs[i])
    return is_one(final_exponentiate(f))


pairings_check_jit = jax.jit(pairings_check)


def selfcheck() -> None:
    """Backend exactness guard for the BLS tower (sidecar/bench startup):
    exercises the fq12_mul fold path — whose coefficient sums run closer
    to the f32 conv bound than plain mont_mul — against the host
    reference. Raises on any mismatch; fix with
    HOTSTUFF_TPU_MUL_PRECISION=highest."""
    F.mul_selfcheck()
    rng = np.random.default_rng(17)
    x = tuple(int.from_bytes(rng.bytes(48), "little") % Q for _ in range(12))
    y = tuple(int.from_bytes(rng.bytes(48), "little") % Q for _ in range(12))
    dx = jnp.asarray(host_fq12_to_mont_limbs(x))[None]
    dy = jnp.asarray(host_fq12_to_mont_limbs(y))[None]
    got = np.asarray(F.from_mont(fq12_mul(dx, dy)))[0]
    want = host.fq12_mul(x, y)
    if tuple(F.from_limbs(r) for r in got) != want:
        raise AssertionError(
            "fq12 multiply is not exact on this backend; set "
            "HOTSTUFF_TPU_MUL_PRECISION=highest")


# ---------------------------------------------------------------------------
# Aggregate verification (host orchestration + device check)
# ---------------------------------------------------------------------------

def aggregate_keys(pks):
    """The sum of a certificate's keys (host G1 points), or None when a
    key is malformed or the sum is the identity: nothing to pair."""
    apk = None
    for pk in pks:
        if pk is None or not host.g1_on_curve(pk):
            return None
        apk = pk if apk is None else host.g1_add(apk, pk)
    return apk


def verify_common_apk(apk, msg: bytes, agg_sig, trace=NO_LAUNCH) -> bool:
    """e(apk, H(m)) * e(-g1, agg_sig) == 1 for an aggregate key and an
    on-curve aggregate signature: the digest hashed to G2 and the Miller
    lines of both pairings on the host, the pairing program on the
    device.  ``trace`` (an ``obs.spans.LaunchScope``) writes one span a
    step: ``hash_to_g2``, ``miller_lines``, ``pairing`` (staging, dispatch
    and the wait for the program) and ``d2h``."""
    with trace.stage("hash_to_g2"):
        h = host.hash_to_g2(msg)
    with trace.stage("miller_lines") as tags:
        lines = np.stack([miller_lines(apk, h),
                          miller_lines(host.g1_neg(host.g1_generator()),
                                       agg_sig)])
        if tags is not None:
            tags["pairings"] = len(lines)
    with trace.stage("pairing") as tags:
        out = jax.block_until_ready(pairings_check_jit(jnp.asarray(lines)))
        if tags is not None:
            tags["bytes"] = lines.nbytes
    with trace.stage("d2h"):
        return bool(np.asarray(out))


def verify_aggregate_common(pks, msg: bytes, agg_sig) -> bool:
    """Same-message aggregate verify (the QC shape: 2f+1 votes on one
    digest): e(apk, H(m)) * e(-g1, agg_sig) == 1, pairing math on device.
    pks: list of host G1 points; agg_sig: host G2 point.
    """
    # Same input validation as the host reference: a malformed signature
    # must reject, not crash the Miller-line precomputation.
    if agg_sig is None or not host.g2_on_curve(agg_sig):
        return False
    apk = aggregate_keys(pks)
    if apk is None:
        return False
    return verify_common_apk(apk, msg, agg_sig)


def multi_pairing_rows(pks, msgs, agg_sig):
    """Validate a distinct-message aggregate statement and build its n+1
    Miller-line rows (votes + the -g1/agg row). Returns None if any input
    is malformed — the ONE validation both the single-chip and the
    mesh-sharded verifier share, so they can never accept different
    inputs."""
    if len(pks) != len(msgs) or not pks:
        return None
    if agg_sig is None or not host.g2_on_curve(agg_sig):
        return None
    rows = []
    for pk, msg in zip(pks, msgs):
        if pk is None or not host.g1_on_curve(pk):
            return None
        rows.append(miller_lines(pk, host.hash_to_g2(msg)))
    rows.append(miller_lines(host.g1_neg(host.g1_generator()), agg_sig))
    return rows


def verify_aggregate_multi(pks, msgs, agg_sig) -> bool:
    """Distinct-message aggregate verify (the TC shape: 2f+1 timeout votes
    over per-round digests, consensus/src/messages.rs:307-313):
    prod e(pk_i, H(m_i)) * e(-g1, agg) == 1, all n+1 Miller loops batched
    under ONE final exponentiation on device.  Compiles one program per
    vote count; a committee's TC size is fixed at 2f+1, so that is a
    single shape in practice."""
    rows = multi_pairing_rows(pks, msgs, agg_sig)
    if rows is None:
        return False
    return verify_rows(rows)


def verify_rows(rows) -> bool:
    """The device check of ``multi_pairing_rows``' rows: every Miller
    loop under one final exponentiation."""
    return bool(np.asarray(pairings_check_jit(jnp.asarray(np.stack(rows)))))
