"""GF(q) arithmetic for BLS12-381, batched, in JAX — the base layer of the
TPU pairing engine (ops/bls381.py).

Same substrate philosophy as field25519.py (8-bit limbs in int32 lanes,
depthwise-conv schoolbook products, parallel carries, no data-dependent
control flow), but q = 0x1a0111ea...aaab has no special form, so reduction
is **Montgomery** with R = 2^384:

* elements live in Montgomery form x~ = x*R mod q as (..., 48) int32 limb
  arrays in "weak" form (limbs < 2^9, value < 2^385); a chain of bare
  products stays bounded only from values under R (see pow_const), and
  the Fq12 tower brings every product back with reduce_sum;
* mont_mul does conv(48x48) -> wide carry -> m = T*q' mod R (conv + carry
  with truncation) -> T + m*q (conv) -> exact /R via a float32 carry-out
  dot (the low half's true value is divisible by 2^384, so its carry into
  limb 48 is a small integer recovered exactly in f32).

Reference parity: this underpins the BLS half of the reference's signature
benchmarking (off-chain-benchmarking/bls.py, production/src/main.rs BLS
aggregate path), re-built TPU-first.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NLIMBS = 48
LIMB_BITS = 8
LIMB_MASK = 255

Q = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 1 << 384
R_MOD_Q = R % Q
R2_MOD_Q = R * R % Q
# q' = -q^{-1} mod R (Montgomery constant)
QPRIME = (-pow(Q, -1, R)) % R

# Same escape hatch as field25519: HIGH (bf16x3) is measured exact for
# this workload's <= 2^23.9 coefficient sums; if a backend ever lowers it
# non-exactly, mul_selfcheck trips and the env var forces HIGHEST.
import os as _os

_PRECISION = {
    "high": jax.lax.Precision.HIGH,
    "highest": jax.lax.Precision.HIGHEST,
}[_os.environ.get("HOTSTUFF_TPU_MUL_PRECISION", "high").lower()]


def to_limbs(x: int, n: int = NLIMBS) -> np.ndarray:
    return np.array([(int(x) >> (8 * i)) & 0xFF for i in range(n)],
                    dtype=np.int32)


def from_limbs(limbs) -> int:
    limbs = np.asarray(limbs, dtype=np.int64).reshape(-1)
    return sum(int(v) << (8 * i) for i, v in enumerate(limbs))


_Q_LIMBS = to_limbs(Q)
_QPRIME_LIMBS = to_limbs(QPRIME)
# 64q bias for subtraction: every limb dominates a weak limb (< 2^9), and
# the value is a multiple of q, invisible to Montgomery arithmetic. 64q is
# the smallest power-of-two multiple whose top byte survives the borrow
# spreading below with >= 511 left in limb 47.
_BIAS = [(64 * Q >> (8 * i)) & 0xFF for i in range(NLIMBS)]
_BIAS[NLIMBS - 1] += (64 * Q >> (8 * NLIMBS)) << 8  # fold spill into limb 47
# Spread so every limb >= 511 (dominates any weak limb of b): borrow units
# of 256 from the limb above, ascending so fixed limbs stay fixed.
for _i in range(NLIMBS - 1):
    while _BIAS[_i] < 511:
        _BIAS[_i] += 256
        _BIAS[_i + 1] -= 1
_BIAS_ARR = np.asarray(_BIAS, dtype=np.int32)
assert (_BIAS_ARR >= 511).all(), "subtraction bias must dominate weak limbs"
assert sum(int(v) << (8 * i) for i, v in enumerate(_BIAS_ARR)) == 64 * Q


def constant(x: int) -> jnp.ndarray:
    """Canonical (non-Montgomery) constant as (48,) limbs."""
    return jnp.asarray(to_limbs(x % Q))


def mont_constant(x: int) -> jnp.ndarray:
    """Constant in Montgomery form."""
    return jnp.asarray(to_limbs(x * R % Q))


# ---------------------------------------------------------------------------
# Carries
# ---------------------------------------------------------------------------

def _carry_step_plain(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry step WITHOUT wraparound: the carry out of the top
    limb moves into a fresh position only if the array has room; callers
    size arrays so the top limb's carry is representable (value bounds
    guarantee the top limb stays < 2^9 after the final step)."""
    lo = x & LIMB_MASK
    hi = x >> LIMB_BITS
    shifted = jnp.concatenate(
        [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)
    return lo + shifted


def weak_carry(x: jnp.ndarray, steps: int = 3) -> jnp.ndarray:
    """Bring limbs below ~2^9 (inputs < 2^24-ish need 3 steps). The top
    limb's overflow is kept IN PLACE (weight 256 per unit), so the value
    is preserved only when the caller guarantees it fits the array — the
    per-call-site bound comments establish that."""
    for _ in range(steps):
        lo = x & LIMB_MASK
        hi = x >> LIMB_BITS
        shifted = jnp.concatenate(
            [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)
        # keep the top limb's overflow in place (weight 256 per unit)
        top_keep = jnp.zeros_like(x).at[..., -1].set(
            (x[..., -1] >> LIMB_BITS) << LIMB_BITS)
        x = lo + shifted + top_keep
    return x


def trunc_carry(x: jnp.ndarray, steps: int = 3) -> jnp.ndarray:
    """Carry steps that DROP overflow out of the top limb — i.e. arithmetic
    mod 2^(8*nlimbs). Used for the Montgomery m = T*q' mod R step."""
    for _ in range(steps):
        lo = x & LIMB_MASK
        hi = x >> LIMB_BITS
        shifted = jnp.concatenate(
            [jnp.zeros_like(hi[..., :1]), hi[..., :-1]], axis=-1)
        x = lo + shifted
    return x


# ---------------------------------------------------------------------------
# Schoolbook limb product (depthwise conv, same pattern as field25519.mul)
# ---------------------------------------------------------------------------

def _conv_product(a: jnp.ndarray, b: jnp.ndarray, nb: int) -> jnp.ndarray:
    """(..., na) x (..., nb) limb arrays -> (..., na+nb-1) coefficient
    array (exact in f32: weak limbs < 2^9, <= 48 terms per coefficient)."""
    na = a.shape[-1]
    batch_shape = a.shape[:-1]
    n = 1
    for d in batch_shape:
        n *= d
    lhs = a.reshape(1, n, na).astype(jnp.float32)
    rhs = jnp.flip(b.reshape(n, 1, nb), -1).astype(jnp.float32)
    # A lone product (the final exponentiation's Fermat inverse runs at
    # batch shape ()) is ONE plain convolution, which a TPU may run as
    # three bf16 passes at HIGH: inexact once limbs pass 8 bits, as
    # field25519's was on the v5e (PERF.md §6).  It takes HIGHEST;
    # a grouped convolution keeps the measured-exact HIGH.
    out = jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(1,), padding=[(nb - 1, nb - 1)],
        dimension_numbers=("NCH", "OIH", "NCH"),
        feature_group_count=n,
        precision=jax.lax.Precision.HIGHEST if n == 1 else _PRECISION,
    ).reshape(*batch_shape, na + nb - 1)
    return out.astype(jnp.int32)


def _conv_by_const(a: jnp.ndarray, const_limbs: np.ndarray) -> jnp.ndarray:
    """(..., na) weak limbs times a fixed 48-limb constant."""
    c = jnp.broadcast_to(jnp.asarray(const_limbs),
                         (*a.shape[:-1], NLIMBS))
    return _conv_product(a, c, NLIMBS)


# ---------------------------------------------------------------------------
# Montgomery multiply / add / sub
# ---------------------------------------------------------------------------

_POW_LOW = (2.0 ** (8 * np.arange(NLIMBS) - 384)).astype(np.float32)


def mont_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """REDC(a*b): both in Montgomery weak form -> Montgomery weak form.
    Inputs broadcast against each other (the Fq12 tower relies on it)."""
    a, b = jnp.broadcast_arrays(a, b)
    t = _conv_product(a, b, NLIMBS)                    # 95 coeffs < 2^24
    t = weak_carry(jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, 1)]), 3)
    t_lo = t[..., :NLIMBS]
    m = trunc_carry(_conv_by_const(t_lo, _QPRIME_LIMBS)[..., :NLIMBS], 3)
    mq = _conv_by_const(m, _Q_LIMBS)                   # 95 coeffs
    t2 = t + jnp.pad(mq, [(0, 0)] * (mq.ndim - 1) + [(0, 1)])
    t2 = weak_carry(t2, 3)
    # (t + m*q) is divisible by R; recover the low half's carry-out into
    # limb 48 exactly in f32 (it is a small integer; digits < 2^10).
    c = jnp.round(jnp.sum(t2[..., :NLIMBS].astype(jnp.float32) * _POW_LOW,
                          axis=-1)).astype(jnp.int32)
    hi = t2[..., NLIMBS:].at[..., 0].add(c)
    return weak_carry(hi, 1)


def mont_sqr(a: jnp.ndarray) -> jnp.ndarray:
    return mont_mul(a, a)


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Plain limb add + one carry step (weak in, weak out; mod nothing —
    values stay < 2^386, safely inside the REDC input bound)."""
    return weak_carry(a + b, 1)


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b + 64q (bias keeps limbs nonnegative; value changes by a
    multiple of q, which Montgomery arithmetic doesn't care about)."""
    bias = jnp.asarray(_BIAS_ARR)
    return weak_carry(a + bias - b, 2)


def neg(a: jnp.ndarray) -> jnp.ndarray:
    return sub(jnp.zeros_like(a), a)


_R_MOD_Q_LIMBS = to_limbs(R_MOD_Q)           # fold weight of limb 48
_P385_LIMBS = to_limbs((1 << 385) % Q)       # fold weight of limb 47 bit 9+


def reduce_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Tame a (..., 48) digit array with limbs <= ~2^14 and value <= ~2^390
    back to weak form (limbs <= ~2^9.03, value < 2^385-ish, same residue
    mod q). This is what makes multi-term sums of Montgomery elements —
    the Fq12 tower's anti-diagonal accumulations — safe inputs for the
    next conv: without it the top limb silently accumulates past the f32
    exactness bound (48 * 511^2 < 2^24) and every later product is wrong.

    Steps: widen by one limb, plain-carry (limb 48 absorbs the overflow),
    fold limb 48 back via 2^384 mod q, carry again (limb 47 absorbs),
    fold limb 47's excess beyond 9 bits via 2^385 mod q, one last carry.
    """
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 1)])
    x = weak_carry(x, 3)   # limb 48 absorbs the whole overflow (value bound)
    spill = x[..., 48:49]
    x = x[..., :48] + spill * jnp.asarray(_R_MOD_Q_LIMBS)
    x = weak_carry(x, 2)   # limb 47 absorbs (~2^11); others < 2^9
    excess = x[..., 47] >> 9
    x = x.at[..., 47].set(x[..., 47] & 511)
    x = x + excess[..., None] * jnp.asarray(_P385_LIMBS)
    # Limb 47 may finish around 2^10.6; the conv exactness budget still
    # holds: 47*511^2 + 1540^2 = 14.7M < 2^24.
    return weak_carry(x, 1)


# ---------------------------------------------------------------------------
# Conversion / canonicalization
# ---------------------------------------------------------------------------

def to_mont(a: jnp.ndarray) -> jnp.ndarray:
    """Canonical limbs -> Montgomery form (multiply by R^2 then REDC)."""
    r2 = jnp.broadcast_to(jnp.asarray(to_limbs(R2_MOD_Q)), a.shape)
    return mont_mul(a, r2)


def from_mont(a: jnp.ndarray) -> jnp.ndarray:
    """Montgomery weak form -> canonical limbs in [0, q)."""
    one = jnp.zeros_like(a).at[..., 0].set(1)
    x = mont_mul(a, one)          # == a * R^{-1} mod q, value < q + eps
    return _cond_sub_q(_ripple(x))


def _ripple(x: jnp.ndarray) -> jnp.ndarray:
    """Exact sequential carry to canonical byte digits (value must fit in
    48 limbs, i.e. < 2^384)."""
    limbs = []
    carry = jnp.zeros_like(x[..., 0])
    for i in range(NLIMBS):
        t = x[..., i] + carry
        limbs.append(t & LIMB_MASK)
        carry = t >> LIMB_BITS
    return jnp.stack(limbs, axis=-1)


def _cond_sub_q(x: jnp.ndarray) -> jnp.ndarray:
    q_digits = jnp.asarray(_Q_LIMBS)
    limbs = []
    borrow = jnp.zeros_like(x[..., 0])
    for i in range(NLIMBS):
        d = x[..., i] - q_digits[i] - borrow
        borrow = (d < 0).astype(jnp.int32)
        limbs.append(d + (borrow << LIMB_BITS))
    sub_res = jnp.stack(limbs, axis=-1)
    keep = (borrow > 0)[..., None]
    return jnp.where(keep, x, sub_res)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field equality of Montgomery weak forms."""
    return jnp.all(from_mont(a) == from_mont(b), axis=-1)


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(from_mont(a) == 0, axis=-1)


# ---------------------------------------------------------------------------
# Exponentiation (for inversion and square roots; scan over constant bits)
# ---------------------------------------------------------------------------

def pow_windowed(x, exponent: int, mul, one, window: int = 4):
    """Generic left-to-right windowed exponentiation over a static python
    exponent via lax.scan; shared by Fq (here) and the Fq12 tower
    (ops/bls381.py). `mul` is the group law, `one` the identity element
    broadcast to x's shape."""
    assert exponent >= 0
    nbits = max(1, exponent.bit_length())
    nsteps = -(-nbits // window)
    digits = [(exponent >> (window * (nsteps - 1 - i))) & ((1 << window) - 1)
              for i in range(nsteps)]
    entries = [one, x]
    for _ in range(2, 1 << window):
        entries.append(mul(entries[-1], x))
    table = jnp.stack(entries)

    def body(acc, digit):
        for _ in range(window):
            acc = mul(acc, acc)
        return mul(acc, jnp.take(table, digit, axis=0)), None

    acc, _ = jax.lax.scan(body, one, jnp.asarray(digits, dtype=jnp.int32))
    return acc


def pow_const(x: jnp.ndarray, exponent: int, window: int = 4) -> jnp.ndarray:
    """x^exponent in Montgomery form, static exponent, windowed scan.

    The REDC output of a*b is at least a*b/R, so products of values
    above R grow: from a weak input near 2^385 (reduce_sum's output
    reaches it) the table's powers x^2 .. x^15 outgrew the conv
    exactness bound, and on the chip 5 of 64 valid certificates' final
    exponentiations came back wrong (PERF.md §6).  A product by
    Montgomery one first leaves the residue and brings the value under
    x*(R mod q)/R + q ~ 2^382.1, where every product stays below R."""
    one = jnp.broadcast_to(mont_constant(1), x.shape).astype(jnp.int32)
    return pow_windowed(mont_mul(x, one), exponent, mont_mul, one, window)


def inv(x: jnp.ndarray) -> jnp.ndarray:
    """Fermat inverse (0 -> 0), Montgomery form in and out."""
    return pow_const(x, Q - 2)


# ---------------------------------------------------------------------------
# Self-check (bench/deploy startup guard, like field25519.mul_selfcheck)
# ---------------------------------------------------------------------------

def mul_selfcheck(batch: int = 64, seed: int = 0) -> None:
    """mont_mul against Python integers on a batch (grouped convolutions)
    and on one pair at batch shape () (plain convolutions: the final
    exponentiation's inverse runs there)."""
    rng = np.random.default_rng(seed)
    xs = [int(rng.integers(0, 2**62)) ** 7 % Q for _ in range(batch)]
    ys = [int(rng.integers(0, 2**62)) ** 7 % Q for _ in range(batch)]
    a = jnp.asarray(np.stack([to_limbs(x * R % Q) for x in xs]))
    b = jnp.asarray(np.stack([to_limbs(y * R % Q) for y in ys]))
    got = np.asarray(from_mont(mont_mul(a, b)))
    # weak limbs (an add leaves them up to 2^9) into the lone product
    lone = np.asarray(from_mont(mont_mul(add(a[0], b[0]), b[0])))
    for i, (x, y) in enumerate(zip(xs, ys)):
        want = x * y % Q
        have = from_limbs(got[i])
        if have != want:
            raise AssertionError(
                f"field381 mont_mul mismatch at row {i}")
    if from_limbs(lone) != (xs[0] + ys[0]) * ys[0] % Q:
        raise AssertionError("field381 mont_mul mismatch at batch shape ()")
