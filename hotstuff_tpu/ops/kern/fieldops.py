"""In-kernel GF(2^255-19) limb arithmetic for the graftkern Pallas layer.

Carry-save (8, 128)-tile layout: a field element is 32 radix-2^8 int32
limbs stored in lanes 0..31 of a 128-lane vector row (lanes 32..127
zero), rows batched over sublanes — the native VPU tile shape, so every
helper below is pure elementwise/roll work on full tiles.  The extra
lanes are not waste: the schoolbook product needs 63 coefficient slots,
so the carry-save accumulator lives in the SAME padded row as its
inputs and the whole multiply never changes layout.

Every function here is traced INSIDE a pallas kernel body and is a
bit-identical transliteration of the lax reference (ops/field25519):
same weak-normal form invariant (limbs < 2^9), same carry-step count
per op, pure int32 — so kernel outputs match the reference limb for
limb, which is what tests/test_kern.py's property sweeps assert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...utils.intmath import D, P

NLIMBS = 32
NLANES = 128
LIMB_MASK = 0xFF
K2D = (2 * D) % P


def limb_digits(x: int) -> list[int]:
    """Python int -> 32 canonical byte digits, little-endian (static
    python lists: pallas kernel bodies may not capture ARRAY constants,
    so constant rows are synthesized in-kernel via const_row)."""
    return [(x >> (8 * i)) & 0xFF for i in range(NLIMBS)]


# 8p bias for subtraction without negative intermediates — the same
# limb-dominating bias field25519.sub uses (every limb >= 1016 > any
# weak limb).
_SUB_BIAS_DIGITS = [8 * d for d in limb_digits(P)]
_K2D_DIGITS = limb_digits(K2D)


def lane_iota(shape) -> jnp.ndarray:
    """Per-lane index, broadcast over the leading dims (TPU needs >= 2-D
    iota; the padded rows always are)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def const_row(lane: jnp.ndarray, digits: list[int]) -> jnp.ndarray:
    """Broadcast a static limb vector into the padded-lane layout from
    scalar selects (pallas kernels cannot capture array constants; 32
    vector selects trace once per shape and cost nothing next to the
    conv's 32 MACs)."""
    x = jnp.zeros_like(lane)
    for i, d in enumerate(digits):
        if d:
            x = jnp.where(lane == i, d, x)
    return x


def carry_step(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry step on padded rows — field25519._carry_step
    in the 128-lane layout.  Every limb keeps its low byte; high bits
    move one lane up; the carry out of limb 31 wraps to lane 0 scaled by
    38 (2^256 === 38 mod p).  Lanes >= 32 are forced back to zero (the
    roll would otherwise leak limb 31's carry into lane 32)."""
    lane = lane_iota(x.shape)
    lo = x & LIMB_MASK
    hi = x >> 8
    wrapped = jnp.where(lane == 0,
                        jnp.roll(hi, 1 - NLIMBS, axis=-1) * 38,
                        jnp.roll(hi, 1, axis=-1))
    return jnp.where(lane < NLIMBS, lo + wrapped, 0)


def conv32(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product of two padded (rows, 128) limb rows:
    coefficient j lands in lane j (j = 0..62, zeros above).

    Formulation: per-row outer product, then ONE dot against a
    synthesized 0/1 anti-diagonal matrix (i + k == j) — the MXU form.
    A 32-step shifted-MAC loop computes the same thing on the VPU, but
    each of its rolls lowers to multiple HLO ops and XLA compile time
    explodes when the tree/window loops replicate the body (measured
    14x slower to compile); the dot keeps the kernel one op deep.  The
    select matrix is built in-kernel from iotas because pallas bodies
    may not capture array constants.

    Exactness: products < 2^18 and coefficient sums < 32 * (2^9)^2 =
    2^23 are exact in f32 at HIGHEST precision (same argument as the
    lax conv path; field25519.mul_selfcheck trips on any backend where
    that ever stops holding)."""
    ai = a[..., :NLIMBS]
    bi = b[..., :NLIMBS]
    outer = (ai[..., :, None] * bi[..., None, :]).astype(jnp.float32)
    outer = outer.reshape(*a.shape[:-1], NLIMBS * NLIMBS)
    i = jax.lax.broadcasted_iota(jnp.int32, (NLIMBS * NLIMBS, NLANES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (NLIMBS * NLIMBS, NLANES), 1)
    antidiag = ((i // NLIMBS + i % NLIMBS) == j).astype(jnp.float32)
    return jnp.dot(outer, antidiag,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def conv32_const(a: jnp.ndarray, digits: list[int]) -> jnp.ndarray:
    """Schoolbook product of padded (rows, 128) limb rows by a STATIC
    limb vector: coefficient j = sum_i a_i * digits[j - i] lands in
    lane j.  ONE dot against the 128 x 128 Toeplitz matrix of the
    digits, synthesized in-kernel from iotas and scalar selects.

    Why not conv32(a, const_row(...)): a row built only from a lane
    iota is replicated along sublanes, and Mosaic ABORTS the compiling
    process relaying out the outer product's broadcast of such a value
    (``array.h:480 Check failed: limits[i] <= dim(i) (32 vs. 1)`` on
    jax 0.9.0 / libtpu 0.0.34 for v5e) — a crash, not an exception.
    The Toeplitz form never broadcasts the constant, and is one
    (rows, 128) x (128, 128) MXU pass instead of a 1024-wide one.

    Exactness: a_i < 2^9, digits < 2^8, 32 terms — sums < 2^22, exact
    in f32 at HIGHEST precision (the conv32 argument)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (NLANES, NLANES), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (NLANES, NLANES), 1)
    diff = jnp.where(i < NLIMBS, j - i, -1)   # lanes >= 32 of a: ignored
    toeplitz = jnp.zeros((NLANES, NLANES), jnp.float32)
    for k, d in enumerate(digits):
        if d:
            toeplitz = jnp.where(diff == k, float(d), toeplitz)
    return jnp.dot(a.astype(jnp.float32), toeplitz,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _fold_carry(acc: jnp.ndarray) -> jnp.ndarray:
    """63 product coefficients -> weak limbs: the wrap-38 fold (lane j
    += 38 * lane j+32) and four parallel carry steps — the tail of
    field25519.mul, same carry counts."""
    lane = lane_iota(acc.shape)
    folded = acc + 38 * jnp.roll(acc, -NLIMBS, axis=-1)
    x = jnp.where(lane < NLIMBS, folded, 0)
    for _ in range(4):
        x = carry_step(x)
    return x


def f_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a * b mod p, weak in / weak out — field25519.mul fused: one
    conv, the wrap-38 fold, four parallel carry steps.  Same op
    sequence, same carry counts: bit-identical."""
    return _fold_carry(conv32(a, b))


def f_mul_const(a: jnp.ndarray, digits: list[int]) -> jnp.ndarray:
    """f_mul by a static field constant (conv32_const says why the
    constant is not a const_row operand of f_mul)."""
    return _fold_carry(conv32_const(a, digits))


def f_add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """field25519.add: one carry step restores limbs < 2^9."""
    return carry_step(a + b)


def f_sub(a: jnp.ndarray, b: jnp.ndarray,
          bias: jnp.ndarray | None = None) -> jnp.ndarray:
    """field25519.sub: add the 8p bias, two carry steps.  ``bias``: the
    const_row of _SUB_BIAS_DIGITS, for a caller that subtracts inside a
    loop and builds the row (32 selects) once outside it."""
    if bias is None:
        bias = const_row(lane_iota(a.shape), _SUB_BIAS_DIGITS)
    return carry_step(carry_step(a + bias - b))


def f_neg(a: jnp.ndarray) -> jnp.ndarray:
    return f_sub(jnp.zeros_like(a), a)


# ---------------------------------------------------------------------------
# Point helpers (tuples of 4 padded coordinate rows: X, Y, Z, T ext /
# Y+X, Y-X, Z, 2dT cached) — transliterations of ed25519.to_cached_t /
# add_t, the exact op sequence the lax _tree_sum executes.
# ---------------------------------------------------------------------------


def to_cached(p):
    """(x, y, z, t) -> cached (y+x, y-x, z, 2d*t) — ed25519.to_cached_t."""
    x, y, z, t = p
    return (f_add(y, x), f_sub(y, x), z, f_mul_const(t, _K2D_DIGITS))


def add_cached(p, qc):
    """Complete unified addition ext + cached -> ext (8 muls) —
    ed25519.add_t's separate-conv shape, op for op."""
    x1, y1, z1, t1 = p
    ypx2, ymx2, z2, t2d2 = qc
    a = f_mul(f_sub(y1, x1), ymx2)
    b = f_mul(f_add(y1, x1), ypx2)
    c = f_mul(t1, t2d2)
    zz = f_mul(z1, z2)
    d = f_add(zz, zz)
    e = f_sub(b, a)
    f = f_sub(d, c)
    g = f_add(d, c)
    h = f_add(b, a)
    return (f_mul(e, f), f_mul(g, h), f_mul(f, g), f_mul(e, h))


# ---------------------------------------------------------------------------
# Row-grid plumbing shared by the batched kernels
# ---------------------------------------------------------------------------

# Rows per grid block: 256 x 128 int32 = 128 KB per operand — three
# buffers plus the accumulator stay far inside the ~16 MB VMEM envelope
# while blocks stay multiples of the 8-sublane tile.
BLOCK_ROWS = 256


def row_block(n: int) -> tuple[int, int]:
    """Batch row count -> (block, padded_rows): block is the per-grid-
    step row count (multiple of 8, capped at BLOCK_ROWS), padded_rows
    the total the caller must pad to (a multiple of block)."""
    n8 = -(-max(n, 1) // 8) * 8
    block = min(BLOCK_ROWS, n8)
    return block, -(-n // block) * block


def launch_rows(launcher, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The shared wrapper of the row-batched binary kernels (field_mul,
    scalar_mont_mul): broadcast the (..., 32) operands, flatten batch
    dims to rows, pad limbs into the 128-lane layout and rows to the
    grid block, hand the padded pair to ``launcher`` (a jitted
    pallas_call over (rows, 128) int32 inputs), and slice back."""
    batch = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, (*batch, NLIMBS))
    b = jnp.broadcast_to(b, (*batch, NLIMBS))
    n = 1
    for d in batch:
        n *= d
    if n == 0:
        return jnp.zeros((*batch, NLIMBS), jnp.int32)
    _, rows = row_block(n)
    pad = [(0, rows - n), (0, NLANES - NLIMBS)]
    out = launcher(
        jnp.pad(a.reshape(n, NLIMBS).astype(jnp.int32), pad),
        jnp.pad(b.reshape(n, NLIMBS).astype(jnp.int32), pad))
    return out[:n, :NLIMBS].reshape(*batch, NLIMBS)
