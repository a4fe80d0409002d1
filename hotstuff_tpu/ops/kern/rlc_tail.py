"""graftkern kernel 4: the batch-() tail of the RLC check.

What follows the batched MSM in ed25519.rlc_finish works on ONE point:
the Horner fold of the 64 window sums (64 x (4 doublings + 1 add)) and
the fixed-base sum [c]B (32 adds of comb entries).  As lax ops that is
~2,650 one-row convolutions with their carries, issued one after the
other — 31 of the 42 ms of a quorum-certificate verify on a v5e (PERF.md
§5, PR 27).  Here both chains run inside ONE kernel: the accumulators
never leave vregs/VMEM between field operations, and the only traffic
is the 96 input tiles read once.

Layout: a point is one (8, 128) int32 tile — coordinate c (X, Y, Z, T;
or Y+X, Y-X, Z, 2dT of a cached point) in sublane c, limbs in lanes
0..31 (the fieldops padded row), sublanes 4..7 idle.  The four
independent products of a point operation are the four sublanes of ONE
multiply, so a doubling or an addition is two multiplies, not eight;
the sums and differences between them run on tiles whose sublanes all
hold the same element (a sublane broadcast), so no operand is ever
shuffled across lanes.  T rides along in every doubling for free —
ed25519.point_dbl(with_t=False) only saves a product that this layout
does not pay for, and the next doubling does not read it.

The multiply (_mul4) is the Toeplitz form of the limb convolution: the
left operand block-diagonal in the 128 lanes (sublane r's limbs at
lanes 32r..), the right one a (128, 128) matrix whose row 32c+i is
element c rotated i lanes (a sublane broadcast and one strided rotate
a block), and ONE (8, 128) x (128, 128) MXU pass gives all four
coefficient rows.  fieldops.conv32 flattens an (8, 32, 32) outer
product through a (1024, 128) matrix instead: eight times the weights
for the same coefficients, which on a dependent chain is all latency.

Bit-identity: the same field operations in the same order as
ed25519.msm_horner / comb_mul_base (add-2008-hwcd-3, dbl-2008-hwcd,
fieldops' carry counts), exact integer coefficients — the two points
leave limb for limb as the lax scans produce them
(tests/test_kern.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fieldops as FK
from .backend import interpret_default

_WINDOWS = 64
_COMB_POSITIONS = 32
_SUBLANES = 8


def _row(shape) -> jnp.ndarray:
    """Per-sublane index (fieldops.lane_iota's other axis)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0)


def _sublane(tile: jnp.ndarray, k: int) -> jnp.ndarray:
    """Sublane k of a tile on every sublane."""
    return jnp.broadcast_to(tile[k:k + 1, :], tile.shape)


def _stack4(r0, r1, r2, r3) -> jnp.ndarray:
    """Tile whose sublane c is sublane c of r_c (sublanes 4..7 follow
    r3: some field element, never read)."""
    row = _row(r0.shape)
    return jnp.where(row == 0, r0,
                     jnp.where(row == 1, r1, jnp.where(row == 2, r2, r3)))


def _mul4(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Sublanes 0..3 of a times sublanes 0..3 of b, weak in / weak out
    — four fieldops.f_mul in one MXU pass (module docstring).

    Exactness: limbs < 2^9, 32 terms a coefficient — sums < 2^23, exact
    in f32 at HIGHEST precision (the fieldops.conv32 argument)."""
    # Sublane r's limbs to lanes 32r..: three plain rotates and selects.
    # (ONE rotate of stride 32 a sublane says the same and passes the
    # interpreter, but came back unrotated from a v5e: PERF.md, PR 28.)
    row = _row(a.shape)
    lhs = a
    for r in range(1, 4):
        lhs = jnp.where(row == r, pltpu.roll(a, r * FK.NLIMBS, 1), lhs)
    rhs = jnp.concatenate(
        [pltpu.roll(jnp.broadcast_to(b[c:c + 1, :], (FK.NLIMBS, FK.NLANES)),
                    0, 1, stride=1, stride_axis=0) for c in range(4)],
        axis=0)
    coeffs = jnp.dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return FK._fold_carry(coeffs.astype(jnp.int32))


def _efgh(e, f, g, h) -> jnp.ndarray:
    """(e*f, g*h, f*g, e*h): the X, Y, Z, T that close both the
    doubling and the addition."""
    return _mul4(_stack4(e, g, f, e), _stack4(f, h, g, h))


def _dbl(p: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """ed25519.dbl_t on a point tile."""
    xy = FK.f_add(_sublane(p, 0), _sublane(p, 1))
    q = jnp.where(_row(p.shape) == 3, xy, p)        # x, y, z, x+y
    s = _mul4(q, q)
    a, b, zz, s3 = (_sublane(s, k) for k in range(4))
    c = FK.f_add(zz, zz)
    e = FK.f_sub(FK.f_sub(s3, a, bias), b, bias)    # 2*X1*Y1
    g = FK.f_sub(b, a, bias)
    f = FK.f_sub(g, c, bias)
    h = FK.f_sub(jnp.zeros_like(a), FK.f_add(a, b), bias)
    return _efgh(e, f, g, h)


def _add(p: jnp.ndarray, qc: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """ed25519.add_t: ext tile + cached tile -> ext tile."""
    x, y = _sublane(p, 0), _sublane(p, 1)
    m = _mul4(_stack4(FK.f_add(y, x), FK.f_sub(y, x, bias), p, p), qc)
    b, a, zz, c = (_sublane(m, k) for k in range(4))
    d = FK.f_add(zz, zz)
    e = FK.f_sub(b, a, bias)
    f = FK.f_sub(d, c, bias)
    g = FK.f_add(d, c)
    h = FK.f_add(b, a)
    return _efgh(e, f, g, h)


def _tail_kernel(w_ref, comb_ref, o_ref):
    """w_ref (64, 8, 128): cached window sums, MSB first; comb_ref
    (32, 8, 128): the comb entries c's digits selected; o_ref
    (2, 8, 128): the Horner total and [c]B, ext.

    One rolled loop of 32 steps, each two windows of the fold and one
    comb addition: the two chains do not depend on each other, so the
    scheduler fills one's multiply latency with the other's carries."""
    shape = (_SUBLANES, FK.NLANES)
    lane = FK.lane_iota(shape)
    row = _row(shape)
    bias = FK.const_row(lane, FK._SUB_BIAS_DIGITS)
    # identity: X = 0, Y = Z = 1, T = 0
    ident = jnp.where((lane == 0) & ((row == 1) | (row == 2)), 1, 0)

    def window(acc, j):
        for _ in range(4):
            acc = _dbl(acc, bias)
        return _add(acc, w_ref[j], bias)

    def step(i, carry):
        acc, cb = carry
        acc = window(window(acc, 2 * i), 2 * i + 1)
        return acc, _add(cb, comb_ref[i], bias)

    acc, cb = jax.lax.fori_loop(0, _COMB_POSITIONS, step, (ident, ident))
    o_ref[0] = acc
    o_ref[1] = cb


def _tile(points: jnp.ndarray) -> jnp.ndarray:
    """(n, 4, 32) points -> (n, 8, 128) tiles."""
    return jnp.pad(points.astype(jnp.int32),
                   [(0, 0), (0, _SUBLANES - 4), (0, FK.NLANES - FK.NLIMBS)])


# jit-wrapped: every rlc program (each bucket, each program of the
# mesh's bisection, its replicated finish) runs this one shape, so the
# kernel is traced once a process (kern package docstring).
@jax.jit
def _tail(w_cached: jnp.ndarray, comb_sel: jnp.ndarray):
    out = pl.pallas_call(
        _tail_kernel,
        out_shape=jax.ShapeDtypeStruct((2, _SUBLANES, FK.NLANES), jnp.int32),
        name="rlc_tail",
        interpret=interpret_default(),
    )(_tile(w_cached), _tile(comb_sel))
    return out[0, :4, :FK.NLIMBS], out[1, :4, :FK.NLIMBS]


def rlc_tail(w_cached: jnp.ndarray,
             comb_sel: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The two serial sums of ed25519.rlc_finish in one kernel.

    Args:
      w_cached: (64, 4, 32) int32 MSB-first MSM window sums in CACHED
                form (ed25519.to_cached of msm_window_sums' result: one
                batched lax op, off the serial chain).
      comb_sel: (32, 4, 32) int32 cached-affine comb entries, entry j =
                comb_table()[j][digit j of c].
    Returns:
      (msm, cb): (4, 32) ext points — sum_j 16^(63-j) W_j, limb for
      limb ed25519.msm_horner's, and [c]B, limb for limb
      comb_mul_base's.
    """
    if w_cached.shape != (_WINDOWS, 4, FK.NLIMBS) or \
            comb_sel.shape != (_COMB_POSITIONS, 4, FK.NLIMBS):
        raise ValueError(
            f"rlc_tail takes (64, 4, 32) window sums and (32, 4, 32) comb "
            f"entries, got {w_cached.shape} and {comb_sel.shape}")
    return _tail(w_cached, comb_sel)
