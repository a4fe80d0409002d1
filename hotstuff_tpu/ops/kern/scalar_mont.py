"""graftkern kernel 3: the mod-L Montgomery multiply.

scalar25519.mont_mul — REDC at the byte-aligned R = 2^256 — as one
fused kernel: the two schoolbook convolutions (a*b and m*L), the m =
T * L' mod R fold, both exact ripple-carry chains and the final
conditional subtract all happen on carry-save rows in VMEM; the lax
path runs them as separate conv launches with XLA-scheduled buffers in
between.  This is the scalar half of the RLC check (z_i * S_i and
z_i * k_i mod L next to the MSM that consumes them); reduce512_mod_l
and mul_mod_l compose this same primitive, so routing mont_mul covers
them.

Bit-identity: same intermediate widths, same carry chains (exact ripple
unrolled per limb, final carries dropped exactly where the lax code
proves them zero), same single conditional subtract — outputs match
scalar25519's Montgomery product byte for byte (tests/test_kern.py,
including the one-input-up-to-2^256 headroom path reduce512 rides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...utils.intmath import L
from . import fieldops as FK
from .backend import interpret_default

R = 1 << 256
LPRIME = (-pow(L, -1, R)) % R

_L_DIGITS = FK.limb_digits(L)
_LPRIME_DIGITS = FK.limb_digits(LPRIME)


def _carry_bytes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Exact ripple carry of non-negative int32 coefficient lanes into
    ``width`` canonical byte lanes (scalar25519._carry_bytes, unrolled
    per limb on vector rows; the final carry out is dropped — callers
    size ``width`` so it is provably zero)."""
    carry = jnp.zeros_like(x[..., 0])
    outs = []
    for i in range(width):
        t = x[..., i] + carry
        outs.append(t & 0xFF)
        carry = t >> 8
    out = jnp.stack(outs, axis=-1)
    return jnp.pad(out, [(0, 0)] * (out.ndim - 1)
                   + [(0, FK.NLANES - width)])


def _cond_sub_l(x: jnp.ndarray) -> jnp.ndarray:
    """If x >= L (x canonical bytes in lanes 0..31), subtract L —
    scalar25519._cond_sub's borrow chain, unrolled per limb."""
    borrow = jnp.zeros_like(x[..., 0])
    outs = []
    for i in range(FK.NLIMBS):
        d = x[..., i] - _L_DIGITS[i] - borrow
        borrow = (d < 0).astype(jnp.int32)
        outs.append(d + (borrow << 8))
    sub_res = jnp.stack(outs, axis=-1)
    sub_res = jnp.pad(sub_res, [(0, 0)] * (sub_res.ndim - 1)
                      + [(0, FK.NLANES - FK.NLIMBS)])
    keep = (borrow > 0)[..., None]  # borrow out => x < L => keep x
    return jnp.where(keep, x, sub_res)


def _mont_kernel(a_ref, b_ref, o_ref):
    a = a_ref[:]
    b = b_ref[:]
    lane = FK.lane_iota(a.shape)
    # T = a * b, canonical 64 bytes.
    t = _carry_bytes(FK.conv32(a, b), 64)
    # m = (T mod R) * L' mod R: coefficients at lane >= 32 carry weight
    # >= 2^256 == 0 (mod R) — dropped BEFORE the carry, like the lax
    # slice; the carry's own final out is dropped for the same reason.
    t_lo = jnp.where(lane < FK.NLIMBS, t, 0)
    m_coeffs = FK.conv32_const(t_lo, _LPRIME_DIGITS)
    m = _carry_bytes(jnp.where(lane < FK.NLIMBS, m_coeffs, 0), FK.NLIMBS)
    # U = T + m*L < 2RL: 64 canonical bytes; U/R is the high lane slice.
    u = _carry_bytes(FK.conv32_const(m, _L_DIGITS) + t, 64)
    hi = jnp.pad(u[..., FK.NLIMBS:64],
                 [(0, 0)] * (u.ndim - 1) + [(0, FK.NLANES - FK.NLIMBS)])
    o_ref[:] = _cond_sub_l(hi)


# jit-wrapped: one pallas trace per shape (kern package docstring).
@jax.jit
def _mont_rows(a_pad: jnp.ndarray, b_pad: jnp.ndarray) -> jnp.ndarray:
    rows = a_pad.shape[0]
    block, _ = FK.row_block(rows)
    return pl.pallas_call(
        _mont_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, FK.NLANES), jnp.int32),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, FK.NLANES), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((block, FK.NLANES), lambda i: (i, 0)),
        interpret=interpret_default(),
    )(a_pad, b_pad)


def scalar_mont_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a * b * R^-1 mod L for canonical (..., 32) byte-limb scalars —
    the Pallas route of scalar25519.mont_mul (same signature and
    headroom contract: a*b < R*L, so one input may range to 2^256 - 1
    when the other stays < L).  Returns canonical bytes < L.  Batch
    flattening / lane padding / row-block plumbing is the shared
    fieldops.launch_rows wrapper."""
    return FK.launch_rows(_mont_rows, a, b)
