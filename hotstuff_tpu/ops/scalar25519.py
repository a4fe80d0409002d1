"""Arithmetic mod the Ed25519 group order L, batched, in JAX limb form.

The random-linear-combination (RLC) batch verification check
(ops/ed25519.verify_rlc_packed, crypto/eddsa.verify_batch_rlc) needs the
per-signature scalar products ``z_i * S_i mod L`` and ``z_i * k_i mod L``
and their sum computed ON DEVICE, next to the multi-scalar multiply that
consumes them — round-tripping 2n scalars through the host would put two
transfers in the middle of the one-dispatch verify program.

Representation: the same dense radix-2^8 int32 limb layout as
ops/field25519 — shape ``(..., 32)``, little-endian canonical bytes — so
scalars flow straight into the nibble-digit expansion the MSM windows use
(ops/ed25519.unpack_nibbles_msb).  Unlike the field module there is no
"weak" form here: every public entry point returns canonical bytes with
value in ``[0, L)``.

Reduction strategy: L = 2^252 + delta is not byte-aligned, so the
field-style fold-at-2^256 trick does not converge (2^256 mod L is itself
~2^252).  Instead multiplication reduces by **Montgomery reduction** at
R = 2^256, which is exactly byte-aligned: all intermediates stay
non-negative, truncation mod R and exact division by R are limb slicing,
and the whole thing is two schoolbook convolutions plus one exact carry
chain.  ``mul_mod_l`` composes two Montgomery multiplies (the second by
R^2 mod L) so callers never see the Montgomery domain.

The schoolbook products use the same depthwise-conv formulation as
field25519.mul: partial-product sums are < 32 * 255^2 < 2^21, exact in
float32, so the scalar path rides the MXU like the field path does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import field25519 as F
from . import kern as _kern
from ..utils.intmath import L

NLIMBS = 32
LIMB_MASK = 0xFF

# delta = L - 2^252 (125 bits): why 4-bit window schedules over scalars
# reduced mod L are 64 windows, not 63 — L needs 253 bits.
DELTA = L - (1 << 252)

# Montgomery constants at R = 2^256.
R = 1 << 256
LPRIME = (-pow(L, -1, R)) % R      # -L^-1 mod R
R2 = (R * R) % L                   # to-Montgomery / fixup factor
R1 = R % L

_L_LIMBS = F.to_limbs(L)
_LPRIME_LIMBS = F.to_limbs(LPRIME)
_R2_LIMBS = F.to_limbs(R2)
# Shifted multiples for reducing a value < 2^256 ( < 16L ) to [0, L):
# 8L = 2^255 + 8*delta < 2^256 still fits 32 canonical bytes.
_L_MULTIPLES = [F.to_limbs(8 * L), F.to_limbs(4 * L),
                F.to_limbs(2 * L), F.to_limbs(L)]


# ---------------------------------------------------------------------------
# Host <-> limb conversion (python ints; not jitted) — shared layout with
# field25519, re-exported so scalar callers need one import.
# ---------------------------------------------------------------------------

to_limbs = F.to_limbs
from_limbs = F.from_limbs
batch_to_limbs = F.batch_to_limbs
batch_from_limbs = F.batch_from_limbs


# ---------------------------------------------------------------------------
# Exact limb plumbing (non-negative int32 coefficient vectors)
# ---------------------------------------------------------------------------

def _conv_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Schoolbook product of limb vectors: (..., Wa) x (..., Wb) ->
    (..., Wa+Wb-1) int32 coefficients (no reduction, no carrying).

    Same depthwise-conv shape as field25519.mul; inputs must be canonical
    bytes (< 2^8) so every coefficient sum stays < 32 * 255^2 < 2^21 —
    exact in float32 at the field module's measured precision setting.
    """
    wa, wb = a.shape[-1], b.shape[-1]
    batch_shape = jnp.broadcast_shapes(a.shape[:-1], b.shape[:-1])
    a = jnp.broadcast_to(a, (*batch_shape, wa))
    b = jnp.broadcast_to(b, (*batch_shape, wb))
    n = 1
    for d in batch_shape:
        n *= d
    lhs = a.reshape(1, n, wa).astype(jnp.float32)
    rhs = jnp.flip(b.reshape(n, 1, wb), -1).astype(jnp.float32)
    out = jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(1,), padding=[(wb - 1, wb - 1)],
        dimension_numbers=("NCH", "OIH", "NCH"),
        feature_group_count=n,
        precision=F._PRECISION,
    ).reshape(*batch_shape, wa + wb - 1).astype(jnp.int32)
    return out


def _carry_bytes(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Exact ripple carry of non-negative int32 coefficients into ``width``
    canonical byte limbs (one unrolled sequential pass, like
    field25519._sequential_carry but width-generic and wrap-free).

    The represented value must fit in 8*width bits; the final carry out is
    dropped (callers size ``width`` so it is provably zero).
    """
    pad = width - x.shape[-1]
    if pad > 0:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    limbs = []
    carry = jnp.zeros_like(x[..., 0])
    for i in range(width):
        t = x[..., i] + carry
        limbs.append(t & LIMB_MASK)
        carry = t >> 8
    return jnp.stack(limbs, axis=-1)


def _cond_sub(x: jnp.ndarray, modulus_limbs: np.ndarray) -> jnp.ndarray:
    """If x >= m (x canonical 32 bytes), subtract m (borrow chain, like
    field25519._cond_sub_p but for an arbitrary 32-byte modulus)."""
    digits = jnp.asarray(modulus_limbs, dtype=jnp.int32)
    limbs = []
    borrow = jnp.zeros_like(x[..., 0])
    for i in range(NLIMBS):
        d = x[..., i] - digits[i] - borrow
        borrow = (d < 0).astype(jnp.int32)
        limbs.append(d + (borrow << 8))
    sub_res = jnp.stack(limbs, axis=-1)
    keep = (borrow > 0)[..., None]  # borrow out => x < m => keep x
    return jnp.where(keep, x, sub_res)


def mod_small(x: jnp.ndarray) -> jnp.ndarray:
    """(..., 32) canonical bytes, value < 2^256 (< 16L) -> value mod L.

    Four conditional subtractions of 8L, 4L, 2L, L — each multiple still
    fits 32 canonical bytes since 8L < 2^256."""
    for m in _L_MULTIPLES:
        x = _cond_sub(x, m)
    return x


# ---------------------------------------------------------------------------
# Montgomery multiplication at R = 2^256
# ---------------------------------------------------------------------------

def mont_mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a * b * R^-1 mod L for canonical byte-limb scalars.

    Valid whenever a * b < R*L (both inputs < L always qualifies; one
    input may range up to 2^256 - 1 if the other stays < L — the
    ``reduce512_mod_l`` high-half path uses that headroom).  Returns
    canonical bytes < L.

    Routed: ``HOTSTUFF_TPU_KERN=pallas`` dispatches the graftkern fused
    REDC kernel (ops/kern/scalar_mont), bit-identical to the lax
    reference below; ``mul_mod_l``/``reduce512_mod_l`` compose this
    primitive, so the route covers them too.
    """
    if _kern.use_pallas():
        return _kern.scalar_mont_mul(a, b)
    return _mont_mul_lax(a, b)


def _mont_mul_lax(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The lax reference REDC (and the HOTSTUFF_TPU_KERN=lax route).

    REDC with byte-aligned R: T = a*b; m = (T mod R) * L' mod R;
    U = T + m*L is divisible by R, so U >> 256 is limb slicing after one
    exact carry chain; U < 2R*L makes a single conditional subtract
    enough.  Everything stays non-negative — no signed-limb handling.
    """
    t = _carry_bytes(_conv_mul(a, b), 64)          # T = a*b, canonical
    # m = (T mod R) * L' mod R: coefficients at index >= 32 carry weight
    # >= 2^256 == 0 (mod R), so they are dropped BEFORE the carry; the
    # final carry out of limb 31 is dropped for the same reason.
    m = _carry_bytes(_conv_mul(t[..., :32], jnp.asarray(_LPRIME_LIMBS))
                     [..., :32], 32)
    # U = T + m*L < R*L + R*L = 2R*L < 2^510: 64 canonical bytes.
    u = _conv_mul(m, jnp.asarray(_L_LIMBS))
    u = jnp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, 64 - u.shape[-1])]) + t
    u = _carry_bytes(u, 64)
    # U is an exact multiple of R: its low 32 canonical bytes are zero and
    # U/R = U >> 256 is the high slice; U < 2R*L => U >> 256 < 2L.
    return _cond_sub(u[..., 32:], _L_LIMBS)


def mul_mod_l(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a * b mod L for canonical byte-limb scalars (a*b < R*L; both < L
    always qualifies).  Two REDC passes: (abR^-1) then * R^2 * R^-1."""
    return mont_mul(mont_mul(a, b), jnp.asarray(_R2_LIMBS))


def add_mod_l(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a + b mod L for canonical scalars < L (sum < 2L < 2^254 fits 32
    bytes)."""
    return _cond_sub(_carry_bytes(a + b, NLIMBS), _L_LIMBS)


def reduce_limbsum_mod_l(s: jnp.ndarray) -> jnp.ndarray:
    """(..., 32) int32 limb-wise sums of canonical scalars (limbs < 2^24,
    i.e. up to 2^16 summed terms) -> canonical value mod L.

    Value < 2^16 * L < 2^269 splits at the byte-aligned 2^256 boundary as
    hi*2^256 + lo with hi < 2^16, and hi*2^256 mod L == mont_mul(hi,
    R^2 mod L) — the same REDC primitive the products use.  The sharded
    verifier feeds this a psum of per-shard limb sums (limb-wise integer
    sums commute with the ICI reduction; the mod-L fold happens once,
    replicated)."""
    wide = _carry_bytes(s, 36)                     # < 2^269: 34 bytes + slack
    lo = wide[..., :32]
    hi = jnp.pad(wide[..., 32:],
                 [(0, 0)] * (wide.ndim - 1) + [(0, NLIMBS - 4)])
    return add_mod_l(mont_mul(hi, jnp.asarray(_R2_LIMBS)), mod_small(lo))


def sum_mod_l(u: jnp.ndarray, axis: int = -2) -> jnp.ndarray:
    """Sum of canonical scalars < L along ``axis``, mod L: limb-wise
    integer sum (n <= 4096 terms keep limbs < 2^20, far inside int32),
    then one fold through reduce_limbsum_mod_l."""
    return reduce_limbsum_mod_l(jnp.sum(u, axis=axis))


def add_small_multiple_of_l(x: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """x (..., 32) canonical < L  +  t (...,) int32 in [0, 8)  ->
    canonical 32 bytes of x + t*L  (< 8L < 2^256).

    The CRT lift to the full-group exponent 8L used by the RLC torsion
    handling (ops/ed25519.rlc_partials): x + t*L ≡ x (mod L) leaves the
    prime-order component untouched while choosing the scalar's mod-8
    residue, which is what the 8-torsion component of a point actually
    sees."""
    return _carry_bytes(x + t[..., None] * jnp.asarray(_L_LIMBS), NLIMBS)


def reduce512_mod_l(x: jnp.ndarray) -> jnp.ndarray:
    """(..., 64) canonical little-endian bytes (a 512-bit value) -> value
    mod L as canonical (..., 32) bytes.

    Split at 2^256: x = hi*2^256 + lo; hi < 2^256 rides the mont_mul
    headroom (hi * R2 < 2^256 * L), lo < 2^256 < 16L reduces by shifted
    conditional subtracts."""
    lo, hi = x[..., :32].astype(jnp.int32), x[..., 32:].astype(jnp.int32)
    return add_mod_l(mont_mul(hi, jnp.asarray(_R2_LIMBS)), mod_small(lo))
