"""Ed25519 curve operations and batched signature verification on TPU.

The device-side half of the framework's equivalent of the reference's
``Signature::verify`` / ``Signature::verify_batch``
(reference: crypto/src/lib.rs:177-224).  Scalars, hashing (SHA-512) and
encoding checks live on the host (see hotstuff_tpu/crypto/eddsa.py); the
device receives raw scalar/point bytes and returns a per-signature
validity mask — the mask shape is what quorum-certificate verification
consumes (consensus/src/messages.rs:180-198 in the reference).

The check [S]B - [k]A == R splits into a fixed-base comb for [S]B (32
adds against a host-precomputed affine table, zero doublings) plus a
4-bit windowed variable-base ladder for [k](-A) (64 scan steps of four
doublings and one add against an on-device 16-entry table).  The
measurements behind this shape predate PR 1 and are not reproducible;
PERF.md holds what has been measured on the chip since.

TPU-first design notes:
* Points are dense ``(..., 4, 32)`` int32 arrays (X, Y, Z, T) in extended
  twisted-Edwards coordinates — a pytree-free layout that vmaps/shards
  cleanly along the batch axis.
* All control flow is static: complete addition formulas (no exceptional
  cases), `lax.scan` over fixed digit schedules, table selection via
  `take_along_axis` (gather on device).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import field25519 as F
from ..utils.intmath import BX, BY, D, L, P, SQRT_M1, next_pow2

K2D = (2 * D) % P

_const = F.constant

# A/B switches for the point-op conv shapes (see scripts/eval_device.py).
# Defaults are the slope-measured winners on a real v5e chip.
import os as _os

_STACK_MULS = _os.environ.get("HOTSTUFF_TPU_STACK_MULS", "0") == "1"
_ONEHOT_SELECT = _os.environ.get("HOTSTUFF_TPU_ONEHOT_SELECT", "0") == "1"
_JOINT_DECOMPRESS = _os.environ.get("HOTSTUFF_TPU_JOINT_DECOMPRESS", "1") == "1"
# Carry point coordinates through the ladder/comb scans as a 4-tuple of
# (B, 32) arrays instead of one stacked (B, 4, 32) array. Hypothesis was
# that _pack/_unpack in the scan body cost real data movement; measured on
# a v5e the packed layout is consistently ~1-2 ms/batch FASTER (XLA fuses
# the packing; the stacked table gather beats 4 per-coordinate gathers),
# so the default stays packed.
_TUPLE_POINTS = _os.environ.get("HOTSTUFF_TPU_TUPLE_POINTS", "0") == "1"


# ---------------------------------------------------------------------------
# Point representation helpers.  ext = (X, Y, Z, T); cached = (Y+X, Y-X, Z, 2dT)
# ---------------------------------------------------------------------------

_EXT_X, _EXT_Y, _EXT_Z, _EXT_T = range(4)


def _pack(x, y, z, t):
    return jnp.stack([x, y, z, t], axis=-2)


def _unpack(p):
    return p[..., 0, :], p[..., 1, :], p[..., 2, :], p[..., 3, :]


def identity_ext(batch_shape=()) -> jnp.ndarray:
    one, zero = _const(1), _const(0)
    pt = _pack(zero, one, one, zero)
    return jnp.broadcast_to(pt, (*batch_shape, 4, F.NLIMBS))


def basepoint_ext() -> jnp.ndarray:
    return _pack(_const(BX), _const(BY), _const(1), _const(BX * BY % P))


def to_cached(p: jnp.ndarray) -> jnp.ndarray:
    return _pack(*to_cached_t(_unpack(p)))


def cached_neg(c: jnp.ndarray) -> jnp.ndarray:
    """cached(P) -> cached(-P): swap (Y+X, Y-X), negate 2dT."""
    ypx, ymx, z, t2d = _unpack(c)
    return _pack(ymx, ypx, z, F.neg(t2d))


def point_add(p: jnp.ndarray, qc: jnp.ndarray) -> jnp.ndarray:
    """Complete unified addition, ext + cached -> ext (8 field muls).

    add-2008-hwcd-3 for a=-1 (the ref10 ge_add shape) — complete on the
    twisted Edwards curve, so it needs no doubling/identity branches: ideal
    for SIMD/scan execution on TPU.  Default: the muls stay separate
    batch-group convs, which XLA overlaps well.  HOTSTUFF_TPU_STACK_MULS=1
    instead fuses the 4 independent input products and the 4 output
    products into two 4*batch-group convs — rejected by a pre-PR-1
    measurement that is not reproducible (ROADMAP D2), kept only as an
    A/B switch.
    """
    if not _STACK_MULS:
        return _pack(*add_t(_unpack(p), _unpack(qc)))
    x1, y1, z1, t1 = _unpack(p)
    ypx2, ymx2, z2, t2d2 = _unpack(qc)
    m = F.mul(_pack(F.sub(y1, x1), F.add(y1, x1), t1, z1),
              _pack(ymx2, ypx2, t2d2, z2))
    a, b, c, zz = _unpack(m)
    d = F.add(zz, zz)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return F.mul(_pack(e, g, f, e), _pack(f, h, g, h))


def point_dbl(p: jnp.ndarray, with_t: bool = True) -> jnp.ndarray:
    """Dedicated doubling (dbl-2008-hwcd, a=-1): 4M + 4S.

    with_t=False skips the T-output multiply (3M + 4S): legal whenever the
    next consumer is another doubling, which only reads X, Y, Z.  Static
    python bool, so each variant compiles to its own fixed program.
    Default: separate batch-group convs (XLA overlaps the 4 independent
    squarings); HOTSTUFF_TPU_STACK_MULS=1 fuses them into stacked convs —
    measured slower (see point_add).
    """
    x1, y1, z1, _ = _unpack(p)
    if not _STACK_MULS:
        out = dbl_t((x1, y1, z1), with_t=with_t)
        if with_t:
            return _pack(*out)
        return _pack(*out, jnp.zeros_like(x1))
    s = F.sqr(_pack(x1, y1, z1, F.add(x1, y1)))
    a, b, zz, s3 = _unpack(s)
    c = F.add(zz, zz)
    e = F.sub(F.sub(s3, a), b)                      # 2*X1*Y1
    g = F.sub(b, a)                                 # B - A   (= D + B, D = -A)
    f = F.sub(g, c)
    h = F.neg(F.add(a, b))                          # -(A+B)  (= D - B)
    if with_t:
        return F.mul(_pack(e, g, f, e), _pack(f, h, g, h))
    out = F.mul(jnp.stack([e, g, f], axis=-2),
                jnp.stack([f, h, g], axis=-2))
    t_zero = jnp.zeros_like(out[..., :1, :])
    return jnp.concatenate([out, t_zero], axis=-2)


# ---------------------------------------------------------------------------
# Decompression (x-recovery), fully on device
# ---------------------------------------------------------------------------

def decompress_t(y_limbs: jnp.ndarray, sign_bit: jnp.ndarray):
    """(..., 32) canonical y limbs + (...,) sign bit ->
    ((x, y, z, t) tuple, ok mask).

    RFC 8032 §5.1.3 x-recovery: x = u v^3 (u v^7)^((p-5)/8), with u = y²-1,
    v = d y²+1; multiply by sqrt(-1) when v x² = -u; fail when neither.
    The (p-5)/8 power runs as a scan over a constant bit schedule.
    """
    one = jnp.broadcast_to(_const(1), y_limbs.shape)
    dd = jnp.broadcast_to(_const(D), y_limbs.shape)
    y2 = F.sqr(y_limbs)
    u = F.sub(y2, one)
    v = F.add(F.mul(dd, y2), one)
    v3 = F.mul(F.sqr(v), v)
    v7 = F.mul(F.sqr(v3), v)
    x = F.mul(F.mul(u, v3), F.pow_p58(F.mul(u, v7)))
    vxx = F.mul(v, F.sqr(x))
    ok_direct = F.eq(vxx, u)
    ok_twist = F.eq(vxx, F.neg(u))
    x = jnp.where(ok_twist[..., None],
                  F.mul(x, jnp.broadcast_to(_const(SQRT_M1), x.shape)), x)
    ok = ok_direct | ok_twist
    # sign adjustment; x == 0 with sign 1 is invalid
    x_zero = F.is_zero(x)
    flip = (F.parity(x) != sign_bit) & ~x_zero
    x = jnp.where(flip[..., None], F.neg(x), x)
    ok = ok & ~(x_zero & (sign_bit == 1))
    t = F.mul(x, y_limbs)
    z = jnp.broadcast_to(_const(1), y_limbs.shape)
    return (x, y_limbs, z, t), ok


def decompress(y_limbs: jnp.ndarray, sign_bit: jnp.ndarray):
    """Packed-layout wrapper over decompress_t: -> ((..., 4, 32) ext, ok)."""
    (x, y, z, t), ok = decompress_t(y_limbs, sign_bit)
    return _pack(x, y, z, t), ok


# ---------------------------------------------------------------------------
# Tuple-layout point ops (the scan-hot-loop form; see _TUPLE_POINTS)
# ---------------------------------------------------------------------------

def identity_t(batch_shape=()):
    one = jnp.broadcast_to(_const(1), (*batch_shape, F.NLIMBS))
    zero = jnp.broadcast_to(_const(0), (*batch_shape, F.NLIMBS))
    return (zero, one, one, zero)


def to_cached_t(p):
    """(x, y, z, t) -> cached (y+x, y-x, z, 2d*t)."""
    x, y, z, t = p
    k2d = jnp.broadcast_to(_const(K2D), t.shape)
    return (F.add(y, x), F.sub(y, x), z, F.mul(t, k2d))


def add_t(p, qc):
    """Complete unified addition on tuples: ext + cached -> ext (8 muls,
    separate batch-group convs — the measured-best conv shape)."""
    x1, y1, z1, t1 = p
    ypx2, ymx2, z2, t2d2 = qc
    a = F.mul(F.sub(y1, x1), ymx2)
    b = F.mul(F.add(y1, x1), ypx2)
    c = F.mul(t1, t2d2)
    zz = F.mul(z1, z2)
    d = F.add(zz, zz)
    e = F.sub(b, a)
    f = F.sub(d, c)
    g = F.add(d, c)
    h = F.add(b, a)
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))


def dbl_t(p, with_t: bool = True):
    """Doubling on tuples (dbl-2008-hwcd, a=-1): 4M+4S (3M+4S w/o T).

    Accepts a 3-tuple (x, y, z) or 4-tuple (T input unused); returns a
    3-tuple when with_t=False."""
    x1, y1, z1 = p[0], p[1], p[2]
    a = F.sqr(x1)
    b = F.sqr(y1)
    zz = F.sqr(z1)
    c = F.add(zz, zz)
    e = F.sub(F.sub(F.sqr(F.add(x1, y1)), a), b)   # 2*X1*Y1
    g = F.sub(b, a)
    f = F.sub(g, c)
    h = F.neg(F.add(a, b))
    if with_t:
        return (F.mul(e, f), F.mul(g, h), F.mul(f, g), F.mul(e, h))
    return (F.mul(e, f), F.mul(g, h), F.mul(f, g))


# ---------------------------------------------------------------------------
# Fixed-base comb table for S*B (host-precomputed, device constant)
# ---------------------------------------------------------------------------

_COMB_W = 8          # one comb position per S byte
_COMB_POSITIONS = 32

_comb_cache: np.ndarray | None = None


def _host_pt_add(p, q):
    """Extended-coordinate add on python ints (table generation only)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * t1 * t2 * D % P
    dd = 2 * z1 * z2 % P
    e, f, g, h = b - a, dd - c, dd + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def comb_table() -> np.ndarray:
    """(32, 256, 4, 32) int32: COMB[j][d] = cached affine form of d*(256^j)*B.

    S*B = sum_j COMB[j][S_byte_j] — 31 additions and ZERO doublings for the
    whole fixed-base half of the verification equation (the little-endian S
    bytes are directly the comb digits). Built lazily on host (~8k python
    point adds + one batched inversion), then baked into the jitted program
    as a constant (~4 MB).
    """
    global _comb_cache
    if _comb_cache is not None:
        return _comb_cache
    base = (BX, BY, 1, BX * BY % P)
    entries = []  # flat ext points, position-major
    for _ in range(_COMB_POSITIONS):
        acc = (0, 1, 1, 0)
        for _ in range(256):
            entries.append(acc)
            acc = _host_pt_add(acc, base)
        base = acc  # 256^{j+1} * B = 256 * (256^j * B); acc ran to 256*base
    # Batch affine normalization: one modular inverse total (Montgomery).
    zs = [e[2] for e in entries]
    prefix = [1]
    for z in zs:
        prefix.append(prefix[-1] * z % P)
    inv_all = pow(prefix[-1], P - 2, P)
    invs = [0] * len(zs)
    for i in range(len(zs) - 1, -1, -1):
        invs[i] = prefix[i] * inv_all % P
        inv_all = inv_all * zs[i] % P
    out = np.zeros((_COMB_POSITIONS, 256, 4, F.NLIMBS), np.int32)
    for idx, ((x, y, _, _), zi) in enumerate(zip(entries, invs)):
        xa, ya = x * zi % P, y * zi % P
        j, d = divmod(idx, 256)
        out[j, d, 0] = F.to_limbs((ya + xa) % P)
        out[j, d, 1] = F.to_limbs((ya - xa) % P)
        out[j, d, 2] = F.to_limbs(1)
        out[j, d, 3] = F.to_limbs(K2D * xa * ya % P)
    _comb_cache = out
    return out


# ---------------------------------------------------------------------------
# Batched verification
# ---------------------------------------------------------------------------

def _digit_select(table: jnp.ndarray, digit: jnp.ndarray) -> jnp.ndarray:
    """table (..., Ktab, 4coord, 32), digit (...,) in [0,K) -> (..., 4, 32).

    Default: take_along_axis (XLA gather).  HOTSTUFF_TPU_ONEHOT_SELECT=1
    switches to a one-hot masked sum, which looked 4x better in an isolated
    microbench but not inside the full verify program (pre-PR-1
    measurement, not reproducible; ROADMAP D2) — kept as an A/B switch.
    """
    if not _ONEHOT_SELECT:
        idx = digit[..., None, None, None].astype(jnp.int32)
        return jnp.take_along_axis(table, idx, axis=-3)[..., 0, :, :]
    k = table.shape[-3]
    d = jax.lax.broadcasted_iota(jnp.int32, (k,), 0)
    mask = (digit[..., None] == d).astype(table.dtype)[..., None, None]
    return jnp.sum(table * mask, axis=-3)




def unpack_nibbles_msb(k_bytes: jnp.ndarray) -> jnp.ndarray:
    """(B, 32) uint8 little-endian scalar -> (B, 64) int32 MSB-first 4-bit
    digits, the schedule of the windowed variable-base ladder.

    Runs on device: the host ships raw scalar bytes; digit expansion is
    free next to the curve arithmetic.
    """
    b = k_bytes.astype(jnp.int32)[..., ::-1]  # big-endian byte order
    hi, lo = b >> 4, b & 0xF
    return jnp.stack([hi, lo], axis=-1).reshape(*b.shape[:-1], 64)


def split_y_sign(y_bytes: jnp.ndarray):
    """(B, 32) uint8 compressed point -> ((B, 32) int32 y limbs with bit
    255 cleared, (B,) int32 x-sign bit). Device-side byte parsing."""
    y = y_bytes.astype(jnp.int32)
    sign = y[..., 31] >> 7
    y = y.at[..., 31].set(y[..., 31] & 0x7F)
    return y, sign


def verify_compact(a_bytes: jnp.ndarray, r_bytes: jnp.ndarray,
                   s_bytes: jnp.ndarray, k_bytes: jnp.ndarray) -> jnp.ndarray:
    """Device-side Ed25519 verification from raw wire bytes.

    Args (all (B, 32) uint8): compressed pubkey A, compressed R, scalar S
    (little-endian), and the host-hashed challenge k = SHA512(R||A||M) mod L.
    130 bytes/signature cross the host->device boundary; limb conversion,
    sign extraction and digit expansion all happen on device.

    Returns (B,) bool validity mask (host-side canonicality checks are
    ANDed by the caller, crypto/eddsa.verify_batch).
    """
    ay, a_sign = split_y_sign(a_bytes)
    ry, r_sign = split_y_sign(r_bytes)
    s_digits = s_bytes.astype(jnp.int32)  # little-endian bytes = comb digits
    k_digits = unpack_nibbles_msb(k_bytes)
    return verify_prepared(ay, a_sign, ry, r_sign, s_digits, k_digits)


def _jit_donated(fn):
    """jit with arg 0 donated: the production verify loop hands each
    packed buffer to the device exactly once, so XLA may reuse its memory
    for temporaries (what it saves: not measured on the chip).  Donation is
    unimplemented on CPU (it would only emit a warning per launch), so
    the CPU test backend gets a plain jit.  The backend choice is read at
    FIRST CALL, not import: jax.default_backend() initializes the
    platform client, and importing this module must stay side-effect-free
    (a second process probing a chip that another process holds would
    otherwise fail at import, and jax.config.update calls after import
    would be pinned out)."""
    jitted = None

    def call(*args):
        nonlocal jitted
        if jitted is None:
            jitted = jax.jit(fn) if jax.default_backend() == "cpu" \
                else jax.jit(fn, donate_argnums=0)
        return jitted(*args)

    return call


# Debug/profiling entry point: scripts re-time one device-resident input
# many times, which donation would invalidate after the first call.
# graftlint: disable=nondonated-buffer
verify_compact_jit = jax.jit(verify_compact)


def verify_packed(packed: jnp.ndarray) -> jnp.ndarray:
    """(B, 128) uint8 rows of A || R || S || k -> (B,) bool mask.

    Single-array variant of verify_compact: one host->device transfer per
    batch instead of four."""
    return verify_compact(packed[..., 0:32], packed[..., 32:64],
                          packed[..., 64:96], packed[..., 96:128])


# Re-timeable variant for the profiling scripts (see _jit_donated).
# graftlint: disable=nondonated-buffer
verify_packed_jit = jax.jit(verify_packed)
# Production launch shape for the sidecar engine: its packed buffers are
# freshly transferred per launch and never touched again.
verify_packed_donated = _jit_donated(verify_packed)


def verify_packed_chunked(packed_g: jnp.ndarray) -> jnp.ndarray:
    """(G, B, 128) uint8 -> (G, B) bool: G sub-batches verified by ONE
    program (lax.scan over sub-batches).

    A dispatch+sync pays a fixed cost regardless of batch (not measured
    on the chip), while per-conv group counts must stay <= ~1024 for sane
    compile times — so large backlogs go through this shape: group count
    stays at the sub-batch size, but G sub-batches share one dispatch.
    This is the production launch shape for the sidecar's bulk path and
    the headline bench.  The mesh twin is
    parallel/sharded_verify.verify_sharded_chunked (graftscale): the
    same scan structure per shard, with the validity counts psum-reduced
    over ICI and the (g, rows) shape set coming from
    parallel/shard_shapes.mesh_chunk_count."""
    def body(_, chunk):
        return None, verify_packed(chunk)
    _, masks = jax.lax.scan(body, None, packed_g)
    return masks


# Re-timeable variant for the profiling scripts (see _jit_donated).
# graftlint: disable=nondonated-buffer
verify_packed_chunked_jit = jax.jit(verify_packed_chunked)
# Production bulk launch shape (the sidecar's backlog drain; bench.py
# builds its own donated outer jit over verify_packed_chunked).
verify_packed_chunked_donated = _jit_donated(verify_packed_chunked)


def verify_prepared(ay: jnp.ndarray, a_sign: jnp.ndarray,
                    ry: jnp.ndarray, r_sign: jnp.ndarray,
                    s_digits: jnp.ndarray,
                    k_digits: jnp.ndarray) -> jnp.ndarray:
    """Device-side Ed25519 verification over a batch.

    Checks [S]B - [k]A == R, split into:
      * [S]B via a fixed-base comb (32 adds against a host-precomputed
        affine table, zero doublings), and
      * [k](-A) via a 4-bit windowed variable-base ladder (64 steps of
        4 doublings + 1 table add against an on-device 16-entry table),
    then one combining add and a projective compare against R. This is
    ~3,350 conv launches vs ~4,900 for the old joint 1-bit ladder — the
    program's bound on the chip: not measured.

    Args:
      ay, ry:   (B, 32) int32 canonical y limbs of pubkey / R point.
      a_sign, r_sign: (B,) int32 x-parity bits.
      s_digits: (B, 32) int32 little-endian base-256 digits of S (= bytes).
      k_digits: (B, 64) int32 MSB-first base-16 digits of
                k = SHA512(R||A||M) mod L (host-hashed).
    Returns:
      (B,) bool validity mask (encoding checks done host-side are ANDed by
      the caller).
    """
    batch_shape = ay.shape[:-1]
    if _JOINT_DECOMPRESS:
        # One stacked decompression for A and R: halves the length of the
        # dependent x-recovery pow chain (one conv at 2*batch groups
        # instead of two dependent batch-group convs).
        both_pt, ok_both = decompress_t(
            jnp.concatenate([ay, ry], axis=0),
            jnp.concatenate([a_sign, r_sign], axis=0))
        n = ay.shape[0]
        a_pt = tuple(c[:n] for c in both_pt)
        r_pt = tuple(c[n:] for c in both_pt)
        ok_a, ok_r = ok_both[:n], ok_both[n:]
    else:
        a_pt, ok_a = decompress_t(ay, a_sign)
        r_pt, ok_r = decompress_t(ry, r_sign)

    # -- variable-base half: [k](-A), 4-bit windows ------------------------
    ax, ay_l, az, at = a_pt
    neg_a = (F.neg(ax), ay_l, az, F.neg(at))
    neg_a_cached = to_cached_t(neg_a)
    # 16-entry table of d*(-A), d = 0..15, in cached form.
    entries = [identity_t(batch_shape), neg_a]
    for _ in range(2, 16):
        entries.append(add_t(entries[-1], neg_a_cached))
    cached_entries = [to_cached_t(e) for e in entries]

    if _TUPLE_POINTS:
        # Per-coordinate tables: 4 arrays of (..., 16, 32); selection is 4
        # per-coordinate gathers, and the scan carry is a coordinate tuple
        # (no stacked-layout packing anywhere in the hot loop).
        table_t = tuple(
            jnp.stack([e[c] for e in cached_entries], axis=-2)
            for c in range(4))

        def select_t(digit_row):
            idx = digit_row[..., None, None].astype(jnp.int32)
            return tuple(
                jnp.take_along_axis(tc, idx, axis=-2)[..., 0, :]
                for tc in table_t)

        def ladder_body(p, digit_row):
            p = dbl_t(p, with_t=False)
            p = dbl_t(p, with_t=False)
            p = dbl_t(p, with_t=False)
            p = dbl_t(p)  # the add below reads T
            p = add_t(p, select_t(digit_row))
            return p, None

        ka_pt, _ = jax.lax.scan(ladder_body, identity_t(batch_shape),
                                jnp.moveaxis(k_digits, -1, 0))

        # -- fixed-base half: [S]B via the comb ----------------------------
        comb = jnp.asarray(comb_table())  # (32, 256, 4, 32) constant
        comb_coords = tuple(comb[:, :, c, :] for c in range(4))

        def comb_body(acc, xs):
            digit_row = xs[-1]
            entry = tuple(jnp.take(cj, digit_row, axis=0) for cj in xs[:4])
            return add_t(acc, entry), None

        sb_pt, _ = jax.lax.scan(
            comb_body, identity_t(batch_shape),
            (*comb_coords, jnp.moveaxis(s_digits, -1, 0)))

        lhs = add_t(sb_pt, to_cached_t(ka_pt))  # [S]B - [k]A
        x3, y3, z3 = lhs[0], lhs[1], lhs[2]
        rx, ry_, rz = r_pt[0], r_pt[1], r_pt[2]
    else:
        table = jnp.stack([_pack(*e) for e in cached_entries], axis=-3)

        def ladder_body(p, digit_row):
            p = point_dbl(p, with_t=False)
            p = point_dbl(p, with_t=False)
            p = point_dbl(p, with_t=False)
            p = point_dbl(p)  # the add below reads T
            p = point_add(p, _digit_select(table, digit_row))
            return p, None

        ka_pt, _ = jax.lax.scan(ladder_body, identity_ext(batch_shape),
                                jnp.moveaxis(k_digits, -1, 0))

        comb = jnp.asarray(comb_table())  # (32, 256, 4, 32) constant

        def comb_body(acc, xs):
            comb_j, digit_row = xs
            entry = jnp.take(comb_j, digit_row, axis=0)  # (B, 4, 32)
            return point_add(acc, entry), None

        sb_pt, _ = jax.lax.scan(
            comb_body, identity_ext(batch_shape),
            (comb, jnp.moveaxis(s_digits, -1, 0)))

        lhs = point_add(sb_pt, to_cached(ka_pt))
        x3, y3, z3, _ = _unpack(lhs)
        rx, ry_, rz = r_pt[0], r_pt[1], r_pt[2]

    # -- projective equality: all four cross-products in one conv ----------
    cross = F.canonical(F.mul(_pack(x3, rx, y3, ry_),
                              _pack(rz, z3, rz, z3)))
    ok_eq = jnp.all(cross[..., 0, :] == cross[..., 1, :], axis=-1) & \
            jnp.all(cross[..., 2, :] == cross[..., 3, :], axis=-1)
    return ok_a & ok_r & ok_eq


# Test/debug entry point over already-split arrays; callers (tests,
# eval_device A/B runs) reuse their device-resident inputs across calls.
# graftlint: disable=nondonated-buffer
verify_prepared_jit = jax.jit(verify_prepared)


# ---------------------------------------------------------------------------
# Random-linear-combination batch verification: ONE multi-scalar multiply
# for the whole quorum
# ---------------------------------------------------------------------------
#
# Per-signature verification solves n independent equations
# [S_i]B == R_i + [k_i]A_i — two scalar ladders per vote.  Drawing random
# coefficients z_i and summing z_i * (eq_i) collapses the quorum to ONE
# equation,
#
#     [sum z_i S_i mod L] B  ==  sum [z_i] R_i  +  sum [z_i k_i mod L] A_i,
#
# whose right side is a 2n-point multi-scalar multiplication (MSM).  A
# batch of all-valid votes always satisfies it (the defects sum to exactly
# zero); an invalid vote escapes only if its defect cancels against the
# z-weighted sum, probability ~2^-128 for >=128-bit coefficients (see
# crypto/eddsa.verify_batch_rlc for the PRF and the one per-signature
# launch that pinpoints culprits when the combined check fails).
#
# MSM shape (Straus with shared 4-bit windows): per-point 16-entry tables
# (14 batched adds — the same table build the per-signature ladder does),
# then for each of the 64 nibble windows select each point's table entry
# and fold the batch axis with a masked segment-style binary tree of
# point adds (padding/excluded rows select entry 0 = identity, so no
# separate mask tensor is needed).  Windows are processed in chunks of
# _MSM_WINDOW_CHUNK inside one lax.scan — chunking trades conv group
# count (chunk * 2n per level) against scan depth, keeping groups inside
# the ~1024-group compile-time envelope at quorum sizes while the scan
# body still compiles once.  Window sums combine by a 64-step Horner
# fold (4 doublings + 1 add per window, on ONE point), and the
# fixed-base [c]B side reuses the zero-doubling comb (32 adds on one
# point).  Total point-op work is ~78n + 352 versus ~350n for n
# per-signature ladders — the arithmetic win the RLC check exists for.
# That one-point tail is a latency chain, not arithmetic: as lax scans
# (msm_horner, comb_mul_base) it is ~2,650 one-row convolutions with
# their carries, issued one after the other (38 a Horner step) — 38 ms
# of a v5e's time whatever the batch (PERF.md, PR 28).  On a TPU
# rlc_finish therefore runs it as ONE Pallas kernel (rlc_tail below,
# ops/kern/rlc_tail: 0.58 ms); the scans stay as the reference the
# kernel is held to limb for limb, and as the tail off the chip.
#
# Pippenger-style shared buckets (15 buckets per window, scatter by
# digit) were considered and rejected for this substrate: point adds
# cannot ride XLA's scatter/segment-sum (the group law is not an
# elementwise monoid op), so bucket accumulation would need a masked add
# per (bucket, point) pair — 15x the work of the per-point-table Straus
# form on a SIMD machine.  The per-point tables cost 2n*16 points of
# memory (~128 KB at n=512), which is noise next to the conv workspace.

from . import kern as _kern  # noqa: E402  (graftkern Pallas route)
from . import scalar25519 as S  # noqa: E402  (device scalar arithmetic)

_MSM_WINDOW_CHUNK = int(_os.environ.get("HOTSTUFF_TPU_MSM_WINDOW_CHUNK",
                                        "8"))
if 64 % _MSM_WINDOW_CHUNK != 0:
    raise ValueError("HOTSTUFF_TPU_MSM_WINDOW_CHUNK must divide 64")


def msm_window_chunk() -> int:
    """The Straus window-chunk size — env-pinned once at import
    (HOTSTUFF_TPU_MSM_WINDOW_CHUNK, default 8), re-pinnable in-process
    via :func:`set_msm_window_chunk`.  Read at trace time by
    msm_window_sums, so the v5e sweep (bench.py msm_chunk_sweep) can
    measure every value from ONE process instead of re-exec'ing a
    subprocess per value."""
    return _MSM_WINDOW_CHUNK


def set_msm_window_chunk(chunk: int) -> None:
    """Re-pin the window-chunk size in-process.  Clears the global jit
    caches: every compiled MSM program baked the chunk it was traced
    with, so a stale trace would keep the old scan shape.  The chunk
    only trades conv group count against scan depth — results are
    bit-identical across values (asserted in tests/test_kern.py)."""
    global _MSM_WINDOW_CHUNK
    if not isinstance(chunk, int) or chunk < 1 or 64 % chunk != 0:
        raise ValueError(
            f"msm window chunk must be a positive divisor of 64, "
            f"got {chunk!r}")
    if chunk != _MSM_WINDOW_CHUNK:
        _MSM_WINDOW_CHUNK = chunk
        jax.clear_caches()


def msm_table(points: jnp.ndarray) -> jnp.ndarray:
    """(B, 4, 32) ext points -> (B, 16, 4, 32) ext table of 0..15 multiples
    (entry 0 is the identity: digit-0 selections vanish without a mask)."""
    cached_p = to_cached(points)
    entries = [identity_ext(points.shape[:-2]), points]
    for _ in range(2, 16):
        entries.append(point_add(entries[-1], cached_p))
    return jnp.stack(entries, axis=-3)


def _tree_sum(pts: jnp.ndarray) -> jnp.ndarray:
    """(M, ..., 4, 32) ext -> (..., 4, 32): binary tree of point adds over
    the leading axis (M a power of two; identity entries make padding
    free).  log2(M) sequential adds at M/2, M/4, ... conv groups — the
    wide-SIMD segment reduction the MSM rests on."""
    m = pts.shape[0]
    while m > 1:
        m //= 2
        pts = point_add(pts[:m], to_cached(pts[m:]))
    return pts[0]


def msm_window_sums(points: jnp.ndarray, digits: jnp.ndarray) -> jnp.ndarray:
    """Per-window sums of a Straus MSM: (64, 4, 32) ext points W_j with
    sum_i [s_i]P_i = sum_j 16^(63-j) W_j (windows MSB-first).

    Args:
      points: (B, 4, 32) ext points.  B is padded to a power of two with
              identity points internally, so any batch size is legal.
      digits: (B, 64) int32 MSB-first 4-bit windows of the scalars
              (unpack_nibbles_msb of canonical 32-byte scalars < L).

    This is the shardable half of the MSM: window sums from disjoint
    point shards simply point-add together (parallel/sharded_verify
    all-gathers them over ICI and tree-combines before the Horner pass).
    """
    b = points.shape[0]
    b_pad = next_pow2(b)
    if b_pad != b:
        points = jnp.concatenate(
            [points, identity_ext((b_pad - b,))], axis=0)
        digits = jnp.pad(digits, [(0, b_pad - b), (0, 0)])
    table = msm_table(points)                        # (B, 16, 4, 32)
    if _kern.use_pallas():
        # graftkern route: selection + tree fused per window, window
        # sums bit-identical to the chunked scan below (the chunk knob
        # does not apply — the kernel grids over single windows).
        return _kern.msm_window_accum(table, digits)
    return _window_sums_lax(table, digits)


def _window_sums_lax(table: jnp.ndarray, digits: jnp.ndarray) -> jnp.ndarray:
    """The lax reference window accumulator (and the
    HOTSTUFF_TPU_KERN=lax route): per-window table selection + masked
    tree reduction, windows processed in chunks of msm_window_chunk()
    inside one lax.scan.  ``table`` (B, 16, 4, 32) from msm_table,
    ``digits`` (B, 64) with B already a power of two."""
    b_pad = digits.shape[0]
    chunk = msm_window_chunk()
    # (64, B) MSB-first -> (64/chunk, chunk, B)
    dig = jnp.moveaxis(digits, -1, 0).reshape(64 // chunk, chunk, b_pad)

    def chunk_sums(_, dch):
        tab = jnp.broadcast_to(table[None], (chunk, *table.shape))
        sel = _digit_select(tab, dch)                # (chunk, B, 4, 32)
        return None, _tree_sum(jnp.moveaxis(sel, 1, 0))

    _, wsums = jax.lax.scan(chunk_sums, None, dig)   # (64/chunk, chunk,..)
    return wsums.reshape(64, 4, F.NLIMBS)


def msm_horner(wsums: jnp.ndarray) -> jnp.ndarray:
    """(64, 4, 32) MSB-first window sums -> (4, 32) ext total:
    64 x (4 doublings + 1 add) on one point.  The lax reference of the
    rlc_tail kernel (and msm_straus' fold)."""
    def horner(acc, w):
        acc = point_dbl(acc, with_t=False)
        acc = point_dbl(acc, with_t=False)
        acc = point_dbl(acc, with_t=False)
        acc = point_dbl(acc)
        return point_add(acc, to_cached(w)), None

    acc, _ = jax.lax.scan(horner, identity_ext(()), wsums)
    return acc


def msm_straus(points: jnp.ndarray, digits: jnp.ndarray) -> jnp.ndarray:
    """sum_i [s_i] P_i: (B, 4, 32) ext points + (B, 64) MSB-first nibble
    digits -> (4, 32) ext sum.  See msm_window_sums for the shape rules."""
    return msm_horner(msm_window_sums(points, digits))


def comb_mul_base(c_digits: jnp.ndarray) -> jnp.ndarray:
    """[c]B for one scalar given as (32,) int32 base-256 little-endian
    digits: the fixed-base comb at batch shape () — 32 adds, zero
    doublings.  The lax reference of the rlc_tail kernel's second
    sum."""
    comb = jnp.asarray(comb_table())                 # (32, 256, 4, 32)

    def body(acc, xs):
        comb_j, digit = xs
        return point_add(acc, jnp.take(comb_j, digit, axis=0)), None

    acc, _ = jax.lax.scan(body, identity_ext(()),
                          (comb, c_digits.astype(jnp.int32)))
    return acc


def rlc_tail(wsums: jnp.ndarray, c_digits: jnp.ndarray):
    """(msm_horner(wsums), comb_mul_base(c_digits)), limb for limb, as
    ONE kernel (ops/kern/rlc_tail): both are serial chains on a single
    point, which as lax scans cost ~2,650 one-row convolutions issued
    one after the other.  What is batched stays outside the chain: the
    64 window sums go to cached form in one op, and the 32 comb entries
    come from one gather."""
    if _kern.interpret_default():
        # Not on a TPU: the Pallas interpreter inlines the kernel body
        # into every rlc program, ~16 s more XLA:CPU compile a program
        # than the two scans, so off the chip the lax reference IS the
        # tail.  The kernel body itself still runs on the CPU in
        # tests/test_kern.py.
        return msm_horner(wsums), comb_mul_base(c_digits)
    comb = jnp.asarray(comb_table())                 # (32, 256, 4, 32)
    entries = comb[jnp.arange(_COMB_POSITIONS), c_digits.astype(jnp.int32)]
    return _kern.rlc_tail(to_cached(wsums), entries)


def rlc_partials(packed: jnp.ndarray, z: jnp.ndarray):
    """Shard-local half of the RLC check.

    Args:
      packed: (B, 128) uint8 rows of A || R || S || k.
      z:      (B, 32) uint8 canonical coefficient rows; an ALL-ZERO row is
              excluded (zero scalars select only identity table entries
              and its decompression result is ignored) — bucket padding
              and host-rejected votes are plain zero rows.
    Returns:
      wsums:   (64, 4, 32) MSB-first MSM window sums of
               sum [z_i k_i]A_i + [z_i]R_i over this shard's rows.
      u_sum:   (32,) int32 limb-wise sum of the z_i*S_i mod L terms
               (fold with scalar25519.reduce_limbsum_mod_l — it commutes
               with an ICI psum).
      bad:     () int32 count of included rows whose A or R failed
               decompression.
    Window sums from disjoint shards point-add together, which is what
    lets the MSM buckets shard across the mesh
    (parallel/sharded_verify.verify_rlc_sharded).
    """
    ay, a_sign = split_y_sign(packed[..., 0:32])
    ry, r_sign = split_y_sign(packed[..., 32:64])
    s_l = packed[..., 64:96].astype(jnp.int32)
    k_l = packed[..., 96:128].astype(jnp.int32)
    z_l = z.astype(jnp.int32)

    present = jnp.any(z_l != 0, axis=-1)
    # A points first, R points second — matching the digit concat below.
    pts, ok = decompress(jnp.concatenate([ay, ry], axis=0),
                         jnp.concatenate([a_sign, r_sign], axis=0))
    present2 = jnp.concatenate([present, present], axis=0)
    bad = jnp.sum(~ok & present2).astype(jnp.int32)

    w = S.mul_mod_l(z_l, k_l)          # z_i * k_i mod L  (A_i scalars)
    u = S.mul_mod_l(z_l, s_l)          # z_i * S_i mod L

    # Torsion-exact CRT lift to the full-group exponent 8L.  E(Fp) is
    # Z/8 x Z/L: a scalar acts mod L on the prime-order component but
    # mod 8 on a point's 8-torsion component, and reducing z*k mod L
    # scrambles the mod-8 residue — a combined check built from the
    # reduced scalars weighs each row's torsion defect by an
    # L-reduction artifact an adversary can grind (a mixed-order pubkey
    # A' + T would slip through whenever the artifact hits 0 mod 8).
    # Lifting A's scalar to w' ≡ w (mod L), w' ≡ k (mod 8) and R's to
    # z' ≡ z (mod L), z' ≡ 1 (mod 8) makes every row's torsion defect
    # enter the sum with the SAME coefficient the per-signature
    # cofactorless equation uses — so a single defective row passes or
    # fails the combined check exactly as verify_compact would.
    # (L ≡ 5 (mod 8), self-inverse; excluded rows keep scalar 0.)
    present_i = present.astype(jnp.int32)
    t_w = (5 * ((k_l[..., 0] & 7) - (w[..., 0] & 7))) % 8 * present_i
    t_z = (5 * (1 - (z_l[..., 0] & 7))) % 8 * present_i
    w_lift = S.add_small_multiple_of_l(w, t_w)
    z_lift = S.add_small_multiple_of_l(z_l, t_z)

    digits = unpack_nibbles_msb(jnp.concatenate([w_lift, z_lift], axis=0))
    wsums = msm_window_sums(pts, digits)
    return wsums, jnp.sum(u, axis=-2), bad


def rlc_finish(wsums: jnp.ndarray, u_limbsum: jnp.ndarray,
               bad: jnp.ndarray) -> jnp.ndarray:
    """Combine (possibly mesh-reduced) RLC partials into the () bool
    verdict: Horner-fold the window sums and comb [c]B from the reduced
    scalar sum (rlc_tail: one kernel on a TPU), compare projectively,
    and veto on any bad point."""
    c = S.reduce_limbsum_mod_l(u_limbsum)
    msm, cb = rlc_tail(wsums, c)       # sum [w_i]A_i + [z_i]R_i, [c]B

    x1, y1, z1, _ = _unpack(cb)
    x2, y2, z2, _ = _unpack(msm)
    cross = F.canonical(F.mul(_pack(x1, x2, y1, y2),
                              _pack(z2, z1, z2, z1)))
    eq = jnp.all(cross[..., 0, :] == cross[..., 1, :], axis=-1) & \
        jnp.all(cross[..., 2, :] == cross[..., 3, :], axis=-1)
    return (bad == 0) & eq


def verify_rlc_packed(packed: jnp.ndarray, z: jnp.ndarray) -> jnp.ndarray:
    """(B, 128) uint8 rows of A || R || S || k  +  (B, 32) uint8 canonical
    coefficient rows -> () bool: the whole batch passes the combined
    random-linear-combination check.  An all-excluded batch returns True
    (vacuous).  B should be a power-of-two bucket (crypto/eddsa._bucket
    discipline, the shapes warmup compiles); scalar products z*S and z*k
    reduce mod L on device (ops/scalar25519), so the caller only ships
    160 bytes per row.
    """
    return rlc_finish(*rlc_partials(packed, z))


# Re-timeable variant for profiling scripts (see _jit_donated).
# graftlint: disable=nondonated-buffer
verify_rlc_packed_jit = jax.jit(verify_rlc_packed)
# Production launch shape: each packed buffer is transferred once and
# consumed once (the z rows are small and not donated).
verify_rlc_packed_donated = _jit_donated(verify_rlc_packed)
