"""Plain Ed25519 (RFC 8032) in Python integers: the benchmark's reference.

A copy of ``hotstuff_tpu/crypto/ref_ed25519.py`` with the curve constants
of ``hotstuff_tpu/utils/intmath.py`` inlined, kept under ``benchmark/`` so
that the yardstick does not move with the program (the originals stay the
program's; PERF.md lists them under Open questions).  One ``verify`` per
signature, strict as dalek's ``verify_strict``: it decides what the
verdict mask of a request has to be.  About 4 ms a verify: the driver
checks a seeded sample with it, outside the measured window.
"""

from __future__ import annotations

import hashlib

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
BY = (4 * pow(5, P - 2, P)) % P


def recover_x(y: int, sign: int) -> int | None:
    """RFC 8032 section 5.1.3 x-recovery; None when y is not on the curve
    or the encoding is invalid."""
    if y >= P:
        return None
    x2 = (y * y - 1) * pow(D * y * y + 1, P - 2, P) % P
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P != 0:
        x = x * SQRT_M1 % P
    if (x * x - x2) % P != 0:
        return None
    if x == 0 and sign:
        return None
    if x % 2 != sign:
        x = P - x
    return x


BX = recover_x(BY, 0)  # canonical basepoint x (even)

B = (BX, BY, 1, BX * BY % P)
IDENT = (0, 1, 1, 0)


def pt_add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * t1 * t2 * D % P
    dd = 2 * z1 * z2 % P
    e, f, g, h = b - a, dd - c, dd + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def pt_dbl(p):
    return pt_add(p, p)


def scalar_mult(s: int, p):
    q = IDENT
    while s > 0:
        if s & 1:
            q = pt_add(q, p)
        p = pt_dbl(p)
        s >>= 1
    return q


def pt_equal(p, q) -> bool:
    x1, y1, z1, _ = p
    x2, y2, z2, _ = q
    return (x1 * z2 - x2 * z1) % P == 0 and (y1 * z2 - y2 * z1) % P == 0


def encode_point(p) -> bytes:
    x, y, z, _ = p
    zi = pow(z, P - 2, P)
    x, y = x * zi % P, y * zi % P
    return (y | ((x & 1) << 255)).to_bytes(32, "little")


def decode_point(s: bytes):
    val = int.from_bytes(s, "little")
    y = val & ((1 << 255) - 1)
    sign = val >> 255
    x = recover_x(y, sign)
    if x is None:
        return None
    return (x, y, 1, x * y % P)


def _h(data: bytes) -> int:
    return int.from_bytes(hashlib.sha512(data).digest(), "little")


def _clamp(a: int) -> int:
    a &= (1 << 254) - 8
    a |= 1 << 254
    return a


def public_key(seed: bytes) -> bytes:
    a = _clamp(int.from_bytes(hashlib.sha512(seed).digest()[:32], "little"))
    return encode_point(scalar_mult(a, B))


def generate_keypair(seed: bytes) -> tuple[bytes, bytes]:
    """seed (32 bytes) -> (seed, public_key).  Analogue of the reference's
    generate_keypair (crypto/src/lib.rs:169-175)."""
    assert len(seed) == 32
    return seed, public_key(seed)


def sign(seed: bytes, msg: bytes) -> bytes:
    h = hashlib.sha512(seed).digest()
    a = _clamp(int.from_bytes(h[:32], "little"))
    prefix = h[32:]
    pk = encode_point(scalar_mult(a, B))
    r = _h(prefix + msg) % L
    r_enc = encode_point(scalar_mult(r, B))
    k = _h(r_enc + pk + msg) % L
    s = (r + k * a) % L
    return r_enc + s.to_bytes(32, "little")


def is_small_order(pt) -> bool:
    """True for the 8-torsion points ([8]P == identity)."""
    return pt_equal(scalar_mult(8, pt), IDENT)


def verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """Host reference verifier: [S]B == R + [k]A (cofactorless, strict).

    Strictness matches dalek's ``verify_strict`` (the reference's
    single-signature path, crypto/src/lib.rs:204-208): small-order A or R
    is rejected — with A small-order, ``sig = R||S`` where R = [S]B - [k]A
    verifies ANY message (for A = identity, any R = [S]B works), so
    accepting such keys breaks vote attribution in the committee.
    """
    if len(sig) != 64 or len(pk) != 32:
        return False
    a_pt = decode_point(pk)
    r_pt = decode_point(sig[:32])
    s = int.from_bytes(sig[32:], "little")
    if a_pt is None or r_pt is None or s >= L:
        return False
    if is_small_order(a_pt) or is_small_order(r_pt):
        return False
    k = _h(sig[:32] + pk + msg) % L
    return pt_equal(scalar_mult(s, B), pt_add(r_pt, scalar_mult(k, a_pt)))
