"""Ed25519 verification tests: device verifier vs pure-python reference and
the `cryptography` library as independent ground truth.

Parity model: crypto/src/tests/crypto_tests.rs (verify_valid_signature,
verify_invalid_signature, verify_valid_batch, verify_invalid_batch) in the
reference repo.
"""


import numpy as np
import pytest

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref


def make_sigs(n, msg_len=32, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sk = rng.bytes(32)
        msg = rng.bytes(msg_len)
        _, pk = ref.generate_keypair(sk)
        out.append((msg, pk, ref.sign(sk, msg)))
    return out


def test_ref_impl_against_cryptography_lib():
    """Anchor the pure-python reference to an independent implementation."""
    pytest.importorskip(
        "cryptography",
        reason="third-party `cryptography` (OpenSSL) not installed on "
               "this image; the cross-check needs an independent "
               "implementation to anchor against")
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
    rng = np.random.default_rng(7)
    for _ in range(4):
        seed = rng.bytes(32)
        lib_sk = Ed25519PrivateKey.from_private_bytes(seed)
        lib_pk = lib_sk.public_key().public_bytes_raw()
        msg = rng.bytes(100)
        lib_sig = lib_sk.sign(msg)
        _, pk = ref.generate_keypair(seed)
        assert pk == lib_pk
        assert ref.sign(seed, msg) == lib_sig  # Ed25519 is deterministic
        assert ref.verify(pk, msg, lib_sig)


def test_device_verify_valid():
    triples = make_sigs(4)
    msgs, pks, sigs = zip(*triples)
    mask = eddsa.verify_batch(list(msgs), list(pks), list(sigs))
    assert mask.all()


def test_device_verify_invalid():
    triples = make_sigs(6, seed=1)
    msgs, pks, sigs = map(list, zip(*triples))
    # corrupt in distinct ways
    sigs[0] = sigs[0][:10] + bytes([sigs[0][10] ^ 1]) + sigs[0][11:]   # R bits
    sigs[1] = sigs[1][:40] + bytes([sigs[1][40] ^ 1]) + sigs[1][41:]   # S bits
    msgs[2] = msgs[2] + b"!"                                           # message
    pks[3] = pks[0]                                                    # wrong key
    sigs[4] = b"\x00" * 64                                             # garbage
    mask = eddsa.verify_batch(msgs, pks, sigs)
    assert list(mask) == [False, False, False, False, False, True]


def test_noncanonical_rejected():
    (msg, pk, sig), = make_sigs(1, seed=2)
    # S >= L
    s = int.from_bytes(sig[32:], "little") + ref.L
    bad_s = sig[:32] + s.to_bytes(32, "little")
    # y >= p in R encoding
    r = int.from_bytes(sig[:32], "little")
    bad_r = ((r | ((1 << 255) - 1)) & ~(1 << 255)).to_bytes(32, "little") + sig[32:]
    mask = eddsa.verify_batch([msg, msg], [pk, pk], [bad_s, bad_r])
    assert not mask.any()


def test_small_order_universal_forgery_rejected():
    """verify_strict parity (crypto/src/lib.rs:204-208): with pk A = the
    identity encoding, sig = ([S]B || S) satisfies [S]B == R + [k]A for ANY
    message — a universal forgery unless small-order keys are rejected."""
    s = 12345
    r_enc = ref.encode_point(ref.scalar_mult(s, ref.B))
    forged = r_enc + s.to_bytes(32, "little")
    identity_pk = (1).to_bytes(32, "little")
    for msg in (b"any message at all", b"another one"):
        # cofactorless equation holds...
        a_pt = ref.decode_point(identity_pk)
        r_pt = ref.decode_point(forged[:32])
        k = ref._h(forged[:32] + identity_pk + msg) % ref.L
        assert ref.pt_equal(ref.scalar_mult(s, ref.B),
                            ref.pt_add(r_pt, ref.scalar_mult(k, a_pt)))
        # ...but both verifiers must reject it.
        assert not ref.verify(identity_pk, msg, forged)
        assert not eddsa.verify(identity_pk, msg, forged)


def test_small_order_r_identity_forgery_rejected():
    """R = identity with S = k*a mod L satisfies the cofactorless equation
    ([S]B == [k]A) for an honest key — the one R-side case the small-order
    check changes from accept to reject."""
    seed = b"\x09" * 32
    sk, pk = ref.generate_keypair(seed)
    import hashlib
    a = ref._clamp(int.from_bytes(hashlib.sha512(seed).digest()[:32],
                                  "little"))
    ident = ref.encode_point(ref.IDENT)
    msg = b"r-identity forgery"
    k = ref._h(ident + pk + msg) % ref.L
    s = k * a % ref.L
    forged = ident + s.to_bytes(32, "little")
    assert ref.pt_equal(ref.scalar_mult(s, ref.B),
                        ref.scalar_mult(k, ref.decode_point(pk)))
    assert not ref.verify(pk, msg, forged)
    assert not eddsa.verify(pk, msg, forged)


def test_small_order_table_matches_derived_torsion():
    """Pin _SMALL_ORDER_Y to the 8-torsion subgroup derived from reference
    arithmetic: a typo'd or missing row fails here, not in production."""
    # Find an order-8 generator: [L]P for any curve point lies in the
    # torsion subgroup; scan deterministic y encodings until one has
    # full order 8, then enumerate its multiples.
    gen = None
    y = 2
    while gen is None:
        pt = ref.decode_point(y.to_bytes(32, "little"))
        y += 1
        if pt is None:
            continue
        t = ref.scalar_mult(ref.L, pt)
        if not ref.pt_equal(ref.scalar_mult(4, t), ref.IDENT):
            gen = t
    derived = set()
    for i in range(8):
        enc = bytearray(ref.encode_point(ref.scalar_mult(i, gen)))
        enc[31] &= 0x7F
        derived.add(bytes(enc))
    assert derived == {bytes(row) for row in eddsa._SMALL_ORDER_Y}


def test_small_order_encodings_rejected_everywhere():
    """All 14 canonical-or-sign-flipped small-order encodings are rejected
    as A and as R, on host prep and in the reference verifier."""
    torsion = []
    for row in eddsa._SMALL_ORDER_Y:
        for sign in (0, 0x80):
            enc = bytearray(bytes(row))
            enc[31] |= sign
            if ref.decode_point(bytes(enc)) is not None:
                torsion.append(bytes(enc))
    assert len(torsion) >= 8
    (msg, pk, sig), = make_sigs(1, seed=7)
    for enc in torsion:
        prep = eddsa.prepare_batch([msg, msg], [enc, pk],
                                   [sig, enc + sig[32:]])
        assert not prep["host_ok"].any(), enc.hex()
        assert not ref.verify(enc, msg, sig)


def test_batch_padding_and_single():
    triples = make_sigs(3, seed=3)
    msgs, pks, sigs = map(list, zip(*triples))
    mask = eddsa.verify_batch(msgs, pks, sigs)  # pads 3 -> 8
    assert mask.all() and mask.shape == (3,)
    assert eddsa.verify(pks[0], msgs[0], sigs[0])
    assert not eddsa.verify(pks[0], msgs[1], sigs[0])


def test_empty_and_wrong_lengths():
    assert eddsa.verify_batch([], [], []).shape == (0,)
    (msg, pk, sig), = make_sigs(1, seed=4)
    assert not eddsa.verify_batch([msg], [pk[:31]], [sig])[0]
    assert not eddsa.verify_batch([msg], [pk], [sig[:63]])[0]


def test_fuzz_device_matches_reference():
    """Randomized agreement: valid sigs, bit flips, random keys."""
    rng = np.random.default_rng(11)
    msgs, pks, sigs, expect = [], [], [], []
    for i in range(12):
        sk = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        msg = rng.bytes(int(rng.integers(0, 64)))
        sig = ref.sign(sk, msg)
        if i % 3 == 1:
            pos = int(rng.integers(0, 64))
            sig = sig[:pos] + bytes([sig[pos] ^ (1 << int(rng.integers(8)))]) + sig[pos + 1:]
        elif i % 3 == 2:
            pk = rng.bytes(32)
        msgs.append(msg); pks.append(pk); sigs.append(sig)
        expect.append(ref.verify(pk, msg, sig))
    mask = eddsa.verify_batch(msgs, pks, sigs)
    assert list(mask) == expect


def test_chunked_batch_over_subbatch_cap():
    """n > MAX_SUBBATCH runs as a chunked-scan single dispatch; the chunk
    count rounds to the next power of two (1500 -> g=2), not the row
    bucket's minimum of 8."""
    n = eddsa.MAX_SUBBATCH + 476
    triples = make_sigs(4, seed=13)
    msgs, pks, sigs = [], [], []
    for i in range(n):
        m, p, s = triples[i % 4]
        msgs.append(m); pks.append(p); sigs.append(s)
    sigs[eddsa.MAX_SUBBATCH + 7] = bytes(64)  # invalid, lands in chunk 2
    mask = eddsa.verify_batch(msgs, pks, sigs)
    assert mask.shape == (n,)
    assert not mask[eddsa.MAX_SUBBATCH + 7]
    assert mask.sum() == n - 1


@pytest.mark.slow  # ~44 s: recompiles the ladder per flag combination
def test_ab_flag_variants_match_reference():
    """Every import-time A/B switch of ops/ed25519.py (ROADMAP D2) must
    produce reference-identical verdicts: a correctness bug in a flagged
    code path would otherwise surface only mid-A/B on a live device."""
    import importlib
    import os

    from hotstuff_tpu.ops import ed25519 as E

    flags = {
        "HOTSTUFF_TPU_STACK_MULS": "0",
        "HOTSTUFF_TPU_ONEHOT_SELECT": "0",
        "HOTSTUFF_TPU_TUPLE_POINTS": "0",
        "HOTSTUFF_TPU_JOINT_DECOMPRESS": "1",
    }
    triples = make_sigs(6, seed=31)
    msgs, pks, sigs = map(list, zip(*triples))
    sigs[2] = sigs[2][:40] + bytes([sigs[2][40] ^ 4]) + sigs[2][41:]
    msgs[4] = b"tampered"
    expect = [ref.verify(pk, m, s) for m, pk, s in zip(msgs, pks, sigs)]
    assert expect == [True, True, False, True, False, True]
    prep = eddsa.prepare_batch(msgs, pks, sigs)
    assert prep["host_ok"].all()

    saved = {k: os.environ.get(k) for k in flags}
    try:
        for flag, default in flags.items():
            os.environ[flag] = "0" if default == "1" else "1"
            E2 = importlib.reload(E)
            got = eddsa.verify_prepared_rows(prep["packed"], len(msgs))
            assert list(got) == expect, f"{flag} variant diverges"
            os.environ[flag] = default
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        importlib.reload(E)
