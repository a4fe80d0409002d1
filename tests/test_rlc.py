"""RLC batch-verification soundness tests.

Contract under test (crypto/eddsa.verify_batch_rlc): the mask it returns
is bit-identical to the per-signature verify_batch on EVERY input —
all-valid batches ride the one-MSM fast path, any failure is resolved
by one per-signature program, so a bad vote is always pinpointed.  Parity
model: the reference's verify_valid_batch / verify_invalid_batch
(crypto/src/tests/crypto_tests.rs) plus the batch-forgery cases a
combined check uniquely has to survive.
"""

import numpy as np
import pytest

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref

RNG = np.random.default_rng(42)


def sig_pool(n, seed=7, msg_len=32):
    """n distinct (msg, pk, sig) triples from the reference signer."""
    r = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        sk = r.bytes(32)
        msg = r.bytes(msg_len)
        _, pk = ref.generate_keypair(sk)
        out.append((msg, pk, ref.sign(sk, msg)))
    return out


POOL = sig_pool(16)


def corrupt_sig(sig: bytes, where: int = 40) -> bytes:
    return sig[:where] + bytes([sig[where] ^ 1]) + sig[where + 1:]


def test_all_valid_batch_passes_fast_path():
    msgs, pks, sigs = map(list, zip(*POOL[:6]))
    mask = eddsa.verify_batch_rlc(msgs, pks, sigs)
    assert mask.all() and len(mask) == 6


def test_each_single_corrupted_index_is_pinpointed():
    """For every index of a 6-vote batch: corrupt exactly that vote; the
    combined check must fail and the resolution must blame exactly it."""
    for bad in range(6):
        msgs, pks, sigs = map(list, zip(*POOL[:6]))
        sigs[bad] = corrupt_sig(sigs[bad])
        mask = eddsa.verify_batch_rlc(msgs, pks, sigs)
        want = [i != bad for i in range(6)]
        assert mask.tolist() == want, f"index {bad}: {mask.tolist()}"


def test_rlc_agrees_with_per_signature_on_200_random_batches():
    """Randomized agreement sweep: batch sizes 1..8 sampled from the
    pool, ~1/4 of batches with one corrupted signature, plus occasional
    garbage keys / non-canonical encodings — the mask must match
    verify_batch exactly on every one."""
    r = np.random.default_rng(1234)
    for trial in range(200):
        n = int(r.integers(1, 9))
        take = r.integers(0, len(POOL), n)
        msgs = [POOL[i][0] for i in take]
        pks = [POOL[i][1] for i in take]
        sigs = [POOL[i][2] for i in take]
        if n and trial % 4 == 0:
            k = int(r.integers(0, n))
            sigs[k] = corrupt_sig(sigs[k], int(r.integers(0, 64)))
        if n and trial % 17 == 0:
            pks[int(r.integers(0, n))] = bytes(r.bytes(32))
        if n and trial % 23 == 0:
            sigs[int(r.integers(0, n))] = b"\xff" * 64  # S >= L
        got = eddsa.verify_batch_rlc(msgs, pks, sigs)
        want = eddsa.verify_batch(msgs, pks, sigs)
        assert got.tolist() == want.tolist(), \
            f"trial {trial}: rlc {got.tolist()} != per-sig {want.tolist()}"


def test_wrong_message_and_swapped_sigs_fail():
    msgs, pks, sigs = map(list, zip(*POOL[:4]))
    msgs[2] = b"not the signed message............"
    got = eddsa.verify_batch_rlc(msgs, pks, sigs)
    assert got.tolist() == [True, True, False, True]
    msgs, pks, sigs = map(list, zip(*POOL[:4]))
    sigs[0], sigs[1] = sigs[1], sigs[0]
    got = eddsa.verify_batch_rlc(msgs, pks, sigs)
    assert got.tolist() == [False, False, True, True]


def test_empty_and_tiny_batches():
    assert eddsa.verify_batch_rlc([], [], []).shape == (0,)
    m, p, s = POOL[0]
    assert eddsa.verify_batch_rlc([m], [p], [s]).tolist() == [True]
    assert eddsa.verify_batch_rlc(
        [m], [p], [corrupt_sig(s)]).tolist() == [False]


def test_coefficients_are_deterministic_nonzero_128bit():
    rows = np.frombuffer(RNG.bytes(8 * 128), np.uint8).reshape(8, 128)
    z1 = eddsa._rlc_coeffs(rows, b"")
    z2 = eddsa._rlc_coeffs(rows, b"")
    assert (z1 == z2).all()                       # deterministic per call
    assert z1.shape == (8, 32)
    assert (z1[:, 16:] == 0).all()                # < 2^128 < L
    assert z1[:, :16].any(axis=1).all()           # never excluded
    # content-keyed: flipping one bit of one row changes coefficients
    rows2 = rows.copy()
    rows2[3, 60] ^= 1
    assert (eddsa._rlc_coeffs(rows2, b"") != z1).any()
    # path-keyed: the mesh's bisection halves draw fresh coefficients
    assert (eddsa._rlc_coeffs(rows, b"L") != z1).any()


def test_msm_matches_reference_scalar_mults():
    """msm_straus against the python-int reference on random points and
    scalars (the raw device primitive, no RLC wrapping)."""
    import jax.numpy as jnp

    from hotstuff_tpu.ops import ed25519 as E, field25519 as F
    from hotstuff_tpu.utils.intmath import L, P

    r = np.random.default_rng(5)
    n = 5  # deliberately not a power of two: exercises identity padding
    pts, scalars = [], []
    arr = np.zeros((n, 4, 32), np.int32)
    for i in range(n):
        k = int.from_bytes(r.bytes(32), "little") % L or 1
        s = int.from_bytes(r.bytes(32), "little") % L
        pt = ref.scalar_mult(k, ref.B)
        zi = pow(pt[2], P - 2, P)
        x, y = pt[0] * zi % P, pt[1] * zi % P
        arr[i, 0] = F.to_limbs(x)
        arr[i, 1] = F.to_limbs(y)
        arr[i, 2] = F.to_limbs(1)
        arr[i, 3] = F.to_limbs(x * y % P)
        pts.append((x, y, 1, x * y % P))
        scalars.append(s)
    digits = E.unpack_nibbles_msb(jnp.asarray(np.stack([
        np.frombuffer(s.to_bytes(32, "little"), np.uint8) for s in
        scalars]).astype(np.int32)))
    out = E.msm_straus(jnp.asarray(arr), digits)
    got = tuple(F.from_limbs(np.asarray(F.canonical(out[c])))
                for c in range(3))
    want = ref.IDENT
    for s, pt in zip(scalars, pts):
        want = ref.pt_add(want, ref.scalar_mult(s, pt))
    assert ref.pt_equal((got[0], got[1], got[2], 0),
                        (want[0], want[1], want[2], 0))


def test_mixed_order_pubkey_agrees_with_per_signature():
    """Torsion-exactness regression: a pubkey A' + T (T of order 8, so A
    passes the host small-order screen) signed honestly with A''s secret
    is accepted by the cofactorless per-signature check iff
    k = H(R||A||M) ≡ 0 (mod 8).  The RLC path must agree on EVERY
    message — before the CRT lift to exponent 8L, reducing z*k mod L
    scrambled the torsion coefficient and a grinding adversary could
    split the two paths in a handful of attempts."""
    import hashlib

    from hotstuff_tpu.utils.intmath import L

    ty = int.from_bytes(eddsa._SMALL_ORDER_Y[3].tobytes(), "little")
    t_pt = ref.decode_point(ty.to_bytes(32, "little"))
    assert ref.is_small_order(t_pt)

    seed = b"\x09" * 32
    h = hashlib.sha512(seed).digest()
    a = ref._clamp(int.from_bytes(h[:32], "little"))
    prefix = h[32:]
    pk = ref.encode_point(ref.pt_add(ref.scalar_mult(a, ref.B), t_pt))

    filler = POOL[:3]
    accepted = rejected = 0
    for trial in range(24):
        msg = b"grind-%d" % trial
        r = ref._h(prefix + msg) % L
        r_enc = ref.encode_point(ref.scalar_mult(r, ref.B))
        k = ref._h(r_enc + pk + msg) % L
        sig = r_enc + ((r + k * a) % L).to_bytes(32, "little")
        msgs = [msg] + [f[0] for f in filler]
        pks = [pk] + [f[1] for f in filler]
        sigs = [sig] + [f[2] for f in filler]
        per = eddsa.verify_batch(msgs, pks, sigs).tolist()
        rlc = eddsa.verify_batch_rlc(msgs, pks, sigs).tolist()
        assert per == rlc, f"trial {trial}: per={per} rlc={rlc}"
        accepted += per[0]
        rejected += not per[0]
    # both branches of the torsion behavior were actually exercised
    # (k ≡ 0 mod 8 happens ~1/8 of the time; 24 tries miss it with
    # probability ~0.04 — seeds above are fixed, so this is stable)
    assert accepted >= 1 and rejected >= 1


def test_torsion_in_r_rejected_by_both_paths():
    m, pk, sig = POOL[0]
    ty = int.from_bytes(eddsa._SMALL_ORDER_Y[3].tobytes(), "little")
    t_pt = ref.decode_point(ty.to_bytes(32, "little"))
    r_mix = ref.pt_add(ref.decode_point(sig[:32]), t_pt)
    sig2 = ref.encode_point(r_mix) + sig[32:]
    assert eddsa.verify_batch([m], [pk], [sig2]).tolist() == [False]
    assert eddsa.verify_batch_rlc([m], [pk], [sig2]).tolist() == [False]


def _mixed_order_vote(trial: int):
    """The vector of test_mixed_order_pubkey_agrees_with_per_signature:
    (msg, pk, sig, k) with pk = A' + T, T of order 8, signed with A''s
    secret — valid for the cofactorless check iff k = 0 (mod 8)."""
    import hashlib

    from hotstuff_tpu.utils.intmath import L

    ty = int.from_bytes(eddsa._SMALL_ORDER_Y[3].tobytes(), "little")
    t_pt = ref.decode_point(ty.to_bytes(32, "little"))
    h = hashlib.sha512(b"\x09" * 32).digest()
    a = ref._clamp(int.from_bytes(h[:32], "little"))
    pk = ref.encode_point(ref.pt_add(ref.scalar_mult(a, ref.B), t_pt))
    msg = b"grind-%d" % trial
    r = ref._h(h[32:] + msg) % L
    r_enc = ref.encode_point(ref.scalar_mult(r, ref.B))
    k = ref._h(r_enc + pk + msg) % L
    return msg, pk, r_enc + ((r + k * a) % L).to_bytes(32, "little"), k


@pytest.mark.parametrize("program", [
    # ~70 s of XLA:CPU compile each; the mesh program holds all that the
    # single-device one does, so that one waits in the slow lane.
    pytest.param("verify_rlc_packed", marks=pytest.mark.slow),
    "sharded"])
def test_rlc_tail_kernel_keeps_the_torsion_verdicts(monkeypatch, program):
    """The combined check with its serial tail as the rlc_tail KERNEL
    (the route a TPU takes; here through the Pallas interpreter) gives
    the verdicts of the lax tail on the torsion vectors above — on one
    device, and replicated over a two-device mesh.  The route is steered
    HERE: ops/ed25519.rlc_tail reads kern.interpret_default at trace
    time, the kernel's own module keeps its copy (-> interpreter)."""
    import jax

    from hotstuff_tpu.ops import ed25519 as E, kern
    from hotstuff_tpu.parallel.mesh import make_mesh
    from hotstuff_tpu.parallel.sharded_verify import make_sharded_rlc_verifier

    votes = {}
    for trial in range(24):                 # one k = 0 (mod 8), one not
        vote = _mixed_order_vote(trial)
        votes.setdefault(vote[3] % 8 == 0, vote[:3])
    m, pk, sig = POOL[0]
    ty = int.from_bytes(eddsa._SMALL_ORDER_Y[3].tobytes(), "little")
    r_mix = ref.pt_add(ref.decode_point(sig[:32]),
                       ref.decode_point(ty.to_bytes(32, "little")))
    batches = {
        "all valid": (POOL[:4], True),
        "mixed-order key, k = 0 mod 8": ([votes[True]] + POOL[:3], True),
        "mixed-order key, k != 0 mod 8": ([votes[False]] + POOL[:3], False),
        "torsion in R": ([(m, pk, ref.encode_point(r_mix) + sig[32:])]
                         + POOL[1:4], False),
    }

    def inputs(batch):
        prep = eddsa.prepare_batch(*map(list, zip(*batch)))
        assert prep["host_ok"].all()
        rows = np.pad(prep["packed"], [(0, 8 - len(batch)), (0, 0)])
        z = np.zeros((8, 32), np.uint8)
        z[:len(batch)] = eddsa._rlc_coeffs(prep["packed"], b"")
        return rows, z

    before = {name: bool(E.verify_rlc_packed_jit(*inputs(batch)))
              for name, (batch, _) in batches.items()}
    assert before == {name: want for name, (_, want) in batches.items()}

    monkeypatch.setattr(kern, "interpret_default", lambda: False)
    # Fresh function objects: jit caches traces by function, and the
    # route is read at trace time.
    verifier = make_sharded_rlc_verifier(make_mesh(2)) \
        if program == "sharded" \
        else jax.jit(lambda p, z: E.verify_rlc_packed(p, z))
    for name, (batch, _) in batches.items():
        assert bool(verifier(*inputs(batch))) == before[name], name


@pytest.mark.slow
def test_rlc_at_quorum_256_matches_and_is_measured():
    """The n=256 MSM bench shape: one combined check over a full large
    quorum, valid and with one corrupted vote (slow lane: this compiles
    the bucket-256 MSM program)."""
    pool = sig_pool(256, seed=99)
    msgs, pks, sigs = map(list, zip(*pool))
    assert eddsa.verify_batch_rlc(msgs, pks, sigs).all()
    sigs[137] = corrupt_sig(sigs[137])
    mask = eddsa.verify_batch_rlc(msgs, pks, sigs)
    assert not mask[137] and mask.sum() == 255
