"""Ed25519 batch verification API: host-side preparation + TPU execution.

This is the framework's equivalent of the reference's signature API surface
(crypto/src/lib.rs:177-224): ``verify`` / ``verify_batch`` — except batch
verification returns a *per-signature validity mask* computed on device,
which is what quorum-certificate verification wants
(consensus/src/messages.rs:180-198 rejects a QC when any vote fails).

Host responsibilities (cheap, byte-oriented): SHA-512 challenge hashing,
encoding canonicality checks (y < p, S < L), limb/bit unpacking into dense
arrays.  Device responsibilities (the FLOPs): point decompression, the
fixed-base comb + windowed variable-base ladder (ops/ed25519.py), batched
across the whole quorum.

Batch shapes are padded to power-of-two buckets so XLA compiles a handful of
program shapes, then results are sliced back.
"""

from __future__ import annotations

import hashlib

import jax.numpy as jnp
import numpy as np

from ..obs.spans import NO_LAUNCH
from ..ops import ed25519 as E
from ..utils.intmath import next_pow2  # noqa: F401  (re-export: THE
# bucketing rule — sharded_verify and the sidecar import it from here)

P = E.P
L = E.L

_MIN_BUCKET = 8


def _bucket(n: int) -> int:
    return next_pow2(n, _MIN_BUCKET)


_L_BYTES = np.frombuffer(L.to_bytes(32, "little"), np.uint8).astype(np.int16)


def _ge_p(y_bytes: np.ndarray) -> np.ndarray:
    """(B, 32) u8 little-endian values with bit 255 cleared: rows >= p."""
    return ((y_bytes[:, 31] == 0x7F)
            & (y_bytes[:, 1:31] == 0xFF).all(axis=1)
            & (y_bytes[:, 0] >= 0xED))


def _lt_L(s_bytes: np.ndarray) -> np.ndarray:
    """(B, 32) u8 little-endian scalars: rows < L (vectorized lex compare)."""
    diff = s_bytes[:, ::-1].astype(np.int16) - _L_BYTES[::-1]
    nonzero = diff != 0
    first = np.argmax(nonzero, axis=1)
    lead = diff[np.arange(len(diff)), first]
    return nonzero.any(axis=1) & (lead < 0)


# The eight small-order (8-torsion) points have five distinct y values, and
# the set is closed under negation — so comparing the sign-cleared y against
# this table is an exact small-order test for canonically-encoded points
# (non-canonical y >= p is rejected separately by _ge_p).  dalek's
# verify_strict rejects small-order A and R (crypto/src/lib.rs:204-208);
# without the check, pk = identity encoding plus sig = ([S]B || S) verifies
# ANY message, a universal forgery that breaks vote attribution.
_SMALL_ORDER_Y = np.frombuffer(b"".join(
    y.to_bytes(32, "little")
    for y in (
        0,       # order-4 pair (x = +-sqrt(-1))
        1,       # identity
        P - 1,   # (0, -1), order 2
        # order-8 pairs: y8 and p - y8
        0x7A03AC9277FDC74EC6CC392CFA53202A0F67100D760B3CBA4FD84D3D706A17C7,
        0x05FC536D880238B13933C6D305ACDFD5F098EFF289F4C345B027B2C28F95E826,
    )), np.uint8).reshape(5, 32)


def _small_order(y_bytes: np.ndarray) -> np.ndarray:
    """(B, 32) u8 sign-cleared y encodings: rows that are 8-torsion."""
    return (y_bytes[:, None, :] == _SMALL_ORDER_Y[None]).all(-1).any(-1)


def prepare_batch(msgs, pks, sigs):
    """Lists of (msg bytes, pk 32B, sig 64B) -> dict of device-ready arrays.

    Returns compact uint8 arrays — a (B,32), r (B,32), s (B,32), k (B,32) —
    plus the host_ok canonicality mask. 130 B/signature is all that crosses
    the host->device boundary; limb/bit expansion happens on device
    (ops/ed25519.verify_compact), which matters wherever the transfer,
    not the ladder, bounds throughput (the chip's host->device share:
    not measured). The per-signature SHA-512
    challenge hash is the only non-vectorized host work.
    """
    n = len(msgs)
    assert len(pks) == n and len(sigs) == n
    if all(len(pk) == 32 for pk in pks) and all(len(s) == 64 for s in sigs):
        # Common case: two bulk copies instead of 2n per-row frombuffers
        # (the per-row path costs ~2 us/sig of pure python overhead).
        pk_arr = np.frombuffer(b"".join(pks), np.uint8).reshape(n, 32).copy()
        sig_arr = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64).copy()
        len_ok = np.ones((n,), bool)
    else:
        pk_arr = np.zeros((n, 32), np.uint8)
        sig_arr = np.zeros((n, 64), np.uint8)
        len_ok = np.zeros((n,), bool)
        for i, (pk, sig) in enumerate(zip(pks, sigs)):
            if len(pk) == 32 and len(sig) == 64:
                pk_arr[i] = np.frombuffer(pk, np.uint8)
                sig_arr[i] = np.frombuffer(sig, np.uint8)
                len_ok[i] = True

    ay_b = pk_arr.copy()
    ay_b[:, 31] &= 0x7F
    ry_b = sig_arr[:, :32].copy()
    ry_b[:, 31] &= 0x7F
    s_bytes = np.ascontiguousarray(sig_arr[:, 32:])
    host_ok = (len_ok & ~_ge_p(ay_b) & ~_ge_p(ry_b) & _lt_L(s_bytes)
               & ~_small_order(ay_b) & ~_small_order(ry_b))

    # challenge scalars k = SHA512(R||A||M) mod L (host hashing, C-speed).
    # One contiguous bytearray + a single frombuffer at the end: per-row
    # numpy assignments dominated this loop before (~2 us/sig of pure
    # overhead at N=1024).
    k_buf = bytearray(32 * n)
    sig_rows, pk_rows = sig_arr.tobytes(), pk_arr.tobytes()
    for i in np.nonzero(host_ok)[0]:
        h = hashlib.sha512(sig_rows[64 * i:64 * i + 32]
                           + pk_rows[32 * i:32 * i + 32] + msgs[i]).digest()
        k = int.from_bytes(h, "little") % L
        k_buf[32 * i:32 * i + 32] = k.to_bytes(32, "little")
    k_bytes = np.frombuffer(bytes(k_buf), np.uint8).reshape(n, 32)

    # One allocation; a/r/s/k are views into it (the sharded path slices,
    # the single-device path ships the whole row).
    packed = np.concatenate(
        [pk_arr, sig_arr[:, :32], s_bytes, k_bytes], axis=1)
    return dict(a=packed[:, 0:32], r=packed[:, 32:64], s=packed[:, 64:96],
                k=packed[:, 96:128], packed=packed, host_ok=host_ok)


def split_packed_rows(packed: np.ndarray, host_ok=None) -> dict:
    """(n, 128) already-prepared rows -> the prepare_batch dict shape,
    without re-deriving anything.  The mesh's RLC bisection slices prepared
    rows by index and re-enter the batch verifiers with them; rows
    selected through a host_ok mask are canonical by construction, so the
    default mask is all-True."""
    n = packed.shape[0]
    if host_ok is None:
        host_ok = np.ones((n,), bool)
    return dict(a=packed[:, 0:32], r=packed[:, 32:64], s=packed[:, 64:96],
                k=packed[:, 96:128], packed=packed, host_ok=host_ok)


# Per-program sub-batch cap (scripts/eval_device.py is the A/B; its
# pre-PR-1 result is not reproducible, the value is not re-measured on
# the chip): larger batches run as sub-batches of this size scanned
# inside ONE dispatch (ops/ed25519.verify_packed_chunked), which
# amortizes the fixed per-dispatch cost while keeping every conv's
# group count at a size XLA handles well.
MAX_SUBBATCH = 1024


def verify_batch(msgs, pks, sigs, *, pad: bool = True) -> np.ndarray:
    """Batch Ed25519 verify on the default JAX device -> (N,) bool mask.

    TPU analogue of ``Signature::verify_batch``
    (reference: crypto/src/lib.rs:210-223), with per-signature results.
    Any batch size works: n <= 1024 pads to a power-of-two bucket and runs
    one plain program; larger n runs as ceil(n/1024) sub-batches inside a
    single chunked-scan dispatch.
    """
    return verify_batch_submit(msgs, pks, sigs, pad=pad)()


def verify_batch_submit(msgs, pks, sigs, *, pad: bool = True):
    """Dispatch a batch verify WITHOUT fetching the result.

    Returns a zero-argument ``fetch`` callable producing the (N,) bool
    mask.  Dispatch is asynchronous on the device, so the caller can
    submit the next batch (or do host work) while this one executes —
    the fixed per-dispatch cost (not measured on the chip) otherwise
    serializes every launch behind the previous launch's result fetch.
    """
    return verify_batch_pack(msgs, pks, sigs, pad=pad)()


def verify_batch_pack(msgs, pks, sigs, *, pad: bool = True,
                      trace=NO_LAUNCH):
    """Pack stage of a batch verify: ALL host-side work — byte decode,
    canonicality checks, SHA-512 challenges, bucket padding and the
    h2d transfer — happens here, on the caller's thread.  The returned
    ``dispatch()`` fires the donated device program (cheap — the input
    already lives on device) and returns ``fetch() -> (N,) bool mask``.

    This is the three-stage split the sidecar engine's double-buffered
    pipeline needs: its pack thread stages launch N+1 (this function)
    while launch N executes, and the engine thread only ever pays the
    dispatch + fetch cost.  ``verify_batch_submit`` is the two-stage
    wrapper (pack + dispatch in one call) for callers without a pack
    thread.

    ``trace`` is the engine's tracer bound to this launch
    (``obs.spans.LaunchScope``; default the null scope): the staging and
    fetch steps below write their ``h2d`` / ``fetch_wait`` / ``d2h``
    spans through it.
    """
    n = len(msgs)
    if n == 0:
        return lambda: (lambda: np.zeros((0,), bool))
    prep = prepare_batch(msgs, pks, sigs)
    host_ok = prep["host_ok"]
    dispatch_rows = _pack_rows(prep["packed"], n, pad, trace)

    def dispatch():
        fetch_rows = dispatch_rows()
        return lambda: fetch_rows() & host_ok

    return dispatch


def _fetch(dev, trace) -> np.ndarray:
    """``np.asarray(dev)``; on a traced launch as two spans:
    ``fetch_wait`` until the result is ready (the wait ``np.asarray``
    would block on anyway) and ``d2h`` for the copy itself."""
    if not trace.enabled:
        return np.asarray(dev)
    with trace.stage("fetch_wait"):
        dev.block_until_ready()
    with trace.stage("d2h") as tags:
        out = np.asarray(dev)
        tags["bytes"] = out.nbytes
    return out


def _pack_rows(packed: np.ndarray, n: int, pad: bool, trace=NO_LAUNCH):
    """(n, 128) prepared rows -> staged device input; returns
    dispatch() -> fetch() -> (n,) bool mask.  Single home of the
    bucket/pad/chunk policy shared by the eager, submit and pack paths.
    The h2d transfer happens HERE (pack stage); the donated program
    launch happens inside dispatch().  The ``h2d`` span is the host time
    of the staging call, not the wire time of the copy."""
    # The launches below DONATE their input buffer; forcing host-side
    # rows here guarantees each jnp.asarray is a fresh device copy, so a
    # caller's (possibly device-resident) array is never invalidated.
    packed = np.asarray(packed)
    if n <= MAX_SUBBATCH:
        m = _bucket(n) if pad else n
        if m != n:
            packed = np.pad(packed, [(0, m - n), (0, 0)])
        with trace.stage("h2d") as tags:
            dev_in = jnp.asarray(packed)
            if tags is not None:
                tags["bytes"] = packed.nbytes

        def dispatch():
            dev = E.verify_packed_donated(dev_in)
            return lambda: _fetch(dev, trace)[:n]

        return dispatch
    g = -(-n // MAX_SUBBATCH)
    if pad:  # bound the number of compiled scan lengths: next power of two
        g = next_pow2(g)
    m = g * MAX_SUBBATCH
    if m != n:
        packed = np.pad(packed, [(0, m - n), (0, 0)])
    with trace.stage("h2d") as tags:
        dev_in = jnp.asarray(packed.reshape(g, MAX_SUBBATCH, 128))
        if tags is not None:
            tags["bytes"] = packed.nbytes

    def dispatch():
        dev = E.verify_packed_chunked_donated(dev_in)
        return lambda: _fetch(dev, trace).reshape(m)[:n]

    return dispatch


def _dispatch_rows(packed: np.ndarray, n: int, pad: bool):
    """Two-stage form of :func:`_pack_rows` (pack + dispatch in one
    call); returns fetch() -> (n,) bool mask."""
    return _pack_rows(packed, n, pad)()


def verify_prepared_rows(packed: np.ndarray, n: int, *,
                         pad: bool = True) -> np.ndarray:
    """(n, 128) prepared rows -> (n,) device mask (no host_ok fold)."""
    return _dispatch_rows(packed, n, pad)()


def verify(pk: bytes, msg: bytes, sig: bytes) -> bool:
    """Single-signature verify routed through the device path."""
    return bool(verify_batch([msg], [pk], [sig])[0])


# ---------------------------------------------------------------------------
# Random-linear-combination batch verification (one MSM per quorum)
# ---------------------------------------------------------------------------

# Below this the per-signature program is cheaper than the MSM's fixed
# Horner/comb tail: a batch with fewer canonical rows goes per signature
# from the start.  The mesh (parallel/sharded_verify) also stops its
# bisection here.
RLC_MIN_MSM = 4

_RLC_DOMAIN = b"hotstuff-tpu/rlc-batch-v1"


def _rlc_coeffs(rows: np.ndarray, salt: bytes) -> np.ndarray:
    """(n, 128) prepared rows -> (n, 32) uint8 coefficient rows: 128-bit
    nonzero z_i in canonical little-endian bytes (high 16 bytes zero).

    Deterministic per call: a SHA-512 counter-mode PRF seeded by the
    batch CONTENT (all rows), ``salt`` (empty here; the mesh's bisection
    path, parallel/sharded_verify) and a domain tag.  Soundness needs
    the z_i to be unpredictable to whoever chose the signatures *before*
    the batch was formed — hashing every row into the seed gives the
    standard derandomized batch-verification argument:
    changing any bit of any signature re-randomizes every coefficient.
    128-bit coefficients put an adversarial cancellation at ~2^-128, the
    scheme's security level; anything shorter would make the combined
    check the weakest link (see ops/ed25519 module notes).
    """
    n = rows.shape[0]
    seed = hashlib.sha512(_RLC_DOMAIN + salt + rows.tobytes()).digest()
    blocks = -(-n // 4)  # 4 x 16-byte coefficients per SHA-512 block
    stream = b"".join(
        hashlib.sha512(seed + i.to_bytes(4, "little")).digest()
        for i in range(blocks))
    z = np.zeros((n, 32), np.uint8)
    z[:, :16] = np.frombuffer(stream, np.uint8)[:16 * n].reshape(n, 16)
    # An all-zero row (p = 2^-128) would EXCLUDE the signature from the
    # combined check; force its low byte to 1 (still deterministic).
    dead = ~z.any(axis=1)
    z[dead, 0] = 1
    return z


def verify_batch_rlc(msgs, pks, sigs, *, pad: bool = True) -> np.ndarray:
    """Batch Ed25519 verify via the random-linear-combination check ->
    (N,) bool mask, bit-identical to :func:`verify_batch`.

    Fast path: ONE device dispatch checks the combined equation
    [sum z_i S_i]B == sum [z_i]R_i + sum [z_i k_i]A_i over the whole
    batch (ops/ed25519.verify_rlc_packed).  All-valid batches — the
    steady state of quorum-certificate verification — pay one MSM
    instead of 2n scalar ladders.  When the combined check fails, ONE
    per-signature program over the batch's canonical rows pinpoints each
    bad vote — so the returned mask always matches verify_batch exactly,
    valid or not.  An adversary can never make us accept a bad vote (up
    to the 2^-128 RLC bound), but can make us pay MORE than the
    per-signature price: a forged batch costs the failed combined check
    AND the per-signature launch, two round trips however many votes
    are forged (PERF.md §5, the cell ``qc100f33.byz``).

    Batches beyond MAX_SUBBATCH fall back to the per-signature chunked
    path (the MSM's conv group count scales with batch, and quorums that
    size should shard across the mesh instead —
    parallel/sharded_verify.verify_rlc_sharded).
    """
    return verify_batch_rlc_submit(msgs, pks, sigs, pad=pad)()


def verify_batch_rlc_submit(msgs, pks, sigs, *, pad: bool = True,
                            on_bisect=None, on_resolved=None):
    """Dispatch the combined RLC check WITHOUT fetching its verdict.

    Returns a zero-argument ``fetch`` producing the (N,) bool mask
    (bit-identical to :func:`verify_batch`), so the sidecar engine can
    pipeline the next launch behind this one exactly like
    :func:`verify_batch_submit`.  The all-valid steady state stays fully
    asynchronous (one dispatched MSM, verdict read at fetch); only a
    failed combined check resolves synchronously inside ``fetch`` — the
    adversarial slow path: one per-signature program over the canonical
    rows, run to its verdicts on the fetching thread.
    ``on_bisect`` (if given) is invoked once when that happens, and
    ``on_resolved(programs, rows_per_sig, bad_rows)`` once when the mask
    is complete (``programs`` 1, ``rows_per_sig`` the canonical rows) —
    how the scheduler's telemetry counts ``rlc_bisect`` launches and the
    ``bisect`` totals without the crypto layer importing it.

    Host-canonicality failures and degenerate sizes (fewer than
    RLC_MIN_MSM canonical rows, or more than MAX_SUBBATCH) dispatch the
    per-signature program instead — same contract, same mask.
    """
    return verify_batch_rlc_pack(msgs, pks, sigs, pad=pad,
                                 on_bisect=on_bisect,
                                 on_resolved=on_resolved)()


def verify_batch_rlc_pack(msgs, pks, sigs, *, pad: bool = True,
                          on_bisect=None, on_resolved=None,
                          trace=NO_LAUNCH):
    """Pack stage of the combined RLC check: host preparation, the
    coefficient PRF, bucket padding and the h2d transfers happen here;
    the returned ``dispatch()`` fires the donated one-MSM program and
    returns the ``fetch`` described on :func:`verify_batch_rlc_submit`
    (which is this function's two-stage wrapper).  ``trace`` as on
    :func:`verify_batch_pack`; a failed combined check adds one
    ``bisect`` span around the whole resolution (tags ``launches``,
    ``bad``, ``n``) and, under it, ONE ``bisect_step`` span, from before
    the canonical rows are staged to their verdicts on the host (tags
    ``n``, ``route`` ``per_sig``, ``bucket``, ``ok``, ``depth`` 0)."""
    n = len(msgs)
    if n == 0:
        return lambda: (lambda: np.zeros((0,), bool))
    prep = prepare_batch(msgs, pks, sigs)
    packed = prep["packed"]
    idx = np.nonzero(prep["host_ok"])[0]
    m = len(idx)
    if m < RLC_MIN_MSM or m > MAX_SUBBATCH:
        rows = np.ascontiguousarray(packed[idx])
        dispatch_rows = _pack_rows(rows, m, pad, trace) if m else None

        def dispatch_degenerate():
            fetch_rows = dispatch_rows() if dispatch_rows else None

            def fetch_degenerate():
                mask = np.zeros(n, bool)
                if fetch_rows is not None:
                    mask[idx] = fetch_rows()
                return mask

            return fetch_degenerate

        return dispatch_degenerate
    rows = np.ascontiguousarray(packed[idx])
    bucket = _bucket(m) if pad else m
    z = np.zeros((bucket, 32), np.uint8)
    z[:m] = _rlc_coeffs(rows, b"")
    if bucket != m:
        rows = np.pad(rows, [(0, bucket - m), (0, 0)])
    # Fresh host arrays -> fresh device buffers; the launch donates arg 0
    # (same discipline as _pack_rows).
    with trace.stage("h2d") as tags:
        dev_rows, dev_z = jnp.asarray(rows), jnp.asarray(z)
        if tags is not None:
            tags["bytes"] = rows.nbytes + z.nbytes

    def dispatch():
        dev = E.verify_rlc_packed_donated(dev_rows, dev_z)

        def fetch():
            mask = np.zeros(n, bool)
            if bool(_fetch(dev, trace)):
                mask[idx] = True
                return mask
            if on_bisect is not None:
                on_bisect()
            bisect = trace.stage("bisect")
            with bisect as tags:
                # ONE per-signature program over the canonical rows names
                # every bad vote in one round trip, whatever their number.
                with trace.stage("bisect_step", bisect.id) as step:
                    verdicts = verify_prepared_rows(rows[:m], m, pad=pad)
                    if step is not None:
                        step.update(n=m, route="per_sig", depth=0,
                                    bucket=bucket, ok=bool(verdicts.all()))
                mask[idx] = verdicts
                bad = m - int(np.count_nonzero(verdicts))
                if tags is not None:
                    tags.update(launches=1, bad=bad, n=m)
            if on_resolved is not None:
                on_resolved(1, m, bad)
            return mask

        return fetch

    return dispatch
