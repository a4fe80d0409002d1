"""Multi-chip Ed25519 quorum verification: shard_map over the batch axis with
a psum-reduced validity count over ICI.

This is the TPU-native answer to the reference's single-threaded
``Signature::verify_batch`` call inside ``QC::verify``
(crypto/src/lib.rs:210-223, consensus/src/messages.rs:180-198): for large
committees the 2f+1 votes of a quorum certificate are data-parallel across
chips; each chip verifies its shard of votes and the chips agree on the QC
verdict via an integer ``psum`` of failure counts (one scalar over ICI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

try:  # jax >= 0.6: top-level export, replication checking via check_vma
    from jax import shard_map
    _SHARD_MAP_KW = {"check_vma": False}
except ImportError:  # jax 0.4.x: experimental module, check_rep kwarg
    from jax.experimental.shard_map import shard_map
    _SHARD_MAP_KW = {"check_rep": False}
from jax.sharding import Mesh, PartitionSpec as Pspec

from ..crypto.eddsa import MAX_SUBBATCH, RLC_MIN_MSM, _rlc_coeffs, next_pow2
from ..ops import ed25519 as E
from ..ops import scalar25519  # noqa: F401  (re-export surface for tests)
from .mesh import BATCH_AXIS
from .shard_shapes import (mesh_chunk_count,  # noqa: F401
                           shard_aligned_rows, shard_bucket)
# (shard_bucket / mesh_chunk_count re-exported: the scheduler's shape
# registry and tests read per-shard buckets and scan chunk counts from
# the same module that launches them)


def _make_shard_body(max_subbatch: int):
    def _shard_body(a, r, s, k, present):
        """present: (B,) int32 — 1 for a real, host-canonical vote; 0 for
        batch padding or votes already rejected on host (non-canonical
        encodings)."""
        bs = a.shape[0]
        if bs > max_subbatch:
            # Per-shard chunked scan, same shape discipline as the
            # single-chip bulk path (ops/ed25519.verify_packed_chunked):
            # every conv stays at <= max_subbatch groups while the whole
            # shard shares one program. Caller pads so bs divides evenly.
            g = bs // max_subbatch

            def body(_, xs):
                aa, rr, ss, kk = xs
                return None, E.verify_compact(aa, rr, ss, kk)

            _, masks = jax.lax.scan(
                body, None,
                tuple(x.reshape(g, max_subbatch, *x.shape[1:])
                      for x in (a, r, s, k)))
            mask = masks.reshape(bs)
        else:
            mask = E.verify_compact(a, r, s, k)
        mask = mask & (present > 0)
        # QC verdict: count of present-but-invalid votes, psum over ICI.
        bad = jnp.sum((present > 0) & ~mask).astype(jnp.int32)
        bad_total = jax.lax.psum(bad, BATCH_AXIS)
        return mask, bad_total
    return _shard_body


def make_sharded_verifier(mesh: Mesh, max_subbatch: int = MAX_SUBBATCH,
                          donate: bool = False):
    """Returns jitted fn over compact byte arrays + present mask (global
    batch B, B % n_devices == 0; shards larger than max_subbatch must
    divide into max_subbatch chunks) -> ((B,) bool mask, () int32 invalid
    vote count).

    Note: ``bad_total`` counts votes with present=1 whose signature fails on
    device; host-side encoding rejections must be folded into ``present`` by
    the caller (verify_batch_sharded does).

    ``donate=True`` donates every input buffer (the engine's production
    launch shape: each per-shard buffer is transferred once at pack time
    and consumed once at dispatch); unsupported on the CPU test backend,
    where the caller gets the plain jit instead (see _cached_*_donated).
    """
    batched = Pspec(BATCH_AXIS)
    # Replication checking off (_SHARD_MAP_KW): the ladder scans carry
    # broadcast constants (identity point, exponent accumulators) that
    # VMA/rep tracking would flag as unvarying vs the varying body
    # outputs; the checking adds nothing here.
    fn = shard_map(
        _make_shard_body(max_subbatch),
        mesh=mesh,
        in_specs=(batched,) * 5,
        out_specs=(batched, Pspec()),
        **_SHARD_MAP_KW,
    )
    if donate:
        return jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4))
    return jax.jit(fn)


@functools.cache
def _cached_verifier(mesh: Mesh, max_subbatch: int = MAX_SUBBATCH):
    return make_sharded_verifier(mesh, max_subbatch)


@functools.cache
def _cached_verifier_donated(mesh: Mesh, max_subbatch: int = MAX_SUBBATCH):
    # Donation is unimplemented on CPU (a warning per launch, nothing
    # else) — share the plain jit there so the test suite compiles each
    # mesh shape once, not twice.
    if jax.default_backend() == "cpu":
        return _cached_verifier(mesh, max_subbatch)
    return make_sharded_verifier(mesh, max_subbatch, donate=True)


def _shard_put(mesh: Mesh, arr: np.ndarray):
    """Host array -> committed device array sharded over the batch axis.
    This is the pack-stage h2d transfer: it runs on the engine's pack
    thread, overlapping the device compute of the launch in flight."""
    from jax.sharding import NamedSharding

    return jax.device_put(arr, NamedSharding(mesh, Pspec(BATCH_AXIS)))


def _pack_sharded_arrays(mesh: Mesh, prep: dict, m: int):
    """Pad the five per-record arrays to the shard-aligned row count and
    ship them to the mesh (pack-stage work: byte padding + h2d)."""
    n = prep["a"].shape[0]
    arrays = dict(prep)
    arrays["present"] = prep["host_ok"].astype(np.int32)
    out = []
    for key in ("a", "r", "s", "k", "present"):
        a = arrays[key]
        if m != n:
            a = np.pad(a, [(0, m - n)] + [(0, 0)] * (a.ndim - 1))
        out.append(_shard_put(mesh, a))
    return out


def verify_batch_sharded_pack(mesh: Mesh, prep: dict, *,
                              max_subbatch: int = MAX_SUBBATCH):
    """Pack stage of a sharded per-signature verify launch.

    Host work (shard-aligned padding + the h2d transfer of every
    per-shard buffer) happens HERE, on the caller's thread; the returned
    ``dispatch()`` fires the donated mesh program and returns
    ``fetch() -> (N,) bool mask`` — the three-stage split the sidecar
    engine's double-buffered pipeline rides (pack launch N+1 while
    launch N executes).  The per-shard row count comes from THE
    shard-alignment rule (parallel/shard_shapes): the padded bucket
    always divides evenly across the mesh, so every launch lands on a
    shape the warmup compiled.
    """
    n = prep["a"].shape[0]
    n_dev = mesh.devices.size
    m = shard_aligned_rows(n, n_dev, max_subbatch)
    dev = _pack_sharded_arrays(mesh, prep, m)

    def dispatch():
        mask_dev, _bad = _cached_verifier_donated(
            mesh, max_subbatch)(*dev)

        def fetch():
            return np.asarray(mask_dev)[:n]

        return fetch

    return dispatch


def ring_slot_pack(mesh: Mesh, prep: dict, rows: int, *,
                   max_subbatch: int = MAX_SUBBATCH):
    """graftcadence: arm ONE cadence-ring slot with this batch at the
    ring's FIXED shard-aligned row count.

    Same ``dispatch() -> fetch()`` contract (and the same donated mesh
    program, hence bit-identical masks) as
    :func:`verify_batch_sharded_pack`, with one difference: the padded
    row count is pinned to ``rows`` — the ring's per-tick quota bucket,
    a shape the warmup compiled — instead of the batch's own bucket.
    Every cadence tick therefore re-dispatches the SAME resident
    compiled program regardless of how full the tick was (partially-
    filled ticks are pad-filled from the bulk backlog upstream; what
    remains is dead rows with ``present = 0``), which is the
    fixed-shape ring discipline: never a fresh compile mid-run.

    "Pre-donated" means the SHAPES are resident, not the bytes:
    donation consumes a buffer per dispatch, so each generation's
    transfer happens at arm time on the pack thread — overlapping the
    in-flight generations' device compute exactly like the staged
    pipeline's h2d — into buffers of the one ring shape.  A batch
    larger than ``rows`` (defensive; the scheduler's tick quota caps
    the coalesce) falls back to its own shard-aligned bucket."""
    n = prep["a"].shape[0]
    n_dev = mesh.devices.size
    m = max(int(rows), shard_aligned_rows(n, n_dev, max_subbatch))
    dev = _pack_sharded_arrays(mesh, prep, m)

    def dispatch():
        mask_dev, _bad = _cached_verifier_donated(
            mesh, max_subbatch)(*dev)

        def fetch():
            return np.asarray(mask_dev)[:n]

        return fetch

    return dispatch


def verify_batch_sharded(mesh: Mesh, prep: dict, *, return_bad_total=False,
                         max_subbatch: int = MAX_SUBBATCH):
    """Run a host-prepared batch (see crypto/eddsa.prepare_batch) across the
    mesh.  Pads the batch so every shard gets the same power-of-two row
    count (shard_shapes.shard_aligned_rows — the sidecar pre-compiles
    exactly those shapes, so any other per-shard size, e.g. 3000 sigs on
    8 devices -> 375-row shards, would hit a first-time XLA compile on
    the engine thread mid-traffic); padding and host-rejected votes are
    excluded from the device-side verdict count."""
    n = prep["a"].shape[0]
    n_dev = mesh.devices.size
    m = shard_aligned_rows(n, n_dev, max_subbatch)
    out = _pack_sharded_arrays(mesh, prep, m)
    mask, bad_total = _cached_verifier(mesh, max_subbatch)(*out)
    mask = np.asarray(mask)[:n]
    if return_bad_total:
        return mask, int(bad_total)
    return mask


# ---------------------------------------------------------------------------
# Whole-backlog chunked mesh scan (graftscale): ONE compiled program that
# drains a bulk backlog across the mesh
# ---------------------------------------------------------------------------
#
# The mesh analogue of ops/ed25519.verify_packed_chunked: each shard
# scans g chunks of ``rows`` packed rows inside one program (a dispatch
# has a fixed cost — not measured on the chip — so a backlog sliced
# into per-launch_cap ladder launches pays it per slice, the scan once
# for the whole backlog), with the per-shard
# validity counts psum-reduced over ICI like the per-signature path.
# The (g, rows) shape comes from THE shard-alignment rule
# (shard_shapes.mesh_chunk_count over the warmup's top per-shard
# bucket), so every launchable scan length is a shape the
# ``--warm-rlc-sharded`` warmup compiled and the scheduler's registry
# marked (ShapeRegistry.mesh_chunks) — an unwarmed scan length never
# dispatches; the engine falls back to the sliced ladder path instead.


def _make_chunk_scan_body(g: int, rows: int):
    def _chunk_body(packed, present):
        """packed: (g*rows, 128) uint8 rows of A || R || S || k per
        shard; present: (g*rows,) int32 — 1 for a real, host-canonical
        record; 0 for padding or host-rejected rows."""
        def body(_, chunk):
            return None, E.verify_packed(chunk)

        _, masks = jax.lax.scan(body, None,
                                packed.reshape(g, rows, 128))
        mask = masks.reshape(g * rows) & (present > 0)
        bad = jnp.sum((present > 0) & ~mask).astype(jnp.int32)
        return mask, jax.lax.psum(bad, BATCH_AXIS)
    return _chunk_body


def make_chunk_scan_verifier(mesh: Mesh, g: int, rows: int,
                             donate: bool = False):
    """Returns a jitted fn over ((B, 128) packed rows, (B,) int32
    present), B == n_devices * g * rows -> ((B,) bool mask, () int32
    invalid count): each shard verifies its g chunks of ``rows`` rows as
    a lax.scan inside ONE dispatch.  ``donate=True`` donates both input
    buffers (production launches transfer each once, consume each
    once)."""
    batched = Pspec(BATCH_AXIS)
    fn = shard_map(
        _make_chunk_scan_body(g, rows),
        mesh=mesh,
        in_specs=(batched, batched),
        out_specs=(batched, Pspec()),
        **_SHARD_MAP_KW,
    )
    if donate:
        return jax.jit(fn, donate_argnums=(0, 1))
    return jax.jit(fn)


@functools.cache
def _cached_chunk_verifier(mesh: Mesh, g: int, rows: int):
    return make_chunk_scan_verifier(mesh, g, rows)


@functools.cache
def _cached_chunk_verifier_donated(mesh: Mesh, g: int, rows: int):
    # Same CPU-backend sharing as _cached_verifier_donated: one compile
    # per scan shape on the test backend, donation on real devices.
    if jax.default_backend() == "cpu":
        return _cached_chunk_verifier(mesh, g, rows)
    return make_chunk_scan_verifier(mesh, g, rows, donate=True)


def _pack_chunk_arrays(mesh: Mesh, prep: dict, m: int):
    """Shared pack step of the scan entries: pad packed rows + present
    mask to ``m`` total rows and ship both to the mesh."""
    n = prep["a"].shape[0]
    packed = np.asarray(prep["packed"])
    present = prep["host_ok"].astype(np.int32)
    if m != n:
        packed = np.pad(packed, [(0, m - n), (0, 0)])
        present = np.pad(present, [(0, m - n)])
    return _shard_put(mesh, packed), _shard_put(mesh, present)


def verify_sharded_chunked_pack(mesh: Mesh, prep: dict, *,
                                rows: int | None = None,
                                max_subbatch: int = MAX_SUBBATCH):
    """Pack stage of a whole-backlog chunked mesh scan; returns
    ``dispatch() -> fetch() -> (N,) bool mask``, the same three-stage
    contract as :func:`verify_batch_sharded_pack` (and the same mask —
    per-signature verification, just batched into one program).

    Pack (this thread): shard-aligned padding to ``n_devices * g *
    rows`` total rows plus the h2d transfer of the packed rows and the
    present mask.  ``rows`` is the per-shard chunk row count (the
    registry's warmed ``scan_rows``; defaults to the per-shard bucket of
    the batch itself, capped at ``max_subbatch``) and g comes from
    shard_shapes.mesh_chunk_count — the one place the scan's chunk
    arithmetic lives, so dispatch and warmup can never disagree about
    which (g, rows) programs exist.
    """
    n = prep["a"].shape[0]
    n_dev = mesh.devices.size
    if rows is None:
        rows = min(shard_bucket(n, n_dev, max_subbatch), max_subbatch)
    g = mesh_chunk_count(n, n_dev, rows)
    dev_rows, dev_present = _pack_chunk_arrays(mesh, prep,
                                               n_dev * g * rows)

    def dispatch():
        mask_dev, _bad = _cached_chunk_verifier_donated(
            mesh, g, rows)(dev_rows, dev_present)

        def fetch():
            return np.asarray(mask_dev)[:n]

        return fetch

    return dispatch


def verify_sharded_chunked(mesh: Mesh, prep: dict, *,
                           rows: int | None = None,
                           return_bad_total: bool = False,
                           max_subbatch: int = MAX_SUBBATCH):
    """Run a host-prepared backlog (crypto/eddsa.prepare_batch) through
    ONE chunked mesh scan -> (N,) bool mask, matching
    verify_batch_sharded row for row.  Eager twin of
    :func:`verify_sharded_chunked_pack` (same shared pack step) that
    can also surface the psum'd invalid count — the sidecar engine
    uses the staged form behind the scheduler's ``scan_sharded``
    route."""
    n = prep["a"].shape[0]
    n_dev = mesh.devices.size
    if rows is None:
        rows = min(shard_bucket(n, n_dev, max_subbatch), max_subbatch)
    g = mesh_chunk_count(n, n_dev, rows)
    dev_rows, dev_present = _pack_chunk_arrays(mesh, prep,
                                               n_dev * g * rows)
    mask, bad_total = _cached_chunk_verifier(mesh, g, rows)(
        dev_rows, dev_present)
    mask = np.asarray(mask)[:n]
    if return_bad_total:
        return mask, int(bad_total)
    return mask


# ---------------------------------------------------------------------------
# Sharded random-linear-combination verification: the MSM buckets
# themselves shard across the mesh
# ---------------------------------------------------------------------------
#
# The RLC check (crypto/eddsa.verify_batch_rlc) splits mesh-natively:
# window sums of an MSM over disjoint point shards simply point-add
# together, and the fixed-base scalar sum is a limb-wise integer sum that
# commutes with an ICI psum.  The per-shard window sums route through
# the SAME graftkern Pallas kernels as the single-chip path when
# HOTSTUFF_TPU_KERN=pallas — the shard body calls ops/ed25519
# (rlc_partials -> msm_window_sums / scalar25519.mont_mul), and the
# kernel route lives behind those signatures, so mesh launches pick it
# up with zero changes here.  So each chip runs the shard-local half
# (ops/ed25519.rlc_partials — decompression, mod-L scalar products,
# per-point tables, masked tree reduction to 64 window sums), the mesh
# exchanges 64 points + 32 limbs + 1 counter per chip (an all_gather and
# two psums — a few KB over ICI, vs. the votes themselves staying
# sharded), and every chip finishes the tiny replicated tail (Horner,
# comb, projective compare) to the same () bool verdict.


def _rlc_shard_body(packed, z):
    wsums, u_sum, bad = E.rlc_partials(packed, z)
    bad_total = jax.lax.psum(bad, BATCH_AXIS)
    u_total = jax.lax.psum(u_sum, BATCH_AXIS)
    allw = jax.lax.all_gather(wsums, BATCH_AXIS)   # (n_dev, 64, 4, 32)
    n_dev = allw.shape[0]
    n_pad = next_pow2(n_dev)
    if n_pad != n_dev:
        allw = jnp.concatenate(
            [allw, E.identity_ext((n_pad - n_dev, 64))], axis=0)
    combined = E._tree_sum(allw)                   # (64, 4, 32)
    return E.rlc_finish(combined, u_total, bad_total)


def make_sharded_rlc_verifier(mesh: Mesh, donate: bool = False):
    """Returns a jitted fn over ((B, 128) packed rows, (B, 32) coefficient
    rows), B % n_devices == 0 -> () bool combined-RLC verdict, replicated
    across the mesh.  Zero-coefficient rows are excluded (padding).
    ``donate=True`` donates both input buffers (production launches
    transfer each once and consume each once)."""
    batched = Pspec(BATCH_AXIS)
    fn = shard_map(
        _rlc_shard_body,
        mesh=mesh,
        in_specs=(batched, batched),
        out_specs=Pspec(),
        **_SHARD_MAP_KW,
    )
    if donate:
        return jax.jit(fn, donate_argnums=(0, 1))
    return jax.jit(fn)


@functools.cache
def _cached_rlc_verifier(mesh: Mesh):
    return make_sharded_rlc_verifier(mesh)


@functools.cache
def _cached_rlc_verifier_donated(mesh: Mesh):
    # Same CPU-backend sharing as _cached_verifier_donated: one compile
    # per mesh shape on the test backend, donation on real devices.
    if jax.default_backend() == "cpu":
        return _cached_rlc_verifier(mesh)
    return make_sharded_rlc_verifier(mesh, donate=True)


def _pack_rlc_rows(mesh: Mesh, packed: np.ndarray, idx: np.ndarray,
                   n: int, m: int, salt: bytes):
    """Coefficient rows + padding to the shard-aligned row count ``m``
    (callers derive it via shard_aligned_rows) + h2d for one sharded RLC
    launch over ``packed[:n]`` with host-canonical rows ``idx``."""
    z = np.zeros((m, 32), np.uint8)
    if len(idx):
        z[idx] = _rlc_coeffs(np.ascontiguousarray(packed[idx]), salt)
    if m != n:
        packed = np.pad(packed, [(0, m - n), (0, 0)])
    return _shard_put(mesh, packed), _shard_put(mesh, z)


def verify_rlc_sharded_pack(mesh: Mesh, prep: dict, *, salt: bytes = b"",
                            on_bisect=None):
    """Pack stage of a sharded one-MSM RLC verify launch; returns
    ``dispatch() -> fetch() -> (N,) bool mask``, bit-identical to
    :func:`verify_batch_sharded` (and therefore to
    crypto/eddsa.verify_batch).

    Pack (this thread): coefficient PRF, shard-aligned padding
    (shard_shapes.shard_aligned_rows — every shard gets a warmed
    power-of-two bucket), h2d of the packed rows + coefficient rows.
    Dispatch (engine thread): ONE donated mesh program computing the
    combined verdict.  Fetch: when the combined check passes (the steady
    state) the mask is just host_ok; on failure the batch BISECTS with
    fresh per-sub-batch coefficients down to the RLC_MIN_MSM floor,
    below which the per-signature sharded path pinpoints each bad vote —
    ``on_bisect`` (if given) fires once so the scheduler's telemetry
    counts the slow path.  Degenerate batches (fewer than RLC_MIN_MSM
    canonical rows, or per-shard sizes beyond the one-dispatch envelope)
    dispatch the per-signature sharded program instead — same contract,
    same mask.
    """
    n = prep["a"].shape[0]
    host_ok = prep["host_ok"]
    if n == 0:
        return lambda: (lambda: np.zeros((0,), bool))
    n_dev = mesh.devices.size
    idx = np.nonzero(host_ok)[0]
    if len(idx) < RLC_MIN_MSM or shard_bucket(n, n_dev) > MAX_SUBBATCH:
        # Too few canonical rows for the MSM to win, or a quorum beyond
        # the mesh's one-dispatch RLC envelope (same policy as
        # verify_batch_rlc): per-signature sharded, identical mask.
        return verify_batch_sharded_pack(mesh, prep)
    packed = np.asarray(prep["packed"])
    dev_rows, dev_z = _pack_rlc_rows(
        mesh, packed, idx, n, shard_aligned_rows(n, n_dev), salt)

    def dispatch():
        ok_dev = _cached_rlc_verifier_donated(mesh)(dev_rows, dev_z)

        def fetch():
            if bool(np.asarray(ok_dev)):
                return host_ok.copy()
            if on_bisect is not None:
                on_bisect()
            mask = np.zeros((n,), bool)
            mid = len(idx) // 2
            _rlc_sharded_resolve(mesh, packed, idx[:mid], mask,
                                 salt + b"L")
            _rlc_sharded_resolve(mesh, packed, idx[mid:], mask,
                                 salt + b"R")
            return mask

        return fetch

    return dispatch


def _rlc_sharded_resolve(mesh: Mesh, packed: np.ndarray,
                         indices: np.ndarray, out: np.ndarray,
                         salt: bytes) -> None:
    """Resolve ``out[indices]`` for host-canonical rows across the mesh:
    combined sharded RLC check first, bisection with fresh coefficients
    on failure, per-signature sharded floor below RLC_MIN_MSM.  Every
    sub-batch re-pads through the shard-alignment rule, so bisection can
    only ever land on warmed per-shard buckets (smaller than the batch
    that failed)."""
    n = len(indices)
    if n == 0:
        return
    rows = np.ascontiguousarray(packed[indices])
    if n < RLC_MIN_MSM:
        from ..crypto.eddsa import split_packed_rows

        # Through the pack entry, NOT the eager wrapper: the warmup only
        # compiles the donated programs on a real device backend, and a
        # mid-traffic bisection must never pay a cold compile.
        prep = split_packed_rows(rows)
        out[indices] = verify_batch_sharded_pack(mesh, prep)()()
        return
    m = shard_aligned_rows(n, mesh.devices.size)
    dev_rows, dev_z = _pack_rlc_rows(mesh, rows, np.arange(n), n, m, salt)
    # Same donated program the warmup compiled (the buffers above are
    # fresh device arrays consumed exactly once — donation-safe).
    ok = bool(np.asarray(_cached_rlc_verifier_donated(mesh)(
        dev_rows, dev_z)))
    if ok:
        out[indices] = True
        return
    mid = n // 2
    _rlc_sharded_resolve(mesh, packed, indices[:mid], out, salt + b"L")
    _rlc_sharded_resolve(mesh, packed, indices[mid:], out, salt + b"R")


def verify_rlc_sharded(mesh: Mesh, prep: dict, *,
                       salt: bytes = b"") -> np.ndarray:
    """Run a host-prepared batch (crypto/eddsa.prepare_batch) through the
    mesh-sharded RLC check -> (N,) bool mask, matching verify_batch_sharded.

    Eager wrapper over :func:`verify_rlc_sharded_pack` (pack, dispatch
    and fetch in one call) — the sidecar engine uses the staged form;
    the ``--warm-rlc-sharded`` warmup (sidecar/service) pre-compiles
    every per-shard bucket this can launch, and the scheduler's shape
    registry only routes batches onto buckets that warmup marked.
    """
    return verify_rlc_sharded_pack(mesh, prep, salt=salt)()()
