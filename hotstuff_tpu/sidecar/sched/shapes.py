"""Warmed-shape registry: which compiled launch shapes exist, and which
verify path a batch of size n should take.

The engine may only launch shapes whose XLA programs were compiled
before the socket bound (sidecar/service._warmup*): a first-time compile
on the engine thread is a silent 30-60 s stall mid-traffic.  This
registry is the single record of what was warmed:

  * ``buckets``   — padded power-of-two batch shapes (8 .. MAX_SUBBATCH)
                    for the per-signature ladder program;
  * ``chunks``    — chunked-scan lengths g (2 .. 16) for bulk backlogs
                    (g * MAX_SUBBATCH signatures in ONE dispatch);
  * ``rlc_buckets`` — padded shapes of the one-MSM RLC program
                    (ops/ed25519.verify_rlc_packed), compiled by
                    ``--warm-rlc``;
  * ``shard_buckets`` / ``rlc_shard_buckets`` — PER-SHARD padded row
                    counts of the mesh programs (verify_batch_sharded /
                    verify_rlc_sharded), compiled by the mesh warmup and
                    ``--warm-rlc-sharded``;
  * ``mesh_chunks`` / ``scan_rows`` — chunk counts g (and the per-shard
                    chunk row count) of the whole-backlog mesh scan
                    (verify_sharded_chunked — g * scan_rows rows per
                    shard in ONE dispatch), compiled by the graftscale
                    leg of ``--warm-rlc-sharded``; ``enable_bulk`` on a
                    mesh registry is gated on them.

``route`` turns (batch size, warmed state) into the launch path — the
policy that wires the one-MSM verifiers into the engine's coalesced
launch path: batches of ``RLC_MIN_LAUNCH`` or more signatures whose
bucket (per-shard bucket, on a mesh) is RLC-warmed pay one Straus MSM
instead of 2n scalar ladders, and the resolution inside the RLC paths
(one per-signature launch on a single chip, a bisection on a mesh) keeps
the verdict mask bit-identical to the per-signature program whenever
the combined check fails.  Mesh deployments route between
``rlc_sharded`` and ``ladder_sharded`` the same way single-chip ones
route between ``rlc`` and ``per_sig``.

Bucketing arithmetic is delegated: single-chip sizes to ``crypto/eddsa``
(``next_pow2`` / ``_bucket``) and mesh sizes to
``parallel/shard_shapes`` (``shard_bucket`` / ``shard_aligned_rows``) —
THE padding rules the graftlint padshape checker pins — so the registry
can never disagree with the dispatch layer about which shape a size
lands on.
"""

from __future__ import annotations

from ...crypto.eddsa import MAX_SUBBATCH, _bucket, next_pow2
from ...parallel.shard_shapes import (mesh_chunk_count, shard_aligned_rows,
                                      shard_bucket)

# Engine-path RLC floor: below this the combined check's fixed
# Horner/comb tail outweighs the saved ladders (crypto/eddsa.RLC_MIN_MSM
# is a different constant: the fewest canonical rows an admitted batch
# may be left with and still run the MSM, and the mesh's bisection
# floor; the engine wants to start where the MSM wins).
RLC_MIN_LAUNCH = 16

# Largest chunk count the whole-backlog mesh scan warms (graftscale):
# the mesh twin of the single-chip MAX_COALESCED / MAX_SUBBATCH = 16
# scan-length bound — it caps both the compiled (g, rows) program set
# and how long one backlog drain can occupy the engine ahead of a
# consensus-latency QC verify.
MESH_SCAN_CHUNKS = 16

# Verify paths route() can answer (also the stats path-counter keys).
PATH_PER_SIG = "per_sig"
PATH_RLC = "rlc"
PATH_HOST = "host"
PATH_RLC_SHARDED = "rlc_sharded"
PATH_LADDER_SHARDED = "ladder_sharded"
# graftscale: a coalesced backlog bigger than any warmed ladder bucket
# drains as ONE chunked whole-backlog mesh scan when its (g, rows)
# shape is warmed (parallel/sharded_verify.verify_sharded_chunked).
PATH_SCAN_SHARDED = "scan_sharded"
# Legacy mesh route: a registry flagged mesh without a device count
# cannot compute per-shard buckets, so it keeps the old catch-all.
PATH_MESH = "mesh"


def quorum_sigs(committee: int) -> int:
    """Signature count of a quorum certificate for an n-node committee
    with unit stakes: 2n/3 + 1 (the node's own quorum formula,
    native/src/consensus/config.hpp — NOT 2f+1 from n=3f+1, which
    disagrees for n not of that form).  The committee-size-derived
    threshold the giant-committee warmup sizes itself off: a QC-shaped
    latency batch of this many votes must land on a warmed sharded-RLC
    bucket, never the sliced ladder."""
    return 2 * committee // 3 + 1


class ShapeRegistry:
    """Tracks warmed shapes; owned by the engine, read by the scheduler.

    Mutations happen on the warmup path (before the server socket binds)
    or from tests; reads happen on the engine thread.  No lock: the sets
    are only ever grown, and a stale read can at worst route one batch
    down the always-safe per-signature path.
    """

    def __init__(self, use_host: bool = False, mesh: bool = False,
                 n_devices: int = 0, committee: int | None = None):
        self.use_host = use_host
        self.n_devices = int(n_devices or 0)
        self.mesh = bool(mesh) or self.n_devices > 1
        # Committee size served (graftscale): sizes the quorum-shaped
        # warmup floor so a 2f+1 QC batch — ~667 signatures at N=1000 —
        # always lands on a warmed sharded-RLC bucket instead of the
        # sliced ladder (qc_sigs below; None = unknown committee).
        self.committee = int(committee) if committee else None
        self.buckets: set[int] = set()
        self.chunks: set[int] = set()
        self.rlc_buckets: set[int] = set()
        # Per-SHARD padded row counts the mesh programs were compiled at
        # (the mesh analogue of buckets / rlc_buckets).
        self.shard_buckets: set[int] = set()
        self.rlc_shard_buckets: set[int] = set()
        # Whole-backlog mesh scan shapes (graftscale): the per-shard
        # chunk row count the scan programs were compiled at, and the
        # warmed chunk counts g (the mesh analogue of ``chunks``).
        self.scan_rows = 0
        self.mesh_chunks: set[int] = set()
        # Per-launch cap in signatures; raised to the bulk cap only after
        # the chunked-scan shapes are warmed (enable_bulk — on a mesh,
        # gated on the whole-backlog scan shapes instead).
        self.launch_cap = MAX_SUBBATCH

    @property
    def qc_sigs(self) -> int | None:
        """Signature count of one quorum certificate for the served
        committee (None when the committee size is unknown)."""
        if self.committee and self.committee > 1:
            return quorum_sigs(self.committee)
        return None

    # -- warmup bookkeeping -------------------------------------------------

    def mark_bucket(self, n: int):
        self.buckets.add(_bucket(n))
        if self.n_devices > 1:
            # A mesh warmup compiles per-shard shapes, not global ones.
            self.shard_buckets.add(shard_bucket(n, self.n_devices))

    def mark_chunks(self, g: int):
        self.chunks.add(g)

    def mark_rlc(self, n: int):
        self.rlc_buckets.add(_bucket(n))

    def mark_rlc_sharded(self, n: int):
        """Record that the sharded one-MSM program was compiled for the
        per-shard bucket an n-record launch lands on."""
        if self.n_devices > 1:
            self.rlc_shard_buckets.add(shard_bucket(n, self.n_devices))

    def mark_mesh_chunks(self, g: int, rows: int):
        """Record that the whole-backlog mesh scan program was compiled
        for g chunks of ``rows`` per-shard rows (graftscale warmup).
        One ``rows`` value per registry: the warmup compiles every g at
        its top per-shard bucket, and a second rows value would mean two
        scan ladders the router cannot tell apart."""
        if self.n_devices <= 1:
            return
        if self.scan_rows and self.scan_rows != rows:
            raise ValueError(
                f"mesh scan chunk rows already warmed at "
                f"{self.scan_rows}, cannot also warm {rows}")
        self.scan_rows = rows
        self.mesh_chunks.add(g)

    def scan_shape_of(self, n: int):
        """(g, rows) of the warmed whole-backlog scan an n-record
        launch would dispatch as, or None when no warmed scan shape
        covers it (no scan warmup ran, or the backlog outgrows the
        largest warmed chunk count — the caller falls back to the
        sliced ladder path)."""
        if self.n_devices <= 1 or not self.scan_rows \
                or not self.mesh_chunks:
            return None
        g = mesh_chunk_count(n, self.n_devices, self.scan_rows)
        if g in self.mesh_chunks:
            return g, self.scan_rows
        return None

    def scan_capacity(self) -> int:
        """Largest backlog ONE whole-backlog mesh scan can drain
        (0 when no scan shapes are warmed): the launch-cap ceiling
        enable_bulk may raise a mesh registry to.

        Worked suppression: this is capacity arithmetic over shapes the
        warmup ALREADY compiled (every g in mesh_chunks was marked by
        mark_mesh_chunks after its program built) — no launch size is
        derived here, so the shard-alignment rule's cold-compile hazard
        cannot arise; launch sizing goes through scan_shape_of, whose
        mesh_chunk_count call is the pinned helper."""
        if self.n_devices <= 1 or not self.mesh_chunks:
            return 0
        # graftlint: disable=shard-misaligned-launch
        return self.n_devices * max(self.mesh_chunks) * self.scan_rows

    def ladder_cap(self) -> int:
        """Slice size for the sliced-ladder mesh fallback: the largest
        launch whose per-shard bucket the warmup actually compiled
        (device count x top warmed bucket).  The scan-raised launch_cap
        must never leak into ladder slicing — a 16384-sig slice would
        land on a per-shard shape only the SCAN programs know, a cold
        XLA compile on the engine thread mid-traffic.  With no warmed
        buckets at all, a mesh registry floors at MAX_SUBBATCH (the
        pre-graftscale slicing step) — never the raised launch_cap,
        even when a scan-only warmup (--warm-bulk without the RLC leg)
        raised it; single-chip registries keep launch_cap (their
        enable_bulk is ungated and warms the chunk shapes it needs).

        Worked suppression (same rationale as scan_capacity): this is
        capacity arithmetic over buckets the warmup ALREADY compiled —
        every element of shard_buckets was marked after its program
        built; the slice sizes derived from it re-enter
        verify_batch_sharded_pack, whose shard_bucket call is the
        pinned helper."""
        if self.n_devices > 1:
            if self.shard_buckets:
                # graftlint: disable=shard-misaligned-launch
                return self.n_devices * max(self.shard_buckets)
            return min(self.launch_cap, MAX_SUBBATCH)
        return self.launch_cap

    def enable_bulk(self, max_coalesced: int):
        """Raise the per-launch cap; call only after the chunked-scan
        shapes up to max_coalesced / MAX_SUBBATCH are compiled.  On a
        mesh registry the raise is GATED on the whole-backlog scan
        shapes (mark_mesh_chunks): without them a coalesced backlog
        beyond MAX_SUBBATCH would have to slice — or worse, land a
        per-shard shape warmup never compiled — so the cap stays put
        and the coalescer keeps assembling single-bucket launches.
        Raise-only: a small warmed scan capacity must never LOWER the
        cap below its current value."""
        if self.n_devices > 1:
            cap = self.scan_capacity()
            if not cap:
                return
            self.launch_cap = max(self.launch_cap,
                                  min(max_coalesced, cap))
            return
        self.launch_cap = max_coalesced

    # -- shape queries ------------------------------------------------------

    def shard_bucket_of(self, n: int) -> int | None:
        """Per-shard padded row count an n-record mesh launch lands on
        (None when this registry has no mesh size)."""
        if self.n_devices > 1:
            return shard_bucket(n, self.n_devices)
        return None

    def bucket_capacity(self, n: int) -> int:
        """Padded device capacity of an n-signature launch: the bucket
        (or chunk-scan, or shard-aligned mesh) shape the dispatch layer
        will actually compile — the free room pad-fill may use without
        growing the launch.

        Host mode has NO padding (the host path verifies exactly n
        records, one ref.verify each), so there the capacity is the
        batch itself and fill never happens.  Mesh launches pad to the
        shard-aligned row count (per-shard power-of-two bucket x device
        count — parallel/shard_shapes), so their pad-fill room is real
        free capacity too: filling up to it never grows any shard's
        compiled shape."""
        if self.use_host:
            return n
        if self.n_devices > 1:
            return shard_aligned_rows(n, self.n_devices)
        if self.mesh:
            return n  # legacy mesh-without-count: no sizing knowledge
        if n <= MAX_SUBBATCH:
            return _bucket(n)
        g = next_pow2(-(-n // MAX_SUBBATCH))
        return g * MAX_SUBBATCH

    def route(self, n: int) -> str:
        """Verify path for a coalesced batch of n unique records."""
        if self.use_host:
            return PATH_HOST
        if self.n_devices > 1:
            per = shard_bucket(n, self.n_devices)
            if n >= RLC_MIN_LAUNCH and per <= MAX_SUBBATCH and \
                    per in self.rlc_shard_buckets:
                return PATH_RLC_SHARDED
            # A backlog bigger than any warmed ladder bucket drains as
            # ONE whole-backlog scan when its chunk count is warmed;
            # otherwise the ladder path slices it at the launch cap
            # (the pre-graftscale behavior, kept as the safe fallback).
            if per not in self.shard_buckets and \
                    self.scan_shape_of(n) is not None:
                return PATH_SCAN_SHARDED
            return PATH_LADDER_SHARDED
        if self.mesh:
            return PATH_MESH
        if RLC_MIN_LAUNCH <= n <= MAX_SUBBATCH and \
                _bucket(n) in self.rlc_buckets:
            return PATH_RLC
        return PATH_PER_SIG

    def snapshot(self) -> dict:
        return {
            "launch_cap": self.launch_cap,
            "buckets": sorted(self.buckets),
            "chunks": sorted(self.chunks),
            "rlc_buckets": sorted(self.rlc_buckets),
            "n_devices": self.n_devices,
            "shard_buckets": sorted(self.shard_buckets),
            "rlc_shard_buckets": sorted(self.rlc_shard_buckets),
            "scan_rows": self.scan_rows,
            "mesh_chunks": sorted(self.mesh_chunks),
            "committee": self.committee,
        }
