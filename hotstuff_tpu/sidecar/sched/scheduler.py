"""Deadline-aware two-class batching scheduler for the verify engine.

Replaces the engine's single FIFO coalescing loop with explicit policy,
the shape continuous-batching servers converged on (Orca's per-class
admission + iteration-level scheduling, adapted to signature batches):

Strict latency priority.
    Whenever latency-class work is queued, the next launch is assembled
    from the latency queue only — a QC verify never waits behind a bulk
    backlog, only behind the launch already in flight (the engine's
    pipeline bounds that to PIPELINE_DEPTH launches).

Carry-over within a class.
    Coalescing never splits a request.  A head request that does not fit
    the remaining launch budget simply stays queued and is guaranteed to
    LEAD the next launch of its class (``carries`` telemetry counts how
    often) — the FIFO position is the fairness token, so an over-budget
    bulk batch cannot be displaced forever by smaller arrivals.

Bulk pad-fill (carry-over fairness across classes).
    Launch shapes are padded to power-of-two buckets, so a latency
    launch of n unique records ships ``bucket(n) - n`` dead slots
    anyway.  Those slots are filled with whole bulk requests that fit
    (room is sized off the DEDUPED latency record count — see
    ``_assemble_locked`` — so fill can never grow the compiled shape) —
    the latency launch shape, and therefore its time, is unchanged, and
    bulk traffic keeps draining at least at the pad-waste rate even
    under 100%% sustained latency load.  Strict priority alone would
    starve bulk in exactly that regime; a time-slice would trade
    consensus latency away.  Pad-fill does neither.

Bounded backpressure.
    Both queues are bounded in signature records; ``offer`` never
    blocks.  A full queue is an explicit queue-full reply to the client
    (which falls back to host verify or retries), never a connection
    thread wedged on an unbounded ``put`` — the engine always sees an
    honest queue it can reason about.

The scheduler owns queues and policy only; the device, the verify paths
and the reply fan-out stay in ``sidecar/service.VerifyEngine``.
"""

from __future__ import annotations

import os
import threading
from time import monotonic

from ...crypto.eddsa import MAX_SUBBATCH
from .classes import BULK, LATENCY, ClassQueue, Launch, Pending
from .shapes import ShapeRegistry
from .stats import SchedStats
from .surge import AdmissionController

# Admission caps (signature records queued, not requests).  Latency is
# sized for bursts of full-committee QC verifies; bulk for a few whole
# coalesced launches — beyond that, shedding to the client beats hiding
# an ever-growing backlog inside the sidecar.  These are the STATIC
# defaults; deployments that know their committee size / client rate get
# caps sized from those parameters instead (size_queue_caps below), and
# the HOTSTUFF_TPU_{LATENCY,BULK}_QUEUE_CAP_SIGS env vars override both.
_DEFAULT_LATENCY_CAP_SIGS = 64 * 1024
_DEFAULT_BULK_CAP_SIGS = 128 * 1024

# Per-replica async verify pipeline depth the latency sizing assumes —
# the C++ node's MAXIMUM adaptive in-flight budget (TpuVerifier::
# kInflightBudgetMax; the budget only ever shrinks below this).
_INFLIGHT_PER_REPLICA = 64


def _env_cap(name: str):
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        return None
    return v if v > 0 else None


def _clamp(v: int, lo: int, hi: int) -> int:
    return max(lo, min(hi, v))


def size_queue_caps(committee: int | None = None,
                    client_rate: int | None = None):
    """``(latency_cap_sigs, bulk_cap_sigs)`` for a deployment.

    Latency demand scales with the committee: on the shared local
    testbed every replica verifies every certificate, so a worst-case
    burst is ``committee`` replicas x ``_INFLIGHT_PER_REPLICA`` pipelined
    requests x ``quorum`` signatures each.  Bulk demand scales with the
    client transaction rate: the cap admits ~2 s of arrivals, past which
    shedding to the client's host path beats an ever-older backlog
    (their verdicts would miss the batch's consensus round anyway).
    Both are clamped to [default/4, 16x default] so a typo'd parameter
    cannot starve or balloon the sidecar, and the explicit env
    overrides (HOTSTUFF_TPU_LATENCY_QUEUE_CAP_SIGS /
    HOTSTUFF_TPU_BULK_QUEUE_CAP_SIGS) win over everything."""
    lat = _env_cap("HOTSTUFF_TPU_LATENCY_QUEUE_CAP_SIGS")
    if lat is None:
        if committee and committee > 1:
            quorum = 2 * committee // 3 + 1
            lat = _clamp(committee * quorum * _INFLIGHT_PER_REPLICA,
                         _DEFAULT_LATENCY_CAP_SIGS // 4,
                         16 * _DEFAULT_LATENCY_CAP_SIGS)
        else:
            lat = _DEFAULT_LATENCY_CAP_SIGS
    blk = _env_cap("HOTSTUFF_TPU_BULK_QUEUE_CAP_SIGS")
    if blk is None:
        if client_rate and client_rate > 0:
            blk = _clamp(2 * client_rate,
                         _DEFAULT_BULK_CAP_SIGS // 4,
                         16 * _DEFAULT_BULK_CAP_SIGS)
        else:
            blk = _DEFAULT_BULK_CAP_SIGS
    return lat, blk


def size_tenant_caps(latency_cap_sigs: int, bulk_cap_sigs: int,
                     committee: int | None = None):
    """``(latency_tenant_cap_sigs, bulk_tenant_cap_sigs)`` — one
    tenant's admission share of each class queue (graftfleet).

    The latency share is sized off the committee exactly like the class
    cap itself (one committee's worst-case pipelined QC burst), so a
    single-committee tenant never notices the share — while a tenant
    flooding past its own committee's plausible demand sheds on its
    share with the rest of the class cap still open to other tenants.
    The bulk share is half the class cap: bulk is best-effort by
    definition, and half leaves a second tenant's worth of admission
    room under any flood.  Shares only ENGAGE once a second tenant has
    been seen (ClassQueue._offer_locked), so pre-fleet deployments are
    byte-identical."""
    if committee and committee > 1:
        quorum = 2 * committee // 3 + 1
        lat = _clamp(committee * quorum * _INFLIGHT_PER_REPLICA,
                     latency_cap_sigs // 4, latency_cap_sigs)
    else:
        lat = latency_cap_sigs
    return lat, max(1, bulk_cap_sigs // 2)


# Back-compat module constants (env-aware at import): the parameterless
# Scheduler() and older embedders read these.
LATENCY_QUEUE_CAP_SIGS, BULK_QUEUE_CAP_SIGS = size_queue_caps()


class Scheduler:
    def __init__(self, shapes: ShapeRegistry | None = None,
                 stats: SchedStats | None = None,
                 latency_cap_sigs: int = LATENCY_QUEUE_CAP_SIGS,
                 bulk_cap_sigs: int = BULK_QUEUE_CAP_SIGS,
                 admission: AdmissionController | None = None,
                 committee: int | None = None):
        self.shapes = shapes if shapes is not None else ShapeRegistry()
        self.stats = stats if stats is not None else SchedStats()
        # graftsurge: the pack-side admission controller (sched/surge.py)
        # derates bulk intake off the pipeline overlap stats and enforces
        # bulk-before-latency shedding; the stats object forwards the
        # engine's note_pack/note_launch observations into it and folds
        # its counters into the OP_STATS ``surge`` section.
        self.admission = admission if admission is not None \
            else AdmissionController()
        self.stats.surge = self.admission
        self._cond = threading.Condition()
        # graftfleet: per-tenant admission shares sized off the
        # committee (they only engage once a second tenant appears —
        # see ClassQueue._offer_locked).
        lat_share, blk_share = size_tenant_caps(
            latency_cap_sigs, bulk_cap_sigs, committee)
        self._queues = {
            LATENCY: ClassQueue(latency_cap_sigs, self._cond,
                                tenant_cap_sigs=lat_share),
            BULK: ClassQueue(bulk_cap_sigs, self._cond,
                             tenant_cap_sigs=blk_share),
        }

    # -- admission (connection threads) -------------------------------------

    def offer(self, request, reply_fn, cls: str = LATENCY,
              is_bls: bool = False, tenant: str | None = None,
              span=None) -> bool:
        """Admit one request; False means queue-full (the caller must
        reply explicitly — nothing was retained; ``retry_after_ms``
        gives the hint the BUSY reply should carry).

        Admission policy (graftsurge) on top of the plain byte caps:
        bulk is shed outright while the latency class is under shed
        pressure (bulk-before-latency — under overload the consensus
        class is the last to lose capacity), and bulk admits against a
        cap derated by the pipeline-overlap controller (a pack-bound
        engine sheds bulk earlier instead of queueing work the pack
        worker cannot drain).  All checks run under the one admission
        lock, so a bulk request can never be admitted concurrently with
        a latency shed — the fairness guarantee the strict parser mode
        asserts.

        graftfleet adds the tenant key: ``tenant`` (the connection's
        HELLO identity, default for legacy clients) selects the lane,
        the per-tenant share is enforced inside the queue, and a
        latency shed is audited for STARVATION — a refusal at the class
        cap while another tenant sits above its own share would mean a
        flooding tenant displaced this one, which per-lane admission
        makes unreachable; ``tenant_starvation`` is the proof counter
        the strict parser reads."""
        pending = Pending(request, reply_fn, cls, is_bls=is_bls,
                          tenant=tenant, span=span)
        adm = self.admission
        with self._cond:
            if cls == BULK:
                lat = self._queues[LATENCY]
                if adm.latency_pressure() or (
                        lat.sigs and lat.sigs >= lat.cap_sigs):
                    adm.note_shed(BULK, before_latency=True)
                    self.stats.note_queue_full(cls)
                    self.stats.note_tenant_shed(pending.tenant, cls)
                    return False
                cap = int(self._queues[BULK].cap_sigs * adm.bulk_derate())
                if not self._queues[BULK]._offer_locked(pending,
                                                        cap_sigs=cap):
                    adm.note_shed(BULK)
                    self.stats.note_queue_full(cls)
                    self.stats.note_tenant_shed(pending.tenant, cls)
                    return False
            elif not self._queues[cls]._offer_locked(pending):
                if cls == LATENCY:
                    adm.note_latency_shed()
                    q = self._queues[LATENCY]
                    if q.last_refusal == "class-cap" and \
                            q.lanes.any_over_cap_locked(
                                q.tenant_cap_sigs,
                                exclude=pending.tenant):
                        adm.note_tenant_starvation()
                adm.note_shed(cls)
                self.stats.note_queue_full(cls)
                self.stats.note_tenant_shed(pending.tenant, cls)
                return False
            adm.note_admitted(cls)
            self.stats.note_admitted(cls)
            self.stats.note_tenant_admitted(pending.tenant, cls)
            return True

    def retry_after_ms(self, cls: str) -> int:
        """Hint for a BUSY reply: the time this class's backlog needs to
        drain at the recent launch rate (clamped; see surge.py)."""
        return self.admission.retry_after_ms(cls, self._queues[cls].sigs)

    def wake(self):
        """Unblock a next_launch() waiter (shutdown path)."""
        with self._cond:
            self._cond.notify_all()

    def queued_sigs(self, cls: str) -> int:
        return self._queues[cls].sigs

    def queue_caps(self) -> dict:
        """Admission caps per class (OP_STATS telemetry)."""
        return {cls: q.cap_sigs for cls, q in self._queues.items()}

    def tenant_caps(self) -> dict:
        """Per-tenant admission shares per class (OP_STATS telemetry)."""
        return {cls: q.tenant_cap_sigs for cls, q in self._queues.items()}

    def tenant_occupancy(self) -> dict:
        """{class: {tenant: queued sig records}} — the live lane view
        the fleet OP_STATS section exposes (graftfleet)."""
        with self._cond:
            return {cls: q.lanes.occupancy_locked()
                    for cls, q in self._queues.items()}

    # -- assembly (engine thread) -------------------------------------------

    def next_launch(self, block: bool = True,
                    timeout: float | None = None) -> Launch | None:
        """Assemble the next launch, or None when (a) non-blocking and
        idle, or (b) the timeout expired."""
        return self._next(None, block, timeout)

    def next_tick(self, quota_sigs: int,
                  timeout: float | None = None) -> Launch | None:
        """graftcadence: assemble one cadence tick's quota — the same
        strict-priority, carry-over, pad-fill policy as next_launch,
        but the coalesce run is capped at ``quota_sigs`` (the ring's
        per-tick budget, a warmed bucket) instead of the class launch
        cap.  Pad-fill still pads to the compiled bucket of the deduped
        record count: dead slots are free FLOPs whether the launch came
        from a tick quota or a staged coalesce.  Non-blocking by
        default (the ring paces itself); with a timeout the fully-idle
        ring parks here so a fresh offer wakes it immediately instead
        of eating an idle-backoff interval."""
        return self._next(quota_sigs, timeout is not None, timeout)

    def _next(self, cap: int | None, block: bool,
              timeout: float | None) -> Launch | None:
        deadline = None if timeout is None else monotonic() + timeout
        with self._cond:
            while True:
                launch = self._assemble_locked(cap=cap)
                if launch is not None or not block:
                    return launch
                wait = None if deadline is None \
                    else max(0.0, deadline - monotonic())
                if wait == 0.0 or not self._cond.wait(timeout=wait):
                    if deadline is not None and monotonic() >= deadline:
                        return None

    def _assemble_locked(self, cap: int | None = None) -> Launch | None:
        lat, blk = self._queues[LATENCY], self._queues[BULK]
        if lat:
            if lat._head_locked().is_bls:
                launch = Launch("bls", [lat._pop_locked()], LATENCY)
                # BLS runs one request per launch (nothing coalesces);
                # capacity 1 keeps pad-waste at zero while the launch
                # count and the latency queue-wait reservoir — where a
                # seconds-long pairing backlog shows up — stay honest.
                self.stats.note_launch(launch, 1, monotonic())
                return launch
            items, total = self._coalesce_locked(lat, cap=cap)
            # Fill room comes from the DEDUPED record count, not the raw
            # total: the engine dedups (msg, pk, sig) records before
            # dispatch and launches bucket(unique), so under the headline
            # shared-sidecar load (N replicas submitting the SAME QC,
            # total >> unique) sizing fill off the raw total would grow
            # the compiled shape past the latency batch's own bucket —
            # the exact latency cost pad-fill promises not to incur.
            # On a mesh, bucket_capacity is the SHARD-ALIGNED row count
            # (per-shard power-of-two bucket x device count, via
            # parallel/shard_shapes): launches always divide evenly
            # across the devices — no 375-row shards, no cold XLA
            # compiles mid-run — and fill room is computed against that
            # same shard-aligned capacity, so mesh pad slots drain bulk
            # exactly like single-chip ones.
            # Each fill request is counted at its full record count
            # (worst case: all its records are new), so unique-after-fill
            # can never exceed the latency batch's bucket.  The dedup is
            # computed only when fill is actually on the table (bulk
            # queued, batch within one sub-batch) — it hashes every
            # record while holding the admission lock, so the common
            # pure-consensus case must not pay it per launch.
            fill = []
            if blk and total <= MAX_SUBBATCH:
                uniq = len({rec for p in items
                            for rec in zip(p.request.msgs, p.request.pks,
                                           p.request.sigs)})
                capacity = self.shapes.bucket_capacity(uniq)
                fill = self._fill_locked(blk, capacity - uniq)
            else:
                capacity = self.shapes.bucket_capacity(total)
            launch = Launch("verify", items + fill, LATENCY,
                            fill_count=len(fill))
            self.stats.note_launch(launch, capacity, monotonic())
            return launch
        if blk:
            items, total = self._coalesce_locked(blk, cap=cap)
            launch = Launch("verify", items, BULK)
            self.stats.note_launch(
                launch, self.shapes.bucket_capacity(total), monotonic())
            return launch
        return None

    def _coalesce_locked(self, q: ClassQueue, cap: int | None = None):
        """Pop a FIFO run of same-class Ed25519 requests up to the launch
        cap.  The head always ships (an oversized single request slices
        inside the engine dispatch); a later head that would overflow the
        budget stays queued and leads the next launch (carry-over).

        The default cap is the registry's launch_cap: MAX_SUBBATCH until
        the bulk shapes are warmed, then the single-chip MAX_COALESCED —
        or, on a mesh, the whole-backlog scan capacity the gated
        enable_bulk raised it to (graftscale): everything coalesced here
        then drains as ONE chunked mesh scan instead of per-cap ladder
        slices.  The cadence ring passes its per-tick quota instead
        (never above launch_cap — a tick must stay inside one warmed
        shape)."""
        cap = self.shapes.launch_cap if cap is None \
            else min(cap, self.shapes.launch_cap)
        items = [q._pop_locked()]
        total = len(items[0])
        while (nxt := q._head_locked()) is not None and not nxt.is_bls:
            nxt_len = len(nxt)
            if total + nxt_len > cap:
                self.stats.note_carry(items[0].cls)
                break
            items.append(q._pop_locked())
            total += nxt_len
        return items, total

    def _fill_locked(self, blk: ClassQueue, room: int):
        """Whole bulk requests that fit the latency launch's pad slots."""
        fill = []
        while room > 0:
            h = blk._head_locked()
            if h is None or h.is_bls or len(h) > room:
                break
            p = blk._pop_locked()
            fill.append(p)
            room -= len(p)
        return fill
