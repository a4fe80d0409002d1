"""TPU verify sidecar: a long-lived JAX process owning the accelerator.

Architecture mirrors the reference's ``SignatureService`` actor
(crypto/src/lib.rs:226-254) scaled to a process boundary: connection
threads admit requests into the two-class verifysched scheduler
(``sidecar/sched/``); a single device thread asks the scheduler for
launches, dispatches them down the routed verify path (per-signature
ladders, or the one-MSM RLC program for warmed batch shapes), and fans
replies back out.  Request/response framing in ``protocol.py``.

Scheduling policy (details + rationale in sched/scheduler.py):
  * ``latency`` class (consensus QC/TC verifies, all BLS ops) has strict
    priority — it waits behind at most the launches already in flight;
  * ``bulk`` class (OP_VERIFY_BULK mempool/offchain batches) coalesces
    up to the bulk cap, rides the pad slots of latency launches so it
    drains even under sustained latency load, and carries over whole
    requests that miss a launch budget;
  * both queues are bounded — a full queue is an explicit queue-full
    reply (empty mask), never a blocked connection thread;
  * every launch is counted (OP_STATS returns the telemetry snapshot).

Run:  python -m hotstuff_tpu.sidecar --port 7100 [--mesh N]
"""

from __future__ import annotations

import argparse
import logging
import queue
import signal
import socket
import socketserver
import sys
import threading
from collections import OrderedDict
from time import monotonic

import numpy as np

from . import protocol as proto
from . import sched as vsched
from ..obs.spans import NO_LAUNCH
from .guard import BusyReply, WedgedLaunch, bisect_poison

log = logging.getLogger("sidecar")

from ..crypto.eddsa import MAX_SUBBATCH  # per-program sub-batch cap

# With bulk mode warmed (--warm-bulk), one coalesced launch drains up to
# this many queued signatures as sub-batches of MAX_SUBBATCH scanned inside
# ONE program (ops/ed25519.verify_packed_chunked) — a dispatch has a
# fixed cost (not measured on the chip) that a scan pays once.  The
# cap bounds both the compiled scan lengths (g <= 16, the same shape
# bench.py measures) and how long a bulk backlog can occupy the engine
# ahead of consensus-latency QC verifies.  Without bulk warmup the launch
# cap stays at MAX_SUBBATCH so a live backlog can never trigger a
# first-time XLA compile on the engine thread.
MAX_COALESCED = 16 * MAX_SUBBATCH


# Back-compat alias: direct engine tests (and older embedders) wrap a
# (request, reply_fn) pair this way; scheduling metadata defaults to the
# latency class.
_Pending = vsched.Pending


from base64 import b64encode as _b64encode


# OP_BLS verify requests by kind: the guard's shape keys and OP_STATS
# ``bls.requests``.
_BLS_VERIFY_KINDS = {proto.BlsAggRequest: "agg",
                     proto.BlsVotesRequest: "votes",
                     proto.BlsMultiRequest: "multi"}


def _ctx_tag(request):
    """Protocol v5 block-digest context tag -> the base64 string the C++
    node logs in its TRACE lines (common/bytes.hpp base64_encode:
    standard alphabet, padded — python's b64encode matches), so
    obs/trace.py joins on string equality.  None when untagged.

    Callers must gate on ``tracer.enabled`` (the trace_stage cost
    discipline): the un-traced hot path never pays the encode."""
    ctx = getattr(request, "ctx", None)
    if not ctx:
        return None
    return _b64encode(ctx).decode("ascii")


def _ctx_tags(batch):
    """Distinct context tags across one coalesced launch (sorted for a
    stable span schema); empty when no request carried one."""
    tags = {_ctx_tag(p.request) for p in batch}
    tags.discard(None)
    return sorted(tags)


class _RequestSpan:
    """One traced request from its frame to its reply (traced runs only):
    the id its ``request`` root span will carry — handed out when the
    frame is read, so the ``decode``, ``queue`` and ``reply`` children
    can name it before it is written — and the stamps the root needs.
    The connection thread makes it, ``submit()`` carries it to the
    engine on the Pending, the connection's writer closes it."""

    __slots__ = ("tracer", "id", "rid", "t0", "admitted", "tags")

    def __init__(self, tracer, rid: int, t0: float, id=None,  # noqa: A002
                 **tags):
        self.tracer = tracer
        self.id = id            # None: an embedder's request, no root span
        self.rid = rid
        self.t0 = t0
        self.admitted = t0
        self.tags = tags

    def child(self, stage: str, t0: float, t: float | None = None, **tags):
        for key in ("cls", "ctx"):  # a child joins its block as the root does
            if key in self.tags:
                tags[key] = self.tags[key]
        self.tracer.record(stage, t0, t, rid=self.rid, parent=self.id,
                           **tags)

    def close(self, ok: bool, t: float | None = None):
        self.tracer.record("request", self.t0, t, id=self.id, rid=self.rid,
                           parent=None, ok=ok, **self.tags)

    def framed(self, frame: bytes, called: float):
        """The outbox item of a traced reply: ``(frame, sent)``; the
        writer calls ``sent(dequeued, t)`` when ``sendall`` returned, and
        that writes the ``reply`` span (``reply_fn`` called -> sent) and
        closes the root."""
        enqueued = self.tracer.now()

        def sent(dequeued: float, t: float):
            self.child("reply", called, t, bytes=len(frame),
                       outbox_ms=round((dequeued - enqueued) * 1e3, 3))
            self.close(True, t)

        return frame, sent


class ChaosState:
    """Protocol v3 fault-injection hook (OP_CHAOS, behind ``--chaos``).

    Lets the graftchaos harness exercise the *client-side* failure
    handling — C++ host fallback, python SidecarOverloaded, reconnect —
    without process murder, by making a healthy sidecar misbehave in
    three bounded, scripted ways:

      ``delay_ms``  every verify reply is delayed this long (capped at
                    MAX_DELAY_MS; 0 clears) — a slow/contended device
      ``drop``      the next N verify requests close their connection
                    instead of answering — a crashing sidecar, minus the
                    crash
      ``shed``      the next N verify requests get the explicit
                    queue-full backpressure reply — a saturated engine,
                    without needing to actually saturate it
      ``wedge``     the next N device launches HANG past their guard
                    deadline (graftguard): drives the full supervisor
                    ladder — host-fallback replies, quarantine,
                    crash-only reboot, canary — end to end through
                    OP_CHAOS, the fault a hung device call inflicts,
                    minus the device
      ``clear``     reset everything

    Chaos only touches verify/sign opcodes: PING stays honest so
    readiness probes (and the harness's own boot wait) keep working, and
    OP_STATS/OP_CHAOS stay reachable so a degraded sidecar can still be
    observed and un-degraded.  Delayed replies are rescheduled onto a
    timer — the connection's reader thread never sleeps, so a PING
    pipelined behind a delayed verify still answers immediately.
    """

    # Deliberately BELOW the C++ client's Ed25519 reply deadline
    # (TpuVerifier::kRecvTimeoutMs = 1000): a capped delay must model a
    # SLOW sidecar the client still waits out, never an expired request
    # — past the deadline the fault is indistinguishable from an outage,
    # which ``kill`` already scripts (and which would cascade into the
    # wedged-connection teardown + circuit breaker instead of the
    # scripted slow-reply behavior).
    MAX_DELAY_MS = 750

    def __init__(self):
        self._lock = threading.Lock()
        self.delay_ms = 0
        self.shed_left = 0
        self.drop_left = 0
        self.wedge_left = 0

    def configure(self, spec: dict) -> dict:
        """Apply one OP_CHAOS spec; raises ValueError on unknown keys or
        non-integer values (the connection closes, same contract as any
        malformed frame)."""
        unknown = set(spec) - {"delay_ms", "shed", "drop", "wedge",
                               "clear"}
        if unknown:
            raise ValueError(f"unknown chaos key(s) {sorted(unknown)}")
        vals = {}
        for key in ("delay_ms", "shed", "drop", "wedge"):
            if key in spec:
                v = spec[key]
                if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                    raise ValueError(f"chaos {key} must be an int >= 0")
                vals[key] = v
        with self._lock:
            if spec.get("clear"):
                self.delay_ms = self.shed_left = self.drop_left = 0
                self.wedge_left = 0
            if "delay_ms" in vals:
                self.delay_ms = min(vals["delay_ms"], self.MAX_DELAY_MS)
            if "shed" in vals:
                self.shed_left = vals["shed"]
            if "drop" in vals:
                self.drop_left = vals["drop"]
            if "wedge" in vals:
                self.wedge_left = vals["wedge"]
            applied = {"delay_ms": self.delay_ms, "shed": self.shed_left,
                       "drop": self.drop_left, "wedge": self.wedge_left}
        log.warning("chaos hook configured: %s", applied)
        return applied

    def take_wedge(self) -> bool:
        """Consume one scripted launch wedge (graftguard's OP_CHAOS
        drill); called by the engine at dispatch time, not per request
        — a wedge is a DEVICE fault, so it applies to whatever launch
        is next, exactly like the real thing."""
        with self._lock:
            if self.wedge_left > 0:
                self.wedge_left -= 1
                return True
            return False

    def verify_action(self):
        """Consume the chaos decision for one verify/sign request ->
        (drop: bool, shed: bool, delay_s: float)."""
        with self._lock:
            if self.drop_left > 0:
                self.drop_left -= 1
                return True, False, 0.0
            shed = self.shed_left > 0
            if shed:
                self.shed_left -= 1
            return False, shed, self.delay_ms / 1e3


class VerifyEngine:
    """Owns the device; single consumer thread draining scheduler launches."""

    def __init__(self, mesh_devices: int | None = None, use_host: bool = False,
                 committee: int | None = None,
                 client_rate: int | None = None,
                 tracer=None, guard=None, chaos=None, rewarm_fn=None,
                 cadence: bool = False, ring_factory=None):
        # All launch-shape policy lives in the scheduler subsystem: the
        # shape registry records what the warmup compiled (until
        # enable_bulk, launches cap at MAX_SUBBATCH; _warmup covers every
        # padded bucket up to that cap, so warmed deployments never hit a
        # first-time compile on this thread), and the two-class queues
        # decide what each launch contains.  The registry knows the mesh
        # size, so launch capacities and routes are shard-aligned on
        # multi-chip deployments.  Admission caps are sized from the
        # deployment (committee size drives latency-class demand, client
        # rate drives bulk) with env overrides winning — see
        # sched/scheduler.size_queue_caps.
        self._shapes = vsched.ShapeRegistry(
            use_host=use_host, n_devices=mesh_devices or 0,
            committee=committee)
        lat_cap, bulk_cap = vsched.size_queue_caps(
            committee=committee, client_rate=client_rate)
        self._sched = vsched.Scheduler(shapes=self._shapes,
                                       latency_cap_sigs=lat_cap,
                                       bulk_cap_sigs=bulk_cap,
                                       committee=committee)
        self._use_host = use_host
        # grafttrace: one span tree per request (request -> decode,
        # queue, reply; the connection handler writes the socket side)
        # and per launch (pack, dispatch, device -> h2d, fetch_wait, d2h,
        # bisect), on the tracer's one clock (obs/spans.py).  The null
        # tracer short-circuits every call; sites that would read its
        # clock or build tags gate on ``enabled``.
        from ..obs.spans import Tracer

        self._tracer = tracer if tracer is not None else Tracer.disabled()
        self._launches = 0   # launch counter: a launch's ``lid``
        # Device multi-digest pairing programs compile one shape per vote
        # count (minutes each); only counts warmed via _warmup_bls_multi
        # may launch on device — others verify on host so a surprise TC
        # size can never wedge this thread mid-traffic.
        self._bls_multi_warmed: set[int] = set()
        # graftkern compile accounting: serve() attaches a CompileTracker
        # (utils/xla_cache) on device-mode boots so the warmup's manifest
        # hit/miss counts and wall time ride the OP_STATS ``compile``
        # section; host-mode engines compile nothing and keep None.
        self.compile_tracker = None
        # The devices this engine launches on ({platform, kind, count});
        # serve() fills it after a device-mode warmup so a client can
        # tell a CPU sidecar from a TPU one.  Host-mode engines hold no
        # device and keep None.
        self.device_info = None
        # (msg, pk, sig) -> bool verdict, oldest first; see _cache_verdicts.
        self._verdicts: OrderedDict = OrderedDict()
        self._verdicts_lock = threading.Lock()
        # graftfleet dedup accounting: the verdict cache is keyed on
        # record BYTES, so under a shared fleet a QC gossiped to N
        # tenants' replicas is device-verified once and answered from
        # cache for everyone else.  cache_hits counts records answered
        # from the cross-request cache (connection fast path + pack
        # lookups), inbatch_hits records deduped within one coalesced
        # batch, misses records that actually rode a verify path.  The
        # hit-rate rides OP_STATS (``dedup``) and the strict parser
        # asserts it is non-zero under the greedy-flood drill.  inserts
        # counts verdicts written to the cache and evictions the entries
        # the cap pushed out for them (_cache_verdicts).
        self._dedup_cache_hits = 0
        self._dedup_inbatch_hits = 0
        self._dedup_misses = 0
        self._dedup_inserts = 0
        self._dedup_evictions = 0
        # graftguard: the launch supervisor (sidecar/guard.py).  When
        # attached (serve() always attaches one; direct embedders and
        # legacy tests may run bare), every staged dispatch/fetch wait
        # routes through _guarded under a per-shape deadline, a wedge
        # executes the degradation ladder instead of hanging this
        # thread, and the engine can crash-only reboot the device leg
        # off the warm cache (rewarm_fn) while the host path serves.
        self._guard = guard
        self._chaos = chaos
        self._rewarm_fn = rewarm_fn
        self._reboot_lock = threading.Lock()
        self._device_ok = True
        self._rebooting = False
        # THREAD-LOCAL rewarm marker: while the reboot thread runs
        # rewarm_fn, ITS calls into _verify_submit must hit the DEVICE
        # (that is what re-warming means) even though _device_ok is
        # still False — but live traffic on the pack worker must keep
        # host-routing for the whole window, so the flag cannot be
        # engine-global (an engine-global bool would leak concurrent
        # live launches onto the mid-rewarm device).
        self._rewarm_tls = threading.local()
        self._mesh = None
        if mesh_devices and mesh_devices > 1:
            from ..parallel.mesh import make_mesh

            self._mesh = make_mesh(mesh_devices)
        # Double-buffered dispatch: ONE pack worker stages the host side
        # of launch N+1 (byte decode, prepare_batch, h2d transfer) while
        # launch N executes on the device — the engine thread only ever
        # pays dispatch + fetch.  A single worker keeps pack order equal
        # to scheduler assembly order (the strict-priority guarantee
        # rides on it), and the single staged slot + the in-flight cap
        # bound how much work leaves the bounded class queues.
        from concurrent.futures import ThreadPoolExecutor

        self._pack_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="verify-pack")
        self._inflight_n = 0  # launches executing on device (telemetry)
        self._stopped = threading.Event()
        # graftcadence: the resident continuous-batching ring
        # (sidecar/ring.py).  Opt-in (--cadence / HOTSTUFF_TPU_CADENCE)
        # — the staged loop below stays the default until a committed
        # ``cadence`` bench headline shows the ring winning.  The ring
        # runs ON this engine thread first; a wedge fallback (or a
        # constructor without cadence) lands in the staged loop.
        if ring_factory is not None:
            # Tests inject rings with virtual clocks/waits; the factory
            # runs before the engine thread starts so the ring is in
            # place when _run checks for it.
            self._ring = ring_factory(self)
        elif cadence:
            from .ring import CadenceRing

            self._ring = CadenceRing(self)
        else:
            self._ring = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="verify-engine")
        self._thread.start()

    def submit(self, request, reply_fn, cls: str = vsched.LATENCY,
               is_bls: bool = False, tenant: str | None = None,
               span: _RequestSpan | None = None) -> bool:
        """Admit one request into its class queue.  Returns False on
        queue-full — nothing was retained and the CALLER must reply
        (the handler sends the explicit empty-mask backpressure reply);
        never blocks the calling connection thread.  ``span`` is the
        connection's bookkeeping for a traced request (None untraced)."""
        if cls == vsched.BULK and not is_bls:
            # graftingress feed mix: an admission-verify batch carries
            # the pinned ingress ctx tag; everything else on the bulk
            # lane is offchain-fed.  Counted on OFFER (before any shed)
            # so the mix stays honest under backpressure.
            from ..crypto.txsign import INGRESS_CTX

            self._sched.stats.note_bulk_source(
                getattr(request, "ctx", None) == INGRESS_CTX,
                len(getattr(request, "msgs", ()) or ()))
        if self._rebooting and cls == vsched.BULK and not is_bls:
            # Crash-only reboot in progress (graftguard): the device leg
            # is re-warming and the host path is reserved for consensus
            # latency — bulk gets an honest BUSY NOW (the handler's
            # queue-full reply carries the retry-after hint), so the C++
            # breaker reads a live, rebooting sidecar, never silence.
            if self._guard is not None:
                self._guard.stats.note_busy()
            return False
        if self._tracer.enabled:
            now = self._tracer.now()
            if span is None:
                span = _RequestSpan(self._tracer, request.request_id, now)
            span.admitted = now
        return self._sched.offer(request, reply_fn, cls=cls, is_bls=is_bls,
                                 tenant=tenant, span=span)

    def retry_after_ms(self, cls: str) -> int:
        """Hint for a BUSY reply after a shed of class ``cls`` (the
        scheduler's surge controller turns queue depth + drain rate
        into milliseconds)."""
        return self._sched.retry_after_ms(cls)

    def stats_snapshot(self) -> dict:
        """The OP_STATS reply body: scheduler telemetry + warmed shapes."""
        snap = self._sched.stats.snapshot()
        snap["shapes"] = self._shapes.snapshot()
        snap["queue_caps"] = self._sched.queue_caps()
        snap["verdict_cache_entries"] = len(self._verdicts)
        with self._verdicts_lock:
            hits = self._dedup_cache_hits + self._dedup_inbatch_hits
            seen = hits + self._dedup_misses
            snap["dedup"] = {
                "cache_hits": self._dedup_cache_hits,
                "inbatch_hits": self._dedup_inbatch_hits,
                "misses": self._dedup_misses,
                "hit_rate": round(hits / seen, 4) if seen else 0.0,
                "inserts": self._dedup_inserts,
                "evictions": self._dedup_evictions,
            }
        snap["tenant_caps"] = self._sched.tenant_caps()
        occupancy = self._sched.tenant_occupancy()
        if any(occupancy.values()):
            snap["tenant_occupancy"] = occupancy
        if self.compile_tracker is not None:
            snap["compile"] = self.compile_tracker.snapshot()
        if self.device_info is not None:
            snap["device"] = self.device_info
        if self._guard is not None:
            g = self._guard.snapshot()
            g["device_ok"] = self._device_ok
            g["rebooting"] = self._rebooting
            snap["guard"] = g
        if self._ring is not None:
            # graftcadence: tick rate, occupancy hist, pad-fill ratio,
            # generation drops, queue-wait p50/p99 (sidecar/ring.py).
            snap["cadence"] = self._ring.snapshot()
        return snap

    # graftlint: sanitizes=device-verdict
    def cached_verdicts(self, request):
        """[bool] if EVERY (msg, pk, sig) record of this Ed25519 verify
        request already has a cached verdict, else None.  Called from
        connection threads (see _Handler.handle's fast path) without the
        lock the writers hold (_cache_verdicts): a concurrent eviction can
        at worst turn a hit into a miss."""
        verdicts = self._verdicts
        out = []
        for rec in zip(request.msgs, request.pks, request.sigs):
            v = verdicts.get(rec)
            if v is None:
                return None
            out.append(v)
        if out:
            with self._verdicts_lock:
                self._dedup_cache_hits += len(out)
        return out

    @staticmethod
    def bls_cache_key(req):
        """Verdict-cache key for a BLS verify request, or None if the op
        is uncacheable (signing).  Validity is a pure function of the
        request's own bytes, so the whole request keys the verdict — a
        pairing costs seconds on the host, and what a certificate costs
        on the device path is in PERF.md §5 (``qc100bls.votes``), making
        the N-replicas-one-certificate dedup worth far more here than for
        Ed25519."""
        import hashlib

        def h(tag, *parts):
            # Fixed 32-byte keys: BLS requests embed every pk+sig (~32 KB
            # for a 100-vote TC), which would inflate the FIFO's ~15 MB
            # bound 100x if stored verbatim.  Length-prefixed parts keep
            # the encoding injective before hashing.
            d = hashlib.sha256(tag)
            for p in parts:
                seq = p if isinstance(p, (list, tuple)) else (p,)
                d.update(len(seq).to_bytes(4, "big"))  # list boundary
                for b in seq:
                    d.update(len(b).to_bytes(4, "big"))
                    d.update(b)
            return d.digest()

        if isinstance(req, proto.BlsMultiRequest):
            return ("bm", h(b"bm", req.msgs, req.pks, req.sigs))
        if isinstance(req, proto.BlsVotesRequest):
            return ("bv", h(b"bv", req.msg, req.pks, req.sigs))
        if isinstance(req, proto.BlsAggRequest):
            return ("ba", h(b"ba", req.msg, req.pks, req.agg_sig))
        return None

    # graftlint: sanitizes=device-verdict
    def cached_bls_verdict(self, req):
        """[bool] reply if this BLS verify request's verdict is cached,
        else None.  Connection-thread-safe for the same reason as
        cached_verdicts."""
        key = self.bls_cache_key(req)
        if key is None:
            return None
        v = self._verdicts.get(key)
        if v is None:
            return None
        with self._verdicts_lock:
            self._dedup_cache_hits += 1
        return [v]

    def enable_bulk(self):
        """Raise the per-launch cap to MAX_COALESCED; call only after the
        chunked-scan shapes have been compiled (see _warmup_bulk)."""
        self._shapes.enable_bulk(MAX_COALESCED)

    def stop(self):
        self._stopped.set()
        self._sched.wake()  # wake consumer

    # -- consumer ----------------------------------------------------------

    # Ed25519 launches kept in flight before the oldest result is fetched
    # (STAGED path only).  With a pack pending and room here, the engine
    # waits for that pack and dispatches launch n+1 BEFORE it drains
    # launch n, so n's fetch, d2h, verdict-cache insert, fan-out and
    # replies run beside n+1's program instead of in front of it: on
    # the chip eddsa1024.flood went from 71,219 to 87,040 sigs/s with it
    # (PERF.md §6, PR 38), where draining first had left no two
    # `device` spans overlapping in 1,288 launches.  A launch whose
    # result is in waits for a pack that outlasts it: draining it early
    # read slower in both bulk cells, since its Python then runs beside
    # the pack.  Depth 2 keeps one program queued behind the running one;
    # deeper only adds reply latency.  On top of the dispatch depth sits
    # ONE pack slot (the pack worker in __init__): while up to two
    # launches execute, the host side of the next launch — byte decode,
    # prepare_batch, h2d — is already staging; the device waits for it
    # only where a pack outlasts the program before it.
    # Knob hygiene: a constant, not an env knob — the cadence ring
    # (sidecar/ring.py) generalizes it to a TRAINED depth k in {2,4,8}
    # (RingDepth, swept in the bench ``cadence`` headline), so anyone
    # needing depth > 2 turns the ring on rather than growing a second
    # depth knob here.
    PIPELINE_DEPTH = 2

    def _run(self):
        """Engine thread body: the cadence ring first when one is
        attached (graftcadence; returns on stop or on wedge fallback
        with every in-flight generation answered), then the staged
        request-driven loop — the DEFAULT path and the ladder's landing
        zone."""
        ring = self._ring
        if ring is not None:
            ring.run()
            if self._stopped.is_set():
                self._pack_pool.shutdown(wait=False)
                return
        self._run_staged()

    def _run_staged(self):
        import collections
        from concurrent import futures as cfut

        packing = collections.deque()   # (batch, Future[dispatch_fn],
                                        #  launch scope)
        inflight = collections.deque()  # (batch, fetch_fn, guard_key,
                                        #  launch scope, dispatched_at,
                                        #  dispatch hop)
        while not self._stopped.is_set():
            # 1) A pending pack with dispatch room goes onto the device
            #    as soon as it is done — BEFORE the launch in flight is
            #    drained, so that launch's fetch, insert and replies run
            #    beside the next program.  The pack is waited on in
            #    bounded slices: stop() stays observable mid-pack.
            if packing and len(inflight) < self.PIPELINE_DEPTH:
                try:
                    packing[0][1].exception(timeout=0.25)
                except cfut.TimeoutError:
                    continue
                self._dispatch_one(packing, inflight)
                continue
            # 2) A free pack slot admits the next scheduler launch.
            if not packing:
                idle = not inflight
                # Bounded wait when idle so a stop() that races the
                # wait's entry is still observed promptly (same poll
                # discipline as serve_forever).
                launch = self._sched.next_launch(timeout=0.25) if idle \
                    else self._sched.next_launch(block=False)
                if launch is not None:
                    scope = self._begin_launch(launch)
                    # BLS requests run individually (a QC aggregate is
                    # one check; there is nothing to coalesce) on the
                    # same device thread, after the whole Ed25519
                    # pipeline drains.
                    if launch.kind == "bls":
                        (item,) = launch.items
                        while inflight:
                            self._drain_one(inflight)
                        tags = {}
                        if scope.enabled:
                            # The launch's own id: the BLS stages
                            # (bls_prep ... d2h) name it as parent.
                            tags = {"lid": scope.lid, "parent": None,
                                    "id": scope.device_id}
                            ctx = _ctx_tag(item.request)
                            if ctx:
                                # v5 context tag: scheme=bls device spans
                                # join the tagged block's trace exactly
                                # like EdDSA ones (ROADMAP item-2 parity).
                                tags["ctx"] = ctx
                        with self._tracer.span(
                                "device", kind="bls",
                                rid=item.request.request_id, **tags):
                            # Single-reply discipline: _execute_bls owns
                            # its whole failure surface and replies
                            # EXACTLY once through its idempotent
                            # helper — no backstop reply here (the old
                            # one could double-reply when an exception
                            # escaped after a success path had already
                            # answered, e.g. a wedged-then-completing
                            # pairing).
                            self._execute_bls(item, scope)
                        continue
                    batch = launch.items
                    packing.append(
                        (batch, self._pack_pool.submit(self._pack, batch,
                                                       scope), scope))
                    continue
                if idle:
                    continue
            # 3) Pipeline full, or no pack pending: drain the oldest
            #    launch (the one after it, if any, is already running).
            if inflight:
                self._drain_one(inflight)
        # Shutdown: every accepted request still gets its reply (clients
        # would otherwise block until their recv deadline and report a
        # spurious transport failure).
        while packing:
            self._dispatch_one(packing, inflight)
        while inflight:
            self._drain_one(inflight)
        self._pack_pool.shutdown(wait=False)

    def _begin_launch(self, launch):
        """Number the launch the scheduler just assembled (its ``lid``)
        and, when tracing, bind the tracer to it and write one ``queue``
        span per item (admission -> launch assembly, the same wait the
        OP_STATS reservoirs sample) naming the ``lid`` it left for.
        Returns the launch's scope (the null scope untraced)."""
        self._launches += 1
        if not self._tracer.enabled:
            return NO_LAUNCH
        scope = self._tracer.launch(self._launches)
        now = scope.now()
        for p in launch.items:
            tags = {}
            ctx = _ctx_tag(p.request)
            if ctx:
                tags["ctx"] = ctx
            span = p.span
            self._tracer.record(
                "queue", span.admitted if span is not None else now, now,
                rid=p.request.request_id, cls=p.cls, lid=scope.lid,
                parent=span.id if span is not None else None, **tags)
        return scope

    def _last_hop_s(self) -> float:
        """Thread hop of this thread's last guarded call."""
        return self._guard.last_hop_s if self._guard is not None else 0.0

    def _trace_dispatch(self, scope, batch, t0: float, **tags):
        """The ``dispatch`` span of a traced launch, written by the
        staged loop and the ring alike: the engine took the pack at
        ``t0`` and ``_guarded`` has just returned the fetch closure
        (pack-future wait, guard hop and the jitted call included).
        Returns (now, the dispatch's guard hop in seconds)."""
        t = scope.now()
        hop_s = self._last_hop_s()
        ctxs = _ctx_tags(batch)
        if ctxs:
            tags["ctxs"] = ctxs
        waited = scope.pack_end - t0 if scope.pack_end is not None else 0.0
        scope.record("dispatch", t0, t, reqs=len(batch),
                     wait_pack_ms=round(max(0.0, waited) * 1e3, 3),
                     hop_ms=round(hop_s * 1e3, 3), **tags)
        return t, hop_s

    def _trace_device(self, scope, batch, t0: float, hop_s: float, **tags):
        """The ONE ``device`` span of a traced launch (dispatch returned
        at ``t0`` -> fetch returned, now): it includes the d2h copy,
        exactly what the engine pays.  ``hop_ms`` is the guard hop of
        the dispatch (``hop_s``) and of the fetch together; ``tenants``
        the distinct HELLO names among its ``reqs`` requests; ``bucket``
        the padded rows its program ran (``_pack`` left them on the
        scope)."""
        ctxs = _ctx_tags(batch)
        if ctxs:
            tags["ctxs"] = ctxs
        scope.record(
            "device", t0, id=scope.device_id, reqs=len(batch),
            sigs=sum(len(p.request.msgs) for p in batch),
            tenants=len({p.tenant for p in batch}), bucket=scope.bucket,
            hop_ms=round((hop_s + self._last_hop_s()) * 1e3, 3), **tags)

    def _guard_key(self, batch) -> str:
        """Launch-shape key for the guard's per-shape deadlines: the
        power-of-two bucket of the DEDUPED record count — the shape the
        launch actually executes (the pack stage dedups before
        dispatch), so p99 history trained under the shared-sidecar
        headline load (N replicas submitting the SAME QC: raw total >>
        unique) can never tighten the deadline of a genuinely-large
        unique batch that shares a raw total with it.  Sliced launches
        stay self-consistent: the same key always runs the same slice
        count.  The dedup costs one hash pass on the engine thread —
        small next to the launch it sizes, and only the wedge-protected
        path pays it."""
        from ..crypto.eddsa import next_pow2

        uniq = len({rec for p in batch
                    for rec in zip(p.request.msgs, p.request.pks,
                                   p.request.sigs)})
        return f"launch:{next_pow2(max(8, uniq))}"

    def _guarded(self, key: str, thunk):
        """THE deadline helper: every engine-side wait on a staged
        dispatch/fetch future routes through here (graftlint's
        unsupervised-launch rule pins it).  With a guard attached the
        thunk runs on a disposable launch thread under the shape's
        deadline — a WedgedLaunch out of here means the monitor
        declared an overrun and the worker was abandoned.  The chaos
        hook's ``wedge`` knob swaps the thunk for a genuine hang, so
        the scripted drill exercises the identical supervisor path."""
        chaos = self._chaos
        if chaos is not None and self._guard is not None and \
                chaos.take_wedge():
            log.warning("chaos: wedging launch %s", key)

            def thunk():
                # The injected fault IS an unbounded wait: a faithful
                # stand-in for a hung device call.  It parks
                # the disposable launch thread, never this one.
                # graftlint: disable=unsupervised-launch
                threading.Event().wait()
        if self._guard is None:
            return thunk()
        return self._guard.call(key, thunk)

    def _dispatch_one(self, packing, inflight):
        """Move the oldest staged pack onto the device (engine thread)."""
        batch, fut, scope = packing.popleft()
        t0 = scope.now() if scope.enabled else 0.0
        key = self._guard_key(batch)
        try:
            # wait for pack, then device dispatch — both touch the
            # device (pack stages the h2d transfer), so both run under
            # the one guarded deadline
            with scope.annotate("dispatch"):
                fetch = self._guarded(key, lambda: fut.result()())
        except WedgedLaunch:
            self._wedge_ladder(batch, key, stage="dispatch")
            return
        except Exception:
            log.exception("verify batch pack/dispatch failed")
            for p in batch:
                p.reply_fn([False] * len(p.request.msgs))
            return
        ahead = len(inflight)  # launches this one is queued behind
        self._sched.stats.note_dispatch(ahead)
        dispatched_at, hop_s = self._trace_dispatch(
            scope, batch, t0, ahead=ahead) if scope.enabled else (0.0, 0.0)
        inflight.append((batch, fetch, key, scope, dispatched_at, hop_s))
        self._inflight_n = len(inflight)

    def _drain_one(self, inflight):
        batch, fetch, key, scope, dispatched_at, hop_s = inflight.popleft()
        self._inflight_n = len(inflight)
        try:
            mask = self._guarded(key, fetch)
        except WedgedLaunch:
            self._wedge_ladder(batch, key, stage="fetch")
            return
        except Exception:
            log.exception("verify batch failed")
            for p in batch:
                p.reply_fn([False] * len(p.request.msgs))
            return
        if scope.enabled:
            self._trace_device(scope, batch, dispatched_at, hop_s)
        off = 0
        for p in batch:
            n = len(p.request.msgs)
            p.reply_fn([bool(b) for b in mask[off:off + n]])
            off += n

    # -- graftguard: the wedge degradation ladder ---------------------------

    def _wedge_ladder(self, batch, key: str, stage: str):
        """A launch overran its deadline: execute the degradation ladder
        instead of hanging (graftguard).

        1. every latency-class request in the wedged batch is answered
           from the HOST path — ``ref_ed25519.verify`` per record, the
           reference ``verify_batch`` is property-tested bit-identical
           to, so a wedge changes WHERE the verdict came from, never
           what it is;
        2. bulk-class requests get BusyReply (the handler encodes
           OP_BUSY with the drain-derived retry-after) — throughput
           work re-offers once the device leg is back;
        3. the batch's records are quarantined (repeat offenders feed
           the poison bisection after the reboot);
        4. a crash-only engine reboot begins (async; the host path
           serves meanwhile)."""
        from ..crypto import ref_ed25519 as ref

        guard = self._guard
        log.error("guard: %s of launch %s WEDGED (deadline overrun); "
                  "executing degradation ladder", stage, key)
        records = {rec for p in batch if not p.is_bls
                   for rec in zip(p.request.msgs, p.request.pks,
                                  p.request.sigs)}
        pending = guard.quarantine.note_wedged(records)
        if pending:
            log.error("guard: %d repeat-offender record(s) pending "
                      "poison bisection", pending)

        def answer():
            for p in batch:
                if p.cls == vsched.BULK:
                    guard.stats.note_busy()
                    p.reply_fn(
                        BusyReply(self.retry_after_ms(vsched.BULK)))
                    continue
                mask = [bool(ref.verify(pk, m, s))
                        for m, pk, s in zip(p.request.msgs,
                                            p.request.pks,
                                            p.request.sigs)]
                guard.stats.note_host_fallback(len(mask))
                p.reply_fn(mask)

        # The host fallback runs OFF the engine thread: a wedged batch
        # at the coalesced cap is tens of seconds of pure-python
        # verification, and the queued consensus verifies behind it —
        # about to be host-routed by the reboot flag — must drain
        # concurrently, not wait out the very head-of-line stall the
        # supervisor exists to kill.  One-shot body, reply_fn is
        # thread-safe (outbox.put_nowait), no loop to stop.
        # graftlint: disable=daemon-thread-without-stop-flag
        threading.Thread(target=answer, daemon=True,
                         name="guard-ladder").start()
        self._begin_reboot()

    def _begin_reboot(self):
        """Start the crash-only engine reboot (idempotent: repeat wedges
        while one is running fold into it).  Device routing flips OFF
        first — from here until the canary passes, _pack routes every
        launch down the host path and bulk admission replies BUSY."""
        with self._reboot_lock:
            if self._rebooting:
                return
            self._rebooting = True
            self._device_ok = False
        t = threading.Thread(target=self._reboot, daemon=True,
                             name="guard-reboot")
        t.start()

    def _reboot(self):
        """Crash-only reboot of the device leg: tear down the compiled-
        program state, re-warm off the populated XLA cache/manifest
        (rewarm_fn — no recompile, but still a re-trace per shape: on
        the chip 174 s against 515 s cold for ten shapes, PERF.md
        PR 22), and resume device routing only after
        a canary launch passes under the guard's deadline.  Canary
        failures retry up to the guard's max_reboots; past that the
        engine stays on the host path — degraded, live, and visible in
        OP_STATS rather than wedged."""
        guard = self._guard
        t0 = monotonic()
        attempts = 0
        while not self._stopped.is_set():
            attempts += 1
            try:
                self._teardown_device()
                t_warm = monotonic()
                if self._rewarm_fn is not None:
                    # The warmup legs must reach the DEVICE path even
                    # though live routing is host-only right now —
                    # without this, _warm_shapes' engine._verify calls
                    # would "warm" the ladder shapes on the host and
                    # compile nothing, leaving the first post-canary
                    # launch to pay a re-trace under a tight warmed
                    # deadline (a guaranteed re-wedge).  Thread-local:
                    # only THIS thread's verifies force the device;
                    # live traffic keeps host-routing meanwhile.
                    # threading.local: this write is visible ONLY to
                    # the reboot thread — unshared by construction, so
                    # no lock can be needed (that isolation is the fix:
                    # an engine-global flag here leaked live launches
                    # onto the mid-rewarm device).
                    # graftlint: disable=unlocked-shared-write
                    self._rewarm_tls.active = True
                    try:
                        self._rewarm_fn()
                    finally:
                        # graftlint: disable=unlocked-shared-write
                        self._rewarm_tls.active = False
                guard.stats.note_rewarm(monotonic() - t_warm)
                if self._canary():
                    guard.stats.note_canary(True)
                    break
                guard.stats.note_canary(False)
            except Exception:
                log.exception("guard: reboot attempt %d failed", attempts)
                guard.stats.note_canary(False)
            if attempts >= guard.max_reboots:
                log.error("guard: %d reboot attempt(s) failed the canary;"
                          " staying on the host path", attempts)
                with self._reboot_lock:
                    self._rebooting = False
                return
        if self._stopped.is_set():
            return  # engine teardown mid-reboot: nothing left to resume
        # Poison bisection BEFORE resuming device routing: the repeat-
        # offender records must be isolated while the host path still
        # owns live traffic, or the first post-reboot launch could
        # re-wedge on the same poison.
        try:
            self._bisect_quarantine()
        except Exception:
            log.exception("guard: poison bisection failed (pending "
                          "records stay quarantined)")
        with self._reboot_lock:
            self._rebooting = False
            self._device_ok = True
        wall = monotonic() - t0
        guard.stats.note_reboot(wall)
        log.warning("guard: engine rebooted in %.1fs (canary passed "
                    "after %d attempt(s)); device routing resumed",
                    wall, attempts)

    def _teardown_device(self):
        """Crash-only teardown of the device-side state: drop the
        in-process compiled-program caches so the re-warm rebuilds
        every staged entry from the persistent XLA disk cache.
        Host-mode engines have nothing to tear down."""
        if self._use_host:
            return
        try:
            import jax

            jax.clear_caches()
        except Exception:  # noqa: BLE001 — teardown is best-effort
            log.exception("guard: jax cache teardown failed (continuing)")

    def _canary(self) -> bool:
        """One tiny known-good launch through the REAL staged verify
        entry, under the guard's deadline: device routing resumes only
        when this completes in time with an all-valid mask."""
        from ..crypto import ref_ed25519 as ref

        sk = bytes(range(32))
        _, pk = ref.generate_keypair(sk)
        msg = b"\x07" * 32
        sig = ref.sign(sk, msg)
        n = 8
        try:
            mask = self._guard.call(
                "canary:8",
                lambda: np.asarray(self._verify_submit(
                    [msg] * n, [pk] * n, [sig] * n, force_device=True)()))
        except WedgedLaunch:
            log.error("guard: canary launch wedged")
            return False
        except Exception:
            log.exception("guard: canary launch failed")
            return False
        return bool(np.asarray(mask).all())

    def _bisect_quarantine(self):
        """Poison-record bisection (the RLC bisection discipline applied
        to wedges): probe subsets of the repeat-offender records
        through guarded device launches until the minimal poison set is
        isolated; confirmed poison records are host-verified forever
        after (_pack's poison lane)."""
        guard = self._guard
        pending = guard.quarantine.pending()
        if not pending:
            return
        log.warning("guard: bisecting %d repeat-offender record(s) for "
                    "poison", len(pending))

        def probe(subset):
            msgs = [r[0] for r in subset]
            pks = [r[1] for r in subset]
            sigs = [r[2] for r in subset]
            try:
                self._guard.call(
                    f"poison-probe:{len(subset)}",
                    lambda: np.asarray(self._verify_submit(
                        msgs, pks, sigs, force_device=True)()))
                return True
            except WedgedLaunch:
                return False
            except Exception:
                # A clean failure means the launch COMPLETED (the device
                # is not wedged); the record merely verifies False.
                return True

        poison = bisect_poison(pending, probe,
                               max_probes=guard.max_bisect_probes)
        n = guard.quarantine.resolve(poison)
        if n:
            log.error("guard: %d poison record(s) quarantined to the "
                      "host path permanently", n)

    def _submit(self, batch):
        """Two-stage form of the launch path (pack + dispatch in one
        call) for embedders without a pack thread; returns fetch() ->
        concatenated mask."""
        return self._pack(batch)()

    def _pack(self, batch, scope=NO_LAUNCH):
        """Host-side pack stage of one coalesced batch (runs on the pack
        worker): byte concat, verdict-cache lookups, in-batch dedup,
        route selection, host preparation and the h2d transfers.  Returns
        ``dispatch() -> fetch()`` — dispatch fires the (donated) device
        program from the engine thread; the host path computes eagerly
        here instead.  ``scope`` is the tracer bound to this launch
        (``_begin_launch``): the ``pack`` span is written here, and the
        single-chip pack functions write the launch's ``h2d``,
        ``fetch_wait``, ``d2h``, ``bisect`` and ``bisect_step`` spans
        through it; the returned fetch writes ``cache_insert``.

        Verdict cache: signature validity is a pure function of the
        (msg, pk, sig) bytes, so records already verified are answered
        from a bounded FIFO cache without touching the device.  On a
        shared sidecar (the local testbed runs up to 100 replicas against
        ONE sidecar process) every replica verifies the same QC — the
        cache turns N identical quorum verifications per block into one
        device launch plus N-1 lookups.  (Cache reads here happen off the
        engine thread, same dict-read-under-GIL safety as the connection
        threads' fast path; the engine thread stays the only writer.)"""
        t0 = monotonic()
        span_t0 = scope.now() if scope.enabled else 0.0
        hidden = self._inflight_n > 0  # device busy while we pack
        msgs, pks, sigs = [], [], []
        for p in batch:
            msgs += p.request.msgs
            pks += p.request.pks
            sigs += p.request.sigs
        records = list(zip(msgs, pks, sigs))
        cached = [self._verdicts.get(r) for r in records]
        # Dedup WITHIN the batch too: the headline scenario is N replicas
        # verifying the same QC concurrently, whose identical records land
        # in ONE coalesced batch — before anything is cached.  Each unique
        # missed record is dispatched once and fanned out to every index
        # that carried it.
        uniq: dict = {}
        for i, c in enumerate(cached):
            if c is None:
                uniq.setdefault(records[i], []).append(i)
        uniq_records = list(uniq.keys())
        n_cached = sum(1 for c in cached if c is not None)
        if records:
            with self._verdicts_lock:
                self._dedup_cache_hits += n_cached
                self._dedup_inbatch_hits += \
                    len(records) - n_cached - len(uniq_records)
                self._dedup_misses += len(uniq_records)
        # graftguard poison lane: records the bisection confirmed poison
        # are split OUT of the device launch and verified on host right
        # here (pure host work on the pack worker) — a cursed record is
        # still answered and counted, but can never take the device leg
        # down again, and its co-batched neighbors still ride the device.
        guard = self._guard
        poisoned = []
        if guard is not None and guard.quarantine.has_poison():
            device_records = [r for r in uniq_records
                              if not guard.quarantine.is_poisoned(r)]
            if len(device_records) != len(uniq_records):
                poisoned = [r for r in uniq_records
                            if guard.quarantine.is_poisoned(r)]
                # Poison lane LAST so fetch order matches record order.
                uniq_records = device_records + poisoned
                guard.stats.note_poison_host(len(poisoned))
        else:
            device_records = uniq_records
        m_msgs = [r[0] for r in device_records]
        m_pks = [r[1] for r in device_records]
        m_sigs = [r[2] for r in device_records]
        # Route via the warmed-shape registry: batches of RLC_MIN_LAUNCH+
        # unique records whose padded (per-shard, on a mesh) bucket the
        # RLC warmup compiled pay ONE Straus MSM — single-chip via
        # crypto/eddsa.verify_batch_rlc_pack, mesh via
        # parallel/sharded_verify.verify_rlc_sharded_pack — instead of
        # per-signature ladders; when the combined check fails, one
        # per-signature launch over the same rows (on a mesh, a
        # bisection) keeps the verdict mask bit-identical.  While a
        # crash-only reboot is re-warming the device leg (graftguard),
        # everything routes host — the path the ladder already answers
        # wedged batches from.
        stats = self._sched.stats
        path = vsched.PATH_HOST if not self._device_ok \
            else self._shapes.route(len(device_records))
        if device_records:
            stats.note_path(path)

        if not device_records:
            dispatchers = []
        elif path == vsched.PATH_RLC:
            from ..crypto import eddsa

            dispatchers = [eddsa.verify_batch_rlc_pack(
                m_msgs, m_pks, m_sigs, on_bisect=stats.note_bisect,
                on_resolved=stats.note_bisect_resolved, trace=scope)]
        elif path in (vsched.PATH_RLC_SHARDED, vsched.PATH_LADDER_SHARDED,
                      vsched.PATH_SCAN_SHARDED, vsched.PATH_MESH):
            dispatchers = self._pack_sharded(path, m_msgs, m_pks, m_sigs,
                                             stats.note_bisect)
        elif path == vsched.PATH_HOST:
            # Host verification is pure host work — it runs right here on
            # the pack worker (per sub-batch, the pre-scheduler slicing
            # discipline), overlapping whatever the device is doing.
            fetchers = [self._verify_submit(m_msgs[i:i + MAX_SUBBATCH],
                                            m_pks[i:i + MAX_SUBBATCH],
                                            m_sigs[i:i + MAX_SUBBATCH])
                        for i in range(0, len(m_msgs), MAX_SUBBATCH)]
            dispatchers = [(lambda f=f: f) for f in fetchers]
        else:
            # Single-chip per-signature ladders: up to a whole launch-cap
            # window per dispatch, so the fixed per-dispatch cost is
            # paid once.  A single request larger than the cap (the
            # coalescer only bounds *additional* requests) is still
            # sliced here so no request can force an unwarmed compile
            # shape or an unbounded device allocation.
            from ..crypto import eddsa

            step = self._shapes.launch_cap
            dispatchers = [eddsa.verify_batch_pack(m_msgs[i:i + step],
                                                   m_pks[i:i + step],
                                                   m_sigs[i:i + step],
                                                   trace=scope)
                           for i in range(0, len(m_msgs), step)]
        if poisoned:
            # Poison lane: quarantined records verify on HOST, eagerly,
            # here on the pack worker (same discipline as PATH_HOST).
            from ..crypto import ref_ed25519 as ref

            res = np.array([bool(ref.verify(pk, m, s))
                            for m, pk, s in poisoned])
            dispatchers.append(lambda res=res: (lambda: res))
        stats.note_pack(monotonic() - t0, hidden)
        if scope.enabled:
            pack_tags = {}
            pack_ctxs = _ctx_tags(batch)
            if pack_ctxs:
                pack_tags["ctxs"] = pack_ctxs
            # Rows the staged program runs: none where none was staged
            # (every record cached, or the host path).
            on_device = device_records and path != vsched.PATH_HOST
            scope.bucket = self._shapes.bucket_capacity(
                len(device_records)) if on_device else 0
            scope.pack_end = scope.now()
            scope.record("pack", span_t0, scope.pack_end,
                         reqs=len(batch), uniq=len(uniq_records),
                         path=path, hidden=hidden,
                         rids=[p.request.request_id for p in batch],
                         **pack_tags)

        def dispatch():
            fetchers = [d() for d in dispatchers]

            def fetch():
                fresh = []
                for f in fetchers:
                    fresh.extend(f())
                verdicts = list(zip(uniq_records, map(bool, fresh)))
                with scope.stage("cache_insert") as tags:
                    evicted = self._cache_verdicts(verdicts)
                    if tags is not None:
                        tags["n"] = len(verdicts)
                        tags["evicted"] = evicted
                mask = list(cached)
                for record, ok in verdicts:
                    for i in uniq[record]:
                        mask[i] = ok
                return mask

            return fetch

        return dispatch

    def _pack_sharded(self, path, msgs, pks, sigs, on_bisect):
        """Pack-stage dispatchers for the mesh routes: RLC launches go
        whole (one MSM across the mesh); scan-routed backlogs go whole
        too (ONE chunked whole-backlog program — graftscale); ladder
        launches slice at the launch cap like the single-chip path.
        Every launch's per-shard buckets (one per slice) land in the
        OP_STATS histogram — counted once per LAUNCH, so the mesh
        launch count stays comparable to the scheduler's own — and scan
        launches land in the ``scan`` section with their chunk count."""
        from ..crypto.eddsa import prepare_batch
        from ..parallel import sharded_verify as shv

        stats = self._sched.stats
        if path == vsched.PATH_RLC_SHARDED:
            stats.note_mesh_launch(
                [self._shapes.shard_bucket_of(len(msgs))])
            return [shv.verify_rlc_sharded_pack(
                self._mesh, prepare_batch(msgs, pks, sigs),
                on_bisect=on_bisect)]
        if path == vsched.PATH_SCAN_SHARDED:
            shape = self._shapes.scan_shape_of(len(msgs))
            if shape is not None:
                # The whole coalesced backlog in ONE dispatch.  The
                # registry only answers this route for chunk counts the
                # warmup marked (mesh_chunks), so an unwarmed scan
                # shape can never compile mid-run; slices_avoided
                # counts the per-MAX_SUBBATCH ladder dispatches the
                # pre-graftscale mesh path would have paid (its launch
                # cap never rose past MAX_SUBBATCH).
                g, rows = shape
                stats.note_scan_launch(
                    g, len(msgs), -(-len(msgs) // MAX_SUBBATCH) - 1)
                return [shv.verify_sharded_chunked_pack(
                    self._mesh, prepare_batch(msgs, pks, sigs),
                    rows=rows)]
            # Defensive fallback (the registry only ever grows, so the
            # shape cannot have vanished since route()): slice below.
        # Slice at the WARMED ladder cap, not launch_cap: enable_bulk
        # raises launch_cap to the scan capacity, and a slice that size
        # would land on a per-shard bucket only the scan programs were
        # compiled for (see ShapeRegistry.ladder_cap).
        step = self._shapes.ladder_cap()
        # graftcadence: while the ring is engaged, every ladder slice
        # arms at the ring's FIXED shard-aligned shape (the ladder-cap
        # bucket — warmed) instead of the slice's own bucket, so each
        # cadence tick re-dispatches ONE resident compiled program
        # (parallel/sharded_verify.ring_slot_pack) with the slack rows
        # dead (present=0) rather than a different shape per fill level.
        ring = self._ring
        ring_rows = None
        if ring is not None and ring.enabled and self._mesh is not None:
            ring_rows = shv.shard_aligned_rows(
                step, self._mesh.devices.size, MAX_SUBBATCH)
        buckets, out = [], []
        for i in range(0, len(msgs), step):
            sl = slice(i, i + step)
            n = len(msgs[sl])
            if ring_rows is not None:
                buckets.append(self._shapes.shard_bucket_of(ring_rows))
                out.append(shv.ring_slot_pack(
                    self._mesh, prepare_batch(msgs[sl], pks[sl], sigs[sl]),
                    ring_rows))
                continue
            buckets.append(self._shapes.shard_bucket_of(n))
            out.append(shv.verify_batch_sharded_pack(
                self._mesh, prepare_batch(msgs[sl], pks[sl], sigs[sl])))
        stats.note_mesh_launch(buckets)
        return out

    # Verdict-cache capacity: ~224 B/record key; 64k entries ~ 15 MB.
    VERDICT_CACHE_CAP = 64 * 1024

    def _cache_verdicts(self, verdicts) -> int:
        """Write one launch's ``(record, ok)`` pairs into the verdict
        cache under ONE hold of the lock; returns how many entries the
        cap evicted for them."""
        # Bounded FIFO by FIRST insertion: an OrderedDict gives up its
        # oldest entry in constant time (a plain dict keeps its deleted
        # entries at the front until a resize, so ``next(iter(d))``
        # walked every earlier eviction: PERF.md sec. 6, PR 32).  A
        # record written again keeps its place and evicts nothing, and
        # a hit never moves an entry.  False verdicts are cached too —
        # validity is deterministic in the record bytes, so a poisoned
        # entry can only ever answer for the same forged bytes, and the
        # cap bounds an attacker to evicting, not growing.
        #
        # graftguard changed the threading story that used to make this
        # lock-free: dispatch/fetch closures now execute on the guard's
        # DISPOSABLE launch threads, and an abandoned (wedged) launch
        # may complete late, concurrent with a fresh launch's fetch —
        # two writers.  The explicit lock makes each insert+evict pair
        # atomic (and the two counters, written nowhere else); readers
        # (connection threads' fast path, _pack's cached-lookup) stay
        # lockless — ``get`` is the dict's own, and a read under the GIL
        # can at worst turn a hit into a miss, exactly as before.
        cache, cap = self._verdicts, self.VERDICT_CACHE_CAP
        evicted = 0
        with self._verdicts_lock:
            for record, ok in verdicts:
                if record not in cache:
                    while len(cache) >= cap:
                        cache.popitem(last=False)
                        evicted += 1
                cache[record] = ok
            self._dedup_inserts += len(verdicts)
            self._dedup_evictions += evicted
        return evicted

    def _bls_guard_key(self, req) -> str:
        """Launch-shape key for BLS work under the guard's per-shape
        deadlines: kind x pow2 committee size — a 4-vote aggregate and a
        100-vote one are genuinely different pairings (the Miller-loop
        count scales with the key set), so their p99 histories must not
        train each other's deadline."""
        from ..crypto.eddsa import next_pow2

        if isinstance(req, proto.BlsSignRequest):
            return "bls:sign"
        kind = _BLS_VERIFY_KINDS[type(req)]
        return f"bls:{kind}:{next_pow2(max(1, len(req.pks)))}"

    def _note_bls_source(self, req, source):
        """Count where a BLS verdict came from (``_execute_bls``)."""
        stats = self._sched.stats
        if source == "bls_pairing":
            stats.note_path(source)
            stats.note_bls_pairings(
                len(req.pks) + 1 if isinstance(req, proto.BlsMultiRequest)
                else 2)
        elif source == "host":
            stats.note_path(source)
        elif source == "cache":
            with self._verdicts_lock:
                self._dedup_cache_hits += 1
        elif source == "reject":
            stats.note_bls_decode_reject()

    def _execute_bls(self, item, scope=NO_LAUNCH):
        """Run one BLS request under the launch guard (engine thread).

        The request body executes on one of the guard's DISPOSABLE
        launch threads under the shape's deadline (``_guarded``), so a
        wedged pairing — a hung device call mid
        ``verify_aggregate`` — trips the BLS arm of the degradation
        ladder instead of parking the engine thread: the client gets the
        TRANSIENT reply (``None`` -> the C++ side reads nullopt and runs
        its own outage handling, e.g. TC re-arm), and the crash-only
        engine reboot begins.  This closes ROADMAP item 3: BLS launches
        no longer sit outside the guard.

        SINGLE-REPLY DISCIPLINE (the PR 14 double-reply hazard, closed):
        ``_execute_bls_inner`` RETURNS its verdict instead of replying —
        replies happen here, on the engine thread, only after the
        guarded call came back clean, so a wedged-then-completing
        pairing's late result is discarded by the guard and can never
        race a ladder reply.  The idempotent ``reply`` helper stays as
        the belt.  _run installs NO backstop reply.

        Reply/caching contract: verdicts are cached ONLY when the inner
        body marks them cacheable — i.e. verdicts that are a pure
        function of the request bytes (decode/subgroup failures,
        completed verifications).  Transient failures (a wedged device, a
        backend exception) must reply ``None`` and NEVER a cacheable
        ``[False]``: the verdict cache is shared by every replica, so one
        poisoned entry would reject a valid certificate fleet-wide.

        Counting: ``bls.requests`` by kind as a verify request arrives;
        on a clean return, where the verdict came from (the inner body
        says): ``paths.bls_pairing`` and ``bls.pairings`` (Miller loops
        the device program ran), ``paths.host``, ``dedup.cache_hits``, or
        ``bls.decode_rejects`` (rejected before any pairing).  ``scope``
        is the launch's tracer scope: the body's stages are children of
        its ``device`` span.
        """
        req = item.request
        stats = self._sched.stats
        cache_key = None
        if not isinstance(req, proto.BlsSignRequest):
            cache_key = self.bls_cache_key(req)
            stats.note_bls_request(_BLS_VERIFY_KINDS[type(req)])
        replied = [False]

        def reply(payload, *, cacheable=False):
            # cacheable=True asserts this verdict is a pure function of
            # the request bytes; nothing else may enter the shared cache.
            if replied[0]:
                log.warning(
                    "BLS double-reply suppressed for rid=%s (%s)",
                    req.request_id, type(req).__name__)
                return
            replied[0] = True
            if cacheable and cache_key is not None and payload:
                self._cache_verdicts([(cache_key, bool(payload[0]))])
            item.reply_fn(payload)

        key = self._bls_guard_key(req)
        try:
            payload, cacheable, source = self._guarded(
                key, lambda: self._execute_bls_inner(req, cache_key, scope))
            self._note_bls_source(req, source)
            reply(payload, cacheable=cacheable)
        except WedgedLaunch:
            # BLS arm of the wedge ladder.  No host re-verify here: the
            # host pairing is the very work that may have wedged, and
            # re-running it inline would re-park the engine thread the
            # guard just saved.  Transient reply only — never a
            # cacheable [False] for a verdict nobody computed.
            log.error("guard: BLS launch %s WEDGED (deadline overrun); "
                      "transient reply, starting crash-only reboot", key)
            reply(None)
            self._begin_reboot()
        except Exception:
            log.exception("BLS request failed")
            # Transient by definition (deterministic failures return
            # cacheable verdicts from the inner body): never cacheable.
            reply(None)
        if not replied[0]:
            # Belt: a path that forgot to answer would leave the client
            # blocked until its recv deadline — reply the transient form.
            log.error("BLS path for rid=%s never replied; replying None",
                      req.request_id)
            reply(None)

    def _execute_bls_inner(self, req, cache_key, scope=NO_LAUNCH):
        """The BLS request body; runs on a disposable guard launch
        thread and RETURNS ``(payload, cacheable, source)`` — it must not
        touch the connection (a wedged call's late completion is
        discarded by the guard; only the engine thread replies).
        ``source`` says where a verdict came from: ``bls_pairing`` (the
        device program), ``host``, ``cache`` or ``reject`` (no pairing
        ran); None for a signature.  A traced common-message verify
        writes ``bls_prep`` (decode, aggregate, subgroup test, keys and
        their sum) and the device path's stages under ``scope``."""
        from ..offchain import bls12381 as bls

        if isinstance(req, proto.BlsSignRequest):
            # Signing is G2 scalar multiplication — host bigint work, no
            # pairing; mirrors the reference keeping signing on CPU.
            sk = int.from_bytes(req.sk, "big")
            return bls.g2_encode(bls.sign(sk, req.msg)), False, None
        # Verdict cache (same FIFO as Ed25519, keyed on the full request):
        # N replicas verifying one certificate cost one pairing.  Decode
        # failures cache as False — deterministic in the request bytes.
        cached = self._verdicts.get(cache_key) if cache_key else None
        if cached is not None:
            return [cached], False, "cache"

        if isinstance(req, proto.BlsMultiRequest):
            # TC shape: per-vote signatures over DISTINCT digests in one
            # RPC (round-3 verdict: this used to cost N sidecar
            # round-trips at view-change time).  Same decode policy as
            # the votes path: lax per-sig, subgroup test on the single
            # aggregate, strict cached decode for committee keys.
            try:
                agg = bls.aggregate(
                    [bls.g2_decode_lax(s) for s in req.sigs])
                if not bls.g2_in_subgroup(agg):
                    return [False], True, "reject"
                pks = [bls.g1_decode(p) for p in req.pks]
            except ValueError:
                return [False], True, "reject"
            if self._use_host or len(pks) not in self._bls_multi_warmed:
                if not self._use_host:
                    log.warning(
                        "BLS multi shape for %d votes not warmed "
                        "(--warm-bls-multi); verifying on host", len(pks))
                ok = bls.verify_aggregate(pks, req.msgs, agg)
                return [bool(ok)], True, "host"
            from ..ops import bls381 as dbls

            rows = dbls.multi_pairing_rows(pks, req.msgs, agg)
            if rows is None:
                return [False], True, "reject"
            return [dbls.verify_rows(rows)], True, "bls_pairing"
        with scope.stage("bls_prep") as tags:
            if tags is not None:
                tags["n"] = len(req.pks)
            try:
                if isinstance(req, proto.BlsVotesRequest):
                    # C++ nodes ship per-vote signatures; aggregate them
                    # here (host G2 adds), then run the same
                    # common-message check.  Fresh per-vote sigs get
                    # on-curve checks only; the single aggregate gets the
                    # [R]P subgroup test — the pairing statement depends
                    # only on the aggregate, so this is the same soundness
                    # at 1/N the host cost (per-vote subgroup ladders
                    # can't be cached the way committee keys can).
                    agg = bls.aggregate(
                        [bls.g2_decode_lax(s) for s in req.sigs])
                    if not bls.g2_in_subgroup(agg):
                        return [False], True, "reject"
                else:
                    agg = bls.g2_decode(req.agg_sig)
                pks = [bls.g1_decode(p) for p in req.pks]
            except ValueError:
                return [False], True, "reject"
            if not self._use_host:
                from ..ops import bls381 as dbls

                # None: a key is the identity, or the keys sum to it.
                apk = dbls.aggregate_keys(pks)
                if agg is None or apk is None:
                    return [False], True, "reject"
        if self._use_host:
            ok = bls.verify_aggregate_common(pks, req.msg, agg)
            return [bool(ok)], True, "host"
        ok = dbls.verify_common_apk(apk, req.msg, agg, scope)
        return [bool(ok)], True, "bls_pairing"

    # graftlint: sanitizes=device-verdict
    def _verify_submit(self, msgs, pks, sigs, force_device: bool = False):
        """Dispatch one slice; returns fetch() -> (n,) bool mask.

        While a graftguard reboot is re-warming the device leg
        (``_device_ok`` False), everything verifies on host; the
        canary and poison-bisection probes pass ``force_device`` to
        exercise the device path they exist to validate."""
        if not msgs:
            return lambda: np.zeros((0,), bool)
        if self._use_host or (not self._device_ok and not force_device
                              and not getattr(self._rewarm_tls,
                                              "active", False)):
            from ..crypto import ref_ed25519 as ref

            res = np.array([ref.verify(p, m, s)
                            for m, p, s in zip(msgs, pks, sigs)])
            return lambda: res
        if self._mesh is not None:
            # The staged production entry (dispatched immediately): the
            # warmup path runs through here, so the exact donated mesh
            # program the engine launches is what gets compiled.
            from ..crypto.eddsa import prepare_batch
            from ..parallel.sharded_verify import verify_batch_sharded_pack

            return verify_batch_sharded_pack(self._mesh, prepare_batch(
                msgs, pks, sigs))()
        from ..crypto import eddsa

        return eddsa.verify_batch_submit(msgs, pks, sigs)

    def _verify(self, msgs, pks, sigs) -> np.ndarray:
        return np.asarray(self._verify_submit(msgs, pks, sigs)())


class _Handler(socketserver.BaseRequestHandler):
    """Reader loop per connection; replies go through a dedicated writer
    thread so a client that stops draining its socket stalls only its own
    connection, never the shared verify-engine thread.

    Tracing (``engine._tracer`` enabled): every verify/sign request gets
    a ``request`` root span — last byte of its frame read -> ``sendall``
    of its reply returned, or the refusal — with ``decode`` and ``reply``
    children written here and ``queue`` by the engine; its reply rides
    the outbox as ``(frame, sent)`` (``_RequestSpan.framed``).  Untraced,
    the outbox carries bare frames."""

    def handle(self):
        sock = self.request
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        engine: VerifyEngine = self.server.engine  # type: ignore[attr-defined]
        tracer = engine._tracer
        outbox: "queue.Queue[bytes | tuple | None]" = \
            queue.Queue(maxsize=1024)

        def writer():
            while True:
                frame = outbox.get()
                if frame is None:
                    return
                sent = None
                if type(frame) is tuple:
                    frame, sent = frame
                    dequeued = tracer.now()
                try:
                    sock.sendall(frame)
                except OSError:
                    return
                if sent is not None:
                    sent(dequeued, tracer.now())

        wt = threading.Thread(target=writer, daemon=True,
                              name="sidecar-conn-writer")
        wt.start()
        # graftfleet: the connection's scheduling tenant.  Set once by a
        # HELLO frame (protocol v6); connections that never HELLO — every
        # pre-v6 client — schedule under the default tenant, so the
        # single-tenant topology behaves exactly as before.
        tenant = proto.DEFAULT_TENANT
        try:
            while True:
                try:
                    payload = proto.read_frame(sock)
                except (ConnectionError, OSError):
                    return
                traced = tracer.enabled
                t_read = tracer.now() if traced else 0.0
                try:
                    opcode, req = proto.decode_request(payload)
                except Exception:
                    log.exception("bad frame; closing connection")
                    return
                t_decoded = tracer.now() if traced else 0.0
                if opcode == proto.OP_HELLO:
                    # Tenant registration.  The reply echoes the server's
                    # protocol version + the accepted tenant id, so the
                    # client can fail fast on a version skew.  Distinct
                    # tenants are bounded server-side: past the cap the
                    # connection is refused (clean close, never a hang)
                    # so a tenant-id fuzzer cannot grow the scheduler's
                    # lane map without limit.
                    if not self.server.register_tenant(req.tenant):
                        log.warning(
                            "HELLO refused: tenant registry full "
                            "(tenant %r); closing connection", req.tenant)
                        return
                    tenant = req.tenant
                    outbox.put(proto.encode_hello_reply(
                        req.request_id, tenant))
                    continue
                if opcode == proto.OP_PING:
                    outbox.put(proto.encode_reply(
                        proto.OP_PING, req.request_id, []))
                    continue
                if opcode == proto.OP_STATS:
                    # Telemetry snapshot, answered on the connection
                    # thread: reading counters must never queue behind
                    # the device work being diagnosed.
                    outbox.put(proto.encode_stats_reply(
                        req.request_id, engine.stats_snapshot()))
                    continue
                chaos: ChaosState | None = \
                    getattr(self.server, "chaos", None)
                if opcode == proto.OP_CHAOS:
                    # [0] = refused (no --chaos): a production sidecar is
                    # not degradable over the wire, and the caller can
                    # tell refusal from success.
                    if chaos is None:
                        outbox.put(proto.encode_reply(
                            opcode, req.request_id, [0]))
                        continue
                    chaos.configure(req.spec)  # ValueError closes conn
                    outbox.put(proto.encode_reply(
                        opcode, req.request_id, [1]))
                    continue
                span = None
                if traced:
                    tags = {}
                    ctx = _ctx_tag(req)
                    if ctx:
                        tags["ctx"] = ctx
                    span = _RequestSpan(
                        tracer, req.request_id, t_read, id=tracer.next_id(),
                        cls=vsched.class_of_opcode(opcode),
                        n=len(getattr(req, "msgs", ()) or ()) or 1, **tags)
                    span.child("decode", t_read, t_decoded,
                               bytes=len(payload))
                delay_s = 0.0
                if chaos is not None:
                    # Scripted misbehavior for verify/sign traffic only
                    # (PING/STATS/CHAOS above stay honest).  Decided
                    # BEFORE the verdict-cache fast path so a scripted
                    # shed/drop cannot be masked by a cache hit.
                    # graftlint: disable=unannotated-gate (fault injector, verify-shaped by name only)
                    drop, shed, delay_s = chaos.verify_action()
                    if drop:
                        log.warning("chaos: dropping connection")
                        return
                    if shed:
                        log.warning("chaos: forcing queue-full shed")
                        outbox.put(proto.encode_busy_reply(
                            req.request_id, engine.retry_after_ms(
                                vsched.class_of_opcode(opcode))))
                        if span is not None:
                            span.close(False)
                        continue

                def send(frame, _delay=delay_s):
                    # Delayed replies reschedule onto a timer so THIS
                    # reader thread keeps draining frames (a pipelined
                    # PING behind a delayed verify answers on time).
                    # put_nowait everywhere: a wedged connection drops
                    # its reply and the reader reaps it, never a blocked
                    # thread (the established outbox policy).
                    def enqueue():
                        try:
                            outbox.put_nowait(frame)
                        except queue.Full:
                            pass
                    if _delay:
                        t = threading.Timer(_delay, enqueue)
                        t.daemon = True
                        t.start()
                    else:
                        enqueue()

                # Cache fast path: a fully-cached Ed25519 verify request is
                # answered on THIS connection thread — no engine queue
                # round trip.  At testbed scale (100 replicas, one
                # sidecar) the common request is the 99th replica
                # verifying a QC the engine already judged; four thread
                # hops per cached answer is what saturates the host, not
                # the device.  Dict reads under the GIL are safe against
                # the engine thread's insert/evict writes.
                is_bls = False
                verdicts = None
                if opcode in (proto.OP_VERIFY_BATCH, proto.OP_VERIFY_BULK):
                    verdicts = engine.cached_verdicts(req)
                elif opcode in (proto.OP_BLS_VERIFY_AGG,
                                proto.OP_BLS_VERIFY_VOTES,
                                proto.OP_BLS_VERIFY_MULTI):
                    is_bls = True
                    verdicts = engine.cached_bls_verdict(req)
                elif opcode == proto.OP_BLS_SIGN:
                    is_bls = True
                if verdicts is not None:
                    if span is not None:
                        span.tags["cached"] = True
                        called = tracer.now()
                    frame = proto.encode_reply(
                        opcode, req.request_id, verdicts)
                    send(frame if span is None
                         else span.framed(frame, called))
                    continue

                def reply(result, _rid=req.request_id, _op=opcode,
                          _send=send, _span=span):
                    if _span is not None:
                        called = _span.tracer.now()
                    if isinstance(result, BusyReply):
                        # graftguard wedge ladder: a bulk request whose
                        # launch wedged gets the honest OP_BUSY with the
                        # drain-derived retry-after, never a fake mask.
                        frame = proto.encode_busy_reply(
                            _rid, result.retry_after_ms)
                    elif _op == proto.OP_BLS_SIGN:
                        frame = proto.encode_reply_raw(
                            _op, _rid, result if result else b"")
                    else:
                        frame = proto.encode_reply(
                            _op, _rid, result if result is not None
                            else [False])
                    _send(frame if _span is None
                          else _span.framed(frame, called))

                # Admission is bounded: a full class queue is answered
                # HERE with an explicit OP_BUSY reply carrying the
                # retry-after hint (protocol v4; clients that predate it
                # still read the off-opcode reply as overload, never as
                # a verdict).  Clients back off / shed to host verify;
                # no connection thread ever blocks on a saturated
                # engine.
                cls = vsched.class_of_opcode(opcode)
                if not engine.submit(req, reply, cls=cls, is_bls=is_bls,
                                     tenant=tenant, span=span):
                    outbox.put(proto.encode_busy_reply(
                        req.request_id, engine.retry_after_ms(cls)))
                    if span is not None:
                        span.close(False)
        finally:
            outbox.put(None)


class SidecarServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True

    # graftfleet: distinct tenant ids one server process will register
    # over its lifetime.  A fleet fronts committees, not the open
    # internet; the bound keeps a HELLO fuzzer from growing the
    # scheduler's lane map and the stats dict without limit.
    TENANT_REGISTRY_CAP = 256

    def __init__(self, addr, engine: VerifyEngine,
                 chaos: ChaosState | None = None):
        super().__init__(addr, _Handler)
        self.engine = engine
        self.chaos = chaos
        self._tenants_seen: set = set()
        self._tenants_lock = threading.Lock()

    def register_tenant(self, tenant: str) -> bool:
        """Accept a HELLO tenant id; False once the registry is full
        (re-HELLOs of a known tenant always succeed — a tenant id
        COLLISION is by design: both connections share one lane)."""
        with self._tenants_lock:
            if tenant in self._tenants_seen:
                return True
            if len(self._tenants_seen) >= self.TENANT_REGISTRY_CAP:
                return False
            self._tenants_seen.add(tenant)
            return True


def serve(host: str = "127.0.0.1", port: int = 7100,
          mesh_devices: int | None = None, use_host: bool = False,
          ready_event: threading.Event | None = None,
          warm_max: int = MAX_SUBBATCH, warm_bls: bool = False,
          warm_bls_multi: int = 0, warm_bulk: bool = False,
          warm_rlc: bool = False, warm_rlc_sharded: bool = False,
          chaos: bool = False,
          committee: int | None = None, client_rate: int | None = None,
          trace_path: str | None = None,
          cadence: bool | None = None,
          tcp: str | None = None):
    # graftcadence opt-in: --cadence wins, then HOTSTUFF_TPU_CADENCE;
    # the staged engine stays the default (ring.cadence_enabled).
    from .ring import RingDepth, cadence_enabled

    if cadence is None:
        cadence = cadence_enabled()
    tracer = None
    if trace_path:
        from ..obs.spans import Tracer

        annotation = None
        if not use_host:
            # Device boots: the launch-scope stages also go into a
            # profiler session's host plane, on the trace's own clock.
            from jax.profiler import TraceAnnotation as annotation
        tracer = Tracer(trace_path, annotation=annotation)
        log.info("grafttrace span emission -> %s", trace_path)
    # graftguard: chaos state is built BEFORE the engine so the wedge
    # knob can reach the dispatch path, and every boot gets a launch
    # supervisor — per-shape deadlines off the compile manifest (device
    # boots) or the defaults (host boots: supervision still catches a
    # hung host stage, and the chaos drill needs it).
    chaos_state = None
    if chaos:
        chaos_state = ChaosState()
        log.warning("chaos hook ENABLED (--chaos): OP_CHAOS requests can "
                    "degrade this sidecar")
    from .guard import LaunchDeadlines, LaunchGuard

    tracker = None
    if not use_host:
        from ..utils.xla_cache import CompileTracker, configure_xla_cache

        cache_dir = configure_xla_cache()
        # graftkern compile accounting: every warmup shape below runs
        # under the tracker, so OP_STATS ``compile`` reports manifest
        # hits/misses + warmup wall time and a second boot against a
        # populated cache proves itself (misses == 0, lower wall).
        tracker = CompileTracker(cache_dir=cache_dir)
        guard = LaunchGuard(deadlines=LaunchDeadlines.from_manifest(
            tracker.manifest, tracker.kernel, cache_dir))
    else:
        # Host-crypto boots compile nothing, so the cold 180 s compile
        # budget would be the wrong deadline class — the warm grace
        # (30 s default: a MAX_SUBBATCH host slice is ~10 s of pure
        # python) is what a hung host launch should be judged against.
        guard = LaunchGuard(deadlines=LaunchDeadlines(warm_boot=True))
    engine = VerifyEngine(mesh_devices=mesh_devices, use_host=use_host,
                          committee=committee, client_rate=client_rate,
                          tracer=tracer, guard=guard, chaos=chaos_state,
                          cadence=cadence)
    if cadence:
        log.info("graftcadence: resident ring ENABLED (depth %d)",
                 engine._ring.depth.depth())
        if tracker is not None:
            # Seed the depth trainer from the manifest's measured
            # per-shape walls, the same record LaunchDeadlines reads
            # for its warm-boot decision.
            engine._ring.depth = RingDepth.from_manifest(
                tracker.manifest, tracker.kernel)
    # Warm the jit cache BEFORE binding: until the socket exists, node
    # crypto gets ECONNREFUSED and falls back to host verify instead of
    # connecting into a server whose device thread is still compiling.
    # (A bound-but-compiling socket accepts into the TCP backlog and
    # silently stalls every client for the whole compile — the round-2
    # 0-TPS failure mode.)
    if not use_host:
        engine.compile_tracker = tracker
        try:
            _warmup(engine, warm_max)
            if warm_bls:
                _warmed(engine, "bls:pairing", _warmup_bls)
            if warm_bls_multi:
                tracker.warm(
                    f"bls_multi:{warm_bls_multi}",
                    lambda: _warmup_bls_multi(engine, warm_bls_multi))
            if warm_bulk:
                # Single-chip: the chunked-scan shapes.  Mesh: the
                # whole-backlog chunked mesh scan (graftscale) — the
                # mesh registry gates enable_bulk on those scan shapes,
                # so the cap only rises when the one-dispatch drain
                # really exists.
                _warmup_bulk(engine, warm_max)
                engine.enable_bulk()
            if warm_rlc and not (mesh_devices and mesh_devices > 1):
                # Single-chip only: the mesh path routes through
                # verify_rlc_sharded, whose warmup is --warm-rlc-sharded
                # below (per-SHARD buckets, not global ones).
                _warmup_rlc(engine, warm_max)
            if warm_rlc_sharded and mesh_devices and mesh_devices > 1:
                # Mesh one-MSM warmup: compiles verify_rlc_sharded AND
                # verify_batch_sharded at every per-shard bucket up to
                # the cap, so the scheduler routes coalesced launches of
                # RLC_MIN_LAUNCH+ unique records down the sharded MSM
                # path with its bisection fallback already compiled.
                _warmup_rlc_sharded(engine, warm_max)
        except BaseException:
            # A failed warmup (a valid signature judged false, a chip
            # that cannot be bound) ends the boot BEFORE the socket
            # binds: nothing may serve verdicts from a device leg that
            # did not prove itself.
            engine.stop()
            guard.close()
            tracker.close()
            raise
        tracker.finish()
        log.info(
            "warmup compile cache: %d hit(s), %d miss(es) in %.1fs "
            "(kernel %s, cache %s)", tracker.hits, tracker.misses,
            tracker.wall_s(), tracker.kernel, cache_dir)
        engine.device_info = _device_info(engine)
        log.info("serving on %s", engine.device_info)

        def _rewarm():
            # graftguard crash-only reboot: re-run the SAME warmup legs
            # this boot ran, against the now-populated XLA disk cache —
            # nothing compiles, every shape re-traces (PERF.md PR 22) —
            # during which the host path owns live traffic.
            # BLS warmups are skipped: the pairing programs are minutes
            # of compile; un-warmed shapes fall back to the host pairing
            # (_bls_multi_warmed), which now runs under the guard's
            # deadline like every other BLS launch.
            _warmup(engine, warm_max)
            if warm_bulk:
                _warmup_bulk(engine, warm_max)
            if warm_rlc and not (mesh_devices and mesh_devices > 1):
                _warmup_rlc(engine, warm_max)
            if warm_rlc_sharded and mesh_devices and mesh_devices > 1:
                _warmup_rlc_sharded(engine, warm_max)

        engine._rewarm_fn = _rewarm
    server = SidecarServer((host, port), engine, chaos=chaos_state)
    log.info("sidecar listening on %s:%d", host, server.server_address[1])
    # graftfleet: --tcp HOST:PORT binds a SECOND listener next to the
    # primary, sharing the same engine, scheduler, verdict cache and
    # chaos hook — the shape a shared fleet member serves remote tenants
    # through while local clients keep the loopback socket.  Both
    # listeners speak the same protocol (HELLO/tenant included); the
    # tenant registry is per-SERVER, so the two listeners' tenants are
    # bounded independently but share the scheduler's lanes.
    tcp_server = None
    tcp_thread = None
    if tcp:
        tcp_host, _, tcp_port = tcp.rpartition(":")
        tcp_server = SidecarServer((tcp_host or "0.0.0.0", int(tcp_port)),
                                   engine, chaos=chaos_state)
        log.info("sidecar fleet listener on %s:%d", tcp_host or "0.0.0.0",
                 tcp_server.server_address[1])
        tcp_thread = threading.Thread(
            target=lambda: tcp_server.serve_forever(poll_interval=0.2),
            daemon=True, name="sidecar-tcp-listener")
        tcp_thread.start()
    if ready_event is not None:
        ready_event.set()
    try:
        server.serve_forever(poll_interval=0.2)
    finally:
        engine.stop()
        guard.close()
        server.server_close()
        if tcp_server is not None:
            tcp_server.shutdown()
            tcp_server.server_close()
        if tracker is not None:
            tracker.close()
        if tracer is not None:
            tracer.close()
    return server


def _device_info(engine) -> dict:
    """The OP_STATS ``device`` section: the devices the engine launches
    on — every device of its mesh, else the process's default device —
    as jax reports them."""
    import jax

    devices = list(engine._mesh.devices.flat) if engine._mesh is not None \
        else jax.devices()[:1]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def _require_valid(mask, what: str):
    """Every warmup verifies VALID signatures: a false verdict means the
    device leg computes wrong answers, and the boot must fail (serve()
    exits before listen()) instead of binding a socket over it."""
    if not np.all(mask):
        raise RuntimeError(f"{what} returned false for a valid signature")


def _warmup_bls(n_pks: int = 3):
    """Compile the device pairing program before listen() so the first QC
    under scheme=bls doesn't eat a multi-minute compile against the C++
    client's 60 s deadline."""
    from ..offchain import bls12381 as bls
    from ..ops import bls381 as dbls

    t0 = monotonic()
    dbls.selfcheck()
    msg = b"warmup"
    keys = [bls.key_gen(bytes([i]) * 32) for i in range(1, n_pks + 1)]
    agg = bls.aggregate([bls.sign(sk, msg) for sk, _ in keys])
    # The served entry (_execute_bls_inner): keys summed, then the check.
    apk = dbls.aggregate_keys([pk for _, pk in keys])
    _require_valid(dbls.verify_common_apk(apk, msg, agg),
                   "BLS warmup verify")
    log.info("BLS pairing warmup done in %.1fs", monotonic() - t0)


def _warmup_bls_multi(engine, n_votes: int):
    """Compile the n-vote multi-digest pairing shape (TC verify at quorum
    size n) before listen(); registers the shape so the engine may launch
    it on device. The program compiles one shape per vote count, so the
    harness passes the committee's quorum size."""
    from ..offchain import bls12381 as bls
    from ..ops import bls381 as dbls

    t0 = monotonic()
    keys = [bls.key_gen(bytes([i + 1]) * 32) for i in range(n_votes)]
    msgs = [bytes([i]) * 32 for i in range(n_votes)]
    agg = bls.aggregate([bls.sign(sk, m)
                         for (sk, _), m in zip(keys, msgs)])
    _require_valid(
        dbls.verify_aggregate_multi([pk for _, pk in keys], msgs, agg),
        "BLS multi warmup verify")
    engine._bls_multi_warmed.add(n_votes)
    log.info("BLS multi-digest warmup (%d votes) done in %.1fs",
             n_votes, monotonic() - t0)


def _warmed(engine, key: str, thunk):
    """Run one warmup shape, through the engine's CompileTracker when
    one is attached (device boots) so the manifest hit/miss accounting
    sees every shape; bare otherwise (tests, host mode)."""
    tracker = getattr(engine, "compile_tracker", None)
    if tracker is None:
        return thunk()
    return tracker.warm(key, thunk)


def _warm_shapes(engine, start: int, stop: int, label: str):
    """Compile padded batch shapes start, 2*start, ... stop through the
    engine's own verify path so the exact jitted callables are cached,
    and record each shape in the scheduler's warmed-shape registry."""
    from ..crypto import ref_ed25519 as ref

    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msg = b"\x00" * 32
    sig = ref.sign(sk, msg)
    n = start
    while n <= stop:
        t0 = monotonic()

        def _one(n=n):
            _require_valid(
                engine._verify([msg] * n, [pk] * n, [sig] * n),
                f"{label} verify at N={n}")

        _warmed(engine, f"{label.replace(' ', '_')}:{n}", _one)
        if n <= MAX_SUBBATCH:
            engine._shapes.mark_bucket(n)
        else:
            engine._shapes.mark_chunks(n // MAX_SUBBATCH)
        log.info("%s N=%d done in %.1fs", label, n, monotonic() - t0)
        n *= 2


def _warmup_bulk(engine, warm_max: int = MAX_SUBBATCH):
    """Compile the chunked-scan shapes (g = 2 .. 16 sub-batches) that bulk
    coalescing can hit once enable_bulk() raises the launch cap.  Cached
    across restarts by the persistent compilation cache.  On a mesh
    engine the bulk drain is the whole-backlog chunked mesh scan
    (graftscale), so that is what gets compiled — and what the
    registry's gated enable_bulk requires."""
    if engine._mesh is not None:
        _warmup_mesh_scan(engine, warm_max)
        return
    _warm_shapes(engine, 2 * MAX_SUBBATCH, MAX_COALESCED, "bulk warmup")


def _warmup_mesh_scan(engine, warm_max: int = MAX_SUBBATCH,
                      scan_chunks: int | None = None):
    """Compile the whole-backlog chunked mesh scan
    (parallel/sharded_verify.verify_sharded_chunked) at every chunk
    count the engine may launch — g = 2, 4, ... MESH_SCAN_CHUNKS chunks
    of the top warmed per-shard bucket — through the REAL staged entry,
    and mark each (g, rows) in the registry (mark_mesh_chunks) so the
    router starts choosing ``scan_sharded`` and the gated enable_bulk
    may raise the launch cap to the scan capacity.  A backlog whose
    chunk count is not marked here falls back to the sliced ladder —
    an unwarmed scan shape never compiles mid-run.  ``scan_chunks``
    lowers the warmed chunk-count ceiling (tests trade drain capacity
    for compile wall; production keeps the default)."""
    from ..crypto import eddsa, ref_ed25519 as ref
    from ..parallel import sharded_verify as shv

    n_dev = engine._shapes.n_devices
    if n_dev < 2 or engine._mesh is None:
        log.warning("mesh scan warmup ignored: no device mesh")
        return
    if engine._shapes.mesh_chunks:
        # Already warmed (a --warm-bulk boot runs this before the
        # --warm-rlc-sharded leg does): every rerun thunk would be a
        # compile-cache hit but still pay a full n_dev*g*rows verify
        # per chunk count — skip the duplicate boot wall.
        return
    if scan_chunks is None:
        scan_chunks = vsched.MESH_SCAN_CHUNKS
    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msg = b"\x03" * 32
    sig = ref.sign(sk, msg)
    # The committee floor applies here exactly as in the RLC warmup, so
    # every caller (--warm-bulk's mesh leg, --warm-rlc-sharded's scan
    # leg) derives the SAME chunk rows — mark_mesh_chunks enforces one
    # rows value per registry.
    cap = min(max(warm_max, engine._shapes.qc_sigs or 0), MAX_SUBBATCH)
    rows = shv.shard_bucket(cap, n_dev)
    g = 2
    while g <= min(scan_chunks, vsched.MESH_SCAN_CHUNKS):
        n = n_dev * g * rows
        t0 = monotonic()

        def _one(n=n, rows=rows):
            prep = eddsa.prepare_batch([msg] * n, [pk] * n, [sig] * n)
            _require_valid(
                shv.verify_sharded_chunked_pack(
                    engine._mesh, prep, rows=rows)()(),
                f"mesh scan warmup verify at N={n}")

        _warmed(engine, f"mesh_scan:{n_dev}x{g}x{rows}", _one)
        engine._shapes.mark_mesh_chunks(g, rows)
        log.info("mesh scan warmup N=%d (%d chunks of %d rows/shard) "
                 "done in %.1fs", n, g, rows, monotonic() - t0)
        g *= 2


def _warmup(engine, warm_max: int = MAX_SUBBATCH):
    """Compile every padded batch shape a live run will hit.

    Requests pad to power-of-two buckets (crypto/eddsa._bucket) and the
    coalescer caps launches at MAX_SUBBATCH, so warming N = 8, 16, ...
    MAX_SUBBATCH covers every shape the engine can launch (a smaller
    warm_max trades boot time for possible mid-traffic compiles). Uses the
    engine's own verify path so the exact jitted callable is cached.
    """
    _warm_shapes(engine, 8, warm_max, "warmup")


def _warmup_rlc_sharded(engine, warm_max: int = MAX_SUBBATCH,
                        scan_chunks: int | None = None):
    """Compile the MESH verify programs at every per-shard bucket the
    engine may launch, and register the shapes so the scheduler's router
    starts choosing the ``rlc_sharded`` path.

    Walks GLOBAL sizes n = n_dev * per_shard for every power-of-two
    per-shard bucket from the floor (parallel/shard_shapes.shard_bucket
    of the smallest batch) up to the launch cap, running each through
    the REAL staged entries — verify_rlc_sharded_pack AND
    verify_batch_sharded_pack — so both the one-MSM program and its
    per-signature bisection/fallback program are compiled for every
    bucket before the socket binds.  Bisection halves land on smaller
    buckets, which this loop has always already compiled (increasing
    order).

    graftscale: the warmup ceiling is raised to the committee's quorum
    size when one is served (``--committee N`` -> ShapeRegistry.qc_sigs
    = 2N/3+1), so a giant-committee QC batch — ~667 signatures at
    N=1000 — always lands on a warmed sharded-RLC bucket and never
    takes the sliced ladder.  Afterwards the whole-backlog chunked
    mesh scan shapes are compiled too (_warmup_mesh_scan) and the
    launch cap rises through the gated enable_bulk, so mesh boots
    (the harness's ``--mesh N --warm-rlc-sharded``) drain coalesced
    bulk backlogs in ONE launch from the first block.
    """
    from ..crypto import eddsa, ref_ed25519 as ref
    from ..parallel import sharded_verify as shv

    n_dev = engine._shapes.n_devices
    if n_dev < 2 or engine._mesh is None:
        log.warning("--warm-rlc-sharded ignored: no device mesh")
        return
    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msg = b"\x02" * 32
    sig = ref.sign(sk, msg)
    per = shv.shard_bucket(1, n_dev)          # the smallest bucket
    # Largest routed launch: warm_max, floored at the served quorum so
    # the committee's own QC shape is always covered.
    cap = min(max(warm_max, engine._shapes.qc_sigs or 0), MAX_SUBBATCH)
    top = shv.shard_bucket(cap, n_dev)        # its per-shard bucket
    while per <= top:
        n = n_dev * per
        t0 = monotonic()

        def _one(n=n):
            # One prep serves both programs: neither pack entry mutates
            # the host dict (padding copies before device_put).
            prep = eddsa.prepare_batch([msg] * n, [pk] * n, [sig] * n)
            _require_valid(
                shv.verify_batch_sharded_pack(engine._mesh, prep)()(),
                f"sharded warmup verify at N={n}")
            _require_valid(
                shv.verify_rlc_sharded_pack(engine._mesh, prep)()(),
                f"RLC sharded warmup verify at N={n}")

        _warmed(engine, f"rlc_sharded:{n_dev}x{per}", _one)
        engine._shapes.mark_bucket(n)
        engine._shapes.mark_rlc_sharded(n)
        log.info("RLC sharded warmup N=%d (per-shard bucket %d) done "
                 "in %.1fs", n, per, monotonic() - t0)
        per *= 2
    # The whole-backlog scan leg: chunk counts over the top bucket just
    # warmed, then the (gated) launch-cap raise — after this, mesh bulk
    # stops slicing at the old MAX_SUBBATCH cap.
    _warmup_mesh_scan(engine, cap, scan_chunks=scan_chunks)
    engine.enable_bulk()


def _warmup_rlc(engine, warm_max: int = MAX_SUBBATCH):
    """Compile the one-MSM RLC program at every padded bucket the engine
    may route to it (RLC_MIN_LAUNCH .. warm_max), and register the shapes
    so the scheduler's router starts choosing the RLC path.

    Runs all-valid batches in INCREASING size through the real
    verify_batch_rlc entry.  A failed combined check is resolved by ONE
    per-signature program at the batch's own bucket, a shape of _warmup,
    which serve() always runs first.  Starts at the bucket floor (8),
    BELOW the routing threshold: since the bisection went, no half of a
    batch launches the small RLC shapes, and bucket 8 is reached only by
    an admitted batch (RLC_MIN_LAUNCH+ records) that the host's
    canonicality checks leave with RLC_MIN_MSM .. 8 rows.  Taking the
    shapes no traffic reaches out of the plan is ROADMAP S2(a)'s."""
    from ..crypto import eddsa, ref_ed25519 as ref

    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msg = b"\x01" * 32
    sig = ref.sign(sk, msg)
    n = 8  # == crypto/eddsa._MIN_BUCKET, the smallest padded shape
    while n <= min(warm_max, MAX_SUBBATCH):
        t0 = monotonic()

        def _one(n=n):
            _require_valid(
                eddsa.verify_batch_rlc([msg] * n, [pk] * n, [sig] * n),
                f"RLC warmup verify at N={n}")

        _warmed(engine, f"rlc:{n}", _one)
        engine._shapes.mark_rlc(n)
        log.info("RLC warmup N=%d done in %.1fs", n, monotonic() - t0)
        n *= 2


class _ExitOnSigterm:
    """``serve()``'s ``ready_event`` for a traced sidecar process: from
    the moment it serves, SIGTERM (the harness's teardown) ends
    ``serve()`` through its ``finally``, which writes the buffered spans
    out, and the process exits 0.  Until then — the whole warm-up, whose
    compiles would hold a Python-level handler back for tens of seconds —
    and in every untraced sidecar, SIGTERM keeps its default action: the
    process dies by the signal at once, as it always did."""

    def set(self):
        signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=7100)
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard verify over an N-device mesh (0 = single)")
    ap.add_argument("--host-crypto", action="store_true",
                    help="pure-host verification (debug/fallback)")
    ap.add_argument("--warm", type=int, default=MAX_SUBBATCH,
                    help="largest batch shape to pre-compile before "
                         "listening (power-of-two buckets up to this; "
                         "default covers every launchable shape)")
    ap.add_argument("--warm-bls", action="store_true",
                    help="also pre-compile the BLS pairing program "
                         "(scheme=bls deployments)")
    ap.add_argument("--warm-bls-multi", type=int, default=0, metavar="N",
                    help="also pre-compile the N-vote multi-digest pairing "
                         "shape (the TC verify at quorum size N); unwarmed "
                         "shapes fall back to host pairing")
    ap.add_argument("--warm-bulk", action="store_true",
                    help="also pre-compile the chunked-scan bulk shapes and "
                         "raise the per-launch cap to %d sigs (bulk/offchain "
                         "workloads)" % MAX_COALESCED)
    ap.add_argument("--warm-rlc", action="store_true",
                    help="also pre-compile the one-MSM RLC batch-verify "
                         "shapes so coalesced batches of %d+ signatures "
                         "route through the combined check"
                         % vsched.RLC_MIN_LAUNCH)
    ap.add_argument("--warm-rlc-sharded", action="store_true",
                    help="with --mesh N: pre-compile the mesh-sharded "
                         "one-MSM RLC programs (and their per-signature "
                         "fallback) at every per-shard bucket, so "
                         "coalesced batches of %d+ signatures route "
                         "through the sharded combined check"
                         % vsched.RLC_MIN_LAUNCH)
    ap.add_argument("--tcp", default=None, metavar="HOST:PORT",
                    help="graftfleet: bind a second listener (same "
                         "engine and scheduler) on HOST:PORT for remote "
                         "tenants — fleet members serve shared traffic "
                         "here while local clients keep the primary "
                         "socket; protocol v6 HELLO frames carry the "
                         "tenant id on either listener")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="append grafttrace JSONL spans (one tree a "
                         "request: request/decode/queue/reply; one a "
                         "launch: pack/dispatch/device and its children; "
                         "obs/spans.py) to PATH, written when the sidecar "
                         "shuts down; obs/trace.py merges them into the "
                         "run's trace.json")
    ap.add_argument("--cadence", action="store_true",
                    help="run the graftcadence resident verify ring "
                         "(continuous batching: depth-k dispatch at a "
                         "load-adaptive tick, generation-tagged "
                         "verdicts) instead of the staged request-"
                         "driven loop; HOTSTUFF_TPU_CADENCE=1 is the "
                         "env equivalent and the staged engine stays "
                         "the default")
    ap.add_argument("--chaos", action="store_true",
                    help="enable the OP_CHAOS fault-injection hook "
                         "(bounded reply delay, forced connection drops, "
                         "forced queue-full sheds) — graftchaos testbeds "
                         "only, never production")
    ap.add_argument("--committee", type=int, default=0, metavar="N",
                    help="committee size served; sizes the latency-class "
                         "admission cap (0 = static default)")
    ap.add_argument("--client-rate", type=int, default=0, metavar="TPS",
                    help="aggregate client tx rate; sizes the bulk-class "
                         "admission cap (0 = static default)")
    ap.add_argument("-v", "--verbose", action="count", default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s.%(msecs)03dZ %(levelname)s [%(name)s] %(message)s",
        datefmt="%Y-%m-%dT%H:%M:%S")
    serve(args.host, args.port, mesh_devices=args.mesh or None,
          ready_event=_ExitOnSigterm() if args.trace else None,
          use_host=args.host_crypto, warm_max=args.warm,
          warm_bls=args.warm_bls, warm_bls_multi=args.warm_bls_multi,
          warm_bulk=args.warm_bulk, warm_rlc=args.warm_rlc,
          warm_rlc_sharded=args.warm_rlc_sharded,
          chaos=args.chaos, committee=args.committee or None,
          client_rate=args.client_rate or None,
          trace_path=args.trace,
          cadence=True if args.cadence else None,
          tcp=args.tcp)


if __name__ == "__main__":
    main()
