"""graftcadence: the continuous-batching resident verify pipeline.

The staged engine (service.VerifyEngine._run_staged) is request-driven:
coalesce -> pack -> launch -> fetch, one launch at a time with a depth-2
double buffer.  At production rates the dominant cost is the fixed
per-launch host overhead, not device FLOPs — exactly what the OP_STATS
``pipeline.overlap_ratio`` measures.  The continuous-batching insight
from LLM serving (Orca, OSDI'22) transfers directly: keep ONE resident
compiled program per warmed shape fed at a fixed, load-adaptive cadence
instead of dispatching per request.

This module is that loop:

  * :class:`CadenceRing` — a fixed ring of ``k`` slots (depth-k
    generalization of the staged engine's depth-2 pipeline).  Every tick
    the ring collects the oldest in-flight verdict when it must (ring
    full, or idle), then arms one free slot with the scheduler's
    per-tick quota (``Scheduler.next_tick``, pad-filled from the bulk
    backlog exactly like the staged coalesce so a partially-filled tick
    never wastes FLOPs).  Shapes come from the warmed ``ShapeRegistry``
    buckets via the engine's own ``_pack`` — never a fresh compile
    mid-run — and on a mesh the pack routes through the pre-donated
    resident entries (``parallel.sharded_verify.ring_slot_pack``).

  * generation tags — every slot carries a generation counter bumped on
    each arm AND each invalidation (expiry re-resolve, wedge fallback).
    A flight's verdict is applied ONLY if its captured generation still
    matches the slot's; anything else is counted as a generation drop
    and discarded, so a stale fetch can never answer a re-armed slot
    (the graftview TC-verdict generation/expiry machinery is the
    template).

  * :class:`RingDepth` — sizes k in {2, 4, 8} from measured dispatch
    overhead vs per-shape device walls, seeded from the compile
    manifest's measured walls the same way graftguard's LaunchDeadlines
    seeds its warm-boot decision (``from_manifest``).

  * :class:`CadenceStats` — the OP_STATS ``cadence`` section: tick
    rate, occupancy histogram, pad-fill ratio, generation drops,
    queue-wait p50/p99.

Supervision: every cadence dispatch/fetch is a guarded launch under the
``tick:`` deadline class (guard.LaunchDeadlines.TICK_CLASS_PREFIX — the
ring only ever launches warmed shapes, so a cold tick key gets the warm
grace, not the compile budget).  A WedgedLaunch drops the ring back to
the staged engine through the existing degradation ladder: the wedged
flight rides ``_wedge_ladder`` (host masks / BUSY + quarantine +
crash-only reboot), every other in-flight generation is invalidated and
re-resolved on the host, and ``run()`` returns with ``enabled`` False —
``VerifyEngine._run`` then falls through to the staged loop.  The
staged path stays the DEFAULT: the ring runs only behind
``--cadence`` / ``HOTSTUFF_TPU_CADENCE`` until a committed bench
headline shows it winning.

Bit-identity is non-negotiable: the ring feeds batches through the very
same ``VerifyEngine._pack`` the staged path uses (same dedup, same
verdict cache, same RLC resolution per generation), so ring verdicts
equal ``verify_batch`` masks by construction — and tests assert it
through the engine.
"""

from __future__ import annotations

import logging
import os
import threading
from collections import deque
from time import monotonic

from ..obs.spans import NO_LAUNCH
from . import sched as vsched
from .guard import BusyReply, WedgedLaunch

log = logging.getLogger("sidecar.ring")

ENV_CADENCE = "HOTSTUFF_TPU_CADENCE"          # "1"/"true"/"on" => ring
ENV_DEPTH = "HOTSTUFF_TPU_CADENCE_DEPTH"      # pin k (else trained)
ENV_TICK_S = "HOTSTUFF_TPU_CADENCE_TICK_S"    # pin tick interval


def cadence_enabled(default: bool = False) -> bool:
    """True iff the environment opts the sidecar into the cadence ring
    (the staged engine stays the default until the committed ``cadence``
    bench headline shows the ring winning)."""
    raw = os.environ.get(ENV_CADENCE)
    if raw is None:
        return default
    return raw.strip().lower() in ("1", "true", "on", "yes")


def _percentile(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(q * (len(sorted_vals) - 1))))
    return float(sorted_vals[idx])


class RingDepth:
    """Trains the ring depth k in {2, 4, 8} from measured host dispatch
    overhead vs per-shape device walls — the same evidence class
    graftguard's LaunchDeadlines trains its deadlines on, seeded the
    same way (:meth:`from_manifest`).

    Depth covers dispatch: with overhead o and device wall w, the device
    stays busy iff k-1 launches execute while the host stages the next,
    so the ideal k is about 1 + o/w rounded up to the next supported
    depth.  Depth beyond that only adds reply latency (the staged
    engine's depth-2 comment, generalized).  Until MIN_OBSERVATIONS
    walls exist the trainer answers the conservative minimum (2)."""

    DEPTHS = (2, 4, 8)
    MIN_OBSERVATIONS = 8
    SAMPLES_CAP = 256

    def __init__(self, pinned: int | None = None):
        if pinned is None:
            raw = os.environ.get(ENV_DEPTH)
            if raw:
                try:
                    pinned = int(raw)
                except ValueError:
                    pinned = None
        self.pinned = self._clamp(pinned) if pinned else None
        self._lock = threading.Lock()
        self._dispatch: deque = deque(maxlen=self.SAMPLES_CAP)
        self._walls: deque = deque(maxlen=self.SAMPLES_CAP)

    @classmethod
    def _clamp(cls, k: int) -> int:
        for d in cls.DEPTHS:
            if k <= d:
                return d
        return cls.DEPTHS[-1]

    @classmethod
    def from_manifest(cls, manifest, kernel: str, **kw) -> "RingDepth":
        """Seed device-wall evidence from the compile manifest's measured
        per-shape walls (LaunchDeadlines.from_manifest is the template:
        tolerant of a missing/corrupt manifest — an empty one just means
        the trainer starts at the conservative minimum)."""
        d = cls(**kw)
        try:
            walls = manifest.shape_walls(kernel)
        except Exception:
            walls = {}
        d.seed(walls)
        return d

    def seed(self, walls: dict) -> None:
        with self._lock:
            for w in walls.values():
                if isinstance(w, (int, float)) and w > 0:
                    self._walls.append(float(w))

    def observe(self, dispatch_s: float, wall_s: float) -> None:
        """One completed flight: host-side dispatch overhead (guarded
        pack-wait + dispatch call) and the device wall it overlapped."""
        with self._lock:
            if dispatch_s > 0:
                self._dispatch.append(float(dispatch_s))
            if wall_s > 0:
                self._walls.append(float(wall_s))

    def depth(self) -> int:
        if self.pinned:
            return self.pinned
        with self._lock:
            if len(self._dispatch) < self.MIN_OBSERVATIONS or \
                    len(self._walls) < self.MIN_OBSERVATIONS:
                return self.DEPTHS[0]
            o = _percentile(sorted(self._dispatch), 0.5)
            w = _percentile(sorted(self._walls), 0.5)
        if w <= 0:
            return self.DEPTHS[0]
        return self._clamp(1 + int(o / w + 0.999))

    def snapshot(self) -> dict:
        k = self.depth()  # takes the lock itself — stay outside it here
        with self._lock:
            return {
                "k": k,
                "pinned": bool(self.pinned),
                "dispatch_samples": len(self._dispatch),
                "wall_samples": len(self._walls),
            }


class CadenceStats:
    """Ring telemetry behind the OP_STATS ``cadence`` section.  All
    counters are written from the ring (engine) thread; snapshot() is
    called from connection threads, so every touch is lock-guarded.
    Queue waits ride a bounded reservoir like SchedStats' — p50/p99 of
    admission -> cadence dispatch."""

    WAIT_SAMPLES_CAP = 4096

    def __init__(self, clock=monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self.ticks = 0
        self.dispatch_ticks = 0
        self.idle_ticks = 0
        self.occupancy_hist: dict = {}
        self.launched_sigs = 0
        self.pad_fill_sigs = 0
        self.generation_drops = 0
        self.expiries = 0
        self.expired_sigs = 0
        self.fallbacks = 0
        self._waits: deque = deque(maxlen=self.WAIT_SAMPLES_CAP)
        self._first_tick_t: float | None = None
        self._last_tick_t: float | None = None

    def note_tick(self, occupied: int, armed: bool) -> None:
        with self._lock:
            now = self._clock()
            if self._first_tick_t is None:
                self._first_tick_t = now
            self._last_tick_t = now
            self.ticks += 1
            if armed:
                self.dispatch_ticks += 1
            else:
                self.idle_ticks += 1
            self.occupancy_hist[occupied] = \
                self.occupancy_hist.get(occupied, 0) + 1

    def note_dispatch(self, total_sigs: int, fill_sigs: int,
                      waits) -> None:
        with self._lock:
            self.launched_sigs += total_sigs
            self.pad_fill_sigs += fill_sigs
            self._waits.extend(waits)

    def note_generation_drop(self) -> None:
        with self._lock:
            self.generation_drops += 1

    def note_expiry(self, sigs: int) -> None:
        with self._lock:
            self.expiries += 1
            self.expired_sigs += sigs

    def note_fallback(self) -> None:
        with self._lock:
            self.fallbacks += 1

    def snapshot(self, *, enabled: bool, depth: int) -> dict:
        with self._lock:
            span = 0.0
            if self._first_tick_t is not None and self.ticks > 1:
                span = self._last_tick_t - self._first_tick_t
            waits = sorted(self._waits)
            return {
                "enabled": enabled,
                "depth": depth,
                "ticks": self.ticks,
                "dispatch_ticks": self.dispatch_ticks,
                "idle_ticks": self.idle_ticks,
                "tick_rate_hz": round((self.ticks - 1) / span, 3)
                if span > 0 else 0.0,
                "occupancy_hist": {str(k): v for k, v
                                   in sorted(self.occupancy_hist.items())},
                "pad_fill": {
                    "sigs": self.pad_fill_sigs,
                    "launched_sigs": self.launched_sigs,
                    "ratio": round(self.pad_fill_sigs / self.launched_sigs,
                                   4) if self.launched_sigs else 0.0,
                },
                "generation": {
                    "drops": self.generation_drops,
                    "expiries": self.expiries,
                    "expired_sigs": self.expired_sigs,
                },
                "fallbacks": self.fallbacks,
                "queue_wait": {
                    "n": len(waits),
                    "p50_ms": round(_percentile(waits, 0.5) * 1e3, 3),
                    "p99_ms": round(_percentile(waits, 0.99) * 1e3, 3),
                },
            }


class RingSlot:
    """One buffer position of the ring.  ``generation`` is bumped on
    every arm and every invalidation; a flight holds the generation it
    was armed under and its verdict applies only on exact match —
    Python ints never wrap, and slot REUSE (the ring cycling back to
    index 0) is exactly the case the tag exists for."""

    __slots__ = ("index", "generation")

    def __init__(self, index: int):
        self.index = index
        self.generation = 0


class _Flight:
    """An armed launch in the device pipeline: the slot + generation it
    was armed under, the batch, and the guarded fetch closure.
    ``traced`` is None, or (launch scope, dispatch end on the tracer's
    clock, dispatch guard hop) for the flight's ``device`` span."""

    __slots__ = ("slot", "generation", "batch", "fetch", "key",
                 "dispatched_at", "dispatch_s", "sigs", "traced")

    def __init__(self, slot, generation, batch, fetch, key,
                 dispatched_at, dispatch_s, sigs, traced=None):
        self.slot = slot
        self.generation = generation
        self.batch = batch
        self.fetch = fetch
        self.key = key
        self.dispatched_at = dispatched_at
        self.dispatch_s = dispatch_s
        self.sigs = sigs
        self.traced = traced


class CadenceRing:
    """The resident cadence loop.  Runs ON the engine thread
    (``VerifyEngine._run`` calls :meth:`run` before falling back to the
    staged loop), so every engine-side invariant — single consumer,
    reply-once, pack worker streaming — carries over unchanged.

    Tick body (see :meth:`_tick_once`; the graftlint ring rule pins the
    discipline — no unbounded waits, no unwarmed-shape launches):

      1. expire: any flight uncollected past its deadline window is
         re-resolved on the host and its generation invalidated, so the
         late device verdict is provably discarded;
      2. collect: when the ring is full (or nothing new arrived), the
         oldest flight's verdict is fetched under the guard and applied
         iff its generation still matches;
      3. arm: a free slot takes the scheduler's per-tick quota
         (pad-filled from the bulk backlog) through the engine's pack
         worker, dispatched under the ``tick:`` guard class.

    Pacing is load-adaptive between MIN_TICK_S and MAX_TICK_S: armed or
    backlogged ticks run flat-out at MIN_TICK_S; idle ticks back off
    exponentially, and a fully-idle ring parks INSIDE
    ``Scheduler.next_tick``'s bounded wait so a fresh latency request
    wakes it immediately rather than eating a full idle interval."""

    MIN_TICK_S = 0.002
    MAX_TICK_S = 0.25
    # A flight uncollected this many multiples of its guard deadline is
    # expired (host re-resolve + generation bump).  The guard already
    # bounds the FETCH; expiry bounds the verdict of a flight the loop
    # never got back to — the one the guard cannot see.
    EXPIRY_DEADLINES = 2.0
    DEFAULT_EXPIRY_S = 30.0

    def __init__(self, engine, *, depth: RingDepth | None = None,
                 tick_s: float | None = None,
                 expiry_s: float | None = None,
                 clock=monotonic, wait=None):
        self.engine = engine
        self.depth = depth if depth is not None else RingDepth()
        if tick_s is None:
            raw = os.environ.get(ENV_TICK_S)
            if raw:
                try:
                    tick_s = float(raw)
                except ValueError:
                    tick_s = None
        self.pinned_tick_s = tick_s
        self.expiry_s = expiry_s
        self.stats = CadenceStats(clock=clock)
        self.enabled = True
        self._clock = clock
        self._wait = wait if wait is not None else engine._stopped.wait
        self._slots = [RingSlot(i) for i in range(max(RingDepth.DEPTHS))]
        self._next_slot = 0
        self._pending: deque = deque()  # _Flight, oldest first
        self._idle_streak = 0

    # -- public --------------------------------------------------------------

    def snapshot(self) -> dict:
        out = self.stats.snapshot(enabled=self.enabled,
                                  depth=self.depth.depth())
        out["depth_trainer"] = self.depth.snapshot()
        return out

    def run(self) -> None:
        """The cadence loop; returns on engine stop (after draining every
        in-flight verdict) or on wedge fallback (``enabled`` False, all
        generations re-resolved — the staged loop takes over with no
        reply outstanding)."""
        engine = self.engine
        log.info("cadence: ring engaged (depth %d)", self.depth.depth())
        while self.enabled and not engine._stopped.is_set():
            t0 = self._clock()
            armed = self._tick_once(t0)
            occupied = len(self._pending)
            self.stats.note_tick(occupied, armed)
            self._note_occupancy(occupied)
            if not self.enabled or engine._stopped.is_set():
                break
            interval = self._interval(armed, occupied)
            elapsed = self._clock() - t0
            if occupied == 0 and not armed:
                # Fully idle: park in the scheduler's bounded wait so a
                # fresh offer wakes the ring immediately.
                launch = engine._sched.next_tick(self._quota_sigs(),
                                                 timeout=interval)
                if launch is not None and self._take_launch(launch):
                    # The park-path arm IS a dispatch tick — record it so
                    # tick accounting matches what actually launched.
                    self.stats.note_tick(len(self._pending), True)
                    self._note_occupancy(len(self._pending))
            elif interval > elapsed:
                self._wait(interval - elapsed)
        if self.enabled:
            # Clean stop: every accepted request still gets its reply.
            while self._pending:
                self._collect_oldest()
        log.info("cadence: ring disengaged (%s)",
                 "stopped" if self.enabled else "wedge fallback")

    # -- tick body -----------------------------------------------------------

    def _tick_once(self, now: float) -> bool:
        """One cadence tick; True iff a slot was armed this tick."""
        self._expire_overdue(now)
        if not self.enabled:
            return False
        k = self.depth.depth()
        if len(self._pending) >= k:
            self._collect_oldest()
        if not self.enabled:
            return False
        armed = False
        if len(self._pending) < k:
            launch = self.engine._sched.next_tick(self._quota_sigs())
            if launch is not None:
                armed = self._take_launch(launch)
        if not armed and self._pending:
            # Nothing new arrived: make progress on the oldest verdict
            # so light load sees one-tick reply latency, not depth-k.
            self._collect_oldest()
        return armed

    def _quota_sigs(self) -> int:
        return self.engine._shapes.launch_cap

    def _take_launch(self, launch) -> bool:
        """Route one per-tick quota: BLS heads run inline after a full
        drain (a QC aggregate is one check — nothing to keep resident);
        Ed25519 quotas arm a ring slot."""
        engine = self.engine
        scope = engine._begin_launch(launch)
        if launch.kind == "bls":
            while self._pending:
                self._collect_oldest()
                if not self.enabled:
                    return False
            (item,) = launch.items
            tags = {"lid": scope.lid, "parent": None} \
                if scope.enabled else {}
            with engine._tracer.span("device", kind="bls",
                                     rid=item.request.request_id, **tags):
                engine._execute_bls(item)
            return True
        return self._arm(launch, scope)

    def _arm(self, launch, scope=NO_LAUNCH) -> bool:
        """Arm the next ring slot with this launch: stream the batch
        through the engine's pack worker, dispatch under the ``tick:``
        guard class, and tag the flight with the slot's new
        generation."""
        engine = self.engine
        batch = launch.items
        key = self._tick_key(batch)
        slot = self._slots[self._next_slot]
        self._next_slot = (self._next_slot + 1) % max(RingDepth.DEPTHS)
        slot.generation += 1
        gen = slot.generation
        fut = engine._pack_pool.submit(engine._pack, batch, scope)
        t0 = self._clock()
        span_t0 = scope.now() if scope.enabled else 0.0
        try:
            # pack wait + device dispatch under one guarded deadline —
            # the identical discipline to the staged _dispatch_one.
            with scope.annotate("dispatch"):
                fetch = engine._guarded(key, lambda: fut.result()())
        except WedgedLaunch:
            slot.generation += 1  # invalidate before the ladder answers
            self._fallback(batch, key, stage="dispatch")
            return False
        except Exception:
            log.exception("cadence: pack/dispatch failed")
            slot.generation += 1
            for p in batch:
                p.reply_fn([False] * len(p.request.msgs))
            return False
        dispatch_s = self._clock() - t0
        sigs = sum(len(p.request.msgs) for p in batch)
        # One schema with the staged loop: the engine's own helpers
        # write the ring's dispatch and device spans.
        traced = (scope,) + engine._trace_dispatch(
            scope, batch, span_t0, cadence=True) if scope.enabled else None
        self._pending.append(_Flight(slot, gen, batch, fetch, key,
                                     self._clock(), dispatch_s, sigs,
                                     traced))
        fill = launch.items[len(launch.items) - launch.fill_count:]
        now = self._clock()
        self.stats.note_dispatch(
            sigs, sum(len(p.request.msgs) for p in fill),
            [now - p.enqueued_at for p in batch])
        return True

    def _collect_oldest(self) -> None:
        """Fetch the oldest flight's verdict under the guard and apply
        it iff the generation still matches (stale => counted drop, no
        reply — whoever bumped the generation already answered)."""
        engine = self.engine
        fl = self._pending.popleft()
        try:
            mask = engine._guarded(fl.key, fl.fetch)
        except WedgedLaunch:
            if fl.generation == fl.slot.generation:
                fl.slot.generation += 1
                self._fallback(fl.batch, fl.key, stage="fetch")
            else:
                self.stats.note_generation_drop()
            return
        except Exception:
            if fl.generation != fl.slot.generation:
                self.stats.note_generation_drop()
                return
            log.exception("cadence: fetch failed")
            fl.slot.generation += 1
            for p in fl.batch:
                p.reply_fn([False] * len(p.request.msgs))
            return
        if fl.generation != fl.slot.generation:
            # Re-armed or expired since dispatch: the verdict is stale
            # BY TAG, regardless of what the device computed.
            self.stats.note_generation_drop()
            return
        wall = self._clock() - fl.dispatched_at
        self.depth.observe(fl.dispatch_s, wall)
        if fl.traced is not None:
            scope, dispatched_at, hop_s = fl.traced
            engine._trace_device(scope, fl.batch, dispatched_at, hop_s,
                                 cadence=True)
        off = 0
        for p in fl.batch:
            n = len(p.request.msgs)
            p.reply_fn([bool(b) for b in mask[off:off + n]])
            off += n

    # -- expiry / fallback ---------------------------------------------------

    def _flight_expiry_s(self, fl) -> float:
        if self.expiry_s is not None:
            return self.expiry_s
        guard = self.engine._guard
        if guard is not None:
            return self.EXPIRY_DEADLINES * guard.deadlines.deadline_s(fl.key)
        return self.DEFAULT_EXPIRY_S

    def _expire_overdue(self, now: float) -> None:
        """Host-re-resolve every flight uncollected past its window and
        invalidate its generation — the late fetch becomes a counted
        drop instead of a double reply."""
        for fl in list(self._pending):
            if fl.generation != fl.slot.generation:
                continue  # already invalidated; drops at collect
            if now - fl.dispatched_at <= self._flight_expiry_s(fl):
                continue
            fl.slot.generation += 1
            self.stats.note_expiry(fl.sigs)
            log.warning("cadence: flight %s expired uncollected; "
                        "re-resolving on host", fl.key)
            self._host_resolve(fl.batch)

    def _host_resolve(self, batch) -> None:
        """Answer a batch without the device: latency-class requests get
        host reference masks (bit-identical by the same property tests
        the wedge ladder leans on), bulk gets BUSY + retry-after."""
        from ..crypto import ref_ed25519 as ref

        engine = self.engine
        for p in batch:
            if p.cls == vsched.BULK:
                p.reply_fn(BusyReply(engine.retry_after_ms(vsched.BULK)))
                continue
            p.reply_fn([bool(ref.verify(pk, m, s))
                        for m, pk, s in zip(p.request.msgs, p.request.pks,
                                            p.request.sigs)])

    def _fallback(self, batch, key: str, stage: str) -> None:
        """A cadence launch wedged: ride the engine's existing ladder for
        the wedged batch (host masks / BUSY, quarantine, crash-only
        reboot), re-resolve every OTHER in-flight generation on the
        host, and disengage — VerifyEngine._run falls through to the
        staged loop."""
        self.stats.note_fallback()
        self.enabled = False
        self.engine._wedge_ladder(batch, key, stage=stage)
        for fl in list(self._pending):
            if fl.generation == fl.slot.generation:
                fl.slot.generation += 1
                self._host_resolve(fl.batch)
        # Flights stay referenced nowhere: their device verdicts die with
        # the reboot's teardown; replies are already out exactly once.
        self._pending.clear()

    # -- pacing --------------------------------------------------------------

    def _interval(self, armed: bool, occupied: int) -> float:
        if self.pinned_tick_s is not None:
            return self.pinned_tick_s
        sched = self.engine._sched
        backlog = sched.queued_sigs(vsched.LATENCY) + \
            sched.queued_sigs(vsched.BULK)
        if armed or occupied or backlog:
            self._idle_streak = 0
            return self.MIN_TICK_S
        self._idle_streak += 1
        return min(self.MAX_TICK_S,
                   self.MIN_TICK_S * (2 ** min(self._idle_streak, 10)))

    # -- helpers -------------------------------------------------------------

    def _tick_key(self, batch) -> str:
        """Per-tick guard deadline class: same deduped power-of-two shape
        bucket as the staged key, under the ``tick:`` prefix so the
        guard applies the warm grace (the ring never launches an
        unwarmed shape) instead of the compile budget."""
        staged = self.engine._guard_key(batch)
        return "tick:" + staged.split(":", 1)[1]

    def _note_occupancy(self, occupied: int) -> None:
        adm = getattr(self.engine._sched, "admission", None)
        if adm is not None:
            adm.note_ring_occupancy(occupied, self.depth.depth())
