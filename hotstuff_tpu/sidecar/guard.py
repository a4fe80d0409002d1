"""graftguard: the supervised verify engine — launch deadlines, wedge
detection, poison-batch quarantine, and crash-only reboot support.

The failure this layer exists for is the *wedged device launch*: one
hung ``dispatch()``/``fetch()`` parks the engine thread — and every
queued consensus verify behind it — until the C++ circuit breaker times
the whole sidecar out.  Production inference
stacks solve exactly this with per-launch deadlines, hung-device
watchdogs, and crash-only restart; the reference's tokio nodes get it
for free from task-level timeouts.  This module is that layer for the
single-threaded verify engine:

    launch ──▶ guard worker thread (disposable, one per launch)
       │            │
       │            ▼ completes within its per-shape deadline
       │        result → engine replies normally
       │
       └──▶ monitor thread sees the deadline overrun → WEDGED
                │
                ▼  the engine's degradation ladder (service._wedge_ladder)
            1. latency-class requests in the wedged batch are answered
               from the HOST path (bit-identical masks — the same
               ref_ed25519 reference verify_batch is property-tested
               against);
            2. bulk-class requests get OP_BUSY with a drain-derived
               retry-after (BusyReply below);
            3. the batch's records are quarantined (repeat offenders
               trigger poison bisection, below);
            4. the engine performs a CRASH-ONLY reboot: tear down the
               device-side caches, re-warm asynchronously off the
               populated XLA cache/manifest (the host path serves
               meanwhile, bulk admission replies BUSY), and resume
               device routing only after a canary launch passes.

Deadlines are per launch shape, derived from the CompileManifest's run
history: a warmed boot (the manifest has entries for this kernel) gets
the tight ``warm_grace_s`` default until the guard has observed enough
launches of a shape to derive ``p99_multiple`` x its measured p99; a
cold boot — where a first-ever compile can legitimately take minutes —
gets the generous ``compile_budget_s``.  Env knobs:

    HOTSTUFF_TPU_GUARD_COMPILE_BUDGET_S   cold/first-compile deadline (180)
    HOTSTUFF_TPU_GUARD_WARM_GRACE_S       warmed-shape fallback deadline (30)
    HOTSTUFF_TPU_GUARD_P99_MULTIPLE      deadline = multiple x observed p99 (8)
    HOTSTUFF_TPU_GUARD_MIN_DEADLINE_S    floor under the p99 rule (3.0)
    HOTSTUFF_TPU_GUARD_MAX_REBOOTS       canary failures before the engine
                                         stays on the host path (3)
    HOTSTUFF_TPU_GUARD_MAX_BISECT_PROBES poison-bisection probe budget (64)

Crash-only discipline: a wedged launch thread is never interrupted (a
hung device call cannot be cancelled from Python) — it is ABANDONED
with its disposable thread (daemon: it dies with the process), its late
completion is discarded, and a fresh thread serves the next launch.
Nothing the abandoned thunk eventually does can reach a client: replies
happen on the engine thread only after a guarded call returns clean.

Poison bisection reuses the RLC bisection discipline (halve, probe,
recurse into the wedging half): repeat wedges on the same records mark
them pending, and after the reboot's canary passes the engine probes
subsets under the guard until the minimal poison set is isolated.  A
poisoned record is host-verified (and counted) forever after — one
adversarial or cursed record can never take the device leg down again.

BLS launches ride the guard too: ``_execute_bls_inner`` RETURNS its
verdict (it never touches the connection), the engine thread replies
only after the guarded call comes back clean, and a wedged pairing gets
the BLS arm of the ladder — transient reply (the C++ client reads
nullopt and runs its own outage handling) plus the crash-only reboot.
The unwarmed-shape host fallback (``_bls_multi_warmed``) remains as the
first line; the guard is what bounds it when the host pairing itself
wedges.
"""

from __future__ import annotations

import itertools
import logging
import os
import threading
from time import monotonic

from ..obs.cpu import ThreadCpu

log = logging.getLogger("sidecar.guard")


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        v = float(raw)
    except ValueError:
        return default
    return v if v > 0 else default


class WedgedLaunch(RuntimeError):
    """A guarded launch overran its deadline; the worker was abandoned."""

    def __init__(self, key: str, deadline_s: float):
        super().__init__(
            f"launch {key} overran its {deadline_s:g}s deadline (wedged)")
        self.key = key
        self.deadline_s = deadline_s


class BusyReply:
    """Sentinel reply value for the wedge ladder's bulk lane: the
    connection handler encodes it as an OP_BUSY frame carrying the
    drain-derived retry-after hint instead of a verdict mask (protocol
    v4 — the C++ client reads it as a shed and the breaker reads it as
    a LIVE sidecar, never silence)."""

    __slots__ = ("retry_after_ms",)

    def __init__(self, retry_after_ms: int):
        self.retry_after_ms = int(retry_after_ms)


def _percentile(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = max(0, min(len(sorted_vals) - 1,
                     int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


class LaunchDeadlines:
    """Per-shape launch deadlines off the compile-manifest run history
    plus the guard's own observed launch walls.

    Until ``MIN_OBSERVATIONS`` launches of a shape key have completed,
    the deadline is the boot-state fallback: ``warm_grace_s`` when the
    manifest says this kernel's shapes were warmed before (the XLA disk
    cache deserializes — nothing should take 30 s), ``compile_budget_s``
    otherwise (a first-ever compile legitimately takes up to a minute
    per program — tests/test_tpu_compile.py — and must not read as a
    wedge).  With enough
    observations the deadline tightens to ``p99_multiple`` x the
    measured p99, floored at ``min_deadline_s``."""

    MIN_OBSERVATIONS = 8
    SAMPLES_CAP = 256
    # Keys that are ALWAYS compile-class regardless of boot state or
    # observed history: the reboot canary and the poison-bisection
    # probes run right after _teardown_device cleared the in-process
    # jit caches, so their first launch re-traces/deserializes — a
    # tight warmed deadline there would false-wedge the recovery
    # itself (observed: a contended host failing every canary).
    COMPILE_CLASS_PREFIXES = ("canary:", "poison-probe:")
    # graftcadence tick launches: the ring only ever dispatches warmed
    # ShapeRegistry buckets (a fresh compile mid-run is the lint rule's
    # whole point), so an unobserved ``tick:`` key gets the warm grace
    # regardless of boot state — the compile budget would let a wedged
    # cadence tick stall the resident pipeline for minutes on a cold
    # manifest that the ring, by construction, never compiles under.
    TICK_CLASS_PREFIX = "tick:"

    def __init__(self, warm_boot: bool = False,
                 compile_budget_s: float | None = None,
                 warm_grace_s: float | None = None,
                 p99_multiple: float | None = None,
                 min_deadline_s: float | None = None):
        self.warm_boot = bool(warm_boot)
        self.compile_budget_s = compile_budget_s if compile_budget_s \
            is not None else _env_float(
                "HOTSTUFF_TPU_GUARD_COMPILE_BUDGET_S", 180.0)
        self.warm_grace_s = warm_grace_s if warm_grace_s is not None \
            else _env_float("HOTSTUFF_TPU_GUARD_WARM_GRACE_S", 30.0)
        self.p99_multiple = p99_multiple if p99_multiple is not None \
            else _env_float("HOTSTUFF_TPU_GUARD_P99_MULTIPLE", 8.0)
        # 3 s, not 1: on a v5e host a launch's completion reaches the
        # process a second or more late now and then (three times in
        # ~345 s of serving, none over 0.2 s in the next 360: PERF.md,
        # PR 28), and a false wedge costs a host fallback, a device
        # reboot and a ~200 s re-warm.  Until PR 28 the rlc route never
        # ran at the floor: 8 x its p99 (a 0.33 s bisection) was 2.6 s.
        self.min_deadline_s = min_deadline_s if min_deadline_s is not None \
            else _env_float("HOTSTUFF_TPU_GUARD_MIN_DEADLINE_S", 3.0)
        self._lock = threading.Lock()
        self._samples: dict[str, list] = {}

    @classmethod
    def from_manifest(cls, manifest, kernel: str, cache_dir: str, **kw):
        """Deadline policy for a boot against ``manifest``: warmed only
        when the manifest holds a shape for this kernel hash that was
        warmed against ``cache_dir`` — the XLA cache this boot uses —
        and that directory still exists (CompileManifest.seen's rule,
        the one CompileTracker counts hits by).  A manifest alone cannot
        prove the compiled programs are on this machine: without the
        directory check a first boot would judge its first compiles by
        the warm grace."""
        try:
            warm = any(manifest.seen(kernel, key, cache_dir=cache_dir)
                       for key in manifest.shape_walls(kernel))
        except Exception:  # noqa: BLE001 — a hostile manifest means cold
            warm = False
        return cls(warm_boot=warm, **kw)

    def observe(self, key: str, dur_s: float):
        with self._lock:
            samples = self._samples.setdefault(key, [])
            samples.append(float(dur_s))
            del samples[:-self.SAMPLES_CAP]

    def deadline_s(self, key: str) -> float:
        if key.startswith(self.COMPILE_CLASS_PREFIXES):
            return self.compile_budget_s
        with self._lock:
            samples = self._samples.get(key, ())
            if len(samples) >= self.MIN_OBSERVATIONS:
                p99 = _percentile(sorted(samples), 0.99)
                return max(self.min_deadline_s, self.p99_multiple * p99)
        if key.startswith(self.TICK_CLASS_PREFIX):
            return self.warm_grace_s
        return self.warm_grace_s if self.warm_boot \
            else self.compile_budget_s

    def snapshot(self) -> dict:
        """JSON-safe per-key summary (bounded by SAMPLES_CAP keys in
        practice: keys are padded launch buckets, a handful per boot)."""
        with self._lock:
            keys = dict(self._samples)
        out = {}
        for key, samples in sorted(keys.items()):
            out[key] = {"n": len(samples),
                        "deadline_s": round(self.deadline_s(key), 3)}
        return out


class Quarantine:
    """Wedge bookkeeping per (msg, pk, sig) record.

    First wedge on a record is weather (a device hiccup wedges whatever
    batch was in flight); a REPEAT wedge marks the record a bisection
    candidate (``pending``), and ``resolve`` — fed by bisect_poison
    after the reboot's canary passes — moves the confirmed poison
    records into the permanent host-verified set."""

    POISON_WEDGES = 2
    CAP = 4096  # wedge-count records kept (FIFO; an attacker evicts, never grows)

    def __init__(self):
        self._lock = threading.Lock()
        self._wedges: dict = {}       # record -> wedge count (bounded FIFO)
        self._pending: list = []      # repeat offenders awaiting bisection
        self._poisoned: set = set()   # confirmed poison: host-verified forever

    def note_wedged(self, records) -> int:
        """Bump wedge counts for every record of a wedged batch; records
        reaching POISON_WEDGES join the pending-bisection set.  Returns
        how many records are now pending."""
        with self._lock:
            for rec in records:
                if rec in self._poisoned:
                    continue
                count = self._wedges.get(rec, 0) + 1
                if rec not in self._wedges:
                    while len(self._wedges) >= self.CAP:
                        self._wedges.pop(next(iter(self._wedges)))
                self._wedges[rec] = count
                if count >= self.POISON_WEDGES and \
                        rec not in self._pending:
                    self._pending.append(rec)
            return len(self._pending)

    def pending(self) -> list:
        with self._lock:
            return list(self._pending)

    def resolve(self, poison_records) -> int:
        """Close one bisection round: ``poison_records`` move to the
        permanent poisoned set, everything else pending is released
        (its wedge count survives, so a third wedge re-marks it).
        Returns how many records were newly poisoned."""
        with self._lock:
            before = len(self._poisoned)
            for rec in poison_records:
                self._poisoned.add(rec)
                self._wedges.pop(rec, None)
            self._pending = []
            return len(self._poisoned) - before

    def is_poisoned(self, record) -> bool:
        return record in self._poisoned

    def has_poison(self) -> bool:
        return bool(self._poisoned)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "suspect_records": len(self._wedges),
                "pending_bisection": len(self._pending),
                "poisoned_records": len(self._poisoned),
            }


def bisect_poison(records, probe, max_probes: int = 64) -> list:
    """Isolate the poison records of a wedging batch by bisection — the
    RLC bisection discipline applied to wedges instead of invalid
    masks.  ``probe(subset) -> bool`` launches the subset under the
    guard's deadline and says whether it COMPLETED (True) or wedged
    (False).  Returns the poison records.

    Rules: a completing subset is clean; a wedging singleton is poison;
    a wedging set whose both halves complete is an interaction the
    bisection cannot split — the whole set is returned (quarantined),
    never silently released.  ``max_probes`` bounds the device time one
    recovery spends probing: leftovers past the budget stay quarantined
    (host-verified), which is safe, just conservative."""
    budget = [int(max_probes)]

    def rec(rs):
        if not rs:
            return []
        if budget[0] <= 0:
            return list(rs)  # unprobed leftovers stay quarantined
        budget[0] -= 1
        if probe(list(rs)):
            return []
        if len(rs) == 1:
            return list(rs)
        mid = len(rs) // 2
        left = rec(rs[:mid])
        right = rec(rs[mid:])
        if not left and not right:
            return list(rs)  # both halves clean alone: interaction set
        return left + right

    return rec(list(records))


class GuardStats:
    """Counters behind the OP_STATS ``guard`` section."""

    def __init__(self):
        self._lock = threading.Lock()
        self.wedges = 0
        self.wedges_by_key: dict[str, int] = {}
        self.late_completions = 0
        self.reboots = 0
        self.canary_passes = 0
        self.canary_failures = 0
        self.host_fallback_records = 0
        self.busy_replies = 0
        self.poison_host_verified = 0
        self.last_reboot_wall_s = 0.0
        self.last_rewarm_wall_s = 0.0
        self.calls = 0          # guarded calls that came back in time
        self.hop_s_total = 0.0  # their thread hops (LaunchGuard.call)

    def note_hop(self, hop_s: float):
        with self._lock:
            self.calls += 1
            self.hop_s_total += hop_s

    def note_wedge(self, key: str):
        with self._lock:
            self.wedges += 1
            self.wedges_by_key[key] = self.wedges_by_key.get(key, 0) + 1

    def note_late_completion(self, key: str):
        with self._lock:
            self.late_completions += 1

    def note_reboot(self, wall_s: float):
        with self._lock:
            self.reboots += 1
            self.last_reboot_wall_s = float(wall_s)

    def note_rewarm(self, wall_s: float):
        with self._lock:
            self.last_rewarm_wall_s = float(wall_s)

    def note_canary(self, ok: bool):
        with self._lock:
            if ok:
                self.canary_passes += 1
            else:
                self.canary_failures += 1

    def note_host_fallback(self, n: int):
        with self._lock:
            self.host_fallback_records += int(n)

    def note_busy(self):
        with self._lock:
            self.busy_replies += 1

    def note_poison_host(self, n: int):
        with self._lock:
            self.poison_host_verified += int(n)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "wedges": self.wedges,
                "wedges_by_key": dict(self.wedges_by_key),
                "late_completions": self.late_completions,
                "reboots": self.reboots,
                "canary_passes": self.canary_passes,
                "canary_failures": self.canary_failures,
                "host_fallback_records": self.host_fallback_records,
                "busy_replies": self.busy_replies,
                "poison_host_verified": self.poison_host_verified,
                "last_reboot_wall_s": round(self.last_reboot_wall_s, 3),
                "last_rewarm_wall_s": round(self.last_rewarm_wall_s, 3),
                "calls": self.calls,
                "hop_s_total": round(self.hop_s_total, 6),
            }


class _GuardedCall:
    __slots__ = ("key", "deadline_s", "started_at", "done", "result",
                 "exc", "wedged", "thunk_s")

    def __init__(self, key: str, deadline_s: float, started_at: float):
        self.key = key
        self.deadline_s = deadline_s
        self.started_at = started_at
        self.done = threading.Event()
        self.result = None
        self.exc = None
        self.wedged = False
        self.thunk_s = 0.0      # the thunk's own time on its launch thread


class LaunchGuard:
    """The launch supervisor: every staged device call runs on a
    DISPOSABLE daemon thread while the caller waits; a monitor thread
    declares a deadline overrun WEDGED, wakes the caller (which raises
    :class:`WedgedLaunch` and executes the engine's degradation
    ladder), and the hung thread is abandoned — crash-only, never
    interrupted or reused.  Thread-per-launch costs ~100 us per
    dispatch (its share of a launch: not measured on the chip); what it
    buys is that one wedge can never poison a shared worker queue."""

    POLL_S = 0.02
    _ids = itertools.count()

    def __init__(self, deadlines: LaunchDeadlines | None = None,
                 stats: GuardStats | None = None, clock=monotonic,
                 max_reboots: int | None = None,
                 max_bisect_probes: int | None = None,
                 cpu: ThreadCpu | None = None):
        self.deadlines = deadlines if deadlines is not None \
            else LaunchDeadlines()
        self.stats = stats if stats is not None else GuardStats()
        self.quarantine = Quarantine()
        self.max_reboots = int(max_reboots) if max_reboots is not None \
            else int(_env_float("HOTSTUFF_TPU_GUARD_MAX_REBOOTS", 3))
        self.max_bisect_probes = int(max_bisect_probes) \
            if max_bisect_probes is not None else int(_env_float(
                "HOTSTUFF_TPU_GUARD_MAX_BISECT_PROBES", 64))
        self._clock = clock
        # The sidecar's thread-CPU registry (obs/cpu.py): every launch
        # thread counts under ``launch``; the engine shares this one.
        self.cpu = cpu if cpu is not None else ThreadCpu()
        self._lock = threading.Lock()
        self._calls: set = set()
        self._last = threading.local()  # per calling thread: last_hop_s
        self._stop = threading.Event()
        self._monitor = threading.Thread(
            target=self._monitor_loop, daemon=True, name="guard-monitor")
        self._monitor.start()

    def close(self):
        self._stop.set()

    @property
    def last_hop_s(self) -> float:
        """Thread hop of the calling thread's last :meth:`call` that
        came back in time (0.0 before the first)."""
        return getattr(self._last, "hop_s", 0.0)

    # -- supervision ---------------------------------------------------------

    def _monitor_loop(self):
        """Declares overruns: any in-flight guarded call past its
        deadline is marked wedged and its waiter woken NOW — the waiter
        abandons the launch thread and runs the ladder."""
        while not self._stop.wait(self.POLL_S):
            now = self._clock()
            with self._lock:
                live = list(self._calls)
            for call in live:
                if call.done.is_set():
                    continue
                if now - call.started_at > call.deadline_s:
                    call.wedged = True
                    call.done.set()

    def _run_call(self, call: _GuardedCall, thunk):
        self.cpu.enter("launch")
        try:
            self._run_thunk(call, thunk)
        finally:
            # The thread's one CPU-clock read, after the caller was
            # woken: outside ``thunk_s`` and the caller's hop.
            self.cpu.leave()

    def _run_thunk(self, call: _GuardedCall, thunk):
        t0 = self._clock()
        try:
            call.result = thunk()
        except BaseException as e:  # noqa: BLE001 — re-raised by call()
            call.exc = e
        call.thunk_s = self._clock() - t0
        if call.wedged:
            # Late completion of an abandoned launch: the engine already
            # answered its batch from the ladder — the result is
            # DISCARDED here and must have no reachable side effects
            # (dispatch/fetch thunks return data; replies happen on the
            # engine thread, and the verdict cache takes its own lock).
            self.stats.note_late_completion(call.key)
            return
        call.done.set()

    def call(self, key: str, thunk):
        """Run ``thunk`` on a disposable launch thread under the shape's
        deadline; returns its result, re-raises its exception, or
        raises :class:`WedgedLaunch` when the monitor declared an
        overrun (the thread is abandoned — crash-only).

        The call measures its own thread hop — its wall time minus the
        thunk's time on the launch thread: thread start, the two
        wake-ups — into :attr:`last_hop_s` (the calling thread's last
        call) and the snapshot's ``hop_s_total`` / ``calls``."""
        call = _GuardedCall(key, self.deadlines.deadline_s(key),
                            self._clock())
        with self._lock:
            self._calls.add(call)
        # One-shot disposable body, not a service loop: it runs exactly
        # one thunk and exits — a stop flag could not interrupt a hung
        # device call anyway, and ABANDONING the thread on a wedge is
        # the crash-only design (daemon: it dies with the process).
        # graftlint: disable=daemon-thread-without-stop-flag
        t = threading.Thread(target=self._run_call, args=(call, thunk),
                             daemon=True,
                             name=f"guard-launch-{next(self._ids)}")
        t.start()
        # The monitor guarantees a wake-up at the deadline, so this wait
        # is bounded by construction (evidence: _monitor_loop sets
        # call.done on every overrun; the monitor thread is started in
        # __init__ and only close() stops it).
        # graftlint: disable=unsupervised-launch
        call.done.wait()
        with self._lock:
            self._calls.discard(call)
        if call.wedged:
            self.stats.note_wedge(key)
            raise WedgedLaunch(key, call.deadline_s)
        wall = self._clock() - call.started_at
        hop = max(0.0, wall - call.thunk_s)
        self._last.hop_s = hop
        self.stats.note_hop(hop)
        self.deadlines.observe(key, wall)
        if call.exc is not None:
            raise call.exc
        return call.result

    # -- reporting -----------------------------------------------------------

    def snapshot(self) -> dict:
        out = self.stats.snapshot()
        out.update(self.quarantine.snapshot())
        out["deadlines"] = self.deadlines.snapshot()
        out["warm_boot"] = self.deadlines.warm_boot
        return out
