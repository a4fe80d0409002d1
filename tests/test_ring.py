"""graftcadence tests: the resident continuous-batching ring.

Covers the depth trainer (clamp to {2,4,8}, manifest seeding, env pin),
the scheduler's per-tick quota assembly, the ``tick:`` guard deadline
class, the generation-tag lifecycle on a virtual clock (stale fetch
discarded, expiry re-resolve answers exactly once, slot wrap-around),
the clean-stop drain, corpus bit-identity through a real cadence
engine, and the forced-wedge drill proving the ladder drops the ring
back to the staged engine with bit-identical masks and no double
reply.  This file is a guard-gate lane (scripts/guard_gate.sh).
"""

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
from hotstuff_tpu.obs.spans import NO_LAUNCH, Tracer
from hotstuff_tpu.sidecar import protocol as proto
from hotstuff_tpu.sidecar import sched as vsched
from hotstuff_tpu.sidecar.guard import (BusyReply, LaunchDeadlines,
                                        LaunchGuard, WedgedLaunch)
from hotstuff_tpu.sidecar.ring import (ENV_CADENCE, ENV_DEPTH,
                                       CadenceRing, RingDepth,
                                       cadence_enabled)
from hotstuff_tpu.sidecar.service import ChaosState, VerifyEngine

# Same real-time guard posture as test_guard.py: warm grace in tens of
# milliseconds so a wedge is caught fast, compile budget generous enough
# that a contended host's canary never false-wedges the recovery.
FAST = dict(warm_boot=True, compile_budget_s=2.0, warm_grace_s=0.15,
            min_deadline_s=0.05)


def _sigs(n, tamper=(), seed=7):
    rng = np.random.default_rng(seed)
    msgs, pks, sigs = [], [], []
    for i in range(n):
        sk = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        msg = rng.bytes(32)
        sig = ref.sign(sk, msg)
        if i in tamper:
            sig = sig[:1] + bytes([sig[1] ^ 0xFF]) + sig[2:]
        msgs.append(msg)
        pks.append(pk)
        sigs.append(sig)
    return msgs, pks, sigs


def _wait(pred, timeout=20.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.005)
    return False


def _collector():
    """Reply recorder that keeps EVERY reply per rid — the double-reply
    assertions ride on the list lengths."""
    done = {}
    cond = threading.Condition()

    def reply_to(rid):
        def _reply(mask):
            with cond:
                done.setdefault(rid, []).append(mask)
                cond.notify_all()
        return _reply

    def wait_for(*rids, timeout=20.0):
        with cond:
            return cond.wait_for(lambda: all(r in done for r in rids),
                                 timeout=timeout)
    return done, reply_to, wait_for


# ---------------------------------------------------------------------------
# env opt-in + depth trainer
# ---------------------------------------------------------------------------

def test_cadence_env_opt_in(monkeypatch):
    monkeypatch.delenv(ENV_CADENCE, raising=False)
    assert not cadence_enabled()
    assert cadence_enabled(default=True)
    for raw, want in (("1", True), ("true", True), ("ON", True),
                      ("yes", True), ("0", False), ("off", False),
                      ("garbage", False)):
        monkeypatch.setenv(ENV_CADENCE, raw)
        assert cadence_enabled() is want


def test_ring_depth_clamps_to_supported_depths():
    assert RingDepth._clamp(1) == 2
    assert RingDepth._clamp(3) == 4
    assert RingDepth._clamp(8) == 8
    assert RingDepth._clamp(9) == 8
    assert RingDepth(pinned=3).depth() == 4


def test_ring_depth_conservative_until_trained():
    d = RingDepth(pinned=None)
    assert d.depth() == 2  # no evidence -> minimum
    for _ in range(RingDepth.MIN_OBSERVATIONS - 1):
        d.observe(0.01, 0.002)
    assert d.depth() == 2  # still short of MIN_OBSERVATIONS


def test_ring_depth_trains_from_dispatch_vs_wall():
    deep = RingDepth(pinned=None)
    for _ in range(RingDepth.MIN_OBSERVATIONS):
        deep.observe(0.010, 0.002)  # o/w = 5 -> 1+5 -> clamp 8
    assert deep.depth() == 8
    mid = RingDepth(pinned=None)
    for _ in range(RingDepth.MIN_OBSERVATIONS):
        mid.observe(0.009, 0.003)   # o/w = 3 -> 1+3 = 4
    assert mid.depth() == 4
    shallow = RingDepth(pinned=None)
    for _ in range(RingDepth.MIN_OBSERVATIONS):
        shallow.observe(0.001, 0.010)  # dispatch hides under one wall
    assert shallow.depth() == 2
    snap = shallow.snapshot()
    assert snap["k"] == 2 and not snap["pinned"]
    assert snap["dispatch_samples"] >= RingDepth.MIN_OBSERVATIONS
    json.dumps(snap)


def test_ring_depth_env_pin(monkeypatch):
    monkeypatch.setenv(ENV_DEPTH, "3")
    d = RingDepth()
    assert d.pinned == 4 and d.depth() == 4
    monkeypatch.setenv(ENV_DEPTH, "not-a-number")
    assert RingDepth().pinned is None


def test_ring_depth_from_manifest_seeds_and_tolerates_garbage(tmp_path):
    from hotstuff_tpu.utils.xla_cache import CompileManifest

    m = CompileManifest(str(tmp_path / "manifest.json"))
    m.record("kern1", "warmup:64", 0.004, cache_dir="/x")
    d = RingDepth.from_manifest(m, "kern1")
    assert d.snapshot()["wall_samples"] == 1

    class Hostile:
        def shape_walls(self, kernel):
            raise RuntimeError("corrupt manifest")

    d = RingDepth.from_manifest(Hostile(), "kern1")
    assert d.depth() == 2  # tolerated: trainer starts at the minimum


# ---------------------------------------------------------------------------
# scheduler per-tick quota
# ---------------------------------------------------------------------------

def _sched():
    return vsched.Scheduler(shapes=vsched.ShapeRegistry(use_host=True),
                            latency_cap_sigs=4096, bulk_cap_sigs=4096)


def _offer(sched, rid, n, cls=vsched.LATENCY, reply=None, seed=None):
    msgs, pks, sigs = _sigs(n, seed=seed if seed is not None else rid)
    assert sched.offer(proto.VerifyRequest(rid, msgs, pks, sigs),
                       reply if reply is not None else (lambda m: None),
                       cls=cls)


def test_next_tick_caps_the_coalesce_run():
    sched = _sched()
    for rid in range(1, 6):
        _offer(sched, rid, 4)
    launch = sched.next_tick(8)
    assert launch is not None and launch.kind == "verify"
    # the quota caps the coalesce run: 2 of the 5 four-sig requests
    assert sum(len(p) for p in
               launch.items[:len(launch.items) - launch.fill_count]) <= 8
    assert sched.queued_sigs(vsched.LATENCY) == 12


def test_next_tick_pad_fills_from_bulk_backlog():
    # Device shapes, not host: host mode verifies exactly n records so
    # bucket_capacity(n) == n and fill never happens; the single-chip
    # registry pads 3 sigs up to its compiled bucket, and next_tick
    # only ASSEMBLES (no dispatch), so no device is touched here.
    sched = vsched.Scheduler(shapes=vsched.ShapeRegistry(),
                             latency_cap_sigs=4096, bulk_cap_sigs=4096)
    _offer(sched, 1, 3)
    _offer(sched, 2, 1, cls=vsched.BULK)
    launch = sched.next_tick(64)
    assert launch is not None
    assert launch.fill_count >= 1  # the partial tick padded from bulk
    assert launch.cls == vsched.LATENCY


def test_next_tick_idle_semantics():
    sched = _sched()
    assert sched.next_tick(64) is None  # non-blocking by default
    t0 = time.monotonic()
    assert sched.next_tick(64, timeout=0.05) is None
    assert time.monotonic() - t0 < 5.0


def test_next_tick_timeout_park_wakes_on_offer():
    sched = _sched()
    got = []

    def park():
        got.append(sched.next_tick(64, timeout=10.0))

    t = threading.Thread(target=park, daemon=True)
    t.start()
    time.sleep(0.05)
    _offer(sched, 1, 4)
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert got and got[0] is not None and got[0].total_sigs == 4


# ---------------------------------------------------------------------------
# the ``tick:`` guard deadline class
# ---------------------------------------------------------------------------

def test_tick_class_gets_warm_grace_even_on_cold_boot():
    d = LaunchDeadlines(warm_boot=False, compile_budget_s=180.0,
                        warm_grace_s=30.0)
    # The ring only launches warmed shapes: a cold-boot tick key must
    # never inherit the minutes-long compile budget.
    assert d.deadline_s("tick:64") == 30.0
    assert d.deadline_s("launch:64") == 180.0


def test_tick_class_trained_p99_wins():
    d = LaunchDeadlines(warm_boot=False, warm_grace_s=30.0,
                        p99_multiple=8.0, min_deadline_s=0.5)
    for _ in range(LaunchDeadlines.MIN_OBSERVATIONS):
        d.observe("tick:64", 0.25)
    assert d.deadline_s("tick:64") == pytest.approx(2.0)
    assert d.deadline_s("tick:512") == 30.0  # untrained keys keep grace


# ---------------------------------------------------------------------------
# generation-tag lifecycle on a virtual clock (FakeEngine-driven)
# ---------------------------------------------------------------------------

class FakeEngine:
    """The minimal engine surface CadenceRing touches, with host-mask
    packs and a controllable guard so the lifecycle tests can drive
    ``_tick_once`` on a virtual clock."""

    def __init__(self):
        self._stopped = threading.Event()
        self._shapes = vsched.ShapeRegistry(use_host=True)
        self._sched = vsched.Scheduler(shapes=self._shapes,
                                       latency_cap_sigs=4096,
                                       bulk_cap_sigs=4096)
        self._pack_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="test-pack")
        self._tracer = Tracer.disabled()
        self._guard = None
        self.wedge_next_guarded = False
        self.laddered = []  # (batch, key, stage) from _wedge_ladder

    def _pack(self, batch, scope=None):
        msgs = [m for p in batch for m in p.request.msgs]
        pks = [k for p in batch for k in p.request.pks]
        sigs = [s for p in batch for s in p.request.sigs]

        def dispatch():
            def fetch():
                return [bool(ref.verify(pk, m, s))
                        for m, pk, s in zip(msgs, pks, sigs)]
            return fetch
        return dispatch

    def _guarded(self, key, thunk):
        if self.wedge_next_guarded:
            self.wedge_next_guarded = False
            raise WedgedLaunch(key, 0.0)
        return thunk()

    def _guard_key(self, batch):
        return "launch:%d" % max(
            1, sum(len(p.request.msgs) for p in batch))

    def retry_after_ms(self, cls):
        return 50

    def _wedge_ladder(self, batch, key, stage):
        self.laddered.append((batch, key, stage))
        for p in batch:
            p.reply_fn([False] * len(p.request.msgs))

    def _begin_launch(self, launch):
        return NO_LAUNCH

    def close(self):
        self._pack_pool.shutdown(wait=False)


@pytest.fixture
def fake_ring():
    now = [100.0]
    engine = FakeEngine()
    ring = CadenceRing(engine, depth=RingDepth(pinned=2), expiry_s=1.0,
                       clock=lambda: now[0], wait=lambda t: False)
    yield engine, ring, now
    engine.close()


def test_expiry_re_resolves_once_then_drops_the_late_fetch(fake_ring):
    engine, ring, now = fake_ring
    msgs, pks, sigs = _sigs(5, tamper={2}, seed=11)
    expect = [bool(b) for b in
              [ref.verify(pk, m, s)
               for m, pk, s in zip(msgs, pks, sigs)]]
    done, reply_to, _ = _collector()
    assert engine._sched.offer(proto.VerifyRequest(1, msgs, pks, sigs),
                               reply_to(1), cls=vsched.LATENCY)
    launch = engine._sched.next_tick(ring._quota_sigs())
    assert ring._arm(launch)
    assert len(ring._pending) == 1
    # Past the injected expiry window: the host re-resolve answers the
    # batch exactly once (bit-identical) and invalidates the generation.
    now[0] += 2.0
    ring._expire_overdue(now[0])
    assert done[1] == [expect]
    snap = ring.stats.snapshot(enabled=True, depth=2)
    assert snap["generation"]["expiries"] == 1
    assert snap["generation"]["expired_sigs"] == 5
    # The late device verdict is a COUNTED drop, never a second reply.
    ring._collect_oldest()
    assert done[1] == [expect]
    snap = ring.stats.snapshot(enabled=True, depth=2)
    assert snap["generation"]["drops"] == 1
    assert not ring._pending


def test_expiry_answers_bulk_with_busy(fake_ring):
    engine, ring, now = fake_ring
    msgs, pks, sigs = _sigs(3, seed=12)
    done, reply_to, _ = _collector()
    assert engine._sched.offer(proto.VerifyRequest(7, msgs, pks, sigs),
                               reply_to(7), cls=vsched.BULK)
    assert ring._arm(engine._sched.next_tick(ring._quota_sigs()))
    now[0] += 2.0
    ring._expire_overdue(now[0])
    (reply,) = done[7]
    assert isinstance(reply, BusyReply)
    assert reply.retry_after_ms == 50
    ring._collect_oldest()
    assert len(done[7]) == 1  # still exactly one reply


def test_slot_wraparound_keeps_generations_straight(fake_ring):
    """More arms than physical slots (> max depth 8): every slot is
    reused, every verdict still lands exactly once — the generation tag
    is what makes reuse safe."""
    engine, ring, now = fake_ring
    done, reply_to, _ = _collector()
    expects = {}
    n_reqs = 2 * len(ring._slots) + 4  # 20 arms over 8 slots
    for rid in range(1, n_reqs + 1):
        msgs, pks, sigs = _sigs(2, tamper={rid % 2}, seed=rid)
        expects[rid] = [bool(ref.verify(pk, m, s))
                        for m, pk, s in zip(msgs, pks, sigs)]
        assert engine._sched.offer(
            proto.VerifyRequest(rid, msgs, pks, sigs), reply_to(rid),
            cls=vsched.LATENCY)
        armed = ring._tick_once(now[0])
        now[0] += 0.01
        assert armed or done  # either armed or collected forward
    while ring._pending:
        ring._collect_oldest()
    assert set(done) == set(expects)
    for rid, masks in done.items():
        assert masks == [expects[rid]], f"rid {rid}"
    snap = ring.stats.snapshot(enabled=True, depth=2)
    assert snap["generation"]["drops"] == 0
    assert snap["generation"]["expiries"] == 0
    # Slots actually cycled: 20 arms over 8 slots bump generations > 1.
    assert max(s.generation for s in ring._slots) >= 2


def test_wedged_fetch_invalidates_and_rides_the_ladder(fake_ring):
    engine, ring, now = fake_ring
    msgs, pks, sigs = _sigs(4, seed=13)
    done, reply_to, _ = _collector()
    assert engine._sched.offer(proto.VerifyRequest(1, msgs, pks, sigs),
                               reply_to(1), cls=vsched.LATENCY)
    assert ring._arm(engine._sched.next_tick(ring._quota_sigs()))
    engine.wedge_next_guarded = True
    ring._collect_oldest()
    assert ring.enabled is False
    assert engine.laddered and engine.laddered[0][2] == "fetch"
    assert len(done[1]) == 1  # the ladder answered, exactly once
    assert ring.stats.snapshot(enabled=False, depth=2)["fallbacks"] == 1


def test_clean_stop_drains_every_inflight_verdict(fake_ring):
    engine, ring, now = fake_ring
    done, reply_to, _ = _collector()
    expects = {}
    for rid in (1, 2):
        msgs, pks, sigs = _sigs(3, tamper={rid}, seed=20 + rid)
        expects[rid] = [bool(ref.verify(pk, m, s))
                        for m, pk, s in zip(msgs, pks, sigs)]
        assert engine._sched.offer(
            proto.VerifyRequest(rid, msgs, pks, sigs), reply_to(rid),
            cls=vsched.LATENCY)
        assert ring._arm(engine._sched.next_tick(ring._quota_sigs()))
    assert len(ring._pending) == 2
    engine._stopped.set()
    ring.run()  # returns immediately, draining both flights
    assert done[1] == [expects[1]] and done[2] == [expects[2]]
    assert not ring._pending


def test_idle_interval_backs_off_and_resets(fake_ring):
    engine, ring, now = fake_ring
    first = ring._interval(False, 0)
    assert first == pytest.approx(2 * CadenceRing.MIN_TICK_S)
    for _ in range(20):
        last = ring._interval(False, 0)
    assert last == CadenceRing.MAX_TICK_S  # capped backoff
    assert ring._interval(True, 1) == CadenceRing.MIN_TICK_S
    assert ring._interval(False, 0) == \
        pytest.approx(2 * CadenceRing.MIN_TICK_S)  # streak reset


def test_pinned_tick_interval_wins(fake_ring):
    engine, _, now = fake_ring
    ring = CadenceRing(engine, depth=RingDepth(pinned=2), tick_s=0.033,
                       clock=lambda: now[0], wait=lambda t: False)
    assert ring._interval(True, 1) == 0.033
    assert ring._interval(False, 0) == 0.033


def test_tick_key_rides_the_staged_bucket(fake_ring):
    engine, ring, _ = fake_ring
    msgs, pks, sigs = _sigs(3, seed=30)
    batch = [vsched.Pending(proto.VerifyRequest(1, msgs, pks, sigs),
                            lambda m: None, vsched.LATENCY)]
    assert ring._tick_key(batch) == "tick:3"


# ---------------------------------------------------------------------------
# the real engine: bit-identity, wedge fallback, OP_STATS round trip
# ---------------------------------------------------------------------------

def _cadence_engine(**kw):
    # No test that boots through here injects a wedge: deadlines a busy
    # host cannot overrun (FAST's 0.15 s grace tripped under six xdist
    # workers and the reboot disengaged the ring: ROADMAP D4).
    g = LaunchGuard(deadlines=LaunchDeadlines(
        **dict(FAST, warm_grace_s=10.0, min_deadline_s=10.0)))
    engine = VerifyEngine(
        use_host=True, guard=g,
        ring_factory=lambda e: CadenceRing(e, depth=RingDepth(pinned=2)),
        **kw)
    return engine, g


def test_cadence_engine_masks_bit_identical_and_supervised():
    """Corpus bit-identity THROUGH the engine: ring verdicts equal
    verify_batch masks, every dispatch supervised under the ``tick:``
    guard class, and the OP_STATS cadence section reports the traffic."""
    engine, g = _cadence_engine()
    try:
        done, reply_to, wait_for = _collector()
        expects = {}
        for rid in range(1, 6):
            msgs, pks, sigs = _sigs(8, tamper={3}, seed=40 + rid)
            expects[rid] = [bool(b) for b in
                            eddsa.verify_batch(msgs, pks, sigs)]
            assert engine.submit(proto.VerifyRequest(rid, msgs, pks,
                                                     sigs),
                                 reply_to(rid), cls=vsched.LATENCY)
        assert wait_for(*expects)
        for rid, expect in expects.items():
            assert done[rid] == [expect], f"rid {rid}"
        snap = engine.stats_snapshot()
        cad = snap["cadence"]
        assert cad["enabled"] and cad["depth"] == 2
        assert cad["ticks"] >= 1 and cad["dispatch_ticks"] >= 1
        assert cad["queue_wait"]["n"] >= 5
        assert cad["generation"]["drops"] == 0
        json.dumps(cad)
        # guard supervision evidence: the tick class trained deadlines
        assert any(k.startswith("tick:") and v["n"] >= 1
                   for k, v in g.snapshot()["deadlines"].items())
    finally:
        engine.stop()
        g.close()


def test_cadence_wedge_falls_back_to_staged_no_double_reply():
    """The forced-wedge drill: a wedged cadence launch answers through
    the ladder bit-identically, the ring disengages, the crash-only
    reboot completes, and the STAGED loop serves the next request —
    with exactly one reply per rid throughout."""
    chaos = ChaosState()
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    engine = VerifyEngine(
        use_host=True, guard=g, chaos=chaos,
        ring_factory=lambda e: CadenceRing(e, depth=RingDepth(pinned=2)))
    try:
        msgs, pks, sigs = _sigs(8, tamper={3}, seed=5)
        expect = [bool(b) for b in eddsa.verify_batch(msgs, pks, sigs)]
        done, reply_to, wait_for = _collector()
        # Healthy cadence traffic first, so the wedge hits a warm ring.
        assert engine.submit(proto.VerifyRequest(1, msgs, pks, sigs),
                             reply_to(1), cls=vsched.LATENCY)
        assert wait_for(1)
        assert done[1] == [expect]
        chaos.configure({"wedge": 1})
        assert engine.submit(proto.VerifyRequest(2, msgs, pks, sigs),
                             reply_to(2), cls=vsched.LATENCY)
        assert wait_for(2)
        assert done[2] == [expect]  # ladder host mask, bit-identical
        assert engine._ring.enabled is False
        cad = engine.stats_snapshot()["cadence"]
        assert cad["fallbacks"] == 1 and not cad["enabled"]
        assert _wait(lambda: engine._device_ok and not engine._rebooting)
        assert engine.stats_snapshot()["guard"]["reboots"] == 1
        # The staged loop now owns the engine thread: traffic serves.
        assert engine.submit(proto.VerifyRequest(3, msgs, pks, sigs),
                             reply_to(3), cls=vsched.LATENCY)
        assert wait_for(3)
        assert done[3] == [expect]
        assert all(len(v) == 1 for v in done.values()), \
            "a rid was answered more than once across the fallback"
    finally:
        engine.stop()
        g.close()


GOLDEN_CLIENT = """\
[2026-07-29T14:54:56.456Z INFO client] Transactions size: 512 B
[2026-07-29T14:54:56.456Z INFO client] Transactions rate: 2000 tx/s
[2026-07-29T14:54:56.525Z INFO client] Start sending transactions
[2026-07-29T14:54:56.577Z INFO client] Sending sample transaction 0
"""

GOLDEN_NODE = """\
[2026-07-29T14:54:55.100Z INFO mempool::config] Garbage collection depth set to 50 rounds
[2026-07-29T14:54:55.100Z INFO mempool::config] Sync retry delay set to 5000 ms
[2026-07-29T14:54:55.100Z INFO mempool::config] Sync retry nodes set to 3 nodes
[2026-07-29T14:54:55.100Z INFO mempool::config] Batch size set to 15000 B
[2026-07-29T14:54:55.100Z INFO mempool::config] Max batch delay set to 100 ms
[2026-07-29T14:54:55.101Z INFO consensus::config] Timeout delay set to 1000 ms
[2026-07-29T14:54:55.101Z INFO consensus::config] Sync retry delay set to 10000 ms
[2026-07-29T14:54:56.577Z INFO mempool::batch_maker] Batch aaa= contains sample tx 0
[2026-07-29T14:54:56.578Z INFO mempool::batch_maker] Batch aaa= contains 15360 B
[2026-07-29T14:54:56.700Z INFO consensus::proposer] Created B2 -> aaa=
[2026-07-29T14:54:57.000Z INFO consensus::core] Committed B2 -> aaa=
"""


def test_cadence_stats_round_trip_wire_to_parser():
    """OP_STATS ``cadence`` section -> JSON wire round trip ->
    LogParser CONFIG note + machine-readable ``parser.cadence``."""
    from hotstuff_tpu.harness import LogParser

    engine, g = _cadence_engine()
    try:
        msgs, pks, sigs = _sigs(6, tamper={1}, seed=55)
        done, reply_to, wait_for = _collector()
        assert engine.submit(proto.VerifyRequest(1, msgs, pks, sigs),
                             reply_to(1), cls=vsched.LATENCY)
        assert wait_for(1)
        stats = engine.stats_snapshot()
        assert stats["launches"] >= 1
        wire = json.loads(json.dumps(stats))  # the wire is JSON verbatim
        parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
        parser.note_sidecar_stats(wire)
        note = next(n for n in parser.notes
                    if n.startswith("Sidecar cadence ring:"))
        assert "depth 2" in note
        assert "tick(s)" in note and "queue wait p50" in note
        assert "FELL BACK TO STAGED" not in note
        assert parser.cadence == wire["cadence"]
    finally:
        engine.stop()
        g.close()


def test_cadence_fallback_note_names_the_disengage():
    from hotstuff_tpu.harness import LogParser

    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    parser.note_sidecar_stats({
        "launches": 3,
        "cadence": {"enabled": False, "depth": 4, "ticks": 12,
                    "dispatch_ticks": 9, "idle_ticks": 3,
                    "tick_rate_hz": 480.0,
                    "pad_fill": {"sigs": 16, "launched_sigs": 128,
                                 "ratio": 0.125},
                    "generation": {"drops": 1, "expiries": 1,
                                   "expired_sigs": 8},
                    "fallbacks": 1,
                    "queue_wait": {"n": 9, "p50_ms": 0.4,
                                   "p99_ms": 2.2}},
    })
    note = next(n for n in parser.notes
                if n.startswith("Sidecar cadence ring:"))
    assert "FELL BACK TO STAGED" in note
    assert "1 generation drop(s)" in note
