"""graftguard tests: launch deadlines, wedge detection, the degradation
ladder (host-fallback masks bit-identical to verify_batch, BUSY for
bulk), crash-only reboot + canary, poison-record bisection, the chaos
``wedge`` drill, OP_STATS/parser round trips, and the kill-proof bench
emit.
"""

import json
import os
import threading
import time
from datetime import datetime
from types import SimpleNamespace

import numpy as np
import pytest

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
from hotstuff_tpu.sidecar import protocol as proto
from hotstuff_tpu.sidecar import sched as vsched
from hotstuff_tpu.sidecar.guard import (BusyReply, GuardStats,
                                        LaunchDeadlines, LaunchGuard,
                                        Quarantine, WedgedLaunch,
                                        bisect_poison)
from hotstuff_tpu.sidecar.service import (ChaosState, SidecarServer,
                                          VerifyEngine)

# Tight real-time deadlines: the monitor must actually preempt a hung
# thread, so tests use tens of milliseconds instead of a virtual clock.
# warm_boot=True keeps launch deadlines on the 0.15 s warm grace; the
# compile budget stays generous enough that a CONTENDED host's canary
# (real work: 8 host verifies after a cache teardown) never false-wedges
# the recovery the tests assert on.
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


@pytest.fixture
def guard():
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    yield g
    g.close()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------

def test_deadline_cold_boot_gets_compile_budget():
    d = LaunchDeadlines(warm_boot=False, compile_budget_s=180.0,
                        warm_grace_s=30.0)
    assert d.deadline_s("launch:512") == 180.0


def test_deadline_warm_boot_gets_grace():
    d = LaunchDeadlines(warm_boot=True, compile_budget_s=180.0,
                        warm_grace_s=30.0)
    assert d.deadline_s("launch:512") == 30.0


def test_deadline_tightens_to_p99_multiple():
    d = LaunchDeadlines(warm_boot=True, warm_grace_s=30.0,
                        p99_multiple=8.0, min_deadline_s=0.5)
    for _ in range(LaunchDeadlines.MIN_OBSERVATIONS):
        d.observe("launch:64", 0.25)
    assert d.deadline_s("launch:64") == pytest.approx(2.0)
    # other shapes keep the fallback
    assert d.deadline_s("launch:512") == 30.0


def test_deadline_floor_under_fast_shapes():
    d = LaunchDeadlines(warm_boot=True, p99_multiple=8.0,
                        min_deadline_s=1.0)
    for _ in range(LaunchDeadlines.MIN_OBSERVATIONS):
        d.observe("launch:8", 0.001)
    assert d.deadline_s("launch:8") == 1.0


def test_deadline_env_knobs(monkeypatch):
    monkeypatch.setenv("HOTSTUFF_TPU_GUARD_COMPILE_BUDGET_S", "77")
    monkeypatch.setenv("HOTSTUFF_TPU_GUARD_WARM_GRACE_S", "11")
    assert LaunchDeadlines(warm_boot=False).deadline_s("x") == 77.0
    assert LaunchDeadlines(warm_boot=True).deadline_s("x") == 11.0


def test_deadlines_from_manifest(tmp_path):
    from hotstuff_tpu.utils.xla_cache import CompileManifest

    path = str(tmp_path / "manifest.json")
    cache_dir = str(tmp_path / "xla")
    os.makedirs(cache_dir)
    m = CompileManifest(path)
    d = LaunchDeadlines.from_manifest(m, "kern123", cache_dir)
    assert not d.warm_boot  # empty manifest = cold boot
    m.record("kern123", "warmup:512", 12.5, cache_dir=cache_dir)
    d = LaunchDeadlines.from_manifest(m, "kern123", cache_dir)
    assert d.warm_boot
    assert m.shape_walls("kern123") == {"warmup:512": 12.5}
    # a different kernel hash is still cold
    assert not LaunchDeadlines.from_manifest(
        m, "other", cache_dir).warm_boot


@pytest.mark.parametrize("case", ["other_dir", "missing_dir",
                                  "recorded_without_dir"])
def test_deadlines_from_manifest_cold_without_this_boots_cache(
        tmp_path, case):
    """A manifest cannot prove the compiled programs are on this
    machine: shapes warmed against ANOTHER cache directory (a committed
    manifest, a moved checkout) or against one that no longer exists
    must leave the boot cold — its first compiles get the compile
    budget, not the warm grace that reads them as wedges."""
    from hotstuff_tpu.utils.xla_cache import CompileManifest

    here = str(tmp_path / "xla")
    recorded = {"other_dir": str(tmp_path / "elsewhere"),
                "missing_dir": here,
                "recorded_without_dir": None}[case]
    if case != "missing_dir":
        os.makedirs(here)
    if case == "other_dir":
        os.makedirs(recorded)
    m = CompileManifest(str(tmp_path / "manifest.json"))
    m.record("kern123", "warmup:128", 27.0, cache_dir=recorded)
    d = LaunchDeadlines.from_manifest(m, "kern123", here)
    assert not d.warm_boot
    assert d.deadline_s("launch:128") == d.compile_budget_s


def test_manifest_cold_wall(tmp_path):
    from hotstuff_tpu.utils.xla_cache import CompileManifest

    m = CompileManifest(str(tmp_path / "manifest.json"))
    assert m.cold_wall_s() is None
    m.record_run("k", hits=0, misses=4, wall_s=149.0, now=1.0)
    m.record_run("k", hits=4, misses=0, wall_s=38.0, now=2.0)
    assert m.cold_wall_s() == 149.0  # warm runs never count as cold


# ---------------------------------------------------------------------------
# the guard itself
# ---------------------------------------------------------------------------

def test_guard_returns_result_and_observes(guard):
    assert guard.call("k", lambda: 41 + 1) == 42
    assert guard.deadlines.snapshot()["k"]["n"] == 1


def test_guard_wedges_a_hung_launch_within_deadline(guard):
    release = threading.Event()
    t0 = time.monotonic()
    with pytest.raises(WedgedLaunch):
        guard.call("k", release.wait)
    wall = time.monotonic() - t0
    assert wall < 2.0  # deadline 0.15s + monitor poll slack
    assert guard.stats.snapshot()["wedges"] == 1
    release.set()  # let the abandoned thread exit


def test_guard_propagates_exceptions(guard):
    with pytest.raises(RuntimeError, match="boom"):
        guard.call("k", lambda: (_ for _ in ()).throw(RuntimeError("boom")))


def test_guard_late_completion_is_discarded(guard):
    release = threading.Event()
    finished = threading.Event()

    def thunk():
        release.wait()
        finished.set()
        return "late"

    with pytest.raises(WedgedLaunch):
        guard.call("k", thunk)
    release.set()
    assert finished.wait(5.0)
    assert _wait(lambda: guard.stats.snapshot()["late_completions"] == 1)
    # a fresh launch on a fresh disposable thread still works
    assert guard.call("k", lambda: "fresh") == "fresh"


def test_guard_snapshot_is_json_safe(guard):
    with pytest.raises(WedgedLaunch):
        guard.call("k", threading.Event().wait)
    json.dumps(guard.snapshot())


# ---------------------------------------------------------------------------
# quarantine + bisection
# ---------------------------------------------------------------------------

def test_quarantine_repeat_offenders_flow():
    q = Quarantine()
    recs = [("m%d" % i, "p", "s") for i in range(4)]
    assert q.note_wedged(recs) == 0          # first wedge: weather
    assert q.pending() == []
    assert q.note_wedged(recs[:2]) == 2      # repeat: pending bisection
    assert set(q.pending()) == set(recs[:2])
    assert q.resolve([recs[0]]) == 1
    assert q.is_poisoned(recs[0]) and not q.is_poisoned(recs[1])
    assert q.has_poison()
    json.dumps(q.snapshot())


def test_bisect_poison_isolates_single_record():
    recs = list(range(8))
    probes = []

    def probe(subset):
        probes.append(list(subset))
        return 5 not in subset

    assert bisect_poison(recs, probe) == [5]
    assert len(probes) <= 2 * len(recs)


def test_bisect_poison_finds_two_records():
    recs = list(range(8))
    assert sorted(bisect_poison(
        recs, lambda s: not ({1, 6} & set(s)))) == [1, 6]


def test_bisect_poison_interaction_set_stays_quarantined():
    # The pair wedges only together: neither half wedges alone, so the
    # whole set is returned (never silently released).
    recs = [0, 1]
    assert sorted(bisect_poison(
        recs, lambda s: not {0, 1} <= set(s))) == [0, 1]


def test_bisect_poison_probe_budget_leaves_rest_quarantined():
    recs = list(range(16))
    out = bisect_poison(recs, lambda s: 3 not in s, max_probes=1)
    # one probe (the full set, wedges) -> everything stays quarantined
    assert sorted(out) == recs


# ---------------------------------------------------------------------------
# the engine ladder
# ---------------------------------------------------------------------------

def _engine(**kw):
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    return VerifyEngine(use_host=True, guard=g, **kw), g


def _collector():
    done = {}
    cond = threading.Condition()

    def reply_to(rid):
        def _reply(mask):
            with cond:
                done[rid] = mask
                cond.notify_all()
        return _reply

    def wait_for(*rids, timeout=20.0):
        with cond:
            return cond.wait_for(lambda: all(r in done for r in rids),
                                 timeout=timeout)
    return done, reply_to, wait_for


def test_wedge_ladder_masks_and_busy_direct():
    """Direct ladder execution on a mixed batch: latency answered from
    the host path bit-identical to verify_batch, bulk answered BUSY."""
    engine, guard = _engine()
    try:
        msgs, pks, sigs = _sigs(6, tamper={1, 4}, seed=3)
        done, reply_to, wait_for = _collector()
        batch = [
            vsched.Pending(proto.VerifyRequest(1, msgs[:3], pks[:3],
                                               sigs[:3]),
                           reply_to(1), vsched.LATENCY),
            vsched.Pending(proto.VerifyRequest(2, msgs[3:], pks[3:],
                                               sigs[3:]),
                           reply_to(2), vsched.BULK),
        ]
        engine._wedge_ladder(batch, "launch:8", stage="test")
        # ladder replies land async (the host fallback runs off the
        # engine thread so queued verifies drain concurrently)
        assert wait_for(1, 2)
        expect = [bool(b) for b in eddsa.verify_batch(msgs, pks, sigs)]
        assert done[1] == expect[:3]
        assert isinstance(done[2], BusyReply)
        assert done[2].retry_after_ms >= 0
        snap = engine.stats_snapshot()["guard"]
        assert snap["host_fallback_records"] == 3
        assert snap["busy_replies"] == 1
        assert snap["suspect_records"] == 6
        assert _wait(lambda: not engine._rebooting and engine._device_ok)
        assert engine.stats_snapshot()["guard"]["reboots"] == 1
    finally:
        engine.stop()
        guard.close()


def test_chaos_wedge_end_to_end_and_recovery():
    """The full drill through submit(): OP_CHAOS-shaped wedge -> ladder
    host-fallback mask -> async crash-only reboot (bulk BUSY, rewarm,
    canary) -> device routing resumes."""
    chaos = ChaosState()
    rewarmed = []
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    engine = VerifyEngine(
        use_host=True, guard=g, chaos=chaos,
        rewarm_fn=lambda: (rewarmed.append(1), time.sleep(0.15)))
    try:
        msgs, pks, sigs = _sigs(8, tamper={3}, seed=5)
        expect = [bool(b) for b in eddsa.verify_batch(msgs, pks, sigs)]
        done, reply_to, wait_for = _collector()
        chaos.configure({"wedge": 1})
        assert engine.submit(proto.VerifyRequest(1, msgs, pks, sigs),
                             reply_to(1), cls=vsched.LATENCY)
        assert wait_for(1)
        assert done[1] == expect  # bit-identical host fallback
        # bulk offered during the reboot window sheds to BUSY
        assert _wait(lambda: engine._rebooting, timeout=5.0)
        assert not engine.submit(proto.VerifyRequest(2, msgs, pks, sigs),
                                 reply_to(2), cls=vsched.BULK)
        assert _wait(lambda: engine._device_ok and not engine._rebooting)
        assert rewarmed
        snap = engine.stats_snapshot()["guard"]
        assert snap["wedges"] == 1 and snap["reboots"] == 1
        assert snap["canary_passes"] >= 1
        assert snap["busy_replies"] >= 1
        # post-recovery traffic serves normally again
        assert engine.submit(proto.VerifyRequest(3, msgs, pks, sigs),
                             reply_to(3), cls=vsched.LATENCY)
        assert wait_for(3)
        assert done[3] == expect
    finally:
        engine.stop()
        g.close()


def test_chaos_wedge_bls_launch_transient_reply_and_reboot():
    """BLS launches ride the guard (ROADMAP item 3 closed): a wedged
    pairing answers TRANSIENT (None — never a cacheable [False] for a
    verdict nobody computed) and starts the crash-only reboot instead of
    parking the engine thread; the shared verdict cache stays empty and
    the recovered engine serves traffic normally."""
    from hotstuff_tpu.offchain import bls12381 as bls

    chaos = ChaosState()
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    engine = VerifyEngine(use_host=True, guard=g, chaos=chaos)
    try:
        msg = b"qc digest under bls".ljust(32, b"\0")
        keys = [bls.key_gen(bytes([i]) * 32) for i in range(1, 4)]
        pks = [bls.g1_encode(pk) for _, pk in keys]
        sigs = [bls.g2_encode(bls.sign(sk, msg)) for sk, _ in keys]
        done, reply_to, wait_for = _collector()
        chaos.configure({"wedge": 1})
        assert engine.submit(proto.BlsVotesRequest(1, msg, pks, sigs),
                             reply_to(1), is_bls=True)
        assert wait_for(1)
        assert done[1] is None  # transient form, not a verdict mask
        snap = engine.stats_snapshot()["guard"]
        assert snap["wedges"] == 1
        assert _wait(lambda: not engine._rebooting and engine._device_ok)
        assert engine.stats_snapshot()["guard"]["reboots"] == 1
        # Nothing entered the shared verdict cache — a wedge must never
        # record a [False] other replicas would then share.
        assert not engine._verdicts
        # ... and the recovered engine serves verify traffic normally
        # (a real pairing would overrun FAST's test deadlines, so the
        # health probe is an Ed25519 batch).
        msgs, vpks, vsigs = _sigs(4, tamper={2}, seed=9)
        expect = [bool(b) for b in eddsa.verify_batch(msgs, vpks, vsigs)]
        assert engine.submit(proto.VerifyRequest(2, msgs, vpks, vsigs),
                             reply_to(2), cls=vsched.LATENCY)
        assert wait_for(2)
        assert done[2] == expect
    finally:
        engine.stop()
        g.close()


def test_repeat_wedge_triggers_poison_bisection():
    """A cursed record that wedges every launch carrying it: after the
    second wedge the bisection isolates EXACTLY that record, and later
    batches verify it on the host poison lane while co-batched records
    ride the device leg again — no third wedge."""
    engine, g = _engine()
    msgs, pks, sigs = _sigs(6, tamper={2}, seed=9)
    cursed = (msgs[2], pks[2], sigs[2])
    real_submit = VerifyEngine._verify_submit

    def hang_on_cursed(self, m, p, s, force_device=False):
        if cursed[0] in m:
            return lambda: threading.Event().wait()
        return real_submit(self, m, p, s, force_device=force_device)

    engine._verify_submit = hang_on_cursed.__get__(engine)
    try:
        expect = [True, True, False, True, True, True]
        done, reply_to, wait_for = _collector()
        for rid in (1, 2):
            assert engine.submit(proto.VerifyRequest(rid, msgs, pks, sigs),
                                 reply_to(rid), cls=vsched.LATENCY)
            assert wait_for(rid)
            assert done[rid] == expect
            assert _wait(
                lambda: engine._device_ok and not engine._rebooting)
        snap = engine.stats_snapshot()["guard"]
        assert snap["poisoned_records"] == 1
        assert g.quarantine.is_poisoned(cursed)
        wedges_after_bisect = snap["wedges"]
        assert engine.submit(proto.VerifyRequest(3, msgs, pks, sigs),
                             reply_to(3), cls=vsched.LATENCY)
        assert wait_for(3)
        assert done[3] == expect
        snap = engine.stats_snapshot()["guard"]
        assert snap["wedges"] == wedges_after_bisect  # poison lane held
        assert snap["poison_host_verified"] >= 1
        assert snap["device_ok"]
    finally:
        engine.stop()
        g.close()


def test_guard_key_uses_deduped_record_count():
    """Deadline history must attach to the shape the launch EXECUTES:
    N replicas submitting the same QC dedup to one bucket, so the raw
    total can never train (and then tighten) the deadline of a
    genuinely-large unique batch."""
    engine, g = _engine()
    try:
        msgs, pks, sigs = _sigs(8, seed=15)
        same = proto.VerifyRequest(1, msgs, pks, sigs)
        batch = [vsched.Pending(proto.VerifyRequest(rid, msgs, pks,
                                                    sigs),
                                lambda m: None, vsched.LATENCY)
                 for rid in range(4)]  # raw total 32, unique 8
        assert engine._guard_key(batch) == "launch:8"
        assert engine._guard_key(
            [vsched.Pending(same, lambda m: None,
                            vsched.LATENCY)]) == "launch:8"
    finally:
        engine.stop()
        g.close()


def test_rewarm_runs_on_the_device_path():
    """The crash-only reboot's re-warm must reach the DEVICE path even
    while live routing is host-only (_device_ok False): a rewarm that
    silently host-verified would compile nothing and leave the first
    post-canary launch to re-wedge on a fresh trace."""
    from unittest import mock

    # A device-mode engine on the CPU jax backend (what tier-1 runs):
    # _verify_submit's non-host branch is the real jitted path.
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    seen = []

    def rewarm():
        # what _warm_shapes does: engine._verify through the engine's
        # own staged entry — with ref.verify forbidden, only the
        # device branch can answer.  The flag is THREAD-LOCAL: live
        # traffic on other threads must keep host-routing meanwhile.
        m, p, s = _sigs(4, seed=25)
        assert engine._rewarm_tls.active
        live = engine._verify_submit(m, p, s)  # another thread's view:
        with mock.patch(
                "hotstuff_tpu.crypto.ref_ed25519.verify",
                side_effect=AssertionError("rewarm took the host path")):
            mask = engine._verify(m, p, s)
        seen.append([bool(b) for b in mask])
        # ...checked from a fresh thread: host-routed, not device
        host_routed = []

        def probe_live():
            import hotstuff_tpu.crypto.ref_ed25519 as refmod
            calls = []
            real = refmod.verify

            def spy(pk, msg, sig):
                calls.append(1)
                return real(pk, msg, sig)
            with mock.patch.object(refmod, "verify", spy):
                engine._verify_submit(m, p, s)()
            host_routed.append(bool(calls))

        t = threading.Thread(target=probe_live)
        t.start()
        t.join(30.0)
        assert host_routed == [True], \
            "live traffic leaked onto the device mid-rewarm"
        del live

    engine = VerifyEngine(use_host=False, guard=g, rewarm_fn=rewarm)
    try:
        engine._wedge_ladder([], "launch:8", stage="test")
        # The rewarm re-traces and reloads a real ladder program (~9 s
        # alone, several times that beside five busy xdist workers).
        assert _wait(lambda: engine._device_ok and not engine._rebooting,
                     timeout=90.0)
        assert seen == [[True, True, True, True]]
        assert not getattr(engine._rewarm_tls, "active", False)
    finally:
        engine.stop()
        g.close()


_LEGS = ("_warmup", "_warmup_bls", "_warmup_bls_multi", "_warmup_bulk",
         "_warmup_rlc", "_warmup_rlc_sharded")


@pytest.mark.parametrize("boot,own_leg", [
    ({}, None),
    ({"warm_bulk": True}, "_warmup_bulk"),
    ({"warm_rlc": True}, "_warmup_rlc"),
    ({"mesh_devices": 4, "warm_rlc": True, "warm_rlc_sharded": True},
     "_warmup_rlc_sharded"),
], ids=["plain", "warm_bulk", "warm_rlc", "mesh4_warm_rlc_sharded"])
def test_rewarm_runs_the_boots_legs_in_the_boots_order(
        boot, own_leg, monkeypatch, tmp_path):
    """serve() writes the order of its warm-up legs twice, once for the
    boot and once in the ``_rewarm`` it hands the engine for a crash-only
    reboot: the reboot must re-trace what the boot warmed, in the boot's
    order (a bisection's smaller shapes before the larger ones that fall
    back on them), less the BLS legs (minutes of compile; an un-warmed
    BLS shape falls back to the host pairing under the guard)."""
    from conftest import boot_without_serving
    from hotstuff_tpu.sidecar import service

    calls = []
    for leg in _LEGS:
        monkeypatch.setattr(
            service, leg,
            lambda *args, _leg=leg: calls.append(
                (_leg, [a for a in args if not isinstance(a, VerifyEngine)])))
    engine = boot_without_serving(monkeypatch, tmp_path, warm_max=32,
                                  warm_bls=True, warm_bls_multi=3, **boot)
    booted, calls[:] = list(calls), []
    engine._rewarm_fn()
    names = [leg for leg, _ in booted]
    assert names[:3] == ["_warmup", "_warmup_bls", "_warmup_bls_multi"]
    assert names[3:] == ([own_leg] if own_leg else [])
    assert calls == [c for c in booted if not c[0].startswith("_warmup_bls")]


def test_engine_without_guard_is_unchanged():
    """Legacy embedders (no guard): no guard section, no supervision
    hop, identical verdicts."""
    engine = VerifyEngine(use_host=True)
    try:
        msgs, pks, sigs = _sigs(4, tamper={1}, seed=13)
        done, reply_to, wait_for = _collector()
        engine.submit(proto.VerifyRequest(1, msgs, pks, sigs),
                      reply_to(1), cls=vsched.LATENCY)
        assert wait_for(1)
        assert done[1] == [True, False, True, True]
        assert "guard" not in engine.stats_snapshot()
    finally:
        engine.stop()


def test_chaos_wedge_knob_configure_roundtrip():
    c = ChaosState()
    applied = c.configure({"wedge": 2})
    assert applied["wedge"] == 2
    assert c.take_wedge() and c.take_wedge() and not c.take_wedge()
    c.configure({"wedge": 1})
    c.configure({"clear": True})
    assert not c.take_wedge()
    with pytest.raises(ValueError):
        c.configure({"wedge": -1})
    with pytest.raises(ValueError):
        c.configure({"wedge": True})


def test_guard_stats_wire_roundtrip():
    """The OP_STATS ``guard`` section survives the wire encoding."""
    engine, g = _engine()
    try:
        g.stats.note_wedge("launch:8")
        g.stats.note_reboot(1.25)
        g.stats.note_canary(True)
        frame = proto.encode_stats_reply(9, engine.stats_snapshot())
        opcode, rid, body = proto.decode_reply_raw(frame[4:])
        assert (opcode, rid) == (proto.OP_STATS, 9)
        snap = proto.decode_stats_body(body)
        assert snap["guard"]["wedges"] == 1
        assert snap["guard"]["reboots"] == 1
        assert snap["guard"]["canary_passes"] == 1
        assert snap["guard"]["device_ok"] is True
    finally:
        engine.stop()
        g.close()


# ---------------------------------------------------------------------------
# plan / SLO / injector
# ---------------------------------------------------------------------------

def test_plan_parses_sidecar_wedge():
    from hotstuff_tpu.chaos import parse_plan

    plan = parse_plan("5 sidecar wedge; 10 sidecar wedge n=2")
    assert [e.action for e in plan.events] == ["wedge", "wedge"]
    assert plan.events[0].params == {}
    assert plan.events[1].params == {"n": 2}


def test_plan_rejects_bad_wedge():
    from hotstuff_tpu.chaos import parse_plan
    from hotstuff_tpu.chaos.plan import PlanError

    with pytest.raises(PlanError):
        parse_plan("5 sidecar wedge n=0")
    with pytest.raises(PlanError):
        parse_plan("5 sidecar wedge x=2")
    with pytest.raises(PlanError):
        parse_plan("5 node:0 wedge")
    with pytest.raises(PlanError):  # wedge needs a live sidecar
        parse_plan("1 sidecar kill; 2 sidecar wedge")


def test_slo_judges_wedge_class():
    from hotstuff_tpu.chaos import judge, summarize_recovery

    events = [{"t": 1.0, "target": "sidecar", "action": "wedge",
               "wall": 100.0, "ok": True}]
    summary = summarize_recovery(events, [100.5])
    verdict = judge(summary)
    (v,) = verdict["verdicts"]
    assert v["class"] == "sidecar-wedge"
    assert v["ok"] and v["slo_ms"] == 20_000.0


def test_local_injector_drives_wedge_through_opchaos():
    """LocalFaultInjector 'sidecar wedge' -> OP_CHAOS -> the engine's
    next launch wedges and the CLIENT still gets the right mask (the
    ladder's host fallback is transparent on the wire)."""
    from hotstuff_tpu.chaos.plan import FaultEvent
    from hotstuff_tpu.harness.faults import LocalFaultInjector
    from hotstuff_tpu.sidecar.client import SidecarClient

    chaos = ChaosState()
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    engine = VerifyEngine(use_host=True, guard=g, chaos=chaos)
    srv = SidecarServer(("127.0.0.1", 0), engine, chaos=chaos)
    t = threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.1), daemon=True)
    t.start()
    try:
        port = srv.server_address[1]
        injector = LocalFaultInjector(
            SimpleNamespace(SIDECAR_PORT=port))
        injector.apply(FaultEvent(0.0, "sidecar", "wedge", {"n": 1}))
        msgs, pks, sigs = _sigs(5, tamper={2}, seed=21)
        with SidecarClient(port=port, timeout=30.0) as client:
            mask = client.verify_batch(msgs, pks, sigs)
        assert mask == [True, True, False, True, True]
        assert _wait(lambda: engine.stats_snapshot()
                     ["guard"]["wedges"] >= 1)
    finally:
        srv.shutdown()
        engine.stop()
        g.close()
        srv.server_close()


# ---------------------------------------------------------------------------
# parser notes
# ---------------------------------------------------------------------------

GOLDEN_CLIENT = """\
[2026-07-29T14:54:56.456Z INFO client] Node address: 127.0.0.1:9701
[2026-07-29T14:54:56.456Z INFO client] Transactions size: 512 B
[2026-07-29T14:54:56.456Z INFO client] Transactions rate: 2000 tx/s
[2026-07-29T14:54:56.525Z INFO client] Start sending transactions
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


def _golden_parser():
    from hotstuff_tpu.harness import LogParser

    return LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)


def test_parser_notes_guard_section():
    parser = _golden_parser()
    parser.note_sidecar_stats({
        "launches": 4,
        "guard": {"wedges": 2, "reboots": 1, "canary_passes": 1,
                  "canary_failures": 0, "last_reboot_wall_s": 0.8,
                  "suspect_records": 8, "poisoned_records": 1,
                  "host_fallback_records": 8, "busy_replies": 3,
                  "device_ok": True, "rebooting": False},
    })
    note = next(n for n in parser.notes if n.startswith("Sidecar guard:"))
    assert "2 wedge(s)" in note
    assert "1 crash-only reboot(s)" in note
    assert "1 poisoned" in note
    assert "8 host-fallback verdict(s)" in note
    assert not any("device leg DOWN" in n for n in parser.notes)


def test_parser_notes_guard_device_down():
    parser = _golden_parser()
    parser.note_sidecar_stats({
        "launches": 4,
        "guard": {"wedges": 1, "reboots": 0, "canary_passes": 0,
                  "canary_failures": 3, "suspect_records": 4,
                  "poisoned_records": 0, "host_fallback_records": 4,
                  "busy_replies": 0, "device_ok": False,
                  "rebooting": False},
    })
    assert any("device leg DOWN" in n for n in parser.notes)


def test_parser_quiet_without_guard_activity():
    parser = _golden_parser()
    parser.note_sidecar_stats({
        "launches": 4,
        "guard": {"wedges": 0, "reboots": 0, "poisoned_records": 0,
                  "device_ok": True},
    })
    assert not any(n.startswith("Sidecar guard:") for n in parser.notes)


# ---------------------------------------------------------------------------
# slow e2e: the acceptance bar
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_wedge_recovery_e2e(tmp_path):
    """Acceptance: a chaos-plan run with ``sidecar wedge`` injected
    mid-traffic commits every in-flight consensus verify via the host
    fallback (masks bit-identical to verify_batch), reboots the engine
    off the warm cache in under half the cold-warmup wall, and the
    parser emits the wedge/reboot notes with the recovery SLO PASS."""
    from hotstuff_tpu.chaos import PlanRunner, parse_plan
    from hotstuff_tpu.chaos.plan import FaultEvent  # noqa: F401
    from hotstuff_tpu.harness import LogParser
    from hotstuff_tpu.harness.faults import LocalFaultInjector
    from hotstuff_tpu.sidecar.client import SidecarClient
    from hotstuff_tpu.utils.xla_cache import CompileManifest

    # The warm cache story: a manifest with a recorded COLD warmup run
    # (the 149 s boot PR 11 measured) against which the reboot's wall
    # must come in under half.
    manifest = CompileManifest(str(tmp_path / "manifest.json"))
    manifest.record_run("kern", hits=0, misses=4, wall_s=149.0, now=1.0)
    cold_wall = manifest.cold_wall_s()
    assert cold_wall == 149.0

    chaos = ChaosState()
    g = LaunchGuard(deadlines=LaunchDeadlines(**FAST))
    rewarm_walls = []
    engine = VerifyEngine(
        use_host=True, guard=g, chaos=chaos,
        rewarm_fn=lambda: (time.sleep(0.1), rewarm_walls.append(1)))
    srv = SidecarServer(("127.0.0.1", 0), engine, chaos=chaos)
    st = threading.Thread(target=srv.serve_forever,
                          kwargs=dict(poll_interval=0.1), daemon=True)
    st.start()
    port = srv.server_address[1]

    masks = []
    expects = []
    errors = []
    stop = threading.Event()

    def traffic(seed):
        # Distinct records per request so verifies hit the engine, not
        # the verdict cache — every one must come back CORRECT whether
        # it rode the device leg, the ladder, or the reboot window.
        # The loop runs until the main thread has SEEN the wedge land
        # (stop event), so there is always traffic in flight when the
        # plan fires, regardless of scheduling weather.
        try:
            with SidecarClient(port=port, timeout=30.0) as client:
                i = 0
                while not stop.is_set() and i < 500:
                    m, p, s = _sigs(4, tamper={i % 4},
                                    seed=seed * 1000 + i)
                    expect = [bool(b) for b in
                              eddsa.verify_batch(m, p, s)]
                    mask = client.verify_batch(m, p, s)
                    masks.append(mask)
                    expects.append(expect)
                    i += 1
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=traffic, args=(k,), daemon=True)
               for k in range(2)]
    for t in threads:
        t.start()

    plan = parse_plan("0.2 sidecar wedge")
    injector = LocalFaultInjector(SimpleNamespace(SIDECAR_PORT=port))
    base_wall = LogParser._to_posix("2026-07-29T14:54:56.900Z")
    runner = PlanRunner(plan, injector, wall=lambda: base_wall)
    runner.start()
    runner.join(timeout=30.0)

    def _guard_snap():
        return engine.stats_snapshot()["guard"]

    assert _wait(lambda: _guard_snap()["wedges"] >= 1, timeout=60.0), \
        "the scripted wedge never caught a launch"
    assert _wait(lambda: _guard_snap()["reboots"] >= 1, timeout=60.0)
    stop.set()
    for t in threads:
        t.join(timeout=60.0)
    assert not errors, errors
    assert masks and all(m == e for m, e in zip(masks, expects)), \
        "a verify answered with a non-bit-identical mask"
    stats = engine.stats_snapshot()
    snap = stats["guard"]
    assert snap["wedges"] >= 1
    assert snap["canary_passes"] >= 1
    assert snap["device_ok"] and not snap["rebooting"]
    # "under half the cold-warmup wall": the reboot re-warms off the
    # populated cache, so its wall must beat cold/2 by a mile.
    assert snap["last_reboot_wall_s"] < 0.5 * cold_wall

    # The parser round trip: guard notes + the sidecar-wedge recovery
    # SLO PASS, exactly what a harness run's summary would carry.
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    parser.note_sidecar_stats(json.loads(json.dumps(stats)))
    events = json.loads(json.dumps(runner.events()))
    assert events and events[0]["ok"], events
    parser.note_chaos_events(events, strict=True)
    guard_note = next(n for n in parser.notes
                      if n.startswith("Sidecar guard:"))
    assert "wedge(s)" in guard_note and "crash-only reboot(s)" in \
        guard_note
    slo_note = next(n for n in parser.notes
                    if n.startswith("Chaos SLO sidecar-wedge:"))
    assert slo_note.endswith("PASS")

    srv.shutdown()
    engine.stop()
    g.close()
    srv.server_close()
