"""graftsurge tests: the heavy-tailed load generator (seeded, virtual
clock), the overlap-driven admission controller, the scheduler's
bulk-before-latency + derated-cap policy, the OP_BUSY/retry-after wire
round trip, the metrics-driven recovery-to-baseline SLO judge, surge
fault-plan events, the LogParser's overload notes + strict fairness
assertion, the bounded-ingress lint rule, and the whole backpressure
loop under sustained overload."""

import threading

import pytest

from hotstuff_tpu.chaos import (
    PlanError,
    client_index,
    fault_class,
    judge_baseline_recovery,
    parse_plan,
    throughput_series,
)
from hotstuff_tpu.harness.loadgen import PARETO, UserLoad
from hotstuff_tpu.harness.logs import LogParser, ParseError
from hotstuff_tpu.sidecar import protocol as proto
from hotstuff_tpu.sidecar import sched as vsched
from hotstuff_tpu.sidecar.client import SidecarClient, SidecarOverloaded
from hotstuff_tpu.sidecar.sched.surge import (
    DERATE_FLOOR,
    MIN_PACKS,
    RETRY_DEFAULT_MS,
    RETRY_MAX_MS,
    AdmissionController,
)
from test_harness import GOLDEN_CLIENT, GOLDEN_NODE


def _request(rid, n):
    recs = [rid.to_bytes(6, "big") + i.to_bytes(2, "big")
            for i in range(n)]
    return proto.VerifyRequest(rid, recs, recs, recs)


# ---------------------------------------------------------------------------
# load generator (python twin of the C++ UserLoadModel)
# ---------------------------------------------------------------------------


def _drive(load, from_s, to_s, tick_s=0.05):
    total = 0
    t = from_s + tick_s
    while t <= to_s + 1e-9:
        total += load.arrivals(t)
        t += tick_s
    return total


def test_loadgen_deterministic_and_aggregate_rate():
    a = UserLoad(rate=2000, users=300, seed=5)
    b = UserLoad(rate=2000, users=300, seed=5)
    for k in range(1, 101):
        assert a.arrivals(k * 0.05) == b.arrivals(k * 0.05)
    total = _drive(a, 5.0, 30.0) + a.sent - a.sent  # continue a's clock
    # 30 virtual seconds at 2000 tx/s: within +-10% despite heavy tails.
    assert 0.9 * 60_000 < a.sent < 1.1 * 60_000
    c = UserLoad(rate=2000, users=300, seed=6)
    _drive(c, 0.0, 30.0)
    assert c.sent != a.sent  # a different world, not a constant


def test_loadgen_gaps_are_heavy_tailed_and_pareto_mean_one():
    lg = UserLoad(rate=100, users=1, seed=7, sigma=1.5)
    gaps = [lg.sample_gap(0.0) for _ in range(20_000)]
    mean = sum(gaps) / len(gaps)
    var = sum(g * g for g in gaps) / len(gaps) - mean * mean
    assert 0.0085 < mean < 0.0115          # user mean gap 10 ms
    assert var ** 0.5 / mean > 1.2         # heavy tail (true CV ~2.9)
    pa = UserLoad(rate=100, users=1, seed=7, dist=PARETO, alpha=2.5)
    gaps = [pa.sample_gap(0.0) for _ in range(20_000)]
    assert 0.0085 < sum(gaps) / len(gaps) < 0.0115


def test_loadgen_busy_defers_per_user_then_recovers():
    lg = UserLoad(rate=1000, users=20, seed=3)
    assert _drive(lg, 0.0, 1.0, 0.01) > 0
    lg.busy(1.0, 0.5)
    assert _drive(lg, 1.0, 1.5, 0.01) == 0  # everything defers
    assert lg.deferred > 0 and lg.busy_events == 1
    assert _drive(lg, 1.5, 6.0, 0.01) > 0   # open loop: load comes back


def test_loadgen_diurnal_profile_means_one():
    lg = UserLoad(rate=2000, users=100, seed=9, diurnal_amp=0.5,
                  diurnal_period_s=100.0)
    acc = sum(lg.profile(100.0 * i / 1000) for i in range(1000)) / 1000
    assert abs(acc - 1.0) < 0.01
    assert lg.profile(25.0) > 1.4 and lg.profile(75.0) < 0.6
    _drive(lg, 0.0, 200.0)
    assert 0.9 * 400_000 < lg.sent < 1.1 * 400_000


def test_loadgen_rejects_bad_config():
    with pytest.raises(ValueError):
        UserLoad(rate=100, users=1, dist="uniform")
    with pytest.raises(ValueError):
        UserLoad(rate=0, users=1)


# ---------------------------------------------------------------------------
# admission controller
# ---------------------------------------------------------------------------


def test_admission_derate_tracks_overlap_with_hysteresis_counts():
    now = [0.0]
    adm = AdmissionController(clock=lambda: now[0])
    # Not enough evidence: full cap regardless of the few packs seen.
    for _ in range(MIN_PACKS - 1):
        adm.note_pack(0.01, hidden=False)
    assert adm.bulk_derate() == 1.0
    # Overlap collapsed: derate engages once, down to the floor.
    for _ in range(64):
        adm.note_pack(0.01, hidden=False)
    assert adm.bulk_derate() == pytest.approx(DERATE_FLOOR)
    assert adm.snapshot()["derate"]["engagements"] == 1
    # Pipeline healthy again: back to full cap, engagement count fixed.
    for _ in range(64):
        adm.note_pack(0.01, hidden=True)
    assert adm.bulk_derate() == 1.0
    snap = adm.snapshot()
    assert snap["derate"]["engagements"] == 1
    assert not snap["derate"]["engaged"]
    # A second collapse is a second engagement (watermark-style count).
    for _ in range(64):
        adm.note_pack(0.01, hidden=False)
    assert adm.snapshot()["derate"]["engagements"] == 2
    # Partial overlap lands between the floor and 1: 16 hidden of the
    # 64-pack window is overlap 0.25, half way up to OVERLAP_KNEE (32
    # hidden would sit exactly ON the knee, which is full cap).
    for _ in range(16):
        adm.note_pack(0.01, hidden=True)
    assert adm.bulk_derate() == pytest.approx(
        DERATE_FLOOR + (1.0 - DERATE_FLOOR) * 0.5)


def test_admission_overlap_window_is_time_bounded():
    """The satellite regression: the derate judges RECENT packs — a
    lifetime average would let hours of healthy history outvote the
    collapse in front of it.  Healthy evidence older than PACK_WINDOW_S
    ages out; with no fresh evidence at all the controller answers the
    full cap, never a verdict off stale telemetry."""
    from hotstuff_tpu.sidecar.sched.surge import PACK_WINDOW_S

    now = [0.0]
    adm = AdmissionController(clock=lambda: now[0])
    for _ in range(64):
        adm.note_pack(0.01, hidden=True)
    assert adm.bulk_derate() == 1.0
    # The surge arrives after a quiet stretch: only fresh packs decide.
    now[0] += PACK_WINDOW_S + 1.0
    for _ in range(MIN_PACKS):
        adm.note_pack(0.01, hidden=False)
    assert adm.recent_overlap() == 0.0
    assert adm.bulk_derate() == pytest.approx(DERATE_FLOOR)
    # ... and once THAT evidence ages out, no evidence -> full cap.
    now[0] += PACK_WINDOW_S + 1.0
    assert adm.recent_overlap() is None
    assert adm.bulk_derate() == 1.0


def test_admission_ring_occupancy_rules_while_fresh_then_goes_stale():
    """graftcadence: while ring occupancy samples are fresh they REPLACE
    the overlap rule (the resident pipeline hides pack time by
    construction); a full ring derates toward the floor, headroom keeps
    the full cap, and stale occupancy (ring disengaged) falls back to
    the overlap rule."""
    from hotstuff_tpu.sidecar.sched.surge import (RING_OCC_KNEE,
                                                  RING_OCC_WINDOW_S)

    now = [100.0]
    adm = AdmissionController(clock=lambda: now[0])
    # Occupancy at the knee or below: headroom, full cap.
    for _ in range(16):
        adm.note_ring_occupancy(2, 4)
    assert adm.bulk_derate() == 1.0
    # Every tick full: the device cannot drain what is admitted.
    for _ in range(64):
        adm.note_ring_occupancy(4, 4)
    derated = adm.bulk_derate()
    assert DERATE_FLOOR <= derated < 1.0
    snap = adm.snapshot()["derate"]
    assert snap["engaged"] and snap["engagements"] >= 1
    assert snap["ring_occupancy_recent"] > RING_OCC_KNEE
    # Fresh ring evidence WINS over a perfectly healthy overlap.
    for _ in range(MIN_PACKS):
        adm.note_pack(0.01, hidden=True)
    assert adm.bulk_derate() == pytest.approx(derated)
    # Ring disengaged (wedge fallback/stop): occupancy goes stale within
    # RING_OCC_WINDOW_S and the healthy overlap rule takes back over.
    now[0] += RING_OCC_WINDOW_S + 1.0
    for _ in range(MIN_PACKS):
        adm.note_pack(0.01, hidden=True)
    snap = adm.snapshot()["derate"]
    assert snap["ring_occupancy_recent"] is None
    assert adm.bulk_derate() == 1.0


def test_admission_retry_after_drain_rate_and_clamps():
    now = [100.0]
    adm = AdmissionController(clock=lambda: now[0])
    # No drain evidence: per-class defaults.
    assert adm.retry_after_ms(vsched.LATENCY, 500) == \
        RETRY_DEFAULT_MS[vsched.LATENCY]
    assert adm.retry_after_ms(vsched.BULK, 500) == \
        RETRY_DEFAULT_MS[vsched.BULK]
    # 1000 sigs/s drain, 500 queued -> ~500 ms.
    adm.note_launch(1000, now=100.0)
    adm.note_launch(1000, now=101.0)
    now[0] = 102.0
    assert 400 <= adm.retry_after_ms(vsched.BULK, 500) <= 600
    # Huge backlog clamps at the max.
    assert adm.retry_after_ms(vsched.BULK, 10_000_000) == RETRY_MAX_MS


def test_admission_fairness_counter_and_pressure_window():
    now = [10.0]
    adm = AdmissionController(clock=lambda: now[0])
    adm.note_latency_shed()
    assert adm.latency_pressure()
    # Bulk admitted inside the pressure window: the violation the
    # scheduler's lock makes unreachable, counted here as proof.
    adm.note_admitted(vsched.BULK)
    assert adm.snapshot()["fairness_violations"] == 1
    now[0] = 12.0  # pressure expired
    assert not adm.latency_pressure()
    adm.note_admitted(vsched.BULK)
    assert adm.snapshot()["fairness_violations"] == 1


# ---------------------------------------------------------------------------
# scheduler policy: bulk-before-latency + derated bulk cap
# ---------------------------------------------------------------------------


def test_scheduler_sheds_bulk_before_latency():
    sched = vsched.Scheduler(latency_cap_sigs=32, bulk_cap_sigs=1024)
    assert sched.offer(_request(1, 32), lambda m: None,
                       cls=vsched.LATENCY)
    # Latency full -> latency shed -> pressure window opens.
    assert not sched.offer(_request(2, 32), lambda m: None,
                           cls=vsched.LATENCY)
    # Bulk has a near-empty queue but is shed FIRST while latency is
    # under pressure.
    assert not sched.offer(_request(3, 8), lambda m: None,
                           cls=vsched.BULK)
    snap = sched.stats.snapshot()["surge"]
    assert snap["shed"]["latency"] == 1
    assert snap["shed"]["bulk"] == 1
    assert snap["bulk_before_latency_sheds"] == 1
    assert snap["fairness_violations"] == 0


def test_scheduler_bulk_admits_against_derated_cap():
    sched = vsched.Scheduler(latency_cap_sigs=1024, bulk_cap_sigs=1000)
    # Collapse the overlap: effective bulk cap becomes 250.
    for _ in range(64):
        sched.admission.note_pack(0.01, hidden=False)
    assert sched.offer(_request(1, 100), lambda m: None, cls=vsched.BULK)
    assert sched.offer(_request(2, 100), lambda m: None, cls=vsched.BULK)
    # 200 queued + 100 > 250: shed — the PLAIN cap (1000) would admit.
    assert not sched.offer(_request(3, 100), lambda m: None,
                           cls=vsched.BULK)
    snap = sched.stats.snapshot()["surge"]
    assert snap["derate"]["engaged"]
    assert snap["shed"]["bulk"] == 1
    # Healthy overlap restores the full cap.
    for _ in range(64):
        sched.admission.note_pack(0.01, hidden=True)
    assert sched.offer(_request(4, 100), lambda m: None, cls=vsched.BULK)


def test_scheduler_retry_after_reflects_queue_depth():
    sched = vsched.Scheduler(latency_cap_sigs=1024, bulk_cap_sigs=1024)
    base = sched.retry_after_ms(vsched.BULK)
    assert base == RETRY_DEFAULT_MS[vsched.BULK]
    assert sched.retry_after_ms(vsched.LATENCY) == \
        RETRY_DEFAULT_MS[vsched.LATENCY]


# ---------------------------------------------------------------------------
# OP_BUSY wire round trip
# ---------------------------------------------------------------------------


def test_busy_reply_roundtrip_and_typed_client_error():
    # v4 introduced OP_BUSY; the protocol has since moved to v6
    # (graftfleet HELLO/tenant) without touching the BUSY layout.
    assert proto.PROTOCOL_VERSION == 6 and proto.OP_BUSY == 10
    frame = proto.encode_busy_reply(9, 137)
    opcode, rid, body = proto.decode_reply_raw(frame[4:])
    assert opcode == proto.OP_BUSY and rid == 9
    assert proto.decode_busy_body(body) == 137
    with pytest.raises(SidecarOverloaded) as exc:
        SidecarClient._unwrap(opcode, body)
    assert exc.value.retry_after_ms == 137
    # Hint clamps to the u16 range; garbage bodies raise.
    big = proto.encode_busy_reply(1, 10_000_000)
    assert proto.decode_busy_body(
        proto.decode_reply_raw(big[4:])[2]) == 0xFFFF
    with pytest.raises(ValueError):
        proto.decode_busy_body(b"\x01\x02\x03")
    # The legacy empty-body shed still reads as overload (no hint).
    legacy = proto.encode_reply(proto.OP_VERIFY_BATCH, 2, [])
    op2, _rid2, body2 = proto.decode_reply_raw(legacy[4:])
    assert SidecarClient._unwrap(op2, body2) == b""  # caller's len check


def test_server_shed_carries_retry_after_hint():
    """End to end through a real served socket: a chaos-forced shed
    answers OP_BUSY and the python client surfaces the typed overload
    with the hint attached."""
    from hotstuff_tpu.sidecar.service import ChaosState, SidecarServer, \
        VerifyEngine

    engine = VerifyEngine(use_host=True)
    srv = SidecarServer(("127.0.0.1", 0), engine, chaos=ChaosState())
    t = threading.Thread(target=srv.serve_forever,
                         kwargs={"poll_interval": 0.05}, daemon=True)
    t.start()
    try:
        port = srv.server_address[1]
        with SidecarClient(port=port, timeout=10.0) as client:
            assert client.chaos(shed=1)
            msgs = [b"\x00" * 32]
            with pytest.raises(SidecarOverloaded) as exc:
                client.verify_batch(msgs, [b"\x01" * 32], [b"\x02" * 64])
            assert isinstance(exc.value.retry_after_ms, int)
            assert exc.value.retry_after_ms >= 0
    finally:
        srv.shutdown()
        srv.server_close()
        engine.stop()


# ---------------------------------------------------------------------------
# metrics-driven recovery-to-baseline judge
# ---------------------------------------------------------------------------


def _series(rates, t0=1000.0):
    """ok samples at 1 Hz whose sigs_launched deltas equal ``rates``
    (None = a failed tick)."""
    out = []
    launched = 0
    for i, r in enumerate(rates):
        t = t0 + i
        if r is None:
            out.append({"t": t, "ok": False, "error": "down"})
            continue
        launched += r
        out.append({"t": t, "ok": True,
                    "stats": {"sigs_launched": launched}})
    return out


def test_throughput_series_clamps_counter_resets():
    samples = _series([1000, 1000, 1000])
    # A restart resets the cumulative counter: negative delta -> 0.
    samples.append({"t": 1003.0, "ok": True,
                    "stats": {"sigs_launched": 50}})
    series = throughput_series(samples)
    assert series[-1][1] == 0.0
    assert all(r >= 0 for _, r in series)


def test_judge_baseline_recovery_pass_fail_unjudged():
    event = {"t": 10.0, "target": "sidecar", "action": "kill",
             "wall": 1010.0, "ok": True}
    # PASS: blackout then full recovery.
    rates = [1000] * 10 + [None] * 3 + [1000] * 10
    out = judge_baseline_recovery(_series(rates), [event])
    assert out["ok"] and out["judged"] == 1
    v = out["verdicts"][0]
    assert v["judged"] and v["baseline_sigs_per_s"] == 1000.0
    assert v["recovered_ms"] is not None
    # FAIL: throughput never returns to 70% of baseline, with the
    # series covering the whole 30 s node-kill recovery budget.
    rates = [1000] * 10 + [100] * 45
    out = judge_baseline_recovery(_series(rates), [event])
    assert not out["ok"]
    assert "never returned" in out["verdicts"][0]["reason"]
    # Unjudged: too little pre-event telemetry (not a failure).
    out = judge_baseline_recovery(_series([1000, 1000]),
                                  [dict(event, wall=1001.5)])
    assert out["ok"] and out["judged"] == 0
    assert not out["verdicts"][0]["judged"]
    # Unjudged: the sampled series ends BEFORE the recovery budget
    # elapsed — the event had no fair chance to recover, so absence of
    # evidence is surfaced, never failed.
    rates = [1000] * 10 + [100] * 5
    out = judge_baseline_recovery(_series(rates), [event])
    assert out["ok"] and out["judged"] == 0
    assert "before the recovery budget" in out["verdicts"][0]["reason"]


def test_judge_baseline_surge_measures_from_window_end():
    # Surge [1010, 1015): depressed during the window, instant recovery
    # after.  Judged from the END, recovery is ~1 s; judged from the
    # injection it would read ~6 s.
    event = {"t": 10.0, "target": "client:0", "action": "surge",
             "wall": 1010.0, "ok": True, "params": {"x": 5, "for": 5}}
    rates = [1000] * 10 + [200] * 5 + [1000] * 10
    out = judge_baseline_recovery(_series(rates), [event])
    assert out["ok"]
    assert out["verdicts"][0]["class"] == "client-surge"
    assert out["verdicts"][0]["recovered_ms"] <= 2000.0


# ---------------------------------------------------------------------------
# surge fault-plan events
# ---------------------------------------------------------------------------


def test_plan_surge_dsl_validation_and_window():
    plan = parse_plan("10 client:0 surge x5 for 20")
    e = plan.events[0]
    assert e.params == {"x": 5.0, "for": 20.0}
    assert client_index(e.target) == 0
    assert fault_class(e.to_json()) == "client-surge"
    assert plan.max_time() == 30.0  # the surge END bounds the window
    # k=v spelling parses to the same plan.
    again = parse_plan("10 client:0 surge x=5 for=20")
    assert again.events[0].params == {"x": 5, "for": 20}
    with pytest.raises(PlanError):
        parse_plan("10 client:0 surge x0.5 for 20")  # x must be > 1
    with pytest.raises(PlanError):
        parse_plan("10 client:0 surge x2 for 0")     # window must be > 0
    with pytest.raises(PlanError):
        parse_plan("10 client:0 kill")               # clients only surge
    with pytest.raises(PlanError):                   # overlapping surges
        parse_plan("10 client:0 surge x2 for 20; "
                   "15 client:0 surge x2 for 1")
    # Back to back (and on another client) is fine.
    parse_plan("10 client:0 surge x2 for 5; 16 client:0 surge x2 for 1; "
               "12 client:1 surge x3 for 2")


def test_plan_surge_omitted_for_means_the_same_default_everywhere():
    """An omitted ``for`` must mean ONE thing across validation, window
    math, the SLO judge, and the injector: plan.SURGE_DEFAULT_FOR_S."""
    from hotstuff_tpu.chaos.plan import SURGE_DEFAULT_FOR_S, \
        surge_window_s
    from hotstuff_tpu.chaos.slo import event_window_end

    plan = parse_plan("10 client:0 surge x3")
    assert plan.max_time() == 10.0 + SURGE_DEFAULT_FOR_S
    assert surge_window_s(plan.events[0].params) == SURGE_DEFAULT_FOR_S
    assert event_window_end(
        {"action": "surge", "wall": 100.0, "params": {"x": 3}}) == \
        100.0 + SURGE_DEFAULT_FOR_S
    # Overlap validation uses the same default: a second surge inside
    # the implied window is rejected.
    with pytest.raises(PlanError):
        parse_plan("10 client:0 surge x3; 15 client:0 surge x2 for 1")


# ---------------------------------------------------------------------------
# LogParser: overload notes + strict fairness / baseline assertions
# ---------------------------------------------------------------------------

# Golden commits land at 14:54:57.000Z and .200Z (test_chaos.py).
from datetime import datetime, timezone  # noqa: E402

_COMMIT0 = datetime(2026, 7, 29, 14, 54, 57, 0,
                    tzinfo=timezone.utc).timestamp()


def _surge_event(wall, dur=0.1):
    return {"t": 5.0, "target": "client:0", "action": "surge",
            "wall": wall, "ok": True, "params": {"x": 4, "for": dur}}


def test_parser_surge_goodput_and_backpressure_notes():
    client = GOLDEN_CLIENT + (
        "[2026-07-29T14:54:58.000Z INFO client] Node busy (retry-after "
        "200 ms); backing off (1 total)\n")
    node = GOLDEN_NODE + (
        "[2026-07-29T14:54:58.100Z WARN mempool::ingress] Ingress "
        "paused: 20000 txs / 1048576 B queued after 256 consecutive "
        "busy sheds (crossing 1); resuming at 10000 txs\n"
        "[2026-07-29T14:54:58.200Z INFO mempool::ingress] Ingress "
        "resumed at 9800 queued txs (low-water mark)\n")
    parser = LogParser([client], [node], faults=0,
                       chaos_events=[_surge_event(_COMMIT0 + 0.05)],
                       strict_chaos=True)
    assert any("Ingress backpressure: 1 receiver pause(s) / 1 "
               "resume(s)" in n for n in parser.notes)
    assert any("busy backoff line(s)" in n for n in parser.notes)
    assert any("goodput retained" in n for n in parser.notes)
    surge = [e for e in parser.chaos["events"]
             if e["action"] == "surge"][0]
    assert "goodput" in surge and surge["goodput"]["before_tps"] > 0


def test_parser_strict_fairness_violation_raises():
    stats = {"launches": 3, "launches_by_class": {"latency": 3},
             "surge": {"admitted": {"latency": 3, "bulk": 1},
                       "shed": {"latency": 2, "bulk": 0},
                       "busy_replies": {}, "derate": {},
                       "bulk_before_latency_sheds": 0,
                       "fairness_violations": 1}}
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                       chaos_events=[_surge_event(_COMMIT0 + 0.05)],
                       strict_chaos=True)
    with pytest.raises(ParseError) as exc:
        parser.note_sidecar_stats(stats)
    assert "fairness" in str(exc.value)
    # Non-strict: surfaced as a note, not a failure.
    lax = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    lax.note_sidecar_stats(stats)
    assert any("VIOLATION" in n for n in lax.notes)
    # A clean surge section reads as fairness held.
    clean = dict(stats, surge=dict(stats["surge"],
                                   fairness_violations=0))
    ok = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    ok.note_sidecar_stats(clean)
    assert any("bulk-before-latency held" in n for n in ok.notes)


def test_parser_metrics_baseline_verdict_strict_and_notes():
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                       chaos_events=[_surge_event(_COMMIT0 + 0.05,
                                                  dur=3.0)],
                       strict_chaos=True)
    wall = _COMMIT0 + 0.05
    # PASS: baseline, surge-window dip, recovery.
    good = _series([1000] * 12 + [200] * 3 + [1000] * 8, t0=wall - 12)
    parser.note_metrics(good)
    assert parser.chaos["slo_metrics"]["ok"]
    assert any("back to baseline" in n for n in parser.notes)
    # FAIL under strict: the curve never comes back.
    parser2 = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                        chaos_events=[_surge_event(_COMMIT0 + 0.05,
                                                   dur=3.0)],
                        strict_chaos=True)
    # The series must cover the client-surge SLO budget past the
    # window end, or the judge (rightly) calls it unjudged.
    bad = _series([1000] * 12 + [100] * 45, t0=wall - 12)
    with pytest.raises(ParseError) as exc:
        parser2.note_metrics(bad)
    assert "recovery SLO breached" in str(exc.value) or \
        "metrics-driven" in str(exc.value)


# ---------------------------------------------------------------------------
# bounded-ingress lint rule
# ---------------------------------------------------------------------------


def _run_ingress(tmp_path, source, name="mod.py"):
    from hotstuff_tpu.analysis import ingress

    (tmp_path / name).write_text(source)
    return ingress.check(str(tmp_path), targets=(name,))


def test_ingress_rule_flags_bypass_enqueues(tmp_path):
    findings = _run_ingress(tmp_path, (
        "class Helper:\n"
        "    def stash(self, p):\n"
        "        self.items.append(p)\n"))
    assert len(findings) == 1
    assert findings[0].rule == "bounded-ingress"
    assert "Helper.stash" in findings[0].message


def test_ingress_rule_allows_admission_scopes(tmp_path):
    assert _run_ingress(tmp_path, (
        "class Q:\n"
        "    def offer(self, p):\n"
        "        self.items.append(p)\n"
        "    def _offer_locked(self, p):\n"
        "        self.items.append(p)\n"
        "class AdmissionController:\n"
        "    def requeue(self, p):\n"
        "        self.backlog.append(p)\n")) == []


def test_ingress_rule_subscripted_queues_and_locals(tmp_path):
    findings = _run_ingress(tmp_path, (
        "class S:\n"
        "    def push(self, cls, p):\n"
        "        self._queues[cls].put(p)\n"))
    assert len(findings) == 1
    # Bare locals named like queues are function-private, not shared.
    assert _run_ingress(tmp_path, (
        "def collect(xs):\n"
        "    items = []\n"
        "    for x in xs:\n"
        "        items.append(x)\n"
        "    return items\n")) == []


def test_ingress_rule_honors_suppressions(tmp_path):
    assert _run_ingress(tmp_path, (
        "class Helper:\n"
        "    def stash(self, p):\n"
        "        # justified: test fixture, never a live queue\n"
        "        # graftlint: disable=bounded-ingress\n"
        "        self.items.append(p)\n")) == []


def test_real_tree_is_ingress_clean():
    import os

    from hotstuff_tpu.analysis import ingress

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert ingress.check(root) == []


# ---------------------------------------------------------------------------
# the whole backpressure loop under sustained overload
# ---------------------------------------------------------------------------


def test_overload_loop_keeps_the_latency_class_bounded():
    """The generator, the real scheduler and its admission controller in
    one closed loop on a virtual clock: 4x a modeled drain capacity of
    BULK work from 200 heavy-tailed users beside a steady consensus-class
    stream, shed bulk requests feeding BUSY backoff into the generator.
    No queue collapse: the consensus class is never shed and its wait
    p99 stays within three ticks, sheds are bulk-before-latency, and the
    generator really deferred users on the hints."""
    tick_s, seconds, offered_x = 0.01, 1.5, 4.0
    cap_sigs_per_tick, qc_sigs, lat_per_tick, bulk_req_sigs = 128, 16, 2, 32
    bulk_req_rate = offered_x * (cap_sigs_per_tick / tick_s) / bulk_req_sigs

    sched = vsched.Scheduler(latency_cap_sigs=4 * 1024,
                             bulk_cap_sigs=8 * 1024)
    # Coalesce at the modeled per-tick drain: the "device" is the tick
    # budget, so launch granularity and drain speak the same units.
    sched.shapes.launch_cap = cap_sigs_per_tick
    adm = sched.admission
    load = UserLoad(rate=bulk_req_rate, users=200, seed=11)

    rid = 0
    offered_at, lat_waits = {}, []
    for k in range(1, int(round(seconds / tick_s)) + 1):
        t = k * tick_s
        for _ in range(lat_per_tick):
            rid += 1
            offered_at[rid] = t
            sched.offer(_request(rid, qc_sigs), lambda m: None,
                        cls=vsched.LATENCY)
        for _ in range(load.arrivals(t)):
            rid += 1
            if not sched.offer(_request(rid, bulk_req_sigs),
                               lambda m: None, cls=vsched.BULK):
                load.busy(t, sched.retry_after_ms(vsched.BULK) / 1e3)
        budget = cap_sigs_per_tick
        while budget > 0:
            launch = sched.next_launch(block=False)
            if launch is None:
                break
            lat_waits += [
                (t - offered_at.pop(p.request.request_id, t)) * 1e3
                for p in launch.items if p.cls == vsched.LATENCY]
            budget -= launch.total_sigs
            # Offered load over drain capacity packs in the open
            # (overlap collapsed): the surge regime's evidence.
            adm.note_pack(0.001, hidden=False)
    snap = adm.snapshot()
    lat_waits.sort()
    assert lat_waits[int(0.99 * (len(lat_waits) - 1))] <= 3 * tick_s * 1e3
    assert snap["shed"].get(vsched.LATENCY, 0) == 0
    assert snap["shed"].get(vsched.BULK, 0) > 0
    assert snap["fairness_violations"] == 0
    assert load.deferred > 0  # the BUSY loop closed
