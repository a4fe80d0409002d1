"""graftchaos tests: plan parsing/validation, the runner's scheduling and
error capture (virtual clock — tier-1 fast), recovery-latency math, the
LogParser integration (notes, strict liveness assertion, chaos-events.json
round trip, client-failure tolerance), and the TC-shaped batch a view
change sends to the verifier."""

import json
import threading
from datetime import datetime, timezone

import pytest

from hotstuff_tpu.chaos import (
    FaultPlan,
    PlanError,
    PlanRunner,
    parse_plan,
    summarize_recovery,
)
from hotstuff_tpu.harness.logs import LogParser, ParseError
from test_harness import GOLDEN_CLIENT, GOLDEN_NODE


# ---------------------------------------------------------------------------
# plan parsing + validation
# ---------------------------------------------------------------------------


def test_parse_inline_dsl_sorts_and_validates():
    plan = parse_plan("10 sidecar restart; 5 sidecar kill; "
                      "3 node:1 pause; 6 node:1 resume")
    assert [e.t for e in plan.events] == [3.0, 5.0, 6.0, 10.0]
    assert plan.node_indices() == {1}
    assert plan.max_time() == 10.0
    # round-trips through JSON and back through the parser
    again = parse_plan(plan.to_json())
    assert again.to_json() == plan.to_json()


def test_parse_dict_list_and_degrade_params():
    plan = parse_plan([
        {"t": 1, "target": "sidecar", "action": "degrade",
         "params": {"delay_ms": 100, "shed": 2}},
        {"t": 2, "target": "sidecar", "action": "degrade",
         "params": {"clear": True}},
    ])
    assert plan.events[0].params == {"delay_ms": 100, "shed": 2}
    # DSL spelling of params
    plan = parse_plan("1 sidecar degrade delay_ms=50 drop=1")
    assert plan.events[0].params == {"delay_ms": 50, "drop": 1}


def test_parse_plan_file(tmp_path):
    path = tmp_path / "plan.json"
    path.write_text(json.dumps({"events": [
        {"t": 5, "target": "sidecar", "action": "kill"},
        {"t": 10, "target": "sidecar", "action": "restart"},
    ]}))
    plan = parse_plan(str(path))
    assert isinstance(plan, FaultPlan) and len(plan.events) == 2
    path.write_text("{not json")
    with pytest.raises(PlanError):
        parse_plan(str(path))


@pytest.mark.parametrize("spec,fragment", [
    ("5 sidecar explode", "unknown action"),
    ("5 moon:1 kill", "target must be"),
    ("-1 sidecar kill", "finite >= 0"),
    ("5 sidecar restart", "must follow a kill"),
    ("5 node:0 resume", "must follow a pause"),
    ("5 node:0 kill; 6 node:0 kill", "already down"),
    ("5 node:0 kill; 6 node:0 pause", "needs a live target"),
    ("5 sidecar kill; 6 sidecar degrade shed=1", "needs a live sidecar"),
    ("5 sidecar pause", "does not support"),
    ("5 node:0 degrade", "does not support"),
    ("5 sidecar degrade zap=1", "unknown degrade param"),
    ("5 sidecar degrade delay_ms=oops", "must be an int >= 0"),
    ("5 sidecar degrade shed=-3", "must be an int >= 0"),
    ("5 node:0 kill extra=1", "only degrade, surge, wedge, and "
                              "leader-cascade take params"),
    ("5 leader-cascade restart", "does not support"),
    ("5 leader-cascade kill k=0", "must be an int >= 1"),
    ("5 leader-cascade kill k=oops", "must be an int >= 1"),
    ("5 leader-cascade kill zap=2", "unknown leader-cascade param"),
    ("5 leader-cascade kill k=2; 8 node:1 kill",
     "mixing leader-cascade with node:<i> events"),
    ("2 node:1 pause; 5 leader-cascade kill; 8 node:1 resume",
     "mixing leader-cascade with node:<i> events"),
    ("nonsense", "want '<t> <target> <action>'"),
    ("", "empty fault plan"),
])
def test_plan_validation_rejects(spec, fragment):
    with pytest.raises(PlanError) as exc:
        parse_plan(spec)
    assert fragment in str(exc.value)


# ---------------------------------------------------------------------------
# runner (virtual clock: instant, deterministic ordering)
# ---------------------------------------------------------------------------


class _Recorder:
    def __init__(self, fail_on=()):
        self.applied = []
        self.fail_on = set(fail_on)

    def apply(self, event):
        if event.action in self.fail_on:
            raise RuntimeError(f"boom on {event.action}")
        self.applied.append((event.t, event.target, event.action))


def _run_virtual(plan, injector, until=None):
    now = [0.0]
    runner = PlanRunner(plan, injector, clock=lambda: now[0],
                        sleep=lambda dt: now.__setitem__(0, now[0] + dt),
                        wall=lambda: 1000.0 + now[0])
    runner.start(t0=0.0)
    runner.join(timeout=30.0)
    return runner


def test_runner_executes_in_order_with_wall_stamps():
    plan = parse_plan("2 sidecar kill; 1 node:0 pause; 3 node:0 resume")
    rec = _Recorder()
    runner = _run_virtual(plan, rec)
    assert rec.applied == [(1.0, "node:0", "pause"),
                           (2.0, "sidecar", "kill"),
                           (3.0, "node:0", "resume")]
    events = runner.events()
    assert [e["wall"] for e in events] == [1001.0, 1002.0, 1003.0]
    assert runner.all_ok()
    # JSON-safe (the logs/chaos-events.json contract)
    json.dumps(events)


def test_runner_records_injection_failure_and_continues():
    plan = parse_plan("1 sidecar kill; 2 sidecar restart")
    rec = _Recorder(fail_on={"kill"})
    runner = _run_virtual(plan, rec)
    events = runner.events()
    assert [e["ok"] for e in events] == [False, True]
    assert "boom on kill" in events[0]["error"]
    assert not runner.all_ok()
    assert rec.applied == [(2.0, "sidecar", "restart")]


def test_runner_stop_skips_pending_events():
    plan = parse_plan("1 sidecar kill; 500 sidecar restart")
    rec = _Recorder()
    now = [0.0]
    stopper = {}

    def sleep(dt):
        now[0] += dt
        if now[0] > 2.0:
            stopper["runner"].stop()

    runner = PlanRunner(plan, rec, clock=lambda: now[0], sleep=sleep,
                        wall=lambda: 1000.0 + now[0])
    stopper["runner"] = runner
    runner.start(t0=0.0)
    runner.join(timeout=30.0)
    assert [e["action"] for e in runner.events()] == ["kill"]


def test_runner_real_clock_smoke():
    """One tiny plan on the real clock: the thread plumbing works."""
    plan = parse_plan("0.01 sidecar kill; 0.03 sidecar restart")
    rec = _Recorder()
    runner = PlanRunner(plan, rec)
    done = threading.Event()
    runner.start()
    runner.join(timeout=10.0)
    done.set()
    assert len(runner.events()) == 2 and runner.all_ok()


# ---------------------------------------------------------------------------
# recovery math
# ---------------------------------------------------------------------------


def test_summarize_recovery_first_commit_after_event():
    events = [
        {"t": 5, "target": "sidecar", "action": "kill", "wall": 100.0,
         "ok": True},
        {"t": 10, "target": "sidecar", "action": "restart", "wall": 105.0,
         "ok": True},
    ]
    commits = [99.0, 100.8, 104.0, 105.4]
    out = summarize_recovery(events, commits)
    assert out["recovered"] and out["injected_ok"]
    assert out["events"][0]["recovery_ms"] == 800.0
    assert out["events"][1]["recovery_ms"] == 400.0
    assert out["max_recovery_ms"] == 800.0


def test_summarize_recovery_flags_stall_and_failed_injection():
    events = [
        {"t": 5, "action": "kill", "target": "node:2", "wall": 100.0,
         "ok": False, "error": "no such pid"},
        {"t": 9, "action": "restart", "target": "node:2", "wall": 104.0,
         "ok": True},
    ]
    out = summarize_recovery(events, [99.0, 101.0])  # nothing after 104
    assert not out["recovered"] and not out["injected_ok"]
    assert out["unrecovered"] == ["t=9s restart node:2"]
    assert out["events"][0]["error"] == "no such pid"


# ---------------------------------------------------------------------------
# LogParser integration
# ---------------------------------------------------------------------------

# Golden commits land at 2026-07-29T14:54:57.000Z and .200Z.
_COMMIT0 = datetime(2026, 7, 29, 14, 54, 57, 0,
                    tzinfo=timezone.utc).timestamp()


def _event(dt_s, action="kill", target="sidecar", ok=True):
    return {"t": 5.0, "target": target, "action": action,
            "wall": _COMMIT0 + dt_s, "ok": ok}


def test_parser_reports_recovery_latency_in_notes():
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                       chaos_events=[_event(-0.1)], strict_chaos=True)
    out = parser.result()
    assert "Chaos plan: 1 event(s), max recovery 100 ms" in out
    assert "Chaos t=5s kill sidecar: recovery 100 ms" in out
    assert parser.chaos["recovered"]
    # labelled RESULTS grammar untouched
    assert "End-to-end TPS" in out and "Consensus latency" in out


def test_parser_strict_chaos_raises_on_stall():
    # Event after the LAST golden commit: nothing ever commits again.
    with pytest.raises(ParseError) as exc:
        LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                  chaos_events=[_event(+10.0)], strict_chaos=True)
    assert "did not resume" in str(exc.value)
    # ... and a failed injection is a hard error too.
    with pytest.raises(ParseError) as exc:
        LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                  chaos_events=[dict(_event(-0.1), ok=False,
                                     error="nope")],
                  strict_chaos=True)
    assert "injection failed" in str(exc.value)
    # non-strict: reported, not raised
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                       chaos_events=[_event(+10.0)], strict_chaos=False)
    assert not parser.chaos["recovered"]
    assert any("UNCONFIRMED" in n for n in parser.notes)


def test_parser_tolerates_client_death_only_under_chaos():
    dead_client = GOLDEN_CLIENT + \
        "[2026-07-29T14:54:58.000Z WARN client] Failed to send transaction\n"
    with pytest.raises(ParseError):
        LogParser([dead_client], [GOLDEN_NODE], faults=0)
    parser = LogParser([dead_client], [GOLDEN_NODE], faults=0,
                       chaos_events=[_event(-0.1, action="pause",
                                            target="node:0")],
                       strict_chaos=True)
    assert any("died with its faulted replica" in n for n in parser.notes)
    # Tolerance is SCOPED: a plan that faults no replica excuses nothing
    # (a sidecar-only plan must not mask a genuine client bug) ...
    with pytest.raises(ParseError):
        LogParser([dead_client], [GOLDEN_NODE], faults=0,
                  chaos_events=[_event(-0.1, action="kill",
                                       target="sidecar")],
                  strict_chaos=True)
    # ... and is bounded by the count of distinct faulted replicas.
    with pytest.raises(ParseError):
        LogParser([dead_client, dead_client], [GOLDEN_NODE], faults=0,
                  chaos_events=[_event(-0.1, action="pause",
                                       target="node:0")],
                  strict_chaos=True)


def test_parser_counts_circuit_breaker_transitions():
    node = GOLDEN_NODE + (
        "[2026-07-29T14:54:58.000Z WARN crypto::sidecar] circuit breaker "
        "OPEN after 3 consecutive transport failures (connect failed): "
        "verifying on host, probing 127.0.0.1:7100 every 2000+ ms\n"
        "[2026-07-29T14:54:59.000Z INFO crypto::sidecar] circuit breaker "
        "CLOSED: re-attached to verify sidecar 127.0.0.1:7100\n")
    parser = LogParser([GOLDEN_CLIENT], [node], faults=0)
    assert any("circuit breaker: 1 open / 1 re-attach" in n
               for n in parser.notes)


def test_parser_process_reads_chaos_events_file(tmp_path):
    (tmp_path / "client-0.log").write_text(GOLDEN_CLIENT)
    (tmp_path / "node-0.log").write_text(GOLDEN_NODE)
    (tmp_path / "chaos-events.json").write_text(json.dumps([_event(-0.1)]))
    parser = LogParser.process(str(tmp_path), faults=0)
    assert parser.chaos is not None and parser.chaos["recovered"]
    # strict mode is on when the file exists: a stalled chaos run fails
    (tmp_path / "chaos-events.json").write_text(json.dumps([_event(10.0)]))
    with pytest.raises(ParseError):
        LogParser.process(str(tmp_path), faults=0)
    # garbage file: chaos mode simply off, parse survives
    (tmp_path / "chaos-events.json").write_text("{nope")
    parser = LogParser.process(str(tmp_path), faults=0)
    assert parser.chaos is None


# ---------------------------------------------------------------------------
# harness wiring + bench headline probe
# ---------------------------------------------------------------------------


def test_local_bench_rejects_bad_plan_targets():
    from hotstuff_tpu.harness.config import BenchParameters
    from hotstuff_tpu.harness.local import LocalBench
    from hotstuff_tpu.harness.utils import BenchError

    params = {"faults": 1, "nodes": 4, "rate": 1000, "tx_size": 512,
              "duration": 60, "fault_plan": "5 node:3 kill"}
    bench = LocalBench(BenchParameters(params))
    # node 3 is the crash fault (alive = 3): the plan cannot execute
    with pytest.raises(BenchError) as exc:
        bench._check_fault_plan()
    assert "never booted" in str(exc.value)

    params["fault_plan"] = "5 sidecar kill; 8 sidecar restart"
    bench = LocalBench(BenchParameters(params))  # no sidecar in this run
    with pytest.raises(BenchError) as exc:
        bench._check_fault_plan()
    assert "boots none" in str(exc.value)

    # An event too close to teardown would either never fire or fail a
    # healthy run's strict recovery assertion: rejected up front.
    # (default timeout_delay 5000 ms -> grace = 2*5 + 3 = 13 s)
    params["fault_plan"] = "55 node:0 kill"
    bench = LocalBench(BenchParameters(params))
    with pytest.raises(BenchError) as exc:
        bench._check_fault_plan()
    assert "headroom" in str(exc.value)

    # ... and the acceptance-shaped plan passes the pre-boot check.
    params["fault_plan"] = \
        "5 sidecar kill; 10 sidecar restart; 12 node:1 pause; 15 node:1 resume"
    params["sidecar_host_crypto"] = True
    LocalBench(BenchParameters(params))._check_fault_plan()

    params["fault_plan"] = "5 nonsense"
    with pytest.raises(BenchError):
        LocalBench(BenchParameters(params))


def test_local_bench_boot_flags_carry_chaos_and_sizing():
    """The sidecar boot command grows --chaos only when a plan exists,
    and always carries the committee/rate sizing parameters."""
    from hotstuff_tpu.harness.config import BenchParameters
    from hotstuff_tpu.harness.local import LocalBench

    def boot_cmd(extra):
        params = {"faults": 0, "nodes": 4, "rate": 1000, "tx_size": 512,
                  "duration": 10, "sidecar_host_crypto": True, **extra}
        bench = LocalBench(BenchParameters(params))
        booted = []
        bench._background_run = \
            lambda cmd, log, append=False: booted.append(cmd)
        bench._wait_sidecar_ready = lambda deadline_s: None
        bench._boot_sidecar(host_crypto=True)
        return booted[0]

    cmd = boot_cmd({})
    assert "--committee 4" in cmd and "--client-rate 1000" in cmd
    assert "--chaos" not in cmd
    cmd = boot_cmd({"fault_plan": "1 sidecar degrade shed=1"})
    assert "--chaos" in cmd


def test_local_bench_boot_flags_carry_mesh():
    """--sidecar-mesh N boots the sidecar with --mesh N and the sharded
    one-MSM warmup; a host-crypto boot carries neither (no device, no
    mesh)."""
    from hotstuff_tpu.harness.config import BenchParameters
    from hotstuff_tpu.harness.local import LocalBench

    def boot_cmd(host_crypto):
        params = {"faults": 0, "nodes": 4, "rate": 1000, "tx_size": 512,
                  "duration": 10, "tpu_sidecar": True, "sidecar_mesh": 8}
        bench = LocalBench(BenchParameters(params))
        booted = []
        bench._background_run = \
            lambda cmd, log, append=False: booted.append(cmd)
        bench._wait_sidecar_ready = lambda deadline_s: None
        bench._boot_sidecar(host_crypto=host_crypto)
        return booted[0]

    cmd = boot_cmd(host_crypto=False)
    assert "--mesh 8 --warm-rlc-sharded" in cmd
    cmd = boot_cmd(host_crypto=True)
    assert "--mesh" not in cmd and "--warm-rlc-sharded" not in cmd


def test_local_fault_injector_signals_real_process_groups(tmp_path):
    """The signal plumbing against live (dummy) process groups: kill
    really SIGKILLs the group, pause really SIGSTOPs it (resume undoes),
    restart re-runs the recorded boot command in append mode, and
    cleanup un-pauses stragglers."""
    import os
    import subprocess
    import sys
    import time

    from hotstuff_tpu.chaos import parse_plan
    from hotstuff_tpu.harness.faults import LocalFaultInjector
    from hotstuff_tpu.harness.local import LocalBench

    bench = LocalBench.__new__(LocalBench)
    bench._procs = []
    bench._node_procs = {}
    bench._node_cmds = {}
    bench._sidecar_proc = None
    restarted = []
    bench._background_run = lambda cmd, log, append=False: (
        restarted.append((cmd, log, append)),
        subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"],
                         preexec_fn=os.setsid))[1]

    def spawn():
        return subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"],
            preexec_fn=os.setsid)

    bench._node_procs = {0: spawn(), 1: spawn()}
    bench._node_cmds = {0: ("cmd0", "log0"), 1: ("cmd1", "log1")}
    injector = LocalFaultInjector(bench)
    plan = parse_plan("0 node:0 kill; 0 node:0 restart; 0 node:1 pause")
    try:
        injector.apply(plan.events[0])   # kill node 0
        assert bench._node_procs[0].poll() is not None
        injector.apply(plan.events[1])   # restart node 0
        assert restarted == [("cmd0", "log0", True)]
        assert bench._node_procs[0].poll() is None
        injector.apply(plan.events[2])   # pause node 1
        time.sleep(0.1)
        with open(f"/proc/{bench._node_procs[1].pid}/stat") as f:
            assert f.read().split()[2] == "T"  # stopped
        injector.cleanup()               # SIGCONT straggler
        time.sleep(0.1)
        with open(f"/proc/{bench._node_procs[1].pid}/stat") as f:
            assert f.read().split()[2] in ("S", "R")
    finally:
        import signal as sig

        for p in bench._node_procs.values():
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), sig.SIGKILL)
                except ProcessLookupError:
                    pass


# ---------------------------------------------------------------------------
# graftview: leader-cascade drill (plan action, SLO class, injector, parser)
# ---------------------------------------------------------------------------


def test_parse_leader_cascade_plan():
    from hotstuff_tpu.chaos.plan import CASCADE_DEFAULT_K, LEADER_CASCADE, \
        cascade_k

    plan = parse_plan("5 leader-cascade kill k=3")
    (e,) = plan.events
    assert e.target == LEADER_CASCADE and e.action == "kill"
    assert cascade_k(e.params) == 3
    assert plan.node_indices() == set()  # victims are a runtime decision
    # default k, JSON round trip
    plan = parse_plan("5 leader-cascade kill")
    assert cascade_k(plan.events[0].params) == CASCADE_DEFAULT_K
    again = parse_plan(plan.to_json())
    assert again.to_json() == plan.to_json()
    # cascades are stateless: two in one plan are legal, and they mix
    # with non-node targets (whose state machine is unaffected)
    parse_plan("5 leader-cascade kill k=1; 20 leader-cascade kill k=2; "
               "2 sidecar degrade shed=1")


def test_cascade_fault_class_slo_and_judge():
    from hotstuff_tpu.chaos import DEFAULT_SLO_MS, fault_class, judge

    assert fault_class({"target": "leader-cascade",
                        "action": "kill"}) == "view-change"
    assert DEFAULT_SLO_MS["view-change"] == 60_000.0
    events = [{"t": 5, "target": "leader-cascade", "action": "kill",
               "params": {"k": 2}, "wall": 100.0, "ok": True}]
    out = summarize_recovery(events, [99.0, 112.0])
    verdict = judge(out)
    assert verdict["ok"]
    assert verdict["verdicts"][0]["class"] == "view-change"
    assert verdict["verdicts"][0]["recovery_ms"] == 12_000.0
    # a breach of the view-change budget fails like any other class
    late = summarize_recovery(events, [99.0, 200.0])
    assert not judge(late)["ok"]


def test_local_fault_injector_cascade_kills_upcoming_leaders(
        tmp_path, monkeypatch):
    """The cascade injector estimates the live round from the node logs,
    maps the next k round-robin leader slots (sorted-key order, the C++
    LeaderElector's rule) to boot indices, and SIGKILLs exactly those
    process groups — skipping already-dead slots, failing only when no
    live leader remains."""
    import base64
    import os
    import subprocess
    import sys

    from hotstuff_tpu.chaos import parse_plan as pp
    from hotstuff_tpu.harness.faults import InjectionError, \
        LocalFaultInjector
    from hotstuff_tpu.harness.local import LocalBench
    from hotstuff_tpu.harness.utils import PathMaker

    def spawn():
        return subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"],
            preexec_fn=os.setsid)

    bench = LocalBench.__new__(LocalBench)
    bench._procs = []
    bench._node_procs = {i: spawn() for i in range(4)}
    bench._node_cmds = {}
    bench._sidecar_proc = None
    # Names whose decoded bytes sort in boot order, so leader(r) =
    # node r % 4 — deterministic mapping for the assertion below.
    bench._node_names = [
        base64.b64encode(bytes([i]) * 32).decode() for i in range(4)]
    monkeypatch.setattr(
        PathMaker, "node_log_file",
        staticmethod(lambda i: str(tmp_path / f"node-{i}.log")))
    # Node 0's log says the committee reached round 10 -> the injector
    # estimates round 11, so a k=2 cascade kills the leaders of rounds
    # 12 and 13 = nodes 0 and 1.
    (tmp_path / "node-0.log").write_text(
        "[2026-07-29T14:54:57.000Z INFO consensus::core] Committed B10\n")
    injector = LocalFaultInjector(bench)
    try:
        injector.apply(pp("0 leader-cascade kill k=2").events[0])
        bench._node_procs[0].wait(timeout=10)
        bench._node_procs[1].wait(timeout=10)
        assert bench._node_procs[0].poll() is not None
        assert bench._node_procs[1].poll() is not None
        assert bench._node_procs[2].poll() is None
        assert bench._node_procs[3].poll() is None
        # A second cascade skips the already-dead slots and kills the
        # next live leaders (rounds 12, 13 again -> dead -> the estimate
        # is unchanged, so k=3 reaches node 2).
        injector.apply(pp("0 leader-cascade kill k=3").events[0])
        bench._node_procs[2].wait(timeout=10)
        assert bench._node_procs[2].poll() is not None
        # No live leader among the next k rounds -> injection failure.
        for p in bench._node_procs.values():
            if p.poll() is None:
                os.killpg(os.getpgid(p.pid), 9)
                p.wait(timeout=10)
        with pytest.raises(InjectionError) as exc:
            injector.apply(pp("0 leader-cascade kill k=2").events[0])
        assert "no live leader" in str(exc.value)
    finally:
        import signal as sig

        for p in bench._node_procs.values():
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), sig.SIGKILL)
                except ProcessLookupError:
                    pass


def test_local_bench_cascade_preflight():
    """A cascade that would kill the quorum is rejected BEFORE boot, and
    the run-window headroom follows the backed-off pacemaker schedule
    the drill will actually execute."""
    from hotstuff_tpu.harness.config import BenchParameters
    from hotstuff_tpu.harness.local import LocalBench
    from hotstuff_tpu.harness.utils import BenchError

    # N=4: quorum 3, so only one replica is expendable — k=2 must fail.
    params = {"faults": 0, "nodes": 4, "rate": 1000, "tx_size": 512,
              "duration": 120, "fault_plan": "5 leader-cascade kill k=2"}
    with pytest.raises(BenchError) as exc:
        LocalBench(BenchParameters(params))._check_fault_plan()
    assert "quorum" in str(exc.value)
    # N=10: quorum 7, k=3 leaves exactly a quorum — legal, given window
    # headroom for 3 backed-off view changes (5+10+20+base ~ 38s grace
    # with the default pacemaker, so duration 120 with t=5 passes ...
    params = {"faults": 0, "nodes": 10, "rate": 1000, "tx_size": 512,
              "duration": 120, "fault_plan": "5 leader-cascade kill k=3"}
    LocalBench(BenchParameters(params))._check_fault_plan()
    # ... and a 30 s window does not).
    params["duration"] = 30
    with pytest.raises(BenchError) as exc:
        LocalBench(BenchParameters(params))._check_fault_plan()
    assert "headroom" in str(exc.value)
    # remote pre-flight: cascades are local-harness only
    from hotstuff_tpu.harness.faults import InjectionError, \
        RemoteFaultInjector

    inj = RemoteFaultInjector(runner=None, hosts=["h0"], repo="/r",
                              node_boots={})
    from hotstuff_tpu.chaos import parse_plan as pp

    with pytest.raises(InjectionError):
        inj.apply(pp("0 leader-cascade kill").events[0])


_VIEWCHANGE_LINES = (
    "[2026-07-29T14:54:56.900Z WARN consensus::core] Timeout reached for "
    "round 2\n"
    "[2026-07-29T14:54:56.910Z WARN consensus::core] Ejected 1 invalid "
    "timeout signer(s) for round 2 (batched TC verify failed; "
    "per-signature fallback)\n"
    "[2026-07-29T14:54:56.950Z INFO consensus::core] Formed TC for round "
    "2 (3 timeouts, batched verify)\n"
    "[2026-07-29T14:54:56.951Z INFO consensus::core] View change: round "
    "2 -> 3 via TC\n"
    "[2026-07-29T14:54:56.960Z WARN consensus::core] Dropped 4 "
    "future-round timeout(s) beyond horizon (round 1000000007 > 3 + "
    "1000)\n")


def test_parser_strict_cascade_requires_viewchange_evidence():
    """Under strict chaos, an executed leader-cascade with NO TC/round
    transition evidence is a drill that drilled nothing — ParseError;
    with the evidence it passes and the view-change notes land."""
    cascade = {"t": 5.0, "target": "leader-cascade", "action": "kill",
               "params": {"k": 1}, "wall": _COMMIT0 - 0.1, "ok": True}
    with pytest.raises(ParseError) as exc:
        LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                  chaos_events=[cascade], strict_chaos=True)
    assert "no view change" in str(exc.value)

    node = GOLDEN_NODE + _VIEWCHANGE_LINES
    parser = LogParser([GOLDEN_CLIENT], [node], faults=0,
                       chaos_events=[cascade], strict_chaos=True)
    out = parser.result()
    assert "Chaos SLO view-change" in out and "PASS" in out
    assert parser.viewchange["tc_rounds"] == [2]
    assert parser.viewchange["transitions"] == 1
    assert parser.viewchange["max_jump"] == 1
    assert parser.viewchange["ejected"] == 1
    assert parser.viewchange["dropped_future"] == 4
    assert any("View change: TC formed for 1 round(s) (2)" in n
               for n in parser.notes)
    assert any("1 invalid timeout signer(s) ejected" in n
               for n in parser.notes)
    assert any("4 future-round timeout(s) dropped" in n
               for n in parser.notes)


def test_parser_tolerates_cascade_client_deaths():
    """A leader-cascade kills up to k replicas chosen at runtime; their
    clients die with them — tolerated, scoped to k like node kills."""
    dead_client = GOLDEN_CLIENT + \
        "[2026-07-29T14:54:58.000Z WARN client] Failed to send transaction\n"
    node = GOLDEN_NODE + _VIEWCHANGE_LINES
    cascade = {"t": 5.0, "target": "leader-cascade", "action": "kill",
               "params": {"k": 2}, "wall": _COMMIT0 - 0.1, "ok": True}
    parser = LogParser([dead_client, dead_client], [node], faults=0,
                       chaos_events=[cascade], strict_chaos=True)
    assert sum("died with its faulted replica" in n
               for n in parser.notes) == 2
    # ... but k bounds it: a third dead client is a real bug.
    with pytest.raises(ParseError):
        LogParser([dead_client] * 3, [node], faults=0,
                  chaos_events=[cascade], strict_chaos=True)


@pytest.mark.slow
def test_leader_cascade_e2e_local(tmp_path, monkeypatch):
    """The graftview acceptance drill against REAL processes: a 10-node
    committee (quorum 7), ``leader-cascade kill 3`` mid-run — three
    leader slots die at once, the committee rides timeout broadcast +
    batched TC assembly + the backoff pacemaker through the chained view
    changes, and the run is judged by the ``view-change`` SLO plus the
    strict parser assertions (recovery after the cascade AND actual
    TC/round-transition evidence: a drill that drilled nothing fails)."""
    import os

    from conftest import NODE_BIN, REPO
    from hotstuff_tpu.harness.config import BenchParameters, NodeParameters
    from hotstuff_tpu.harness.local import LocalBench

    if not os.path.exists(NODE_BIN):
        pytest.skip("native binaries not built (cmake --build native/build)")
    monkeypatch.chdir(tmp_path)
    os.symlink(os.path.join(REPO, "native"), tmp_path / "native")

    params = BenchParameters({
        "faults": 0, "nodes": 10, "rate": 500, "tx_size": 64,
        "duration": 25, "fault_plan": "3 leader-cascade kill k=3"})
    node_params = NodeParameters.default()
    node_params.json["consensus"]["timeout_delay"] = 1_000
    node_params.timeout_delay = 1_000
    parser = LocalBench(params, node_params).run()

    out = parser.result()
    assert "Chaos SLO view-change" in out and "PASS" in out
    assert parser.chaos["slo"]["ok"], parser.chaos["slo"]
    # the strict cascade assertion already enforced this inside run();
    # assert the machine-readable evidence too
    assert parser.viewchange["tc_rounds"], "cascade formed no TC"
    assert any("View change: TC formed" in n for n in parser.notes)
    events = json.load(open("logs/chaos-events.json"))
    assert events[0]["target"] == "leader-cascade" and events[0]["ok"]


def test_tc_shaped_batch_ejects_exactly_the_tampered_signer():
    """The batch a view change sends to the verifier (graftview): the
    quorum's timeout votes all sign ONE shared (round, high_qc_round)
    digest under distinct keys.  One launch accepts the honest quorum;
    with one tampered vote the batch rejects and its mask names exactly
    the signer that per-signature verification rejects (the eject
    contract of the native batched TC assembly, through the python
    engine)."""
    import numpy as np

    from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
    from hotstuff_tpu.sidecar.sched.shapes import quorum_sigs

    rng = np.random.default_rng(37)
    shared = rng.bytes(32)
    q = quorum_sigs(6)
    assert q == 5
    pks, sigs = [], []
    for _ in range(q):
        sk = rng.bytes(32)
        pks.append(ref.generate_keypair(sk)[1])
        sigs.append(ref.sign(sk, shared))
    assert eddsa.verify_batch([shared] * q, pks, sigs).all()
    bad_i = q // 2
    sigs[bad_i] = sigs[bad_i][:1] + bytes([sigs[bad_i][1] ^ 0xFF]) \
        + sigs[bad_i][2:]
    mask = [bool(b) for b in eddsa.verify_batch([shared] * q, pks, sigs)]
    assert mask == [ref.verify(pk, shared, sg) for pk, sg in zip(pks, sigs)]
    assert [i for i, ok in enumerate(mask) if not ok] == [bad_i]


def test_finish_fault_plan_fails_on_skipped_events(tmp_path, monkeypatch):
    """An event the run window closed on (stalled earlier injection) is
    a FAILED chaos run, not a silently shorter one."""
    from hotstuff_tpu.harness.config import BenchParameters
    from hotstuff_tpu.harness.local import LocalBench
    from hotstuff_tpu.harness.utils import BenchError, PathMaker

    monkeypatch.setattr(PathMaker, "chaos_events_file",
                        staticmethod(lambda: str(tmp_path / "ce.json")))
    params = {"faults": 0, "nodes": 4, "rate": 1000, "tx_size": 512,
              "duration": 60, "sidecar_host_crypto": True,
              "fault_plan": "5 sidecar kill; 10 sidecar restart"}
    bench = LocalBench(BenchParameters(params))

    class _Runner:
        def stop(self):
            pass

        def join(self, timeout=None):
            pass

        def events(self):
            return [{"t": 5.0, "target": "sidecar", "action": "kill",
                     "wall": 1.0, "ok": True}]  # second event skipped

    class _Injector:
        def cleanup(self):
            pass

    bench._injector = _Injector()
    with pytest.raises(BenchError) as exc:
        bench._finish_fault_plan(_Runner())
    assert "only 1 of 2" in str(exc.value)
    # the executed events were still persisted for diagnosis
    assert json.load(open(tmp_path / "ce.json"))[0]["action"] == "kill"
