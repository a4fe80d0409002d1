"""Harness tests: log parser against golden logs in the frozen grammar,
committee/parameters writers against the C++ readers' expectations, and
aggregation math. (The reference has no harness tests — SURVEY.md §4 —
but the parser's regex dependence on exact phrasing makes golden-log
coverage essential here.)
"""

import json
import os

import pytest

from hotstuff_tpu.harness import (
    BenchParameters,
    ConfigError,
    LocalCommittee,
    LogParser,
    NodeParameters,
    ParseError,
)

GOLDEN_CLIENT = """\
[2026-07-29T14:54:56.456Z INFO client] Node address: 127.0.0.1:9701
[2026-07-29T14:54:56.456Z INFO client] Transactions size: 512 B
[2026-07-29T14:54:56.456Z INFO client] Transactions rate: 2000 tx/s
[2026-07-29T14:54:56.456Z INFO client] Waiting for all nodes to be online...
[2026-07-29T14:54:54.525Z INFO client] Waiting for all nodes to be synchronized...
[2026-07-29T14:54:56.525Z INFO client] Start sending transactions
[2026-07-29T14:54:56.577Z INFO client] Sending sample transaction 0
[2026-07-29T14:54:56.627Z INFO client] Sending sample transaction 1
"""

GOLDEN_NODE = """\
[2026-07-29T14:54:55.100Z INFO mempool::config] Garbage collection depth set to 50 rounds
[2026-07-29T14:54:55.100Z INFO mempool::config] Sync retry delay set to 5000 ms
[2026-07-29T14:54:55.100Z INFO mempool::config] Sync retry nodes set to 3 nodes
[2026-07-29T14:54:55.100Z INFO mempool::config] Batch size set to 15000 B
[2026-07-29T14:54:55.100Z INFO mempool::config] Max batch delay set to 100 ms
[2026-07-29T14:54:55.101Z INFO consensus::config] Timeout delay set to 1000 ms
[2026-07-29T14:54:55.101Z INFO consensus::config] Sync retry delay set to 10000 ms
[2026-07-29T14:54:55.102Z INFO node::node] Node abc= successfully booted
[2026-07-29T14:54:56.577Z INFO mempool::batch_maker] Batch 2hHolx56fF0YIblphIzIeT2IHMTpt2ISKPP/4qqCsaU= contains sample tx 0
[2026-07-29T14:54:56.578Z INFO mempool::batch_maker] Batch 2hHolx56fF0YIblphIzIeT2IHMTpt2ISKPP/4qqCsaU= contains 15360 B
[2026-07-29T14:54:56.627Z INFO mempool::batch_maker] Batch 8obhcmwCu1dRnxvU+n/mr/KqNZ5OWZueM4no1X1NNCo= contains sample tx 1
[2026-07-29T14:54:56.628Z INFO mempool::batch_maker] Batch 8obhcmwCu1dRnxvU+n/mr/KqNZ5OWZueM4no1X1NNCo= contains 15360 B
[2026-07-29T14:54:56.700Z INFO consensus::proposer] Created B2
[2026-07-29T14:54:56.700Z INFO consensus::proposer] Created B2 -> 2hHolx56fF0YIblphIzIeT2IHMTpt2ISKPP/4qqCsaU=
[2026-07-29T14:54:56.750Z INFO consensus::proposer] Created B3
[2026-07-29T14:54:56.750Z INFO consensus::proposer] Created B3 -> 8obhcmwCu1dRnxvU+n/mr/KqNZ5OWZueM4no1X1NNCo=
[2026-07-29T14:54:57.000Z INFO consensus::core] Committed B2
[2026-07-29T14:54:57.000Z INFO consensus::core] Committed B2 -> 2hHolx56fF0YIblphIzIeT2IHMTpt2ISKPP/4qqCsaU=
[2026-07-29T14:54:57.200Z INFO consensus::core] Committed B3
[2026-07-29T14:54:57.200Z INFO consensus::core] Committed B3 -> 8obhcmwCu1dRnxvU+n/mr/KqNZ5OWZueM4no1X1NNCo=
"""


def test_parser_mines_optional_pacemaker_config():
    """graftview pacemaker knobs are OPTIONAL config lines: logs
    predating the backoff pacemaker parse exactly as before, and logs
    carrying them surface the values machine-readably."""
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    assert "timeout_backoff_factor_pct" not in parser.configs[0]["consensus"]

    node = GOLDEN_NODE + (
        "[2026-07-29T14:54:55.101Z INFO consensus::config] Timeout "
        "backoff factor set to 200 pct\n"
        "[2026-07-29T14:54:55.101Z INFO consensus::config] Timeout "
        "backoff cap set to 60000 ms\n"
        "[2026-07-29T14:54:55.101Z INFO consensus::config] Timeout "
        "jitter set to 10 pct\n"
        "[2026-07-29T14:54:55.101Z INFO consensus::config] Timeout "
        "future horizon set to 1000 rounds\n")
    parser = LogParser([GOLDEN_CLIENT], [node], faults=0)
    cons = parser.configs[0]["consensus"]
    assert cons["timeout_backoff_factor_pct"] == 200
    assert cons["timeout_backoff_cap"] == 60_000
    assert cons["timeout_jitter_pct"] == 10
    assert cons["timeout_future_horizon"] == 1_000
    # a quiet run (no TC/eject/drop lines) adds no view-change notes
    assert parser.viewchange["tc_rounds"] == []
    assert not any("View change" in n for n in parser.notes)


def test_node_parameters_validate_pacemaker_knobs():
    from hotstuff_tpu.harness import ConfigError, NodeParameters

    data = NodeParameters.default().json
    data["consensus"]["timeout_backoff_factor_pct"] = 300
    data["consensus"]["timeout_future_horizon"] = 500
    NodeParameters(data)  # valid overrides pass through
    for key, bad in (("timeout_backoff_factor_pct", 50),
                     ("timeout_backoff_factor_pct", "2x"),
                     ("timeout_jitter_pct", 101),
                     ("timeout_backoff_cap", 0),
                     ("timeout_future_horizon", 0)):
        broken = NodeParameters.default().json
        broken["consensus"][key] = bad
        with pytest.raises(ConfigError):
            NodeParameters(broken)


def test_aggregate_quotes_runs_and_bands(tmp_path, monkeypatch):
    """Multi-run same-settings result files aggregate into a band that
    SAYS how many runs back it (round-5 review, item 4): the plot-file
    grammar keeps its frozen TPS prefix, matrix cells carry the run
    count, and bands() lists every repeated configuration."""
    from hotstuff_tpu.harness.aggregate import LogAggregator, Result
    from hotstuff_tpu.harness.utils import PathMaker

    summary = (
        "-----------------------------------------\n"
        " SUMMARY:\n"
        "-----------------------------------------\n"
        " + CONFIG:\n"
        " Faults: 0 nodes\n"
        " Committee size: 100 nodes\n"
        " Input rate: 1,600 tx/s\n"
        " Transaction size: 512 B\n"
        " Execution time: 60 s\n"
        " + RESULTS:\n"
        " End-to-end TPS: {tps} tx/s\n"
        " End-to-end BPS: 1 B/s\n"
        " End-to-end latency: {lat} ms\n")
    results = tmp_path / "results"
    results.mkdir()
    # one file holding two same-settings runs + a second single-run file
    (results / "bench-0-100-1600-512.txt").write_text(
        summary.format(tps="1,189", lat="19,000")
        + summary.format(tps="703", lat="45,000"))
    (results / "bench-0-100-1600b-512.txt").write_text(
        summary.format(tps="946", lat="32,000"))
    monkeypatch.setattr(PathMaker, "results_path",
                        staticmethod(lambda: str(results)))
    monkeypatch.setattr(PathMaker, "plot_path",
                        staticmethod(lambda: str(tmp_path / "plots")))
    agg = LogAggregator(max_latencies=[60_000])
    (result,) = agg.records.values()
    assert result.runs == 3
    assert result.mean_tps == round((1189 + 703 + 946) / 3)
    assert result.std_tps > 0
    # frozen plot grammar prefix + the run count riding behind it
    text = str(result)
    import re

    assert re.search(r"TPS: (\d+) \+/- (\d+)", text)  # plot.py's regex
    assert "over 3 run(s)" in text
    (band,) = agg.bands()
    assert band["nodes"] == 100 and band["runs"] == 3
    assert agg.bands(min_runs=4) == []
    cell = agg.matrix()[(0, 512)]["cells"][(100, 1600)]
    assert cell["runs"] == 3
    # single runs stay point estimates, honestly labelled
    assert Result(100, 200).runs == 1


def test_parser_mines_golden_logs():
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    # Both batches committed, 15360 B each at 512 B/tx = 60 tx.
    assert len(parser.commits) == 2
    assert len(parser.proposals) == 2
    assert sum(parser.sizes.values()) == 2 * 15360
    # Consensus latency: commits at +300ms and +450ms after proposals.
    lat = parser._consensus_latency()
    assert 0.3 < lat < 0.5
    # e2e latency: sample 0 sent 14:54:56.577, its batch committed .000 ->
    # 423ms; sample 1: .627 -> 57.200 = 573ms; mean ~498ms.
    e2e = parser._end_to_end_latency()
    assert 0.4 < e2e < 0.6
    out = parser.result()
    assert "End-to-end TPS" in out
    assert "Consensus latency" in out


def test_parser_folds_sidecar_stats_into_notes():
    """The verifysched OP_STATS snapshot renders as CONFIG notes — and
    the labelled RESULTS grammar the aggregator parses is untouched."""
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    parser.note_sidecar_stats({
        "launches": 42,
        "launches_by_class": {"latency": 40, "bulk": 2},
        "paths": {"rlc_sharded": 30, "ladder_sharded": 10,
                  "rlc_bisect": 2},
        "queue_wait": {"latency": {"n": 40, "p50_ms": 0.4, "p99_ms": 2.1},
                       "bulk": {"n": 2, "p50_ms": 9.0, "p99_ms": 9.5}},
        "bulk_fill_sigs": 128,
        "pad_waste_sigs": 300,
        "queue_full": {"bulk": 3},
        "mesh": {"sharded_launches": 40,
                 "shard_buckets": {"2": 30, "4": 10}},
        "scan": {"launches": 3, "sigs": 42_000,
                 "chunk_hist": {"4": 1, "16": 2},
                 "slices_avoided": 38},
        "pipeline": {"pack_ms": 120.5, "pack_hidden_ms": 90.4,
                     "overlap_ratio": 0.75},
        "compile": {"kernel": "abcd1234", "hits": 11, "misses": 0,
                    "warm_boot": True, "warmup_wall_s": 3.5},
    })
    out = parser.result()
    assert "Sidecar launches: 42 (latency 40, bulk 2)" in out
    assert ("Sidecar compile cache: 11 hit(s), 0 miss(es) — warm boot, "
            "warmup 3.5 s (kernel abcd1234)") in out
    assert "rlc_sharded=30" in out and "rlc_bisect=2" in out
    assert "latency p50 0.4 ms / p99 2.1 ms" in out
    assert "Sidecar pad fill: 128 sigs (waste 300)" in out
    assert "Sidecar mesh launches: 40 (per-shard buckets 2x30, 4x10)" \
        in out
    assert ("Sidecar whole-backlog scans: 3 (42,000 sigs, chunks 4x1, "
            "16x2), 38 slice(s) avoided") in out
    assert "Sidecar pack overlap: 75% of 120.5 ms packing hidden" in out
    assert "Sidecar queue-full sheds: bulk=3" in out
    # labelled grammar intact
    assert "End-to-end TPS" in out and "Consensus latency" in out
    # an idle / absent snapshot adds nothing
    quiet = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    quiet.note_sidecar_stats({})
    quiet.note_sidecar_stats({"launches": 0})
    assert quiet.notes == []
    # hostile value types (version-skewed sidecar, truncated writer):
    # no exception, no partial note block
    quiet.note_sidecar_stats({"launches": 1, "paths": {"rlc": None}})
    quiet.note_sidecar_stats({"launches": "what", "queue_wait": 3})
    assert quiet.notes == []


def test_parser_process_reads_sidecar_stats_file(tmp_path):
    import json

    (tmp_path / "client-0.log").write_text(GOLDEN_CLIENT)
    (tmp_path / "node-0.log").write_text(GOLDEN_NODE)
    (tmp_path / "sidecar-stats.json").write_text(json.dumps({
        "launches": 7, "launches_by_class": {"latency": 7},
        "bulk_fill_sigs": 0, "pad_waste_sigs": 11}))
    parser = LogParser.process(str(tmp_path), faults=0)
    assert any("Sidecar launches: 7" in n for n in parser.notes)
    # garbage file: telemetry is best-effort, parsing must survive
    (tmp_path / "sidecar-stats.json").write_text("{not json")
    parser = LogParser.process(str(tmp_path), faults=0)
    assert parser.notes == []


def test_parser_rejects_client_error():
    # The two fatal shapes the C++ client can emit.
    bad = GOLDEN_CLIENT + \
        "[2026-07-29T14:55:00.000Z ERROR client] something exploded\n"
    with pytest.raises(ParseError):
        LogParser([bad], [GOLDEN_NODE], faults=0)
    bad = GOLDEN_CLIENT + \
        "[2026-07-29T14:55:00.000Z WARN client] Failed to send transaction\n"
    with pytest.raises(ParseError):
        LogParser([bad], [GOLDEN_NODE], faults=0)


def test_parser_rejects_node_error():
    bad = GOLDEN_NODE + \
        "[2026-07-29T14:55:00.000Z ERROR node::main] uncaught exception\n"
    with pytest.raises(ParseError):
        LogParser([GOLDEN_CLIENT], [bad], faults=0)


def test_parser_real_logs_match_grammar(tmp_path):
    """End-to-end grammar lock: logs produced by the actual C++ binaries
    (committed fixtures from a real 4-node run) must parse."""
    import pathlib

    fixture = pathlib.Path(__file__).parent / "golden_logs"
    if not fixture.exists():
        pytest.skip("golden log fixtures not generated yet")
    parser = LogParser.process(str(fixture), faults=0)
    assert parser.commits, "no commits mined from real logs"
    assert parser._end_to_end_latency() > 0


def test_local_committee_layout(tmp_path):
    names = ["a=", "b=", "c=", "d="]
    committee = LocalCommittee(names, 9000)
    f = tmp_path / "committee.json"
    committee.print(str(f))
    data = json.loads(f.read_text())
    assert set(data) == {"consensus", "mempool"}
    cons = data["consensus"]["authorities"]
    memp = data["mempool"]["authorities"]
    assert cons["a="]["address"] == "127.0.0.1:9000"
    assert memp["a="]["transactions_address"] == "127.0.0.1:9004"
    assert memp["a="]["mempool_address"] == "127.0.0.1:9008"
    assert all(cons[n]["stake"] == 1 for n in names)


def test_node_parameters_roundtrip(tmp_path):
    params = NodeParameters.default(tpu_sidecar="127.0.0.1:7100")
    f = tmp_path / "parameters.json"
    params.print(str(f))
    data = json.loads(f.read_text())
    assert data["consensus"]["timeout_delay"] == 5000
    assert data["mempool"]["batch_size"] == 500_000
    assert data["tpu_sidecar"] == "127.0.0.1:7100"
    # malformed params rejected
    with pytest.raises(ConfigError):
        NodeParameters({"consensus": {}})


def test_bench_parameters_validation():
    ok = BenchParameters({
        "faults": 1, "nodes": 4, "rate": [10_000], "tx_size": 512,
        "duration": 20,
    })
    assert ok.nodes == [4] and ok.rate == [10_000]
    with pytest.raises(ConfigError):
        BenchParameters({
            "faults": 4, "nodes": 4, "rate": 1000, "tx_size": 512,
            "duration": 20,
        })


@pytest.mark.parametrize("depth,ok", [(2, True), (3, True), (4, True),
                                      (8, True), (1, False), (9, False),
                                      ("3", False)])
def test_node_parameters_chain_depth(depth, ok):
    """chain_depth is an int in [2, 8] — the range harness/config.py and
    native/src/consensus/config.hpp both accept (absent = the 2-chain
    default); anything else is rejected before a node boots."""
    from hotstuff_tpu.harness.config import ConfigError

    data = NodeParameters.default().json
    data["consensus"]["chain_depth"] = depth
    if ok:
        NodeParameters(dict(data))
    else:
        with pytest.raises(ConfigError):
            NodeParameters(dict(data))


# ---------------------------------------------------------------------------
# Sidecar lifecycle (a failed readiness wait once leaked a hung sidecar
# process; a device sidecar that does not come up must fail the run)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(
    os.environ.get("HOTSTUFF_TPU_NO_PKILL_TESTS") == "1",
    reason="machine-wide pkill sweep; opt out on shared machines running "
           "a real bench/sidecar")
def test_kill_nodes_sweeps_orphaned_sidecar():
    """_kill_nodes must reap sidecar processes it no longer tracks (a
    wedged device leaves them hung past their process group's SIGTERM)."""
    import subprocess
    import sys
    import time

    from hotstuff_tpu.harness.local import LocalBench

    proc = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(300)",
         "hotstuff_tpu.sidecar"])
    try:
        bench = LocalBench.__new__(LocalBench)
        bench._procs = []
        bench._kill_nodes()
        deadline = time.time() + 5
        while proc.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        assert proc.poll() is not None, "orphaned sidecar survived the sweep"
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.fixture
def no_sidecar_listening(tmp_path, monkeypatch):
    """Every readiness probe is refused, and the sidecar logs live under
    tmp_path."""
    from hotstuff_tpu.harness.utils import PathMaker
    from hotstuff_tpu.sidecar.client import SidecarClient

    monkeypatch.setattr(PathMaker, "logs_path",
                        staticmethod(lambda: str(tmp_path)))

    def refuse(self):
        raise ConnectionRefusedError("nothing listening")

    monkeypatch.setattr(SidecarClient, "__enter__", refuse)


@pytest.mark.parametrize("fleet", [0, 2])
def test_sidecar_boot_never_degrades_to_host_crypto(no_sidecar_listening,
                                                    fleet):
    """A device sidecar (or fleet member) that never becomes ready is a
    BenchError carrying the tail of its log — never a silent reboot
    with --host-crypto, which would hand back a run that did not
    measure the device path."""
    from hotstuff_tpu.harness.local import LocalBench
    from hotstuff_tpu.harness.utils import BenchError

    bench = LocalBench.__new__(LocalBench)
    bench.scheme = "ed25519"
    bench.nodes = 4
    bench.rate = 1000
    bench.fault_plan = None
    bench.sidecar_fleet = fleet
    bench._sidecar_deadline_s = lambda host_crypto: 0
    booted = []

    def background_run(cmd, log, append=False):
        booted.append(cmd)
        with open(log, "w") as f:
            f.write("boot line\nRuntimeError: no chip for this member\n")

    bench._background_run = background_run
    with pytest.raises(BenchError) as err:
        bench._boot_sidecars(host_crypto=False)
    assert "no chip for this member" in err.value.message
    assert len(booted) == max(1, fleet)
    assert not any("--host-crypto" in cmd for cmd in booted)


def test_sidecar_wait_stops_when_the_process_exited(no_sidecar_listening):
    """An exited sidecar (no chip to bind, a warmup verdict of false)
    ends the readiness wait at once, with its exit code and log tail —
    not after the whole compile budget."""
    import subprocess
    import sys

    from hotstuff_tpu.harness.local import LocalBench
    from hotstuff_tpu.harness.utils import BenchError, PathMaker

    with open(PathMaker.sidecar_log_file(), "w") as f:
        f.write("RuntimeError: warmup verify returned false\n")
    proc = subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"])
    proc.wait(timeout=30)
    bench = LocalBench.__new__(LocalBench)
    bench._sidecar_procs = {0: proc}
    with pytest.raises(BenchError) as err:
        bench._wait_sidecar_ready(deadline_s=3600)
    assert "exited with code 3" in err.value.message
    assert "warmup verify returned false" in err.value.message


# ---------------------------------------------------------------------------
# grafttrace: torn-line tolerance, critical-path notes, metrics series,
# sampled-stats fallback (PR 7)
# ---------------------------------------------------------------------------


def test_parser_tolerates_torn_log_lines():
    """Torn/interleaved lines from concurrent writers are skipped and
    counted — including a fragment that would otherwise fake a fatal
    ' ERROR ' hit — and never raise in non-strict mode."""
    torn = (GOLDEN_NODE
            + "mpool::batch_maker] torn tail with ERROR inside\n"
            + "[2026-07-29T14:5[2026-07-29T14:54:58.000Z INFO x] mix\n")
    parser = LogParser([GOLDEN_CLIENT], [torn], faults=0)
    assert parser.malformed_lines == 2
    assert any("skipped 2 torn/malformed log line(s)" in n
               for n in parser.notes)
    assert len(parser.commits) == 2  # metrics unaffected
    with pytest.raises(ParseError):
        LogParser([GOLDEN_CLIENT], [torn], faults=0, strict_lines=True)


def test_parser_keeps_crash_evidence_through_sanitizer():
    """libstdc++ prints 'terminate called ...' with NO log prefix; the
    torn-line sanitizer must keep such lines so a crashed replica still
    raises 'Node(s) failed' instead of parsing as a clean run."""
    crashed = (GOLDEN_NODE
               + "terminate called after throwing an instance of "
               "'std::runtime_error'\n"
               + "  what():  store wedged\n")
    with pytest.raises(ParseError, match="Node"):
        LogParser([GOLDEN_CLIENT], [crashed], faults=0)


def test_parser_notes_commit_critical_path():
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    parser.note_trace({
        "blocks": 5, "complete": 4,
        "segments": {
            "proposal->verify_submit": {"n": 4, "p50_ms": 1.5,
                                        "p99_ms": 3.0},
            "verify_submit->verify_reply": {"n": 4, "p50_ms": 22.0,
                                            "p99_ms": 41.0},
            "verify_reply->commit": {"n": 4, "p50_ms": 9.0,
                                     "p99_ms": 12.0},
            "proposal->commit": {"n": 5, "p50_ms": 50.0, "p99_ms": 80.0},
        },
        "sidecar": {"queue": {"n": 9, "p50_ms": 0.8, "p99_ms": 2.0},
                    "device": {"n": 9, "p50_ms": 17.0, "p99_ms": 25.0},
                    "reply": {"n": 9, "p50_ms": 0.1, "p99_ms": 0.2}},
    })
    out = parser.result()
    assert "Commit critical path (5 block(s), 4 fully traced)" in out
    assert "verify_submit->verify_reply p50 22 ms / p99 41 ms" in out
    assert "proposal->commit p50 50 ms / p99 80 ms" in out
    assert "Sidecar stage latency: device p50 17 ms / p99 25 ms; " \
           "queue p50 0.8 ms / p99 2 ms" in out
    assert parser.trace is not None
    # labelled RESULTS grammar untouched
    assert "End-to-end TPS" in out
    # hostile summaries add nothing and never raise
    quiet = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    quiet.note_trace({"segments": {"proposal->commit": {"n": 1}}})
    quiet.note_trace("garbage")
    quiet.note_trace({"segments": None})
    assert quiet.notes == [] and quiet.trace is None


def test_parser_process_builds_trace_artifact(tmp_path):
    """End-to-end: TRACE lines in a node log -> trace.json artifact +
    'Commit critical path' note out of LogParser.process."""
    trace_lines = "\n".join([
        "[2026-07-29T14:54:56.800Z INFO consensus::core] TRACE "
        "stage=proposal block=xyz= round=2",
        "[2026-07-29T14:54:56.820Z INFO consensus::core] TRACE "
        "stage=verify_submit block=xyz= round=2",
        "[2026-07-29T14:54:56.860Z INFO consensus::core] TRACE "
        "stage=verify_reply block=xyz= round=2",
        "[2026-07-29T14:54:56.900Z INFO consensus::core] TRACE "
        "stage=commit block=xyz= round=2",
    ])
    (tmp_path / "client-0.log").write_text(GOLDEN_CLIENT)
    (tmp_path / "node-0.log").write_text(GOLDEN_NODE + trace_lines + "\n")
    parser = LogParser.process(str(tmp_path), faults=0)
    assert parser.trace is not None
    assert parser.trace["segments"]["proposal->commit"]["n"] == 1
    assert any("Commit critical path" in n for n in parser.notes)
    with open(tmp_path / "trace.json") as f:
        chrome = json.load(f)
    assert any(e.get("ph") == "X" for e in chrome["traceEvents"])


def test_parser_notes_metrics_and_chaos_recovery_curve():
    """The sampled time series lands as a CONFIG note, and under a
    chaos plan each event's verdict cites the telemetry recovery curve
    (resumed N ms after the event, M failed ticks) instead of only the
    first post-fault commit scalar."""
    wall = LogParser._to_posix("2026-07-29T14:54:56.800Z")
    events = [{"t": 5.0, "target": "sidecar", "action": "kill",
               "wall": wall, "ok": True}]
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0,
                       chaos_events=events, strict_chaos=True)
    samples = [
        {"t": wall - 1.0, "ok": True, "stats": {"launches": 3}},
        {"t": wall + 0.5, "ok": False, "error": "down"},
        {"t": wall + 1.5, "ok": True, "stats": {"launches": 4}},
    ]
    parser.note_metrics(samples, malformed=1)
    out = parser.result()
    assert "Sidecar metrics: 3 sample(s) (2 ok) over 2.5 s" in out
    assert "1 torn line(s) skipped" in out
    assert "telemetry resumed 1500 ms after event (1 failed tick(s))" \
        in out
    assert parser.chaos["events"][0]["telemetry"]["resumed"] is True
    # without samples: nothing added
    quiet = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    quiet.note_metrics([])
    assert quiet.notes == [] and quiet.metrics is None


def test_parser_notes_sampled_stats_fallback():
    """A sidecar-stats.json recovered from the periodic sampler (the
    sidecar was chaos-killed before teardown) says so in the notes."""
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    parser.note_sidecar_stats({
        "launches": 7, "launches_by_class": {"latency": 7},
        "bulk_fill_sigs": 0, "pad_waste_sigs": 0,
        "_from_sample_at": 1753800000.0})
    out = parser.result()
    assert "Sidecar stats from last sample @ 2025-07-29T" in out
    assert "(sidecar unreachable at teardown)" in out
    assert "Sidecar launches: 7" in out


def test_fetch_sidecar_stats_falls_back_to_last_sample(tmp_path,
                                                       monkeypatch):
    """LocalBench._fetch_sidecar_stats: when the live OP_STATS fetch
    fails (dead sidecar), the sampler's last good snapshot is persisted
    with the _from_sample_at marker instead of dropping the section."""
    import hotstuff_tpu.harness.local as local_mod
    from hotstuff_tpu.harness.local import LocalBench
    from hotstuff_tpu.harness.utils import PathMaker

    monkeypatch.chdir(tmp_path)
    (tmp_path / "logs").mkdir()
    bench = LocalBench.__new__(LocalBench)
    bench.SIDECAR_PORT = 1  # nothing listens: the fetch must fail

    class _Sampler:
        last = (1753800123.0, {"launches": 5, "sigs_launched": 640})

    bench._sampler = _Sampler()
    bench._fetch_sidecar_stats()
    with open(PathMaker.sidecar_stats_file()) as f:
        stats = json.load(f)
    assert stats["launches"] == 5
    assert stats["_from_sample_at"] == 1753800123.0

    # No sampler snapshot at all: nothing written, no exception.
    (tmp_path / "logs" / "sidecar-stats.json").unlink()
    bench._sampler = None
    bench._fetch_sidecar_stats()
    assert not (tmp_path / "logs" / "sidecar-stats.json").exists()


def test_op_stats_wire_carries_scan_section():
    """What a client reads with OP_STATS after one latency-class and one
    bulk-class request went through the real scheduler of a host-mode
    engine: the snapshot survives protocol.encode_stats_reply ->
    decode_reply_raw -> decode_stats_body, and the graftscale ``scan``
    section and the shape registry's mesh fields ride it (zeros off a
    mesh, but the keys are what LogParser.note_sidecar_stats reads)."""
    import threading

    import numpy as np

    from hotstuff_tpu.crypto import ref_ed25519 as ref
    from hotstuff_tpu.sidecar import protocol as proto
    from hotstuff_tpu.sidecar import sched as vsched
    from hotstuff_tpu.sidecar.service import VerifyEngine

    rng = np.random.default_rng(23)
    msgs, pks, sigs = [], [], []
    for _ in range(6):
        sk, msg = rng.bytes(32), rng.bytes(32)
        msgs.append(msg)
        pks.append(ref.generate_keypair(sk)[1])
        sigs.append(ref.sign(sk, msg))
    engine = VerifyEngine(use_host=True)
    try:
        done = []
        cond = threading.Condition()

        def reply(mask):
            with cond:
                done.append(mask)
                cond.notify()

        engine.submit(proto.VerifyRequest(1, msgs[:4], pks[:4], sigs[:4]),
                      reply, cls=vsched.LATENCY)
        engine.submit(proto.VerifyRequest(2, msgs[4:], pks[4:], sigs[4:]),
                      reply, cls=vsched.BULK)
        with cond:
            assert cond.wait_for(lambda: len(done) == 2, timeout=60.0)
        frame = proto.encode_stats_reply(7, engine.stats_snapshot())
    finally:
        engine.stop()
    opcode, rid, body = proto.decode_reply_raw(frame[4:])
    assert (opcode, rid) == (proto.OP_STATS, 7)
    out = proto.decode_stats_body(body)
    assert out["scan"] == {"launches": 0, "sigs": 0, "chunk_hist": {},
                           "slices_avoided": 0}
    assert out["shapes"]["mesh_chunks"] == []
    assert out["shapes"]["scan_rows"] == 0
