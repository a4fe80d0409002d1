"""grafttrace tests: span writer/parser, clock-offset alignment,
per-block critical-path stitching (including dropped/partial spans),
Chrome trace JSON schema round trip, the live metrics sampler on a
virtual clock across a sidecar kill/restart, and the directory-level
trace build the harness + LogParser drive.

graftscope additions: the protocol-v5 context-tag round trip (legacy
zero-tag frames included), the per-block node<->sidecar span join
(partial chains degrade join_rate, never the trace), and the C++
node's METRICS line reader + per-replica divergence.

All CPU-only and fast (no jax, no device, no sleeps beyond thread
joins) — the suite runs in tier-1.
"""

import json
import threading

import pytest

from hotstuff_tpu.obs import (
    MetricsSampler,
    Tracer,
    build_run_trace,
    chain_spans,
    chrome_trace,
    clock_offset,
    commit_rate_divergence,
    critical_path,
    join_blocks,
    merge_node_series,
    parse_node_metrics,
    parse_node_trace,
    parse_spans,
    persistent_fetch,
    read_samples,
    recovery_curve,
    split_samples,
    stitch_blocks,
    write_run_trace,
)
from hotstuff_tpu.obs.trace import (
    DEVICE_SEGMENT,
    apply_offset,
    device_subsegment,
    estimate_offset,
    probe_host_offset,
    sidecar_breakdown,
)


def _trace_line(sec, stage, block="aaa=", rnd=2, ms="000"):
    return (f"[2026-08-03T12:00:{sec:02d}.{ms}Z INFO consensus::core] "
            f"TRACE stage={stage} block={block} round={rnd}")


def _sc(stage, t, dur_ms, **tags):
    """A hand-written sidecar span ending at ``t``."""
    return {"stage": stage, "t0": t - dur_ms / 1e3, "t": t,
            "dur_ms": dur_ms, **tags}


# ---------------------------------------------------------------------------
# span writer / parser
# ---------------------------------------------------------------------------


def test_tracer_writes_jsonl_spans(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    now = [100.0]
    tracer = Tracer(path, clock=lambda: now[0])
    tok = tracer.begin_span("pack", rid=7, cls="latency")
    now[0] += 0.005
    tracer.end_span(tok)
    tracer.record("device", now[0] - 0.0185, rid=7)
    with tracer.span("bls", rid=9):
        now[0] += 0.002
    tracer.close()
    spans, malformed = parse_spans((tmp_path / "spans.jsonl").read_text())
    assert malformed == 0
    assert [s["stage"] for s in spans] == ["pack", "device", "bls"]
    assert spans[0]["rid"] == 7 and spans[0]["cls"] == "latency"
    assert spans[0]["dur_ms"] == pytest.approx(5.0)
    assert spans[1]["dur_ms"] == pytest.approx(18.5)
    assert spans[2]["dur_ms"] == pytest.approx(2.0)
    # One meaning however the span was written: ``t`` is the END,
    # ``t0`` the start, on the one injected clock.
    assert (spans[0]["t0"], spans[0]["t"]) == pytest.approx((100.0, 100.005))
    assert (spans[1]["t0"], spans[1]["t"]) == \
        pytest.approx((100.005 - 0.0185, 100.005))
    assert (spans[2]["t0"], spans[2]["t"]) == pytest.approx((100.005, 100.007))
    assert len({s["id"] for s in spans}) == 3


def test_disabled_tracer_is_noop(tmp_path):
    tracer = Tracer.disabled()
    tok = tracer.begin_span("pack")
    tracer.end_span(tok)
    tracer.record("device", 0.0)
    with tracer.span("x"):
        pass
    assert not tracer.enabled and tracer.dropped == 0


def test_tracer_survives_dead_sink(tmp_path):
    # A directory as the sink path: open() fails -> tracer disables
    # itself and the caller never sees an exception.
    tracer = Tracer(str(tmp_path))
    tracer.record("pack", tracer.now())
    tracer.close()  # the write-out is where a buffered sink finds out
    assert not tracer.enabled and tracer.dropped == 1
    tracer.record("pack", tracer.now())  # still silent


def test_parse_spans_skips_torn_lines():
    text = (json.dumps(_sc("pack", 1.0, 2.0))
            + "\n{\"stage\": \"dev"              # torn mid-write
            + "\nnot json at all\n"
            + json.dumps({"no_stage": True, "t0": 2.0, "t": 2.0}) + "\n"
            + json.dumps({"stage": "device", "t0": 1.0, "t": "bad"}) + "\n"
            + json.dumps({"stage": "device", "t": 2.5}) + "\n"  # no start
            + json.dumps(_sc("device", 3.0, 1.0))
            + "\n")
    spans, malformed = parse_spans(text)
    assert [s["stage"] for s in spans] == ["pack", "device"]
    assert malformed == 5


# ---------------------------------------------------------------------------
# node TRACE parsing + clock alignment
# ---------------------------------------------------------------------------


def test_parse_node_trace_mines_trace_lines():
    log = "\n".join([
        "[2026-08-03T12:00:01.000Z INFO node::node] Node abc= booted",
        _trace_line(1, "proposal"),
        _trace_line(1, "verify_submit", ms="010"),
        _trace_line(1, "bogus_stage"),          # unknown stage: skipped
        _trace_line(2, "commit"),
    ])
    spans = parse_node_trace(log, host="node-0.log")
    assert [s["stage"] for s in spans] == \
        ["proposal", "verify_submit", "commit"]
    assert all(s["block"] == "aaa=" and s["round"] == 2 for s in spans)
    assert spans[1]["t"] - spans[0]["t"] == pytest.approx(0.010)


def test_clock_offset_two_fake_hosts_with_known_skew():
    """The satellite test: two hosts, one running 2.5 s ahead; the
    RTT-midpoint estimator recovers the skew and alignment makes the
    merged trace causally consistent."""
    skew = 2.5
    rtt = 0.010
    probes = [(t, t + rtt / 2 + skew, t + rtt) for t in (10.0, 11.0, 12.0)]
    offset = estimate_offset(probes)
    assert offset == pytest.approx(skew, abs=1e-9)

    # Host A (reference) sees proposal at 100.0; host B's stamps carry
    # the skew.  After alignment the earliest-wins merge must order the
    # stages causally: B's commit observation lands AFTER A's proposal.
    spans_a = [{"host": "a", "stage": "proposal", "t": 100.0,
                "block": "x=", "round": 4}]
    spans_b = [{"host": "b", "stage": "commit", "t": 100.2 + skew,
                "block": "x=", "round": 4}]
    aligned = spans_a + apply_offset(spans_b, offset)
    traces = stitch_blocks(aligned)
    stages = traces[("x=", 4)]
    assert stages["commit"] - stages["proposal"] == pytest.approx(0.2)


def test_estimate_offset_median_discards_outlier():
    skew = 1.0
    probes = [(0.0, 0.005 + skew, 0.01),
              (1.0, 1.005 + skew, 1.01),
              (2.0, 2.9 + skew, 3.8)]  # one delayed round trip
    assert estimate_offset(probes) == pytest.approx(skew, abs=1e-6)
    assert estimate_offset([]) == 0.0
    assert clock_offset(0.0, 5.05, 0.1) == pytest.approx(5.0)


def test_probe_host_offset_through_fake_transport():
    skew = 0.75
    local = [50.0]

    def clock():
        local[0] += 0.002  # 4 ms RTT (clock read before and after)
        return local[0]

    def run_fn(host, command):
        assert command == "date +%s.%N"
        return f"{local[0] + 0.002 + skew:.9f}\n"

    off = probe_host_offset(run_fn, "host-b", clock, samples=3)
    assert off == pytest.approx(skew, abs=1e-3)

    def broken_run(host, command):
        raise OSError("unreachable")

    assert probe_host_offset(broken_run, "host-b", clock) == 0.0


# ---------------------------------------------------------------------------
# stitching + critical path (incl. dropped/partial spans)
# ---------------------------------------------------------------------------


def _full_block(block, rnd, t0, host="node-0.log"):
    return [
        {"host": host, "stage": "proposal", "t": t0, "block": block,
         "round": rnd},
        {"host": host, "stage": "verify_submit", "t": t0 + 0.010,
         "block": block, "round": rnd},
        {"host": host, "stage": "verify_reply", "t": t0 + 0.030,
         "block": block, "round": rnd},
        {"host": host, "stage": "commit", "t": t0 + 0.050,
         "block": block, "round": rnd},
    ]


def test_critical_path_stitching_with_dropped_span():
    spans = _full_block("a=", 2, 100.0)
    # Partial trace: the verify_reply span was dropped (chaos-killed
    # replica mid-write) — the block still counts for the segments whose
    # endpoints exist, and for the total.
    partial = [s for s in _full_block("b=", 3, 101.0)
               if s["stage"] != "verify_reply"]
    traces = stitch_blocks(spans + partial)
    out = critical_path(traces)
    assert out["blocks"] == 2 and out["complete"] == 1
    segs = out["segments"]
    assert segs["proposal->verify_submit"]["n"] == 2
    assert segs["verify_submit->verify_reply"]["n"] == 1
    assert segs["verify_reply->commit"]["n"] == 1
    assert segs["proposal->commit"]["n"] == 2
    assert segs["proposal->commit"]["p50_ms"] == pytest.approx(50.0)


def test_stitch_merges_earliest_across_replicas():
    # Two replicas observe the same block; the earliest stamp per stage
    # wins (the committee's critical path, the LogParser convention).
    a = _full_block("a=", 2, 100.0, host="node-0.log")
    b = _full_block("a=", 2, 100.020, host="node-1.log")
    stages = stitch_blocks(a + b)[("a=", 2)]
    assert stages["proposal"] == pytest.approx(100.0)
    assert stages["commit"] == pytest.approx(100.050)


def test_sidecar_breakdown_percentiles():
    spans = [{"stage": "queue", "t": 1.0, "dur_ms": d}
             for d in (1.0, 2.0, 3.0, 100.0)]
    spans.append({"stage": "device", "t": 1.0, "dur_ms": 20.0})
    spans.append({"stage": "reply", "t": 1.0})  # no dur: skipped
    out = sidecar_breakdown(spans)
    assert out["queue"]["n"] == 4
    assert out["queue"]["p99_ms"] == pytest.approx(100.0)
    assert out["device"]["p50_ms"] == pytest.approx(20.0)
    assert "reply" not in out


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------


def test_chrome_trace_schema_roundtrip():
    traces = stitch_blocks(_full_block("a=", 2, 100.0))
    sc = [_sc("device", 100.015, 12.0, rid=3, cls="latency")]
    chrome = chrome_trace(traces, sc)
    decoded = json.loads(json.dumps(chrome))
    assert decoded["displayTimeUnit"] == "ms"
    events = decoded["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert len(xs) == 4 and len(metas) == 2  # 3 segments + 1 sidecar
    for e in xs:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["name"]
    # Timestamps are normalized to the earliest span.
    assert min(e["ts"] for e in xs) == 0
    # The sidecar event carries its tags through args.
    dev = next(e for e in xs if e["name"] == "device")
    assert dev["args"] == {"rid": 3, "cls": "latency"}


def test_build_and_write_run_trace_directory(tmp_path):
    log0 = "\n".join([_trace_line(1, "proposal"),
                      _trace_line(1, "verify_submit", ms="010"),
                      _trace_line(1, "verify_reply", ms="030"),
                      _trace_line(1, "commit", ms="050")])
    # Replica 1 observed the same block 0.2 s "later" on a clock the
    # offsets file says runs 0.2 s ahead: after alignment its stamps
    # coincide with replica 0's, so the breakdown is unchanged.
    log1 = "\n".join([_trace_line(1, "proposal", ms="200"),
                      _trace_line(1, "commit", ms="250")])
    (tmp_path / "node-0.log").write_text(log0 + "\n")
    (tmp_path / "node-1.log").write_text(log1 + "\n")
    (tmp_path / "clock-offsets.json").write_text(
        json.dumps({"node-1.log": 0.2}))
    (tmp_path / "sidecar-spans.jsonl").write_text(
        json.dumps(_sc("pack", 1785751201.0, 3.0))
        + "\ntorn lin")
    summary, chrome = build_run_trace(str(tmp_path))
    assert summary["blocks"] == 1 and summary["complete"] == 1
    assert summary["malformed_spans"] == 1
    assert summary["segments"]["proposal->commit"]["p50_ms"] == \
        pytest.approx(50.0)
    assert summary["sidecar"]["pack"]["p50_ms"] == pytest.approx(3.0)
    assert summary["chrome_events"] == len(chrome["traceEvents"])

    assert write_run_trace(str(tmp_path))["blocks"] == 1
    with open(tmp_path / "trace.json") as f:
        assert json.load(f)["traceEvents"]


def test_write_run_trace_without_spans_writes_nothing(tmp_path):
    (tmp_path / "node-0.log").write_text(
        "[2026-08-03T12:00:01.000Z INFO consensus::core] Committed B2\n")
    assert write_run_trace(str(tmp_path)) is None
    assert not (tmp_path / "trace.json").exists()


# ---------------------------------------------------------------------------
# metrics sampler (virtual clock; sidecar kill/restart)
# ---------------------------------------------------------------------------


class _FlakySidecar:
    """fetch() stand-in: healthy, then dead (kill), then healthy again
    (restart) — the exact sequence a chaos plan scripts."""

    def __init__(self, fail_from, fail_until):
        self.calls = 0
        self.fail_from = fail_from
        self.fail_until = fail_until

    def __call__(self):
        self.calls += 1
        if self.fail_from <= self.calls <= self.fail_until:
            raise ConnectionRefusedError("sidecar down")
        return {"launches": self.calls, "sigs_launched": 100 * self.calls}


def test_sampler_keeps_flowing_across_kill_restart(tmp_path):
    """The satellite test: on a virtual clock, samples keep flowing
    across a sidecar kill/restart — failed ticks are recorded, the last
    good snapshot survives, and the gap is visible in the series."""
    path = str(tmp_path / "metrics.jsonl")
    now = [1000.0]
    fetch = _FlakySidecar(fail_from=3, fail_until=4)
    sampler = MetricsSampler(fetch, path, interval_s=1.0,
                             wall=lambda: now[0])
    for _ in range(6):
        sampler.sample_once()
        now[0] += 1.0
    sampler.stop()
    samples, malformed = read_samples(path)
    assert malformed == 0
    assert [s["ok"] for s in samples] == \
        [True, True, False, False, True, True]
    assert sampler.samples == 6 and sampler.ok_samples == 4
    # The failure ticks carry the error, the good ticks the snapshot.
    assert "sidecar down" in samples[2]["error"]
    assert samples[5]["stats"]["launches"] == 6
    # Last good snapshot survives for the stats-file fallback.
    t_last, snap = sampler.last
    assert t_last == pytest.approx(1005.0)
    assert snap["launches"] == 6


def test_sampler_thread_lifecycle(tmp_path):
    """The real thread path (no virtual clock): ticks flow until stop().
    The injected wait hooks the stop event so the test never sleeps."""
    path = str(tmp_path / "metrics.jsonl")
    ticked = threading.Event()

    def fetch():
        ticked.set()
        return {"launches": 1}

    sampler = MetricsSampler(fetch, path, interval_s=0.01)
    sampler.start()
    assert ticked.wait(5.0)
    sampler.stop()
    samples, _ = read_samples(path)
    assert samples and all(s["ok"] for s in samples)
    assert sampler.last is not None


class _Conn:
    """SidecarClient stand-in for the persistent-fetch contract."""

    def __init__(self, broken=False):
        self.broken = broken
        self.closed = False
        self.stats_calls = 0

    def stats(self):
        self.stats_calls += 1
        if self.broken:
            raise ConnectionResetError("sidecar died mid-call")
        return {"launches": self.stats_calls}

    def close(self):
        self.closed = True


def test_persistent_fetch_reuses_one_connection():
    """The satellite regression: ONE dial serves every healthy tick (the
    1 Hz series stops paying a TCP dial per sample); a call failure
    drops the connection before re-raising, and the NEXT call re-dials."""
    conns = []

    def dial():
        conns.append(_Conn())
        return conns[-1]

    fetch = persistent_fetch(dial)
    assert fetch() == {"launches": 1}
    assert fetch() == {"launches": 2}
    assert len(conns) == 1  # reused, never re-dialed while healthy
    # the live connection dies mid-call: dropped (closed) + re-raised
    conns[0].broken = True
    with pytest.raises(ConnectionResetError):
        fetch()
    assert conns[0].closed
    # the next tick re-dials a fresh connection
    assert fetch() == {"launches": 1}
    assert len(conns) == 2
    # teardown closes the held connection
    fetch.close()
    assert conns[1].closed


def test_persistent_fetch_dead_dial_leaves_no_connection():
    calls = [0]

    def dial():
        calls[0] += 1
        raise ConnectionRefusedError("sidecar down")

    fetch = persistent_fetch(dial)
    for _ in range(2):
        with pytest.raises(ConnectionRefusedError):
            fetch()
    assert calls[0] == 2  # every failed tick re-dials, none leaks
    fetch.close()  # nothing held; must not raise


def test_sampler_gap_semantics_with_persistent_connection(tmp_path):
    """Through the sampler: a mid-run kill is exactly one ok-false tick
    (the dropped connection), the restart tick re-dials and records ok
    again — byte-identical gap semantics to the old dial-per-tick
    sampler — and stop() closes the held connection."""
    conns = []

    def dial():
        conns.append(_Conn())
        return conns[-1]

    path = str(tmp_path / "metrics.jsonl")
    now = [50.0]
    sampler = MetricsSampler(persistent_fetch(dial), path,
                             wall=lambda: now[0])
    sampler.sample_once()
    sampler.sample_once()
    conns[0].broken = True  # the kill
    sampler.sample_once()   # the gap tick
    sampler.sample_once()   # the restart: re-dial, healthy again
    sampler.stop()
    samples, malformed = read_samples(path)
    assert malformed == 0
    assert [s["ok"] for s in samples] == [True, True, False, True]
    assert "sidecar died" in samples[2]["error"]
    assert len(conns) == 2
    assert all(c.closed for c in conns)


def test_read_samples_tolerates_garbage(tmp_path):
    path = tmp_path / "metrics.jsonl"
    path.write_text(json.dumps({"t": 1.0, "ok": True, "stats": {}})
                    + "\n{\"t\": 2.0, \"ok\"\ngarbage\n"
                    + json.dumps({"no_t": True, "ok": True}) + "\n")
    samples, malformed = read_samples(str(path))
    assert len(samples) == 1 and malformed == 3
    assert read_samples(str(tmp_path / "absent.jsonl")) == ([], 0)


def test_recovery_curve_cites_the_gap():
    samples = [
        {"t": 10.0, "ok": True},
        {"t": 11.0, "ok": True},
        {"t": 12.0, "ok": False},   # kill at 11.5
        {"t": 13.0, "ok": False},
        {"t": 14.0, "ok": True},    # restart visible here
    ]
    curve = recovery_curve(samples, 11.5)
    assert curve["resumed"] is True
    assert curve["resume_ms"] == pytest.approx(2500.0)
    assert curve["failed_ticks"] == 2
    assert curve["samples_after"] == 3
    dead = recovery_curve(samples[:4], 11.5)
    assert dead["resumed"] is False and dead["resume_ms"] is None
    assert dead["failed_ticks"] == 2


# ---------------------------------------------------------------------------
# engine integration: the sidecar emits the full stage chain
# ---------------------------------------------------------------------------


class _Served:
    """A SidecarServer over ``engine`` on a loopback port, in a thread:
    ``with _Served(engine) as port`` — the socket path ``serve()`` binds,
    minus the warm-up, with the test's own tracer on the engine."""

    def __init__(self, engine):
        from hotstuff_tpu.sidecar.service import SidecarServer

        self.engine = engine
        self.server = SidecarServer(("127.0.0.1", 0), engine)
        self._thread = threading.Thread(
            target=lambda: self.server.serve_forever(poll_interval=0.05),
            daemon=True)

    def __enter__(self):
        self._thread.start()
        return self.server.server_address[1]

    def __exit__(self, *exc):
        self.server.shutdown()
        self.server.server_close()
        self._thread.join(timeout=30)
        self.engine.stop()
        self.engine._thread.join(timeout=30)
        assert not self._thread.is_alive()
        assert not self.engine._thread.is_alive()


def _await_spans(tracer, stage, n, timeout=30.0):
    """The connection's writer records ``reply``/``request`` after the
    client already holds its reply: wait for them."""
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with tracer._lock:
            if sum(1 for r in tracer._buf if r["stage"] == stage) >= n:
                return
        time.sleep(0.005)
    raise AssertionError(f"fewer than {n} {stage!r} span(s) after {timeout}s")


def test_verify_engine_emits_stage_spans(tmp_path):
    """A host-mode VerifyEngine with a live tracer behind its socket: one
    latency verify must leave the whole request -> decode -> queue ->
    pack -> dispatch -> device -> reply chain in the span file, tagged
    with the rid and class."""
    from hotstuff_tpu.crypto import ref_ed25519 as ref
    from hotstuff_tpu.sidecar.client import SidecarClient
    from hotstuff_tpu.sidecar.service import VerifyEngine

    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msg = b"\x05" * 32
    sig = ref.sign(sk, msg)

    path = str(tmp_path / "spans.jsonl")
    tracer = Tracer(path)
    with _Served(VerifyEngine(use_host=True, tracer=tracer)) as port:
        with SidecarClient(port=port) as client:
            assert client.verify_batch([msg], [pk], [sig]) == [True]
            _await_spans(tracer, "request", 1)
    tracer.close()
    spans, malformed = parse_spans((tmp_path / "spans.jsonl").read_text())
    assert malformed == 0
    stages = [s["stage"] for s in spans]
    for stage in ("request", "decode", "queue", "pack", "dispatch",
                  "device", "reply"):
        assert stage in stages, f"missing {stage} span in {stages}"
    request = next(s for s in spans if s["stage"] == "request")
    assert request["cls"] == "latency" and request["ok"] is True \
        and request["n"] == 1
    queue = next(s for s in spans if s["stage"] == "queue")
    assert queue["rid"] == request["rid"] and queue["dur_ms"] >= 0 \
        and queue["parent"] == request["id"]
    pack = next(s for s in spans if s["stage"] == "pack")
    assert pack["path"] == "host" and pack["uniq"] == 1 \
        and pack["rids"] == [request["rid"]] and pack["lid"] == queue["lid"]


# ---------------------------------------------------------------------------
# one span tree per request and per launch (device-route engine, fake
# device programs, virtual clock, real socket)
# ---------------------------------------------------------------------------


class _FakeDev:
    """What a device program returns, as far as the fetch closures use
    it: ``block_until_ready()`` (counted) and ``np.asarray``."""

    waits = 0

    def __init__(self, value):
        import numpy as np

        self._value = np.asarray(value)

    def block_until_ready(self):
        _FakeDev.waits += 1
        return self

    def __array__(self, dtype=None, copy=None):
        return self._value


def _fake_programs(mp, forged_rows):
    """Stand the two single-chip device programs in ``crypto/eddsa`` on
    the host: a row is valid unless its (A, R, S) bytes are in
    ``forged_rows``.  Nothing compiles; ``jnp.asarray`` stays real."""
    import numpy as np

    from hotstuff_tpu.crypto import eddsa

    def bad(rows):
        rows = np.asarray(rows)
        return np.array([r[:96].tobytes() in forged_rows for r in rows])

    mp.setattr(eddsa.E, "verify_rlc_packed_donated",
               lambda rows, z: _FakeDev(not bad(rows).any()))
    mp.setattr(eddsa.E, "verify_packed_donated",
               lambda rows: _FakeDev(~bad(rows)))


def _votes(n, forged=(), salt=0):
    """n signatures of one key over distinct messages (``salt`` keeps
    two certificates' records apart: the verdict cache is keyed on
    them); the indices in ``forged`` carry another message's signature.
    Returns (msgs, pks, sigs, expected mask, the forged rows' (A, R, S)
    bytes)."""
    from hotstuff_tpu.crypto import ref_ed25519 as ref

    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msgs = [bytes([salt, i]) * 16 for i in range(n)]
    sigs = [ref.sign(sk, m) for m in msgs]
    wrong = ref.sign(sk, b"\xee" * 32)
    for i in forged:
        sigs[i] = wrong
    rows = {pk + sigs[i] for i in forged}
    return msgs, [pk] * n, sigs, [i not in forged for i in range(n)], rows


def _device_route_engine(tracer):
    """A device-mode engine whose registry says bucket 16 is warmed for
    the one-MSM route, under a real launch guard."""
    from hotstuff_tpu.sidecar.guard import LaunchGuard
    from hotstuff_tpu.sidecar.service import VerifyEngine

    guard = LaunchGuard()
    engine = VerifyEngine(use_host=False, tracer=tracer, guard=guard)
    for n in (8, 16):
        engine._shapes.mark_bucket(n)
        engine._shapes.mark_rlc(n)
    return engine, guard


SPAN_STAGES = ("request", "decode", "queue", "pack", "h2d", "dispatch",
               "device", "fetch_wait", "d2h", "bisect", "cache_insert",
               "reply")


@pytest.fixture(scope="module")
def span_tree(tmp_path_factory):
    """One traced round trip over the socket on a virtual clock: a valid
    16-vote certificate, the same one again (verdict cache), one with a
    forged vote (resolved per signature).  Yields (spans, annotations
    opened, replies)."""
    import itertools
    from contextlib import contextmanager

    from hotstuff_tpu.sidecar.client import SidecarClient

    tmp = tmp_path_factory.mktemp("span_tree")
    ticks = itertools.count()
    annotations = []

    @contextmanager
    def annotation(name, **kw):
        annotations.append((name, kw))
        yield

    tracer = Tracer(str(tmp / "spans.jsonl"),
                    clock=lambda: 1000.0 + next(ticks) * 1e-4,
                    annotation=annotation)
    valid = _votes(16)
    forged = _votes(16, forged=(5,), salt=1)
    replies = {}
    with pytest.MonkeyPatch.context() as mp:
        _fake_programs(mp, forged[4])
        engine, guard = _device_route_engine(tracer)
        try:
            with _Served(engine) as port:
                with SidecarClient(port=port) as client:
                    replies["valid"] = client.verify_batch(*valid[:3])
                    replies["cached"] = client.verify_batch(*valid[:3])
                    replies["forged"] = client.verify_batch(*forged[:3])
                    _await_spans(tracer, "request", 3)
        finally:
            guard.close()
    tracer.close()
    spans, malformed = parse_spans((tmp / "spans.jsonl").read_text())
    assert malformed == 0
    assert replies == {"valid": valid[3], "cached": valid[3],
                       "forged": forged[3]}
    return spans, annotations, replies


@pytest.mark.parametrize("stage", SPAN_STAGES)
def test_span_tree_has_every_stage_on_one_clock(span_tree, stage):
    spans, annotations, _ = span_tree
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans) and all(
        isinstance(i, int) for i in by_id)
    mine = [s for s in spans if s["stage"] == stage]
    assert mine, f"no {stage!r} span in {sorted({s['stage'] for s in spans})}"
    devices = [s for s in spans if s["stage"] == "device"]
    for s in mine:
        assert s["t0"] <= s["t"]
        assert s["dur_ms"] == pytest.approx((s["t"] - s["t0"]) * 1e3,
                                            abs=1e-3)
        if stage == "request":
            assert s["parent"] is None and s["ok"] is True \
                and s["cls"] == "latency" and s["n"] == 16
        elif stage in ("decode", "queue", "reply"):
            root = by_id[s["parent"]]
            assert root["stage"] == "request" and root["rid"] == s["rid"]
            assert root["t0"] <= s["t0"] and s["t"] <= root["t"]
        elif stage in ("pack", "dispatch", "device"):
            assert s["parent"] is None and isinstance(s["lid"], int)
        elif stage in ("h2d", "fetch_wait", "d2h", "bisect", "cache_insert"):
            parent = by_id[s["parent"]]
            assert parent["stage"] == "device" and parent["lid"] == s["lid"]
            assert (f"sidecar:{stage}", {"lid": s["lid"]}) in annotations
    if stage == "queue":
        for s in mine:  # the launch it left for, and the pack that took it
            assert [d for d in devices if d["lid"] == s["lid"]]
            pack = next(p for p in spans if p["stage"] == "pack"
                        and p["lid"] == s["lid"])
            assert s["rid"] in pack["rids"]
    if stage == "device":
        # ONE device span a launch, the bisected launch included.
        assert sorted(d["lid"] for d in mine) == [1, 2]
        assert all(d["sigs"] == 16 and d["hop_ms"] >= 0 for d in mine)
    if stage == "dispatch":
        assert all(s["wait_pack_ms"] >= 0 and s["hop_ms"] >= 0
                   and ("sidecar:dispatch", {"lid": s["lid"]}) in annotations
                   for s in mine)
    if stage == "bisect":
        # ONE per-signature program over the 16 rows resolves it: one
        # ``bisect_step`` child, with its own profiler annotation.
        (bisect,) = mine
        assert (bisect["launches"], bisect["bad"], bisect["n"]) == (1, 1, 16)
        (step,) = [s for s in spans if s["stage"] == "bisect_step"]
        assert step["parent"] == bisect["id"] and step["lid"] == bisect["lid"]
        assert (step["n"], step["route"], step["bucket"], step["depth"],
                step["ok"]) == (16, "per_sig", 16, 0, False)
        assert bisect["t0"] <= step["t0"] and step["t"] <= bisect["t"]
        assert ("sidecar:bisect_step", {"lid": step["lid"]}) in annotations
    if stage == "cache_insert":
        # ONE a launch, after its d2h, inside its device span; the
        # request answered from the cache writes none.
        assert sorted(s["lid"] for s in mine) == [1, 2]
        assert all(s["n"] == 16 and s["evicted"] == 0 for s in mine)
        for s in mine:
            d2h = max(x["t"] for x in spans if x["stage"] == "d2h"
                      and x["lid"] == s["lid"])
            assert d2h <= s["t0"] and s["t"] <= by_id[s["parent"]]["t"]
    if stage in ("h2d", "d2h", "decode", "reply"):
        assert all(s["bytes"] > 0 for s in mine)
    if stage == "reply":
        assert all(s["outbox_ms"] >= 0 for s in mine)
    if stage == "request":
        # The verdict-cache answer: decode and reply children only.
        (cached,) = [s for s in mine if s.get("cached")]
        kids = sorted(s["stage"] for s in spans
                      if s.get("parent") == cached["id"])
        assert kids == ["decode", "reply"]


@pytest.mark.parametrize("forged", [(), (3,)], ids=["valid", "bisected"])
def test_disabled_tracer_reads_no_clock_and_buffers_nothing(forged):
    """trace_path=None: a full verify round trip over the socket reads
    the tracer's clock zero times, buffers nothing and never calls
    block_until_ready; the guard's hop counters still count."""
    from hotstuff_tpu.sidecar.client import SidecarClient

    reads = []
    tracer = Tracer(None, clock=lambda: reads.append(1) or 0.0)
    msgs, pks, sigs, expect, rows = _votes(16, forged=forged)
    _FakeDev.waits = 0
    with pytest.MonkeyPatch.context() as mp:
        _fake_programs(mp, rows)
        engine, guard = _device_route_engine(tracer)
        try:
            with _Served(engine) as port:
                with SidecarClient(port=port) as client:
                    assert client.verify_batch(msgs, pks, sigs) == expect
                    snap = client.stats()
        finally:
            guard.close()
    assert reads == [] and tracer._buf == [] and _FakeDev.waits == 0
    assert snap["paths"]["rlc"] == 1
    assert snap["paths"].get("rlc_bisect", 0) == (1 if forged else 0)
    assert snap["guard"]["calls"] >= 2 and snap["guard"]["hop_s_total"] >= 0


@pytest.mark.parametrize("how", ["close", "bound", "dead", "busy"])
def test_sink_buffers_until_close_or_bound(tmp_path, how):
    """Nothing reaches the file before close() or the buffer bound,
    everything after; a dead sink disables the tracer at its first
    write-out and the caller never sees an exception; a call that finds
    a write-out under way appends and returns."""
    if how == "dead":
        tracer = Tracer(str(tmp_path))  # a directory: open() fails
        for _ in range(3):
            tracer.record("pack", tracer.now())
        assert tracer.enabled and tracer.dropped == 0
        tracer.close()
        assert not tracer.enabled and tracer.dropped == 3
        return
    path = tmp_path / "spans.jsonl"
    tracer = Tracer(str(path))
    tracer.BUFFER_SPANS = 4
    if how == "busy":
        # Another thread is mid write-out (it holds the I/O lock, not the
        # tracer's): span sites neither wait for it nor write.
        with tracer._io_lock:
            for i in range(6):
                tracer.record("pack", tracer.now(), i=i)
            assert not path.exists() and len(tracer._buf) == 6
        tracer.record("pack", tracer.now(), i=6)  # the next call writes
        assert len(path.read_text().splitlines()) == 7
        tracer.close()
        spans, _ = parse_spans(path.read_text())
        assert [s["i"] for s in spans] == list(range(7))
        return
    for i in range(3):
        tracer.record("pack", tracer.now(), i=i)
    assert not path.exists()
    if how == "bound":
        tracer.record("pack", tracer.now(), i=3)  # the call that fills it
        assert len(path.read_text().splitlines()) == 4
        tracer.record("pack", tracer.now(), i=4)
        assert len(path.read_text().splitlines()) == 4
    tracer.close()
    spans, malformed = parse_spans(path.read_text())
    assert malformed == 0
    assert [s["i"] for s in spans] == list(range(5 if how == "bound" else 3))
    tracer.record("pack", 0.0)  # closed: a silent no-op
    assert tracer._buf == []


@pytest.mark.parametrize("thunk_s", [0.0, 0.02])
def test_guard_call_reports_its_own_hop(thunk_s):
    import time

    from hotstuff_tpu.sidecar.guard import LaunchGuard

    guard = LaunchGuard()
    try:
        t0 = time.monotonic()
        assert guard.call("launch:8", lambda: time.sleep(thunk_s) or 7) == 7
        wall = time.monotonic() - t0
        assert 0.0 <= guard.last_hop_s <= wall - thunk_s + 1e-3
        snap = guard.snapshot()
        assert snap["calls"] == 1
        assert snap["hop_s_total"] == pytest.approx(guard.last_hop_s,
                                                    abs=1e-6)
    finally:
        guard.close()


@pytest.mark.parametrize("phase", ["warming", "in_service"])
def test_compile_tracker_attributes_monitoring_events(tmp_path, phase):
    from hotstuff_tpu.utils.xla_cache import CompileTracker

    listeners = []
    tracker = CompileTracker(cache_dir=str(tmp_path),
                             manifest_path=str(tmp_path / "m.json"),
                             kernel="k", register=listeners.append)
    (emit,) = listeners
    trace_ev, mlir_ev = CompileTracker.LOWER_EVENTS
    backend_ev = CompileTracker.BACKEND_EVENT

    def one_shape():
        emit(trace_ev, 1.5, fun_name="verify_rlc_packed")
        emit(mlir_ev, 0.5)
        emit(backend_ev, 2.0)
        emit("/jax/compilation_cache/cache_retrieval_time_sec", 9.0)

    tracker.warm("rlc:8", one_shape)
    emit(backend_ev, 0.25)           # before finish(), outside any shape
    tracker.warm("rlc:16", lambda: emit(backend_ev, 1.0))
    if phase == "warming":
        snap = tracker.snapshot()
        assert snap["split"] == {"rlc:8": [2.0, 2.0], "rlc:16": [0.0, 1.0]}
        assert snap["lower_s"] == 2.0 and snap["backend_s"] == 3.25
        assert set(snap["shapes"]) == {"rlc:8", "rlc:16"}
        assert snap["in_service"] == {"count": 0, "seconds": 0.0,
                                      "last_at": None}
        return
    tracker.finish()
    emit(trace_ev, 4.0)              # tracing alone builds no program
    emit(backend_ev, 0.75)
    snap = tracker.snapshot()
    assert snap["in_service"]["count"] == 1 \
        and snap["in_service"]["seconds"] == 0.75 \
        and snap["in_service"]["last_at"] > 0
    assert snap["lower_s"] == 2.0 and snap["backend_s"] == 3.25
    json.dumps(snap)


@pytest.mark.parametrize("how", ["serving", "untraced", "warming"])
def test_sigterm_writes_the_spans_of_a_serving_sidecar(tmp_path, how):
    """SIGTERM on ``python -m hotstuff_tpu.sidecar --trace``, once it
    serves, ends serve() through its ``finally``: the buffered spans are
    in the file and the exit code is 0.  Without ``--trace``, and during
    a traced sidecar's warm-up, SIGTERM keeps its default action (the
    process dies by the signal, exit code -15)."""
    import os
    import signal
    import subprocess
    import sys
    import time

    from hotstuff_tpu.sidecar import service

    if how == "warming":
        seen = {}

        def fake_serve(*args, ready_event=None, trace_path=None, **kw):
            seen["warming"] = signal.getsignal(signal.SIGTERM)
            ready_event.set()
            seen["serving"] = signal.getsignal(signal.SIGTERM)

        before = signal.getsignal(signal.SIGTERM)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(service, "serve", fake_serve)
            try:
                service.main(["--host-crypto", "--trace",
                              str(tmp_path / "spans.jsonl")])
            finally:
                installed = signal.signal(signal.SIGTERM, before)
        assert seen["warming"] is before and seen["serving"] is installed
        with pytest.raises(SystemExit) as exc:
            installed(signal.SIGTERM, None)
        assert exc.value.code == 0
        return

    from hotstuff_tpu.sidecar.client import SidecarClient

    from conftest import REPO, free_port

    path = tmp_path / "spans.jsonl"
    port = free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "hotstuff_tpu.sidecar", "--host-crypto",
           "--port", str(port)]
    if how == "serving":
        cmd += ["--trace", str(path)]
    with open(tmp_path / "sidecar.log", "w") as log:
        proc = subprocess.Popen(cmd, cwd=tmp_path, stdout=log, stderr=log,
                                env=env)
        try:
            deadline = time.monotonic() + 120
            while True:
                try:
                    with SidecarClient(port=port, timeout=30) as client:
                        msgs, pks, sigs, expect, _ = _votes(4)
                        assert client.verify_batch(msgs, pks, sigs) == expect
                        # The connection's writer closes the request's
                        # spans after its sendall and before it sends
                        # the next frame: one more round trip orders the
                        # SIGTERM behind them.
                        assert client.ping()
                    break
                except OSError:
                    assert proc.poll() is None and \
                        time.monotonic() < deadline, "sidecar never served"
                    time.sleep(0.1)
            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    if how == "untraced":
        assert rc == -signal.SIGTERM and not path.exists()
        return
    assert rc == 0
    spans, malformed = parse_spans(path.read_text())
    assert malformed == 0
    assert {"request", "decode", "queue", "pack", "device", "reply"} <= \
        {s["stage"] for s in spans}


# ---------------------------------------------------------------------------
# graftscope: protocol v5 context tag round trip
# ---------------------------------------------------------------------------


def _make_records(n=2):
    msgs = [bytes([i]) * 32 for i in range(n)]
    pks = [bytes([0x10 + i]) * 32 for i in range(n)]
    sigs = [bytes([0x20 + i]) * 64 for i in range(n)]
    return msgs, pks, sigs


def test_protocol_v5_ctx_round_trip():
    from hotstuff_tpu.sidecar import protocol as proto

    msgs, pks, sigs = _make_records()
    ctx = bytes(range(32))
    frame = proto.encode_request(7, msgs, pks, sigs, ctx=ctx)
    opcode, req = proto.decode_request(frame[4:])
    assert opcode == proto.OP_VERIFY_BATCH
    assert req.ctx == ctx
    assert req.msgs == msgs and req.pks == pks and req.sigs == sigs
    # Bulk class carries the tag identically.
    frame = proto.encode_request(8, msgs, pks, sigs,
                                 opcode=proto.OP_VERIFY_BULK, ctx=ctx)
    opcode, req = proto.decode_request(frame[4:])
    assert opcode == proto.OP_VERIFY_BULK and req.ctx == ctx


def test_protocol_v5_legacy_and_zero_tag_frames():
    """Legacy tag-less frames AND all-zero tags (the C++ client's 'no
    context' form) both decode as ctx None — a version-skewed peer can
    never desync on the tag."""
    from hotstuff_tpu.sidecar import protocol as proto

    msgs, pks, sigs = _make_records()
    legacy = proto.encode_request(1, msgs, pks, sigs)  # no ctx at all
    _, req = proto.decode_request(legacy[4:])
    assert req.ctx is None
    zero = proto.encode_request(2, msgs, pks, sigs, ctx=proto.ZERO_CTX)
    assert len(zero) == len(legacy) + proto.CTX_LEN
    _, req = proto.decode_request(zero[4:])
    assert req.ctx is None
    # A frame whose length matches neither form still raises.
    bad = legacy[4:] + b"\x01" * 7
    with pytest.raises(ValueError):
        proto.decode_request(bad)


def test_protocol_v5_ctx_rides_bls_ops():
    """scheme=bls trace parity (ROADMAP item 2): the v5 context tag
    rides OP_BLS_VERIFY_VOTES / OP_BLS_VERIFY_MULTI exactly like the
    Ed25519 verifies — optional, length-discriminated (a BLS record is
    >= 288 bytes, so the 32 tag bytes can never alias one), all-zero
    tag decodes as 'no context'."""
    from hotstuff_tpu.sidecar import protocol as proto

    ctx = bytes(range(32))
    msg = b"d" * 32
    pks = [b"k" * 96] * 2
    sigs = [b"g" * 192] * 2

    votes = proto.encode_bls_votes_request(5, msg, pks, sigs, ctx=ctx)
    opcode, req = proto.decode_request(votes[4:])
    assert opcode == proto.OP_BLS_VERIFY_VOTES
    assert req.ctx == ctx
    assert req.msg == msg and req.pks == pks and req.sigs == sigs
    legacy = proto.encode_bls_votes_request(5, msg, pks, sigs)
    assert len(votes) == len(legacy) + proto.CTX_LEN
    _, req = proto.decode_request(legacy[4:])
    assert req.ctx is None
    zero = proto.encode_bls_votes_request(5, msg, pks, sigs,
                                          ctx=proto.ZERO_CTX)
    _, req = proto.decode_request(zero[4:])
    assert req.ctx is None

    msgs = [b"a" * 32, b"b" * 32]
    multi = proto.encode_bls_multi_request(6, msgs, pks, sigs, ctx=ctx)
    opcode, req = proto.decode_request(multi[4:])
    assert opcode == proto.OP_BLS_VERIFY_MULTI
    assert req.ctx == ctx
    assert req.msgs == msgs and req.pks == pks and req.sigs == sigs
    _, req = proto.decode_request(
        proto.encode_bls_multi_request(6, msgs, pks, sigs)[4:])
    assert req.ctx is None


def test_verify_engine_spans_carry_ctx(tmp_path):
    """A verify tagged with a block digest must leave the ctx on its
    per-request spans (request/queue/reply) and the b64 tag in the
    per-launch ctxs lists (pack/dispatch/device) — the exact schema
    obs/trace.py joins on."""
    from base64 import b64encode

    from hotstuff_tpu.crypto import ref_ed25519 as ref
    from hotstuff_tpu.sidecar.client import SidecarClient
    from hotstuff_tpu.sidecar.service import VerifyEngine

    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msg = b"\x06" * 32
    sig = ref.sign(sk, msg)
    ctx = bytes(range(32))
    ctx_b64 = b64encode(ctx).decode()

    path = str(tmp_path / "spans.jsonl")
    tracer = Tracer(path)
    with _Served(VerifyEngine(use_host=True, tracer=tracer)) as port:
        with SidecarClient(port=port) as client:
            assert client.verify_batch([msg], [pk], [sig], ctx=ctx) == [True]
            _await_spans(tracer, "request", 1)
    tracer.close()
    spans, malformed = parse_spans((tmp_path / "spans.jsonl").read_text())
    assert malformed == 0
    by_stage = {s["stage"]: s for s in spans}
    for stage in ("request", "decode", "queue", "reply"):
        assert by_stage[stage]["ctx"] == ctx_b64, by_stage[stage]
    for stage in ("pack", "dispatch", "device"):
        assert by_stage[stage]["ctxs"] == [ctx_b64], by_stage[stage]
    # The chain machinery joins them all onto the one tag.
    chains = chain_spans(spans)
    assert set(s["stage"] for s in chains[ctx_b64]) == \
        {"request", "decode", "queue", "pack", "dispatch", "device",
         "reply"}


# ---------------------------------------------------------------------------
# graftscope: per-block node<->sidecar joins
# ---------------------------------------------------------------------------


def _chain(block, t0, rid=1):
    return [
        _sc("request", t0 + 0.021, 21.0, rid=rid, cls="latency", ctx=block),
        _sc("queue", t0 + 0.001, 1.0, rid=rid, cls="latency", ctx=block),
        _sc("pack", t0 + 0.003, 2.0, reqs=1, ctxs=[block]),
        _sc("device", t0 + 0.017, 12.0, reqs=1, ctxs=[block]),
        _sc("reply", t0 + 0.021, 0.5, rid=rid, cls="latency", ctx=block),
    ]


def test_join_blocks_full_and_missing_chain():
    """The satellite case: one committed block's sidecar chain is
    missing — its trace stays (partial), the join rate degrades to 0.5,
    and the device sub-segment reports only the joined block."""
    traces = stitch_blocks(_full_block("a=", 2, 100.0)
                           + _full_block("c=", 4, 102.0))
    spans = _chain("a=", 100.012)
    join, joined = join_blocks(traces, chain_spans(spans))
    assert join == {"committed": 2, "with_verify": 2, "joined": 1,
                    "rate": 0.5}
    assert list(joined) == [("a=", 2)]
    dev = device_subsegment(joined)
    assert dev["n"] == 1 and dev["p50_ms"] == pytest.approx(12.0)


def test_join_blocks_requires_verify_segment():
    # A block that committed off the cached-certificate path (no verify
    # stages) is out of the join denominator entirely.
    partial = [s for s in _full_block("b=", 3, 101.0)
               if s["stage"] in ("proposal", "commit")]
    traces = stitch_blocks(partial)
    join, joined = join_blocks(traces, chain_spans(_chain("b=", 101.0)))
    assert join == {"committed": 1, "with_verify": 0, "joined": 0,
                    "rate": None}
    assert not joined


def test_join_shared_launch_spans_both_blocks():
    # One coalesced launch carrying two blocks' requests: its pack/
    # device spans list both ctxs and land in BOTH chains.
    traces = stitch_blocks(_full_block("a=", 2, 100.0)
                           + _full_block("b=", 3, 100.5))
    shared = _sc("device", 100.02, 9.0, ctxs=["a=", "b="])
    join, joined = join_blocks(traces, chain_spans([shared]))
    assert join["joined"] == 2 and join["rate"] == 1.0
    assert all(shared in chain for chain in joined.values())


def test_build_run_trace_with_ctx_join(tmp_path):
    """Directory-level: ctx-tagged sidecar spans join onto the mined
    node trace — summary grows join + verify:device, and the Chrome
    artifact nests the chain in the block's consensus row."""
    log = "\n".join([_trace_line(1, "proposal"),
                     _trace_line(1, "verify_submit", ms="010"),
                     _trace_line(1, "verify_reply", ms="030"),
                     _trace_line(1, "commit", ms="050"),
                     _trace_line(2, "proposal", block="xxx=", rnd=3),
                     _trace_line(2, "verify_submit", block="xxx=",
                                 rnd=3, ms="010"),
                     _trace_line(2, "verify_reply", block="xxx=",
                                 rnd=3, ms="030"),
                     _trace_line(2, "commit", block="xxx=", rnd=3,
                                 ms="050")])
    (tmp_path / "node-0.log").write_text(log + "\n")
    t0 = 1785751201.0  # block aaa='s chain only; xxx= stays unjoined
    (tmp_path / "sidecar-spans.jsonl").write_text(
        "\n".join(json.dumps(s) for s in _chain("aaa=", t0)) + "\n")
    summary, chrome = build_run_trace(str(tmp_path))
    assert summary["join"] == {"committed": 2, "with_verify": 2,
                               "joined": 1, "rate": 0.5}
    assert summary["segments"][DEVICE_SEGMENT]["n"] == 1
    assert summary["segments"][DEVICE_SEGMENT]["p50_ms"] == \
        pytest.approx(12.0)
    nested = [e for e in chrome["traceEvents"]
              if e.get("name", "").startswith("sidecar:")]
    assert nested and all(e["args"]["block"] == "aaa=" and e["pid"] == 1
                          for e in nested)
    # The flat sidecar-process timeline is still there for the chain.
    flat = [e for e in chrome["traceEvents"]
            if e.get("cat") == "sidecar" and e.get("pid") == 2]
    assert flat


# ---------------------------------------------------------------------------
# graftscope: node METRICS series + divergence
# ---------------------------------------------------------------------------


def _metrics_line(sec, commits, rate, busy=0, breaker="closed",
                  itx=5, ibytes=2048):
    return (f"[2026-08-03T12:00:{sec:02d}.000Z INFO node::metrics] "
            f"METRICS commits={commits} commit_rate={rate} "
            f"ingress_tx={itx} ingress_bytes={ibytes} busy={busy} "
            f"breaker={breaker}")


def test_parse_node_metrics_and_torn_lines():
    log = "\n".join([
        "[2026-08-03T12:00:01.000Z INFO node::node] Node abc= booted",
        _metrics_line(1, 10, "5.0"),
        _metrics_line(2, 15, "5.0", busy=3, breaker="open"),
        # torn mid-write: missing keys simply don't match
        "[2026-08-03T12:00:03.000Z INFO node::metrics] METRICS commi",
        "garbage line",
        _metrics_line(4, 20, "2.5"),
    ])
    recs = parse_node_metrics(log, host="node-0.log")
    assert len(recs) == 3
    assert all(r["node"] == "node-0.log" and r["ok"] for r in recs)
    assert recs[0]["metrics"] == {
        "commits": 10, "commit_rate": 5.0, "ingress_tx": 5,
        "ingress_bytes": 2048, "busy": 0, "breaker": "closed"}
    assert recs[1]["metrics"]["busy"] == 3
    assert recs[1]["metrics"]["breaker"] == "open"
    assert recs[2]["t"] - recs[0]["t"] == pytest.approx(3.0)


def test_merge_node_series_idempotent(tmp_path):
    (tmp_path / "node-0.log").write_text(_metrics_line(1, 10, "5.0")
                                         + "\n")
    (tmp_path / "node-1.log").write_text(_metrics_line(1, 9, "4.5")
                                         + "\n")
    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"t": 1.0, "ok": True, "stats": {}}) + "\n")
    assert merge_node_series(str(tmp_path)) == 2
    samples, malformed = read_samples(str(tmp_path / "metrics.jsonl"))
    assert malformed == 0
    sidecar, node = split_samples(samples)
    assert len(sidecar) == 1 and len(node) == 2
    # Re-merging the same directory must not duplicate the series.
    assert merge_node_series(str(tmp_path)) == 0
    samples, _ = read_samples(str(tmp_path / "metrics.jsonl"))
    assert len(samples) == 3


def test_commit_rate_divergence_flags_straggler():
    def rec(host, rate):
        return {"t": 1.0, "ok": True, "node": host,
                "metrics": {"commit_rate": rate}}

    samples = [rec("node-0.log", 10.0), rec("node-1.log", 10.5),
               rec("node-2.log", 9.8), rec("node-3.log", 3.0)]
    div = commit_rate_divergence(samples, threshold=0.7)
    assert div["median"] == pytest.approx(9.9)
    assert [s["host"] for s in div["stragglers"]] == ["node-3.log"]
    assert div["stragglers"][0]["ratio"] < 0.7
    # A healthy committee flags nothing; one replica is unjudgeable.
    assert commit_rate_divergence(samples[:3])["stragglers"] == []
    assert commit_rate_divergence(samples[:1])["median"] is None


def test_log_parser_notes_divergence_and_splits_series():
    from test_harness import GOLDEN_CLIENT, GOLDEN_NODE

    from hotstuff_tpu.harness import LogParser

    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    samples = [
        {"t": 1.0, "ok": True, "stats": {"launches": 1}},
        {"t": 2.0, "ok": True, "stats": {"launches": 2}},
    ]
    for host, rate in (("node-0.log", 10.0), ("node-1.log", 9.5),
                       ("node-2.log", 1.0)):
        samples.append({"t": 1.5, "ok": True, "node": host,
                        "metrics": {"commit_rate": rate}})
    parser.note_metrics(samples)
    # The sidecar note counts only sidecar samples.
    assert any("Sidecar metrics: 2 sample(s)" in n for n in parser.notes)
    assert any("Node metrics: 3 sample(s) across 3 replica(s)" in n
               for n in parser.notes)
    straggler = [n for n in parser.notes
                 if "Replica commit-rate divergence" in n]
    assert len(straggler) == 1 and "node-2.log" in straggler[0]
    assert parser.node_metrics["divergence"]["stragglers"]


def test_note_trace_includes_join_rate():
    from test_harness import GOLDEN_CLIENT, GOLDEN_NODE

    from hotstuff_tpu.harness import LogParser

    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    parser.note_trace({
        "blocks": 4, "complete": 4,
        "join": {"committed": 4, "with_verify": 4, "joined": 3,
                 "rate": 0.75},
        "segments": {
            "proposal->commit": {"n": 4, "p50_ms": 50.0, "p99_ms": 80.0},
            DEVICE_SEGMENT: {"n": 3, "p50_ms": 12.0, "p99_ms": 18.0},
        }})
    note = next(n for n in parser.notes if "Commit critical path" in n)
    assert "sidecar join 75% of 4 verify-traced" in note
    assert "verify:device p50 12 ms / p99 18 ms" in note


# ---------------------------------------------------------------------------
# End-to-end grafttrace (slow lane; needs the native build)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_grafttrace_e2e_local_bench(tmp_path, monkeypatch):
    """The acceptance run: a real LocalBench (host-crypto sidecar, a
    scripted sidecar kill/restart) must produce logs/trace.json
    (Perfetto-loadable), logs/metrics.jsonl with >= 2 in-window samples
    showing the kill/restart transition, and a 'Commit critical path'
    note with per-stage percentiles.  graftscope: the same run must
    join >= 90% of its verify-traced committed blocks onto their
    sidecar chains (device time nested inside verify), and the node
    METRICS series must land per-replica next to the sidecar's."""
    import os

    from conftest import NODE_BIN, REPO
    from hotstuff_tpu.harness.config import BenchParameters, NodeParameters
    from hotstuff_tpu.harness.local import LocalBench

    if not os.path.exists(NODE_BIN):
        pytest.skip("native binaries not built (cmake --build native/build)")
    monkeypatch.chdir(tmp_path)
    os.symlink(os.path.join(REPO, "native"), tmp_path / "native")

    params = BenchParameters({
        "faults": 0, "nodes": 4, "rate": 500, "tx_size": 64,
        "duration": 12, "sidecar_host_crypto": True,
        "fault_plan": "3 sidecar kill; 5 sidecar restart"})
    node_params = NodeParameters.default(tpu_sidecar="127.0.0.1:7100")
    node_params.json["consensus"]["timeout_delay"] = 1_000
    node_params.timeout_delay = 1_000
    parser = LocalBench(params, node_params).run()

    out = parser.result()
    # critical path out of real node TRACE lines
    assert any("Commit critical path" in n for n in parser.notes), out
    assert parser.trace["segments"]["proposal->commit"]["n"] > 0
    # the Chrome trace artifact
    with open("logs/trace.json") as f:
        chrome = json.load(f)
    assert any(e.get("ph") == "X" for e in chrome["traceEvents"])
    # graftscope acceptance: >= 90% of verify-traced committed blocks
    # carry a joined sidecar chain, and device time rides inside the
    # verify segment of the summary + the Chrome artifact.
    join = parser.trace["join"]
    assert join["with_verify"] > 0, parser.trace
    assert join["rate"] >= 0.9, join
    assert parser.trace["segments"][DEVICE_SEGMENT]["n"] > 0
    assert any(e.get("name") == "sidecar:device"
               and e.get("args", {}).get("block")
               for e in chrome["traceEvents"])
    # >= 2 in-window samples, with the kill/restart visible as a
    # failed->ok transition in the series (sidecar sub-series: the node
    # records merged next to them must not mask the gap)
    samples, _ = read_samples("logs/metrics.jsonl")
    sidecar_series, node_series = split_samples(samples)
    assert len(sidecar_series) >= 2, samples
    assert any("Sidecar metrics:" in n for n in parser.notes)
    oks = [s["ok"] for s in sidecar_series]
    assert False in oks and True in oks[oks.index(False):], \
        "sidecar kill/restart not visible in the sampled series"
    # per-replica node METRICS landed in the same artifact
    assert node_series, "no node METRICS records merged"
    assert len({s["node"] for s in node_series}) >= 2
    assert any("Node metrics:" in n for n in parser.notes)
    # sidecar spans were written and merged
    assert os.path.exists("logs/sidecar-spans.jsonl")
    # the per-event telemetry curve rode into the chaos summary
    assert any("telemetry" in e for e in parser.chaos["events"])


# ---------------------------------------------------------------------------
# plots (per-stage histograms + the metrics time series)
# ---------------------------------------------------------------------------


def test_plot_trace_and_metrics(tmp_path, monkeypatch):
    matplotlib = pytest.importorskip("matplotlib")  # noqa: F841
    from hotstuff_tpu.harness.plot import Ploter, PlotError

    monkeypatch.chdir(tmp_path)
    with pytest.raises(PlotError):
        Ploter().plot_trace()  # no artifact yet
    with pytest.raises(PlotError):
        Ploter().plot_metrics()
    (tmp_path / "logs").mkdir()
    (tmp_path / "plots").mkdir()
    traces = stitch_blocks(_full_block("a=", 2, 100.0)
                           + _full_block("b=", 3, 101.0))
    (tmp_path / "logs" / "trace.json").write_text(
        json.dumps(chrome_trace(traces)))
    lines = []
    for i in range(6):
        ok = i != 3  # one failed tick: the blackout marker path
        rec = {"t": 1000.0 + i, "ok": ok}
        if ok:
            rec["stats"] = {
                "sigs_launched": 100 * i,
                "queue_wait": {"latency": {"n": 4, "p50_ms": 1.0,
                                           "p99_ms": 2.0 + i}}}
        else:
            rec["error"] = "down"
        lines.append(json.dumps(rec))
    (tmp_path / "logs" / "metrics.jsonl").write_text(
        "\n".join(lines) + "\n")
    ploter = Ploter()
    ploter.plot_trace()
    ploter.plot_metrics()
    for name in ("trace-hist", "metrics"):
        assert (tmp_path / "plots" / f"{name}.png").exists()
        assert (tmp_path / "plots" / f"{name}.pdf").exists()
