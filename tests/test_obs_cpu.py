"""The sidecar's host CPU, measured inside it: the thread-CPU registry
by role (``obs/cpu.py``), the launch threads' one read as they end, and
OP_STATS ``host``; that spans carry no CPU-clock reading; the span
buffer's bound.

CPU-only and fast: host-mode engines, one signature a request.
"""

import threading
import time

import pytest

from hotstuff_tpu.obs import Tracer, parse_spans
from hotstuff_tpu.obs.cpu import ROLES, CpuWindow, ThreadCpu

from test_obs import _await_spans, _Served


def _signed(n=1):
    from hotstuff_tpu.crypto import ref_ed25519 as ref

    sk = bytes(range(32))
    _, pk = ref.generate_keypair(sk)
    msgs = [bytes([i + 1]) * 32 for i in range(n)]
    return msgs, [pk] * n, [ref.sign(sk, m) for m in msgs]


def _burn(seconds):
    """Busy CPU on the calling thread for ``seconds`` of its CPU clock."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


class _Clocks:
    """A ``thread_clock`` that reads each thread's value from a table
    (0.0 for a thread not in it), and counts its reads."""

    def __init__(self):
        self.by_thread: dict = {}
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.by_thread.get(threading.get_ident(), 0.0)


def _retire(cpu, clocks, role, seconds):
    """A thread that enters ``role`` and leaves with ``seconds`` of CPU."""
    def body():
        clocks.by_thread[threading.get_ident()] = seconds
        cpu.enter(role)
        cpu.leave()

    t = threading.Thread(target=body)
    t.start()
    t.join()


@pytest.fixture(scope="module")
def traced_round_trip(tmp_path_factory):
    """Two traced requests through a host-mode engine under a real
    launch guard, over the socket; the spans of both, the guard's call
    count and the registry's CPU-clock reads."""
    from hotstuff_tpu.sidecar.client import SidecarClient
    from hotstuff_tpu.sidecar.guard import LaunchGuard
    from hotstuff_tpu.sidecar.service import VerifyEngine

    path = tmp_path_factory.mktemp("cpu_spans") / "spans.jsonl"
    tracer = Tracer(str(path))
    clocks = _Clocks()
    guard = LaunchGuard(cpu=ThreadCpu(thread_clock=clocks))
    msgs, pks, sigs = _signed(4)
    try:
        with _Served(VerifyEngine(use_host=True, tracer=tracer,
                                  guard=guard)) as port:
            with SidecarClient(port=port) as client:
                assert client.verify_batch(msgs[:2], pks[:2], sigs[:2]) \
                    == [True, True]
                assert client.verify_batch(msgs[2:], pks[2:], sigs[2:]) \
                    == [True, True]
                _await_spans(tracer, "request", 2)
            # The connection's handler and writer leave as it closes.
            deadline = time.monotonic() + 10
            while clocks.reads < guard.stats.calls + 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            time.sleep(0.05)
            calls, reads = guard.stats.calls, clocks.reads
    finally:
        guard.close()
    tracer.close()
    spans, malformed = parse_spans(path.read_text())
    assert malformed == 0
    return spans, calls, reads


@pytest.mark.parametrize("stage", ["request", "decode", "queue", "pack",
                                   "dispatch", "device", "cache_insert",
                                   "reply"])
def test_spans_carry_no_cpu_clock_reading(traced_round_trip, stage):
    """Tracing reads no thread CPU clock inside a span: a read costs
    ~25 us on the v5e host and advances in 10 ms ticks there, so it
    would move the span's time and tell nothing of one span."""
    spans, _, _ = traced_round_trip
    mine = [s for s in spans if s["stage"] == stage]
    assert len(mine) == 2
    for s in mine:
        assert not any("cpu" in key for key in s)


def test_a_traced_round_trip_reads_the_cpu_clock_once_a_launch_thread(
        traced_round_trip):
    """Traced, the only CPU-clock reads are the threads' own, one each
    as it ends: a launch thread a guarded call, and the connection's
    handler and writer."""
    _, calls, reads = traced_round_trip
    assert calls >= 2 and reads == calls + 2


def test_a_guard_thread_that_exits_still_counts_under_launch():
    from hotstuff_tpu.sidecar.guard import LaunchGuard

    cpu = ThreadCpu()
    guard = LaunchGuard(cpu=cpu)
    threads = []

    def thunk():
        threads.append(threading.current_thread())
        _burn(0.05)
        return 7

    try:
        assert guard.call("launch:8", thunk) == 7
    finally:
        guard.close()
    (launch,) = threads
    assert launch.name.startswith("guard-launch-")
    launch.join(timeout=10)
    assert not launch.is_alive() and cpu._live == {}
    snap = cpu.snapshot()
    assert snap["launch"] >= 0.05
    assert snap["engine"] == snap["pack"] == snap["conn"] == 0.0


def test_the_launch_threads_read_comes_after_the_caller_woke():
    """The launch thread reads its CPU clock once, after it has handed
    the caller its result: the read cannot hold the call, its
    ``thunk_s`` or its hop.  The clock here waits until every call has
    returned."""
    from hotstuff_tpu.sidecar.guard import LaunchGuard

    returned = threading.Event()
    reads = []

    def clock():
        assert returned.wait(timeout=10)
        reads.append(threading.current_thread())
        return 0.5

    cpu = ThreadCpu(thread_clock=clock)
    guard = LaunchGuard(cpu=cpu)
    try:
        for _ in range(3):
            assert guard.call("launch:8", lambda: 7) == 7
        assert reads == []
        returned.set()
        for t in list(threading.enumerate()):
            if t.name.startswith("guard-launch-"):
                t.join(timeout=10)
    finally:
        guard.close()
    assert len(reads) == 3 and guard.stats.wedges == 0
    assert cpu.snapshot()["launch"] == 1.5


def test_the_engine_and_pack_threads_count_under_their_roles():
    """The engine thread and the pack worker stay live in the registry
    under their roles while the engine runs, and the engine thread
    retires as it stops."""
    from hotstuff_tpu.sidecar.client import SidecarClient
    from hotstuff_tpu.sidecar.guard import LaunchGuard
    from hotstuff_tpu.sidecar.service import VerifyEngine

    cpu = ThreadCpu(read=lambda clock_id: 0.25)
    guard = LaunchGuard(cpu=cpu)
    engine = VerifyEngine(use_host=True, guard=guard)
    msgs, pks, sigs = _signed()
    try:
        with _Served(engine) as port:
            with SidecarClient(port=port) as client:
                assert client.verify_batch(msgs, pks, sigs) == [True]
                roles = sorted(role for role, _, _ in cpu._live.values())
                assert roles.count("engine") == 1
                assert roles.count("pack") == 1
                assert roles.count("conn") >= 1
    finally:
        engine.stop()
        engine._thread.join(timeout=30)
        guard.close()
    assert "engine" not in [role for role, _, _ in cpu._live.values()]


def test_a_thread_that_ends_without_leaving_keeps_its_last_read():
    reads = iter([0.25, OSError("gone")])

    def read(clock_id):
        value = next(reads)
        if isinstance(value, Exception):
            raise value
        return value

    cpu = ThreadCpu(read=read)
    t = threading.Thread(target=cpu.enter, args=("pack",))
    t.start()
    t.join()
    assert cpu.snapshot()["pack"] == 0.25
    assert cpu.snapshot()["pack"] == 0.25 and cpu._live == {}


def test_stats_interval_is_the_time_since_the_previous_reply():
    """Two OP_STATS bodies give an interval whose deltas are the fake
    clocks'; a third's interval starts at the second."""
    from hotstuff_tpu.sidecar.guard import LaunchGuard
    from hotstuff_tpu.sidecar.service import VerifyEngine

    clocks = _Clocks()
    # Live threads (the engine's, the pack worker) read 0.
    cpu = ThreadCpu(thread_clock=clocks, read=lambda clock_id: 0.0)
    guard = LaunchGuard(cpu=cpu)
    engine = VerifyEngine(use_host=True, guard=guard)
    walls = iter([100.0, 102.0, 107.0, 110.0])
    try:
        engine._host = CpuWindow(cpu, wall=lambda: next(walls))
        _retire(cpu, clocks, "pack", 0.5)
        _retire(cpu, clocks, "conn", 0.25)
        _retire(cpu, clocks, "launch", 0.125)
        engine._launches += 10
        first = engine.stats_snapshot()["host"]
        assert first["cpu_s"] == {"engine": 0.0, "launch": 0.125,
                                  "pack": 0.5, "conn": 0.25}
        assert first["launches"] == 10
        assert first["interval"] == {
            "wall_s": 2.0, "launches": 10, "python_cpu_share": 0.4375,
            "engine_cpu_ms_per_launch": 0.0,
            "launch_cpu_ms_per_launch": 12.5, "pack_cpu_ms_per_launch": 50.0,
            "conn_cpu_ms_per_launch": 25.0, "python_cpu_ms_per_launch": 87.5}
        _retire(cpu, clocks, "engine", 1.0)
        engine._launches += 5
        second = engine.stats_snapshot()["host"]["interval"]
        assert second == {
            "wall_s": 5.0, "launches": 5, "python_cpu_share": 0.2,
            "engine_cpu_ms_per_launch": 200.0,
            "launch_cpu_ms_per_launch": 0.0, "pack_cpu_ms_per_launch": 0.0,
            "conn_cpu_ms_per_launch": 0.0, "python_cpu_ms_per_launch": 200.0}
        third = engine.stats_snapshot()["host"]
        assert third["interval"] == {"wall_s": 3.0, "launches": 0,
                                     "python_cpu_share": 0.0}
        assert third["cpu_s"] == {"engine": 1.0, "launch": 0.125,
                                  "pack": 0.5, "conn": 0.25}
    finally:
        engine.stop()
        engine._thread.join(timeout=30)
        guard.close()


def test_host_section_comes_back_through_op_stats():
    """The real socket path: the connection threads count under
    ``conn``, the launch under ``launches``, and the per-launch roles
    sum to the whole."""
    from hotstuff_tpu.sidecar.client import SidecarClient
    from hotstuff_tpu.sidecar.service import VerifyEngine

    msgs, pks, sigs = _signed()
    with _Served(VerifyEngine(use_host=True)) as port:
        with SidecarClient(port=port) as client:
            client.stats()
            assert client.verify_batch(msgs, pks, sigs) == [True]
            host = client.stats()["host"]
    assert set(host["cpu_s"]) == set(ROLES)
    assert host["cpu_s"]["conn"] > 0.0 and host["cpu_s"]["pack"] > 0.0
    assert host["launches"] == 1
    interval = host["interval"]
    assert interval["launches"] == 1 and interval["wall_s"] > 0.0
    roles = sum(interval[f"{role}_cpu_ms_per_launch"] for role in ROLES)
    assert roles == pytest.approx(interval["python_cpu_ms_per_launch"],
                                  abs=2e-4)
    assert interval["python_cpu_share"] * interval["wall_s"] == \
        pytest.approx(interval["python_cpu_ms_per_launch"] / 1e3, rel=1e-2,
                      abs=1e-5)


def test_ten_thousand_spans_trigger_no_write_out(tmp_path):
    """The bound sits above the busiest traced window (~58,000 spans in
    ``ingress20.gate``): the file is written at ``close()``."""
    path = tmp_path / "spans.jsonl"
    tracer = Tracer(str(path))
    assert tracer.BUFFER_SPANS >= 65536
    for i in range(10_000):
        tracer.record("queue", tracer.now(), i=i)
    assert not path.exists() and len(tracer._buf) == 10_000
    tracer.close()
    assert len(path.read_text().splitlines()) == 10_000


def test_registry_loses_no_thread_under_contention():
    """Threads enter and leave while another thread snapshots, with a
    short switch interval: every leave lands in its role's total, and
    no snapshot of a role ever reads less than the one before."""
    import os
    import sys

    cpu = ThreadCpu(thread_clock=lambda: 0.001, read=lambda clock_id: 0.0)
    workers = 4 * (os.cpu_count() or 1)
    done = threading.Event()
    seen = []

    def snapshots():
        while not done.is_set():
            seen.append(cpu.snapshot())

    def work(k):
        role = ROLES[k % len(ROLES)]
        for _ in range(50):
            cpu.enter(role)
            cpu.leave()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        reader = threading.Thread(target=snapshots)
        reader.start()
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        done.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not reader.is_alive() and not any(t.is_alive() for t in threads)
    final = cpu.snapshot()
    for k, role in enumerate(ROLES):
        n = len(range(k, workers, len(ROLES)))
        assert final[role] == pytest.approx(n * 50 * 0.001)
        reads = [s[role] for s in seen]
        assert reads == sorted(reads)
    assert cpu._live == {}
