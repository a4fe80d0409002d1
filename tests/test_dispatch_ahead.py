"""The staged engine's dispatch order (PERF.md §3, the engine layer).

With a pack pending and room in the pipeline, launch n+1 is dispatched
BEFORE launch n is drained, so n's fetch, insert and replies run beside
n+1's program; a launch whose result is ready is drained while a slower
pack still runs; one request in flight keeps today's order, dispatch
then drain.  ``VerifyEngine._pack`` is stubbed: each launch's pack and
device result wait on events, and its dispatch and fetch log one
timeline with the replies.  Every mask is held to the generator's truth
(the plain reference verified the records).
"""

import threading
import time

import numpy as np
import pytest

from hotstuff_tpu.crypto import ref_ed25519 as ref
from hotstuff_tpu.sidecar import protocol as proto
from hotstuff_tpu.sidecar import sched as vsched
from hotstuff_tpu.sidecar.guard import BusyReply, LaunchDeadlines, LaunchGuard
from hotstuff_tpu.sidecar.service import ChaosState, VerifyEngine

TAMPERED = 1  # the forged record of every request


def _request(rid, n=3):
    """A verify request of n real signatures, record TAMPERED forged."""
    rng = np.random.default_rng(rid)
    msgs, pks, sigs = [], [], []
    for i in range(n):
        sk = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        msg = rng.bytes(32)
        sig = ref.sign(sk, msg)
        if i == TAMPERED:
            sig = sig[:1] + bytes([sig[1] ^ 0xFF]) + sig[2:]
        msgs.append(msg)
        pks.append(pk)
        sigs.append(sig)
    return proto.VerifyRequest(rid, msgs, pks, sigs)


def _truth(req):
    return [i != TAMPERED for i in range(len(req.msgs))]


class _Launches:
    """Stands in for ``VerifyEngine._pack``.  A launch is named by its
    first request's id; ``release_pack(rid)`` finishes its pack and
    ``release_device(rid)`` its program (``hold=False``: both at once).
    ``events`` is one timeline of ("pack" |
    "dispatch" | "fetch" | "reply", rid); ``in_flight`` what the engine
    counted at each dispatch, this launch included."""

    def __init__(self, engine, hold=True):
        self.engine = engine
        self.hold = hold
        self.cond = threading.Condition()
        self.events = []
        self.in_flight = []
        self.replies = {}
        self.on_dispatch = {}
        self._gates = {}
        engine._pack = self.pack

    def _gate(self, rid):
        with self.cond:
            gate = self._gates.get(rid)
            if gate is None:
                gate = self._gates[rid] = (threading.Event(),
                                           threading.Event())
                if not self.hold:
                    gate[0].set()
                    gate[1].set()
            return gate

    def release_pack(self, rid):
        self._gate(rid)[0].set()

    def release_device(self, rid):
        self._gate(rid)[1].set()

    def note(self, what, rid):
        with self.cond:
            self.events.append((what, rid))
            self.cond.notify_all()

    def seen(self, what, rid, timeout=10.0):
        with self.cond:
            return self.cond.wait_for(lambda: (what, rid) in self.events,
                                      timeout=timeout)

    def at(self, what, rid):
        return self.events.index((what, rid))

    def reply_to(self, rid):
        def reply(mask):
            with self.cond:
                self.replies.setdefault(rid, []).append(mask)
            self.note("reply", rid)
        return reply

    def pack(self, batch, scope):
        rid = batch[0].request.request_id
        packed, computed = self._gate(rid)
        self.note("pack", rid)
        assert packed.wait(10.0)
        records = [r for p in batch for r in zip(
            p.request.msgs, p.request.pks, p.request.sigs)]
        mask = [bool(ref.verify(pk, m, s)) for m, pk, s in records]

        def dispatch():
            self.in_flight.append(self.engine._inflight_n + 1)
            self.note("dispatch", rid)
            hook = self.on_dispatch.pop(rid, None)
            if hook is not None:
                hook()

            def fetch():
                self.note("fetch", rid)
                assert computed.wait(10.0)
                return mask

            return fetch

        return dispatch


def _pipeline(engine):
    return engine.stats_snapshot()["pipeline"]


def test_next_launch_is_dispatched_before_the_one_in_flight_drains(
        tmp_path):
    """Three bulk launches: B is dispatched before A's fetch is entered
    once B's pack is done; C's pack done while A and B fill the pipeline
    waits for A's drain; the dispatch spans carry ``ahead`` 0, 1, 1."""
    from hotstuff_tpu.obs.spans import Tracer, parse_spans

    path = tmp_path / "spans.jsonl"
    tracer = Tracer(str(path))
    engine = VerifyEngine(use_host=True, tracer=tracer)
    stub = _Launches(engine)
    reqs = {rid: _request(rid) for rid in (1, 2, 3)}
    try:
        assert engine.submit(reqs[1], stub.reply_to(1), cls=vsched.BULK)
        assert stub.seen("pack", 1)
        assert engine.submit(reqs[2], stub.reply_to(2), cls=vsched.BULK)
        stub.release_pack(1)
        assert stub.seen("pack", 2)  # admitted the moment A was dispatched
        assert engine.submit(reqs[3], stub.reply_to(3), cls=vsched.BULK)
        stub.release_pack(2)
        assert stub.seen("dispatch", 2)
        assert stub.seen("fetch", 1)
        assert stub.at("dispatch", 2) < stub.at("fetch", 1)
        assert stub.seen("pack", 3)
        stub.release_pack(3)
        time.sleep(0.2)  # the pipeline is full: C waits for A's drain
        assert ("dispatch", 3) not in stub.events
        stub.release_device(1)
        assert stub.seen("dispatch", 3)
        assert stub.at("reply", 1) < stub.at("dispatch", 3)
        stub.release_device(2)
        stub.release_device(3)
        for rid in (2, 3):
            assert stub.seen("reply", rid)
        assert stub.in_flight == [1, 2, 2]
        assert max(stub.in_flight) <= VerifyEngine.PIPELINE_DEPTH
        assert stub.replies == {rid: [_truth(r)] for rid, r in reqs.items()}
        pipe = _pipeline(engine)
        assert (pipe["dispatches"], pipe["dispatch_ahead"]) == (3, 2)
        assert pipe["dispatch_ahead_share"] == pytest.approx(0.667)
    finally:
        engine.stop()
        engine._thread.join(timeout=10.0)
        tracer.close()
    spans, malformed = parse_spans(path.read_text())
    assert malformed == 0
    dispatches = sorted((s for s in spans if s["stage"] == "dispatch"),
                        key=lambda s: s["t0"])
    assert [s["ahead"] for s in dispatches] == [0, 1, 1]


def test_a_slower_pack_goes_out_before_the_finished_launch_drains():
    """B's pack outlasts launch A: the engine waits for the pack, not for
    A's result, so B goes out with A still in flight (``ahead`` 1) and A
    is drained beside B's program."""
    engine = VerifyEngine(use_host=True)
    stub = _Launches(engine)
    reqs = {rid: _request(rid) for rid in (1, 2)}
    try:
        assert engine.submit(reqs[1], stub.reply_to(1), cls=vsched.BULK)
        assert stub.seen("pack", 1)
        assert engine.submit(reqs[2], stub.reply_to(2), cls=vsched.BULK)
        stub.release_pack(1)
        assert stub.seen("pack", 2)
        assert stub.seen("dispatch", 1)
        stub.release_device(1)
        time.sleep(0.2)  # A's result is in; B's pack still runs
        assert ("fetch", 1) not in stub.events
        stub.release_pack(2)
        assert stub.seen("reply", 1)
        assert stub.at("dispatch", 2) < stub.at("fetch", 1)
        stub.release_device(2)
        assert stub.seen("reply", 2)
        assert stub.in_flight == [1, 2]
        assert stub.replies == {rid: [_truth(r)] for rid, r in reqs.items()}
        assert _pipeline(engine)["dispatch_ahead"] == 1
    finally:
        engine.stop()


@pytest.mark.parametrize("cls", [vsched.LATENCY, vsched.BULK])
def test_one_request_in_flight_keeps_dispatch_then_drain(cls):
    """The latency cells' loop (one connection, one in flight): no pack
    is ever pending while a launch runs, so every launch is dispatched,
    then drained, with nothing ahead of it, as before."""
    engine = VerifyEngine(use_host=True)
    stub = _Launches(engine, hold=False)
    reqs = {rid: _request(rid) for rid in (1, 2, 3)}
    try:
        for rid, req in reqs.items():
            assert engine.submit(req, stub.reply_to(rid), cls=cls)
            assert stub.seen("reply", rid)
        order = [e for e in stub.events if e[0] in ("dispatch", "fetch")]
        assert order == [(what, rid) for rid in reqs
                         for what in ("dispatch", "fetch")]
        assert stub.in_flight == [1, 1, 1]
        assert stub.replies == {rid: [_truth(r)] for rid, r in reqs.items()}
        pipe = _pipeline(engine)
        assert (pipe["dispatches"], pipe["dispatch_ahead"],
                pipe["dispatch_ahead_share"]) == (3, 0, 0.0)
    finally:
        engine.stop()


@pytest.mark.parametrize("cls", [vsched.BULK, vsched.LATENCY])
def test_wedge_of_the_older_launch_then_stop_answers_each_request_once(
        cls):
    """Two launches in flight; the chaos hook wedges the OLDER one's
    fetch and stop() comes while the guard waits it out.  The wedged
    launch is answered by the ladder (bulk: BusyReply; latency: the
    host's mask), the younger one by the device, and shutdown drains
    both: every request exactly once."""
    chaos = ChaosState()
    guard = LaunchGuard(deadlines=LaunchDeadlines(
        warm_boot=True, compile_budget_s=2.0, warm_grace_s=1.0,
        min_deadline_s=0.05))
    engine = VerifyEngine(use_host=True, guard=guard, chaos=chaos,
                          rewarm_fn=lambda: None)
    stub = _Launches(engine)
    # The next guarded call after B's dispatch is A's fetch.
    stub.on_dispatch[2] = lambda: chaos.configure({"wedge": 1})
    reqs = {rid: _request(rid) for rid in (1, 2)}
    try:
        assert engine.submit(reqs[1], stub.reply_to(1), cls=cls)
        assert stub.seen("pack", 1)
        assert engine.submit(reqs[2], stub.reply_to(2), cls=vsched.BULK)
        stub.release_pack(1)
        assert stub.seen("pack", 2)
        stub.release_device(2)
        stub.release_pack(2)
        assert stub.seen("dispatch", 2)
        engine.stop()
        assert stub.seen("reply", 1) and stub.seen("reply", 2)
        engine._thread.join(timeout=10.0)
        assert not engine._thread.is_alive()
        time.sleep(0.1)  # a second reply would land here
        assert ("fetch", 1) not in stub.events  # the wedge took its place
        assert stub.in_flight == [1, 2]
        (older,) = stub.replies[1]
        if cls == vsched.BULK:
            assert isinstance(older, BusyReply)
        else:
            assert older == _truth(reqs[1])
        assert stub.replies[2] == [_truth(reqs[2])]
        g = engine.stats_snapshot()["guard"]
        assert g["wedges"] == 1
        assert g["host_fallback_records"] == (3 if cls == vsched.LATENCY
                                              else 0)
    finally:
        engine.stop()
        guard.close()

