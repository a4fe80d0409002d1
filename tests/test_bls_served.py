"""The BLS aggregate-QC path served end to end: ``OP_BLS_VERIFY_VOTES``
frames through ``SidecarClient.bls_verify_votes``, the socket, the
scheduler, the launch guard and the engine's BLS branch, with the spans
and OP_STATS counters that say where each verdict came from.

The device program is replaced by the host reference's product of
pairings over the same Miller lines (its CPU compile alone takes
minutes): these tests hold the plumbing, not the program, which
``tests/test_bls381.py`` holds to the host reference.
"""

import threading
import time

import numpy as np
import pytest

from hotstuff_tpu.offchain import bls12381 as bls
from hotstuff_tpu.sidecar import protocol as proto
from hotstuff_tpu.sidecar.client import SidecarClient

BLS_STAGES = ("bls_prep", "hash_to_g2", "miller_lines", "pairing", "d2h")


def host_pairings_check(lines):
    """``pairings_check`` computed on the host: the lines' Montgomery
    limbs back to Fq12, the Miller accumulation the device runs, the
    product of the pairings, the host's final exponentiation, == 1."""
    from hotstuff_tpu.ops import field381 as F

    lines = np.asarray(lines)
    r_inv = pow(F.R, -1, F.Q)

    def fq12(limbs):
        return tuple(F.from_limbs(c) * r_inv % F.Q for c in limbs)

    f = bls.FQ12_ONE
    for pairing in lines:
        acc = bls.FQ12_ONE
        for step in pairing:
            acc = bls.fq12_mul(bls.fq12_mul(acc, acc), fq12(step[0]))
            acc = bls.fq12_mul(acc, fq12(step[1]))
        f = bls.fq12_mul(f, acc)
    return np.bool_(bls.final_exponentiate(f) == bls.FQ12_ONE)


def certificate(msg: bytes, n: int = 3, forged_row=None, seed: int = 90):
    """(pks, sigs) encoded as a replica ships them: n validators' keys
    and votes over ``msg``; ``forged_row`` signs another digest."""
    keys = [bls.key_gen(bytes([seed + i]) * 32) for i in range(n)]
    sigs = [bls.sign(sk, b"another digest" if i == forged_row else msg)
            for i, (sk, _) in enumerate(keys)]
    return ([bls.g1_encode(pk) for _, pk in keys],
            [bls.g2_encode(s) for s in sigs])


@pytest.fixture(scope="module")
def device_sidecar(tmp_path_factory):
    """``serve()`` as a scheme=bls replica's sidecar boots it (warm_bls,
    the smallest Ed25519 warm-up), traced, with the pairing program
    replaced; yields (port, trace path, server, program calls)."""
    from hotstuff_tpu.ops import bls381 as dbls
    from hotstuff_tpu.sidecar import service

    calls = []

    def program(lines):
        calls.append(np.asarray(lines).shape)
        return host_pairings_check(lines)

    tmp = tmp_path_factory.mktemp("bls")
    trace = tmp / "spans.jsonl"
    servers = []

    class Recording(service.SidecarServer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            servers.append(self)

    mp = pytest.MonkeyPatch()
    mp.setenv("HOTSTUFF_TPU_COMPILE_MANIFEST", str(tmp / "manifest.json"))
    mp.setattr(dbls, "pairings_check_jit", program)
    mp.setattr(service, "SidecarServer", Recording)
    ready, errors = threading.Event(), []

    def run():
        try:
            service.serve(port=0, ready_event=ready, committee=100,
                          warm_max=8, warm_bls=True, trace_path=str(trace))
        except Exception as e:  # noqa: BLE001 — handed to the test
            errors.append(e)
            ready.set()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    assert ready.wait(600), "serve() never became ready"
    assert not errors, errors
    try:
        yield servers[0].server_address[1], trace, servers[0], calls
    finally:
        servers[0].shutdown()
        thread.join(timeout=60)
        mp.undo()


def _stats(port):
    with SidecarClient(port=port, timeout=120.0) as client:
        return client.stats()


def test_an_off_curve_vote_is_rejected_without_a_launch(device_sidecar):
    """A vote that is not on G2 fails at decode: False, counted in
    ``bls.decode_rejects``, and the pairing program never runs."""
    port, _, _, calls = device_sidecar
    msg = b"off-curve qc".ljust(32)
    pks, sigs = certificate(msg, seed=160)
    bad = bytearray(sigs[1])
    bad[-1] ^= 1
    sigs[1] = bytes(bad)
    before, n_calls = _stats(port), len(calls)
    with SidecarClient(port=port, timeout=120.0) as client:
        assert client.bls_verify_votes(msg, pks, sigs) is False
    after = _stats(port)
    assert len(calls) == n_calls
    assert after["bls"]["decode_rejects"] == \
        before["bls"]["decode_rejects"] + 1
    assert after["paths"].get("bls_pairing") == \
        before["paths"].get("bls_pairing")


def test_votes_are_served_by_the_device_path_with_spans(device_sidecar):
    """Valid and forged VOTES certificates: the reference's verdicts;
    each one launch of the pairing program (``paths.bls_pairing`` +1,
    two Miller loops, no host path); its five stages nest under the
    launch's ``device`` span and fit inside it.  Last of the fixture's
    tests: it stops the server to have the spans written."""
    from hotstuff_tpu.obs.spans import parse_spans

    port, trace, server, calls = device_sidecar
    before = _stats(port)
    sent = [(b"qc digest %d" % i, *certificate(b"qc digest %d" % i,
                                               forged_row=row))
            for i, row in enumerate((None, 1, None))]
    with SidecarClient(port=port, timeout=120.0) as client:
        got = [client.bls_verify_votes(msg, pks, sigs, ctx=msg.ljust(32))
               for msg, pks, sigs in sent]
    assert got == [True, False, True]
    after = _stats(port)
    paths = after["paths"]
    assert paths.get("bls_pairing", 0) - \
        before["paths"].get("bls_pairing", 0) == 3
    assert "host" not in paths
    assert after["bls"]["requests"]["votes"] - \
        before["bls"]["requests"].get("votes", 0) == 3
    assert after["bls"]["pairings"] - before["bls"]["pairings"] == 6
    assert calls[-1] == (2, 63, 2, 12, 48)

    server.shutdown()  # the spans reach the file when serve() returns
    deadline = time.monotonic() + 60
    spans = []
    while time.monotonic() < deadline:
        if trace.exists():
            spans, _ = parse_spans(trace.read_text())
            if sum(s["stage"] == "device" for s in spans) >= 3:
                break
        time.sleep(0.1)
    devices = [s for s in spans if s["stage"] == "device"
               and s.get("kind") == "bls"]
    # The off-curve certificate of the test before (no ctx) stopped at
    # its decode: no Miller lines, no program.
    rejected = [d for d in devices if "ctx" not in d]
    assert len(rejected) == 1
    assert {s["stage"] for s in spans
            if s.get("parent") == rejected[0]["id"]} == {"bls_prep"}
    devices = [d for d in devices if "ctx" in d]
    assert len(devices) == 3
    for dev in devices:
        kids = {s["stage"]: s for s in spans
                if s.get("parent") == dev["id"]}
        assert set(kids) == set(BLS_STAGES), kids
        assert all(dev["t0"] <= k["t0"] <= k["t"] <= dev["t"]
                   for k in kids.values())
        assert sum(k["dur_ms"] for k in kids.values()) <= dev["dur_ms"]
        assert kids["bls_prep"]["n"] == 3
        assert kids["miller_lines"]["pairings"] == 2
        assert kids["pairing"]["bytes"] == 2 * 63 * 2 * 12 * 48 * 4


def test_a_replayed_certificate_is_a_cache_hit(tmp_path):
    """The same VOTES bytes twice: the second verdict comes from the
    verdict cache (``dedup.cache_hits`` +1, on the connection thread),
    not from a second pairing."""
    from hotstuff_tpu.sidecar.service import SidecarServer, VerifyEngine

    engine = VerifyEngine(use_host=True)
    srv = SidecarServer(("127.0.0.1", 0), engine)
    t = threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.1), daemon=True)
    t.start()
    try:
        port = srv.server_address[1]
        msg = b"replayed qc".ljust(32)
        pks, sigs = certificate(msg, seed=120)
        with SidecarClient(port=port, timeout=120.0) as client:
            assert client.bls_verify_votes(msg, pks, sigs)
            first = client.stats()
            assert client.bls_verify_votes(msg, pks, sigs)
            second = client.stats()
        assert second["dedup"]["cache_hits"] == \
            first["dedup"]["cache_hits"] + 1
        assert second["paths"] == first["paths"] == {"host": 1}
    finally:
        srv.shutdown()
        engine.stop()
        srv.server_close()


def test_a_host_engine_notes_paths_host():
    """``use_host``: every BLS verdict computed on the host is counted
    as ``paths.host`` (one a certificate, and none on the device
    route)."""
    from hotstuff_tpu.sidecar import service

    engine = service.VerifyEngine(use_host=True)
    try:
        replies = []
        for i, row in enumerate((None, 0)):
            msg = b"host qc %d" % i
            pks, sigs = certificate(msg, forged_row=row, seed=140)
            engine._execute_bls(service._Pending(
                proto.BlsVotesRequest(i, msg, pks, sigs), replies.append))
        assert replies == [[True], [False]]
        snap = engine.stats_snapshot()
        assert snap["paths"] == {"host": 2}
        assert snap["bls"]["requests"] == {"votes": 2}
        assert snap["bls"]["pairings"] == 0
    finally:
        engine.stop()


@pytest.mark.parametrize("ctx", [None, b"c" * 32])
def test_bls_verify_votes_frame_round_trips(ctx):
    """``SidecarClient.bls_verify_votes`` sends the frame the C++ replica
    sends: ``decode_request`` reads back the digest, keys, votes and
    context tag, and the one-byte verdict comes back as a bool."""
    import socket

    msg = b"d" * 32
    pks = [bytes([i]) * proto.BLS_PK_LEN for i in range(3)]
    sigs = [bytes([9 - i]) * proto.BLS_SIG_LEN for i in range(3)]
    got = {}
    with socket.create_server(("127.0.0.1", 0)) as lsock:
        port = lsock.getsockname()[1]

        def serve_one():
            conn, _ = lsock.accept()
            with conn:
                opcode, req = proto.decode_request(proto.read_frame(conn))
                got["opcode"], got["req"] = opcode, req
                conn.sendall(proto.encode_reply(opcode, req.request_id,
                                                [True]))

        t = threading.Thread(target=serve_one, daemon=True)
        t.start()
        with SidecarClient(port=port, timeout=30.0) as client:
            assert client.bls_verify_votes(msg, pks, sigs, ctx=ctx) is True
        t.join(timeout=30)
    assert got["opcode"] == proto.OP_BLS_VERIFY_VOTES
    req = got["req"]
    assert isinstance(req, proto.BlsVotesRequest)
    assert (req.msg, req.pks, req.sigs, req.ctx) == (msg, pks, sigs, ctx)
