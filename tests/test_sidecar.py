"""Sidecar service tests: protocol round-trip, server end-to-end, coalescing.

Analogue of the reference's SignatureService tests
(crypto/src/tests/crypto_tests.rs:118-132) at the process boundary.
"""

import threading

import numpy as np
import pytest

from hotstuff_tpu.crypto import ref_ed25519 as ref
from hotstuff_tpu.sidecar import protocol as proto
from hotstuff_tpu.sidecar.client import SidecarClient
from hotstuff_tpu.sidecar.service import SidecarServer, VerifyEngine


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


def test_protocol_roundtrip():
    msgs, pks, sigs = _sigs(3)
    frame = proto.encode_request(42, msgs, pks, sigs)
    opcode, req = proto.decode_request(frame[4:])
    assert opcode == proto.OP_VERIFY_BATCH
    assert req.request_id == 42
    assert req.msgs == msgs and req.pks == pks and req.sigs == sigs

    reply = proto.encode_reply(proto.OP_VERIFY_BATCH, 42, [True, False, True])
    opcode, rid, mask = proto.decode_reply(reply[4:])
    assert (opcode, rid, mask) == (proto.OP_VERIFY_BATCH, 42,
                                   [True, False, True])


@pytest.fixture(scope="module")
def server():
    engine = VerifyEngine()
    srv = SidecarServer(("127.0.0.1", 0), engine)
    t = threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.1), daemon=True)
    t.start()
    yield srv
    srv.shutdown()
    engine.stop()
    srv.server_close()


def test_sidecar_end_to_end(server):
    port = server.server_address[1]
    # The first verify compiles its bucket on the CPU: ~30 s alone, past
    # the client's default 60 s under six loaded workers.
    with SidecarClient(port=port, timeout=300.0) as client:
        assert client.ping()
        msgs, pks, sigs = _sigs(10, tamper={3, 7})
        mask = client.verify_batch(msgs, pks, sigs)
        assert mask == [i not in {3, 7} for i in range(10)]


def test_sidecar_concurrent_clients(server):
    port = server.server_address[1]
    results = {}

    def worker(idx):
        with SidecarClient(port=port) as client:
            tamper = {idx}
            msgs, pks, sigs = _sigs(5, tamper=tamper)
            results[idx] = client.verify_batch(msgs, pks, sigs)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for idx, mask in results.items():
        assert mask == [i != idx for i in range(5)]


def test_sidecar_empty_batch(server):
    port = server.server_address[1]
    with SidecarClient(port=port) as client:
        assert client.verify_batch([], [], []) == []


@pytest.fixture
def served(monkeypatch, tmp_path):
    """Boot the real ``serve()`` entry in a thread; yields ``boot(**kw)
    -> (errors, servers)``: what serve() raised, and every SidecarServer
    the boot constructed (none = the socket never bound)."""
    from hotstuff_tpu.sidecar import service

    monkeypatch.setenv("HOTSTUFF_TPU_COMPILE_MANIFEST",
                       str(tmp_path / "manifest.json"))
    servers, threads = [], []

    class Recording(service.SidecarServer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            servers.append(self)

    monkeypatch.setattr(service, "SidecarServer", Recording)

    def boot(**kw):
        ready = threading.Event()
        errors = []

        def run():
            try:
                service.serve(port=0, ready_event=ready, **kw)
            except Exception as e:  # noqa: BLE001 — handed to the test
                errors.append(e)
                ready.set()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        threads.append(t)
        assert ready.wait(600), "serve() never became ready"
        return errors, servers

    yield boot
    for srv in servers:
        srv.shutdown()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()


@pytest.mark.parametrize("use_host", [False, True])
def test_stats_name_the_device_only_on_a_jax_boot(served, use_host):
    """OP_STATS ``device``: which devices the engine launches on, as jax
    reports them — so a client can tell a CPU sidecar from a TPU one.
    Absent under --host-crypto, which holds no device."""
    errors, servers = served(use_host=use_host, warm_max=8)
    assert not errors
    with SidecarClient(port=servers[0].server_address[1]) as client:
        stats = client.stats()
    if use_host:
        assert "device" not in stats
    else:
        import jax

        assert stats["device"] == {
            "platform": "cpu", "kind": jax.devices()[0].device_kind,
            "count": 1}
        assert stats["compile"]["misses"] + stats["compile"]["hits"] == 1


def test_false_warmup_verdict_aborts_serve_before_bind(served, monkeypatch):
    """A device leg that judges a VALID signature false must not serve:
    serve() raises out of the warmup, before any socket exists."""
    monkeypatch.setattr(
        VerifyEngine, "_verify",
        lambda self, msgs, pks, sigs: np.zeros(len(msgs), bool))
    errors, servers = served(warm_max=8)
    assert len(errors) == 1 and isinstance(errors[0], RuntimeError)
    assert "returned false for a valid signature" in str(errors[0])
    assert servers == []


@pytest.mark.parametrize("tenants,records", [(6, 8), (3, 4)])
def test_coalesced_tenants_get_the_reference_mask(served, tmp_path, tenants,
                                                  records):
    """The path a launch with MANY requests takes (``ingress20.gate`` at
    a size the CPU compiles): ``tenants`` connections, a HELLO name
    each, one bulk request of ``records`` signatures in flight each, two
    rounds, seeded forgeries.  Through the real scheduler and device
    program every mask equals the plain reference per record, some
    launch holds requests of several tenants, no request is split over
    launches and every tenant is answered in every round."""
    import time

    from hotstuff_tpu.crypto.eddsa import _bucket
    from hotstuff_tpu.obs.spans import parse_spans

    rounds = 2
    trace = tmp_path / "spans.jsonl"
    errors, servers = served(warm_max=_bucket(tenants * records),
                             committee=tenants, trace_path=str(trace))
    assert not errors
    port = servers[0].server_address[1]
    rng = np.random.default_rng(records)
    batches = {(t, r): _sigs(
        records, seed=[records, t, r], tamper=set(rng.choice(
            records, rng.integers(0, 3), replace=False).tolist()))
        for t in range(tenants) for r in range(rounds)}
    want = {k: [bool(ref.verify(pk, m, s)) for m, pk, s in zip(*b)]
            for k, b in batches.items()}
    assert sum(m.count(False) for m in want.values()) >= 1
    got = {}
    barrier = threading.Barrier(tenants)

    def gate(t):
        with SidecarClient(port=port, timeout=300.0) as client:
            client.hello(f"gate-{t}")
            for r in range(rounds):
                barrier.wait(timeout=300)
                got[t, r] = client.verify_batch(*batches[t, r], bulk=True)

    threads = [threading.Thread(target=gate, args=(t,), daemon=True)
               for t in range(tenants)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=600)
        assert not th.is_alive()
    assert got == want
    with SidecarClient(port=port) as client:
        stats = client.stats()
    # OP_STATS: every launch is in the tenants histogram, one of them
    # held several tenants, nothing was refused, every gate was served
    # in every round.
    hist = {int(k): v for k, v in stats["tenants_hist"].items()}
    assert sum(hist.values()) == stats["launches"]
    assert max(hist) >= 2
    assert stats["queue_full"] == {} and "host" not in stats["paths"]
    assert stats["sigs_launched"] == tenants * rounds * records
    for t in range(tenants):
        mine = stats["tenants"][f"gate-{t}"]
        assert mine["admitted"] == {"bulk": rounds} and mine["shed"] == {}
        assert mine["queue_wait"]["bulk"]["n"] == rounds
    # The spans reach the file when serve() returns, in one write.
    servers[0].shutdown()
    deadline = time.monotonic() + 60
    devices = []
    while len(devices) < stats["launches"] and time.monotonic() < deadline:
        time.sleep(0.05)
        if trace.exists():
            spans, _ = parse_spans(trace.read_text())
            devices = [s for s in spans if s["stage"] == "device"]
    assert len(devices) == stats["launches"]
    assert sum(d["reqs"] for d in devices) == tenants * rounds
    assert max(d["tenants"] for d in devices) >= 2
    for d in devices:
        assert 1 <= d["tenants"] <= d["reqs"] and d["sigs"] == \
            d["reqs"] * records <= d["bucket"] == _bucket(d["sigs"])
    # No request is split: one ``queue`` span a request, naming the one
    # launch it left for.
    roots = [s for s in spans if s["stage"] == "request"]
    queues = [s for s in spans if s["stage"] == "queue"]
    assert len(roots) == len(queues) == tenants * rounds
    assert sorted(q["parent"] for q in queues) == sorted(
        r["id"] for r in roots)
    by_lid = {d["lid"]: d for d in devices}
    for lid in {q["lid"] for q in queues}:
        assert sum(q["lid"] == lid for q in queues) == by_lid[lid]["reqs"]


@pytest.fixture(scope="module")
def host_server():
    """Host-crypto server: exercises the BLS ops without device compiles."""
    engine = VerifyEngine(use_host=True)
    srv = SidecarServer(("127.0.0.1", 0), engine)
    t = threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.1), daemon=True)
    t.start()
    yield srv
    srv.shutdown()
    engine.stop()
    srv.server_close()


def test_sidecar_bls_sign_and_aggregate_verify(host_server):
    """The scheme=bls wire surface: sidecar signing + common-message
    aggregate verification (the QC verify shape of the reference's bls
    branch)."""
    from hotstuff_tpu.offchain import bls12381 as bls

    port = host_server.server_address[1]
    msg = b"qc digest under bls"
    keys = [bls.key_gen(bytes([i]) * 32) for i in range(1, 4)]
    pk_enc = [bls.g1_encode(pk) for _, pk in keys]
    with SidecarClient(port=port) as client:
        sigs = [client.bls_sign(msg, sk.to_bytes(48, "big"))
                for sk, _ in keys]
        assert all(len(s) == 192 for s in sigs)
        agg = bls.g2_encode(bls.aggregate([bls.g2_decode(s) for s in sigs]))
        assert client.bls_verify_aggregate(msg, agg, pk_enc)
        # tampered aggregate rejects
        bad = bls.g2_encode(bls.aggregate(
            [bls.g2_decode(s) for s in sigs[:2]]
            + [bls.sign(keys[0][0], b"other")]))
        assert not client.bls_verify_aggregate(msg, bad, pk_enc)
        # garbage bytes reject instead of crashing the connection
        assert not client.bls_verify_aggregate(msg, b"\x01" * 192, pk_enc)
        assert client.ping()  # connection still healthy


def test_sidecar_bls_multi_digest_verify(host_server):
    """The TC wire shape (OP_BLS_VERIFY_MULTI): per-vote signatures over
    DISTINCT digests verified in one round-trip (round-3 verdict: this
    used to be N per-signature RPCs at view-change time)."""
    from hotstuff_tpu.offchain import bls12381 as bls

    port = host_server.server_address[1]
    keys = [bls.key_gen(bytes([i]) * 32) for i in range(1, 5)]
    msgs = [bytes([i]) * 32 for i in range(4)]  # distinct per-vote digests
    pk_enc = [bls.g1_encode(pk) for _, pk in keys]
    sig_enc = [bls.g2_encode(bls.sign(sk, m))
               for (sk, _), m in zip(keys, msgs)]
    with SidecarClient(port=port) as client:
        assert client.bls_verify_multi(msgs, pk_enc, sig_enc)
        # one signature over the wrong digest rejects the whole TC
        bad = list(sig_enc)
        bad[2] = bls.g2_encode(bls.sign(keys[2][0], b"wrong" * 7))
        assert not client.bls_verify_multi(msgs, pk_enc, bad)
        # signature order can't matter (the aggregate is a sum) ...
        assert client.bls_verify_multi(msgs, pk_enc,
                                       sig_enc[::-1])
        # ... but the pk<->digest pairing does: swapped keys reject
        swapped_pks = [pk_enc[1], pk_enc[0]] + pk_enc[2:]
        assert not client.bls_verify_multi(msgs, swapped_pks, sig_enc)
        # garbage signature bytes reject instead of crashing
        assert not client.bls_verify_multi(msgs, pk_enc,
                                           [b"\x02" * 192] * 4)
        assert client.ping()


def test_protocol_decode_survives_hostile_bytes():
    """Wire-decode fuzz (python counterpart of native test_serde's
    hostile-bytes pass): decode_request raises ValueError on EVERY
    malformed frame — truncations, trailing bytes, hostile counts,
    random garbage — and decodes intact frames; nothing else escapes."""
    import struct

    rng = np.random.default_rng(99)

    good_frames = [
        proto.encode_request(1, [b"m" * 32] * 3, [b"p" * 32] * 3,
                             [b"s" * 64] * 3),
        proto.encode_request(7, [b"m" * 32] * 3, [b"p" * 32] * 3,
                             [b"s" * 64] * 3,
                             opcode=proto.OP_VERIFY_BULK),
        proto.encode_bls_agg_request(3, b"d" * 32, b"g" * 192,
                                     [b"k" * 96] * 2),
        proto.encode_bls_sign_request(4, b"d" * 32, b"x" * 48),
        proto.encode_bls_votes_request(5, b"d" * 32, [b"k" * 96] * 2,
                                       [b"g" * 192] * 2),
        proto.encode_bls_multi_request(6, [b"d" * 32] * 2, [b"k" * 96] * 2,
                                       [b"g" * 192] * 2),
    ]
    for frame in good_frames:
        payload = frame[4:]
        opcode, req = proto.decode_request(payload)  # intact decodes
        assert req.request_id == opcode  # encoders above used rid == op
        # every strict truncation and any trailing garbage must reject
        for cut in range(len(payload)):
            with pytest.raises(ValueError):
                proto.decode_request(payload[:cut])
        with pytest.raises(ValueError):
            proto.decode_request(payload + b"\x00" * 5)

    # PING/STATS carry no records; trailing bytes are explicitly tolerated
    opcode, req = proto.decode_request(proto.encode_ping(2)[4:] + b"\x00")
    assert opcode == proto.OP_PING
    opcode, req = proto.decode_request(
        proto.encode_stats_request(8)[4:] + b"\x00")
    assert opcode == proto.OP_STATS

    # hostile stats bodies reject instead of crashing the client
    with pytest.raises(ValueError):
        proto.decode_stats_body(b"\xff\xfe not json")
    with pytest.raises(ValueError):
        proto.decode_stats_body(b"[1, 2, 3]")
    assert proto.decode_stats_body(b"{\"launches\": 3}") == {"launches": 3}

    # random garbage: ValueError or (rarely) a well-formed parse, nothing else
    for size in (0, 1, 4, 10, 11, 64, 333):
        try:
            proto.decode_request(bytes(rng.bytes(size)))
        except ValueError:
            pass

    # hostile record counts far beyond the actual frame size must reject
    # BEFORE any allocation sized by the count (uses the real header
    # struct so this tracks wire-format changes)
    for op in (proto.OP_VERIFY_BATCH, proto.OP_VERIFY_BULK,
               proto.OP_BLS_VERIFY_AGG, proto.OP_BLS_VERIFY_VOTES,
               proto.OP_BLS_VERIFY_MULTI):
        hostile = proto._HDR.pack(op, 7, 0xFFFFFF, 32) + b"\x01" * 64
        with pytest.raises(ValueError):
            proto.decode_request(hostile)


def test_engine_mesh_mode_buckets_to_warmed_shapes(monkeypatch):
    """VerifyEngine(mesh_devices=8) on the virtual CPU mesh: requests of
    awkward sizes must verify correctly AND pad to power-of-two per-shard
    shapes (the round-3 advisor's mid-traffic compile hazard — only
    warmed shapes may reach the device program)."""
    from hotstuff_tpu.parallel import sharded_verify as sv

    # Spy on the pack-stage h2d seam (_shard_put): every mesh launch
    # ships its padded per-record arrays through it, so the row counts
    # it sees ARE the launched shapes.  (The verifier factories are
    # functools.cached across the test session and can't be spied.)
    launched = []
    real_put = sv._shard_put

    def spying(mesh, arr):
        launched.append(arr.shape[0])
        return real_put(mesh, arr)

    monkeypatch.setattr(sv, "_shard_put", spying)
    engine = VerifyEngine(mesh_devices=8)
    try:
        # n=3 -> per-shard 1 (floored at _MIN_BUCKET/8) -> m=8;
        # n=13 -> per-shard 2 -> m=16: always n_dev * power-of-two.
        for n, tamper, want_m in ((3, {1}, 8), (8, set(), 8),
                                  (13, {0, 12}, 16)):
            launched.clear()
            msgs, pks, sigs = _sigs(n, tamper=tamper)
            got = engine._verify(msgs, pks, sigs)
            assert list(got) == [i not in tamper for i in range(n)]
            # One ladder launch = the five packed arrays, all at the
            # shard-aligned row count.
            assert launched == [want_m] * 5, (n, launched)
    finally:
        engine.stop()


def test_bls_verdict_cache_dedups_pairings(host_server):
    """N replicas verifying one certificate must cost one pairing: the
    second identical BLS verify answers from the verdict cache (on the
    connection thread - no engine hop), for positive AND negative
    verdicts, without poisoning different requests."""
    from unittest.mock import patch

    from hotstuff_tpu.offchain import bls12381 as bls

    port = host_server.server_address[1]
    engine = host_server.engine
    keys = [bls.key_gen(bytes([40 + i]) * 32) for i in range(1, 4)]
    msg = b"cache me" * 4
    pk_enc = [bls.g1_encode(pk) for _, pk in keys]
    agg = bls.g2_encode(bls.aggregate(
        [bls.sign(sk, msg) for sk, _ in keys]))
    with SidecarClient(port=port) as client:
        assert client.bls_verify_aggregate(msg, agg, pk_enc)
        # Replay: the engine must not pair again.  verify_aggregate_common
        # is the host pairing entry - a second call would go through it.
        with patch.object(bls, "verify_aggregate_common",
                          side_effect=AssertionError("paired twice")):
            assert client.bls_verify_aggregate(msg, agg, pk_enc)
        # Negative verdicts cache too, and only for their exact bytes.
        bad = bls.g2_encode(bls.sign(keys[0][0], b"forged" * 5))
        assert not client.bls_verify_aggregate(msg, bad, pk_enc)
        with patch.object(bls, "verify_aggregate_common",
                          side_effect=AssertionError("paired twice")):
            assert not client.bls_verify_aggregate(msg, bad, pk_enc)
        # Distinct request still verifies correctly (cache miss).
        msg2 = b"other msg" * 3
        agg2 = bls.g2_encode(bls.aggregate(
            [bls.sign(sk, msg2) for sk, _ in keys]))
        assert client.bls_verify_aggregate(msg2, agg2, pk_enc)
    assert any(k and isinstance(k, tuple) and k[0] == "ba"
               for k in engine._verdicts)


def test_bls_transient_failure_replies_none_and_never_caches(host_server):
    """The verdict cache is shared by every replica, so a TRANSIENT
    failure (wedged device, backend exception) must reply None and leave
    the cache untouched — a cached [False] would reject a valid
    certificate fleet-wide.  Verdicts enter the cache only at the
    explicit cacheable=True sites in _execute_bls."""
    from unittest.mock import patch

    from hotstuff_tpu.offchain import bls12381 as bls
    from hotstuff_tpu.sidecar import service

    engine = host_server.engine
    keys = [bls.key_gen(bytes([60 + i]) * 32) for i in range(1, 4)]
    msg = b"transient" * 4
    pk_enc = [bls.g1_encode(pk) for _, pk in keys]
    agg = bls.g2_encode(bls.aggregate([bls.sign(sk, msg)
                                       for sk, _ in keys]))
    req = proto.BlsAggRequest(9, msg, agg, pk_enc)
    key = engine.bls_cache_key(req)
    assert key not in engine._verdicts

    # Engine-thread behavior under a transient backend failure: the
    # exception is contained INSIDE _execute_bls, which answers None
    # through its single idempotent reply helper (graftview satellite:
    # _run installs no backstop reply any more, so a path that both
    # replied and raised can no longer double-reply).
    replies = []
    with patch.object(bls, "verify_aggregate_common",
                      side_effect=RuntimeError("device wedged")):
        engine._execute_bls(service._Pending(req, replies.append))
    assert replies == [None], "transient failure must reply exactly None"
    assert key not in engine._verdicts, "transient failure poisoned cache"

    # A retry without the fault verifies and NOW caches the true verdict.
    engine._execute_bls(service._Pending(req, replies.append))
    assert replies == [None, [True]]
    assert engine._verdicts[key] is True


def test_bls_single_reply_discipline_suppresses_double_reply(host_server):
    """Every BLS path answers EXACTLY once: an exception escaping AFTER
    a successful reply (the wedged-then-completing shape the guard will
    produce once BLS launches are supervised, ROADMAP item 3) must not
    drive the error path into a second reply — the idempotent helper
    suppresses it."""
    from hotstuff_tpu.offchain import bls12381 as bls
    from hotstuff_tpu.sidecar import service

    engine = host_server.engine
    sk, pk = bls.key_gen(bytes([55]) * 32)
    msg = b"once" * 8
    sig = bls.g2_encode(bls.sign(sk, msg))
    req = proto.BlsVotesRequest(11, msg, [bls.g1_encode(pk)], [sig])

    attempts = []

    def reply_then_die(payload):
        attempts.append(payload)
        raise BrokenPipeError("client went away mid-reply")

    # The reply itself raises: _execute_bls's exception handler runs
    # with replied already set — its None is suppressed, and exactly one
    # reply attempt (the real verdict) was made.
    engine._execute_bls(service._Pending(req, reply_then_die))
    assert attempts == [[True]]


def test_bls_decode_failure_is_cacheable_false(host_server):
    """Decode failures are a pure function of the request bytes, so they
    cache as False (same request -> same rejection, no pairing)."""
    from hotstuff_tpu.sidecar import service

    engine = host_server.engine
    req = proto.BlsAggRequest(11, b"m" * 32, b"\x01" * 192, [b"\x02" * 96])
    replies = []
    engine._execute_bls(service._Pending(req, replies.append))
    assert replies == [[False]]
    assert engine._verdicts[engine.bls_cache_key(req)] is False


# ---------------------------------------------------------------------------
# graftchaos: the protocol v3 OP_CHAOS hook (service.ChaosState)
# ---------------------------------------------------------------------------


def test_protocol_chaos_roundtrip_and_hostile_bytes():
    frame = proto.encode_chaos_request(5, {"delay_ms": 100, "shed": 2})
    opcode, req = proto.decode_request(frame[4:])
    assert opcode == proto.OP_CHAOS
    assert req.request_id == 5
    assert req.spec == {"delay_ms": 100, "shed": 2}
    # body length must match the count field; garbage JSON raises
    import struct

    bad = proto._HDR.pack(proto.OP_CHAOS, 1, 4, 0) + b"{}"
    with pytest.raises(ValueError):
        proto.decode_request(bad)
    bad = proto._HDR.pack(proto.OP_CHAOS, 1, 5, 0) + b"{nope"
    with pytest.raises(ValueError):
        proto.decode_request(bad)
    bad = proto._HDR.pack(proto.OP_CHAOS, 1, 2, 0) + b"[]"
    with pytest.raises(ValueError):
        proto.decode_request(bad)
    assert struct.unpack(">I", frame[:4])[0] == len(frame) - 4


@pytest.fixture(scope="module")
def chaos_server():
    """Host-crypto server with the chaos hook armed (--chaos)."""
    from hotstuff_tpu.sidecar.service import ChaosState

    engine = VerifyEngine(use_host=True)
    srv = SidecarServer(("127.0.0.1", 0), engine, chaos=ChaosState())
    t = threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.1), daemon=True)
    t.start()
    yield srv
    srv.shutdown()
    engine.stop()
    srv.server_close()


def test_chaos_refused_without_flag(host_server):
    port = host_server.server_address[1]
    with SidecarClient(port=port) as client:
        assert client.chaos(shed=1) is False
        # ... and nothing was configured: verifies run normally
        msgs, pks, sigs = _sigs(3)
        assert client.verify_batch(msgs, pks, sigs) == [True] * 3


def test_chaos_forced_shed_then_recovers(chaos_server):
    from hotstuff_tpu.sidecar.client import SidecarOverloaded

    port = chaos_server.server_address[1]
    with SidecarClient(port=port) as client:
        assert client.chaos(shed=2) is True
        msgs, pks, sigs = _sigs(4)
        for _ in range(2):
            with pytest.raises(SidecarOverloaded):
                client.verify_batch(msgs, pks, sigs)
        # budget consumed: the next verify is honest again
        assert client.verify_batch(msgs, pks, sigs) == [True] * 4


def test_chaos_bounded_delay_applies_and_clears(chaos_server):
    import threading
    import time

    port = chaos_server.server_address[1]
    with SidecarClient(port=port) as client:
        msgs, pks, sigs = _sigs(2)
        client.verify_batch(msgs, pks, sigs)  # warm: engine, not chaos
        assert client.chaos(delay_ms=300) is True
        t0 = time.monotonic()
        assert client.verify_batch(msgs, pks, sigs) == [True] * 2
        assert time.monotonic() - t0 >= 0.3
        # PING is exempt EVEN when pipelined behind a delayed verify on
        # the same connection: delays reschedule onto a timer, the
        # reader thread keeps draining (readiness probes stay honest).
        done = {}

        def delayed_verify():
            done["mask"] = client.verify_batch(msgs, pks, sigs)

        t = threading.Thread(target=delayed_verify)
        t.start()
        time.sleep(0.05)  # verify request is in flight, reply delayed
        t0 = time.monotonic()
        assert client.ping()
        assert time.monotonic() - t0 < 0.25
        t.join(timeout=10)
        assert done["mask"] == [True] * 2
        assert client.chaos(clear=True) is True
        t0 = time.monotonic()
        assert client.verify_batch(msgs, pks, sigs) == [True] * 2
        assert time.monotonic() - t0 < 0.25


def test_chaos_delay_capped_at_maximum(chaos_server):
    from hotstuff_tpu.sidecar.service import ChaosState

    state = chaos_server.chaos
    state.configure({"delay_ms": 10 ** 9})
    assert state.delay_ms == ChaosState.MAX_DELAY_MS
    state.configure({"clear": True})
    assert state.delay_ms == 0
    with pytest.raises(ValueError):
        state.configure({"explode": 1})
    with pytest.raises(ValueError):
        state.configure({"shed": -1})
    with pytest.raises(ValueError):
        state.configure({"shed": True})


def test_chaos_connection_drop(chaos_server):
    port = chaos_server.server_address[1]
    with SidecarClient(port=port) as control:
        assert control.chaos(drop=1) is True
        msgs, pks, sigs = _sigs(2)
        # The victim connection dies on its next verify...
        with SidecarClient(port=port) as victim:
            with pytest.raises((ConnectionError, OSError)):
                victim.verify_batch(msgs, pks, sigs)
        # ...and the server is healthy for the connection after it.
        assert control.verify_batch(msgs, pks, sigs) == [True] * 2
