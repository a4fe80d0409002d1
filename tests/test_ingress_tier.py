"""graftingress tests: the signed-transaction ingress tier's python
half — per-user key derivation + the bounded keyring LRU, the signed
frame round trip against the documented preimage construction, the
wirecheck ``txframe-mismatch`` constant extractors and the repo-clean
gate, the LogParser's signed-ingress accounting (verified goodput,
strict zero-forged-committed and shard-fairness assertions), the node
METRICS admission-verify suffix, and signed frames through the
verifier's ingress-tagged bulk lane."""

import hashlib
import os

import pytest

from conftest import REPO
from hotstuff_tpu.analysis import wirecheck
from hotstuff_tpu.crypto import txsign
from hotstuff_tpu.harness.logs import LogParser, ParseError
from hotstuff_tpu.obs.sampler import parse_node_metrics
from test_harness import GOLDEN_CLIENT, GOLDEN_NODE

# ---------------------------------------------------------------------------
# key derivation + frame construction (python twin of tx_frame.hpp)
# ---------------------------------------------------------------------------


def test_user_key_derivation_is_deterministic_and_documented():
    # The derivation IS the documented construction: SHA-512(domain ||
    # seed u64 BE || user u64 BE)[:32] — recomputed here from hashlib so
    # a refactor cannot silently change what the C++ side must mirror.
    want = hashlib.sha512(
        txsign.TX_KEY_DOMAIN + (5).to_bytes(8, "big")
        + (9).to_bytes(8, "big")).digest()[:32]
    assert txsign.derive_user_seed(5, 9) == want
    assert txsign.derive_user_keypair(5, 9) == txsign.derive_user_keypair(5, 9)
    assert txsign.derive_user_keypair(5, 9)[1] != \
        txsign.derive_user_keypair(5, 10)[1]
    assert txsign.derive_user_keypair(6, 9)[1] != \
        txsign.derive_user_keypair(5, 9)[1]


def test_keyring_lru_is_bounded_and_rederives_identically():
    ring = txsign.UserKeyring(seed=5, capacity=2)
    pk1 = ring.get(1)[1]
    ring.get(2)
    assert len(ring) == 2 and ring.derivations == 2
    ring.get(2)                       # hit: no new derivation
    assert ring.derivations == 2
    ring.get(3)                       # evicts user 1 (LRU)
    assert len(ring) == 2 and ring.derivations == 3
    assert ring.get(1)[1] == pk1      # re-derived, same key
    assert ring.derivations == 4


def test_frame_preimage_matches_documented_construction():
    kp = txsign.derive_user_keypair(5, 0)
    payload = txsign.build_payload(txsign.TX_MARKER_SAMPLE, 7, size=16)
    frame = txsign.build_signed_tx(kp, nonce=3, payload=payload)
    assert len(frame) == txsign.TX_FRAME_OVERHEAD + len(payload)
    tx = txsign.parse_signed_tx(frame)
    assert tx.pk == kp[1] and tx.nonce == 3 and tx.payload == payload
    # Preimage: SHA-512/32 over the domain tag + the frame with the
    # signature stripped — byte-for-byte, not via the library helper.
    digest, pk, sig = txsign.admission_record(frame)
    assert digest == hashlib.sha512(
        txsign.TX_SIGN_DOMAIN + frame[:-txsign.TX_SIG_LEN]).digest()[:32]
    assert txsign.verify_tx(frame)
    flipped = txsign.build_signed_tx(kp, nonce=3, payload=payload,
                                     flip_sig_bit=True)
    # A forged frame parses identically and dies only at verify.
    assert txsign.parse_signed_tx(flipped)[:3] == tx[:3]
    assert not txsign.verify_tx(flipped)


# ---------------------------------------------------------------------------
# wirecheck: the txframe-mismatch rule's extractors + the repo-clean gate
# ---------------------------------------------------------------------------


def test_wirecheck_txframe_extractors_read_cpp_idioms():
    src = (
        "constexpr size_t kTxMaxPayload = 1u << 20;\n"
        "constexpr size_t kTxFrameHeaderLen = 1 + kTxPkLen;\n"
        "static_assert(kTxFrameHeaderLen == 45, \"drifted\");\n"
        "constexpr char kTxSignDomain[] = \"graftingress-tx-v1\";\n")
    assert wirecheck.cpp_shift_constants(src) == {"kTxMaxPayload": 1 << 20}
    assert wirecheck.cpp_static_assert_values(src) == {
        "kTxFrameHeaderLen": 45}
    assert wirecheck.cpp_char_string_constants(src) == {
        "kTxSignDomain": "graftingress-tx-v1"}
    py = 'TX_SIGN_DOMAIN = b"graftingress-tx-v1"\nOTHER = "not-bytes"\n'
    assert wirecheck.py_bytes_constants(py) == {
        "TX_SIGN_DOMAIN": "graftingress-tx-v1"}


def test_wirecheck_txframe_rule_is_clean_on_repo():
    findings = [f for f in wirecheck.check(REPO)
                if f.rule == "txframe-mismatch"]
    assert findings == [], [f.render() for f in findings]


# ---------------------------------------------------------------------------
# LogParser: signed-ingress accounting + the two strict assertions
# ---------------------------------------------------------------------------

_SIGNED_CLIENT_LINES = (
    "[2026-07-29T14:54:56.456Z INFO client] Signed ingress enabled "
    "(seed 5, forge 1%, user offset 0, sample offset 0)\n"
    "[2026-07-29T14:54:57.100Z INFO client] Forged transaction sent "
    "(3 total)\n"
    "[2026-07-29T14:55:01.500Z INFO client] Sent 1000 transactions\n")

_VERIFY_NODE_LINES = (
    "[2026-07-29T14:54:55.100Z INFO mempool::config] Ingress signature "
    "verification enabled with batch 64 txs\n"
    "[2026-07-29T14:54:56.900Z WARN mempool::tx_verify] Rejected 2 "
    "forged transaction(s) at ingress admission (2 total)\n"
    "[2026-07-29T14:54:57.000Z WARN mempool::tx_verify] Admission "
    "verify busy; shed 2 tx(s) with retry-after 7 ms (2 total)\n"
    "[2026-07-29T14:54:58.000Z INFO node::metrics] METRICS commits=5 "
    "commit_rate=2.50 ingress_tx=100 ingress_bytes=5000 busy=0 "
    "breaker=closed verified=98 forged=2 vq=1\n")


def test_parser_signed_ingress_accounting_and_note():
    parser = LogParser([GOLDEN_CLIENT + _SIGNED_CLIENT_LINES],
                       [GOLDEN_NODE + _VERIFY_NODE_LINES], faults=0)
    ing = parser.ingress
    assert ing["signed"] and ing["verify_on"]
    assert ing["forge_pct"] == 1.0
    assert ing["forged_sent"] == 3
    assert ing["sent"] == 1000
    assert ing["verified"] == 98
    assert ing["forged_rejected"] == 2
    assert ing["busy_shed"] == 2
    assert ing["forged_committed"] == 0
    assert ing["shards"] == 0           # one client process, no shards
    assert parser.configs[0]["mempool"]["verify_batch"] == 64
    assert any(n.startswith("Signed ingress:") for n in parser.notes)


def test_parser_legacy_unsigned_logs_parse_unchanged():
    parser = LogParser([GOLDEN_CLIENT], [GOLDEN_NODE], faults=0)
    assert not parser.ingress["signed"]
    assert not parser.ingress["verify_on"]
    assert parser.ingress["forged_committed"] == 0
    assert not any("Signed ingress" in n for n in parser.notes)


def test_parser_rejects_forged_commit_on_verify_run_only():
    forged_batch = (
        "[2026-07-29T14:54:56.950Z WARN mempool::batch_maker] Batch "
        "2hHolx56fF0YIblphIzIeT2IHMTpt2ISKPP/4qqCsaU= contains forged "
        "tx 9\n")
    # verify-ingress ON + a forged tx inside a sealed batch: the run is
    # meaningless and the parser must say so loudly.
    with pytest.raises(ParseError, match="forged transaction"):
        LogParser([GOLDEN_CLIENT + _SIGNED_CLIENT_LINES],
                  [GOLDEN_NODE + _VERIFY_NODE_LINES + forged_batch],
                  faults=0)
    # verify-ingress OFF (unsigned A/B leg): the same line is counted
    # but not fatal — there was no admission stage to blame.
    parser = LogParser([GOLDEN_CLIENT],
                       [GOLDEN_NODE + forged_batch], faults=0)
    assert parser.ingress["forged_committed"] == 1


def _shard_client(sample_offset, sent):
    return GOLDEN_CLIENT + (
        "[2026-07-29T14:54:56.456Z INFO client] Signed ingress enabled "
        f"(seed 5, forge 1%, user offset 0, sample offset {sample_offset})\n"
        f"[2026-07-29T14:55:01.500Z INFO client] Sent {sent} "
        "transactions\n")


def test_parser_shard_fairness_strict_and_noted():
    # Balanced shards: accepted, with a per-shard note.
    parser = LogParser([_shard_client(0, 1000), _shard_client(100000, 900)],
                       [GOLDEN_NODE + _VERIFY_NODE_LINES], faults=0)
    assert parser.ingress["shards"] == 2
    assert sorted(parser.ingress["shard_sent"]) == [900, 1000]
    assert any(n.startswith("Client shards: 2") for n in parser.notes)
    # A starved shard (beyond 4x divergence) is a parse-level failure.
    with pytest.raises(ParseError, match="fairness"):
        LogParser([_shard_client(0, 1000), _shard_client(100000, 100)],
                  [GOLDEN_NODE + _VERIFY_NODE_LINES], faults=0)


def test_sampler_metrics_verify_suffix_is_optional():
    with_suffix = (
        "[2026-07-29T14:54:58.000Z INFO node] METRICS commits=5 "
        "commit_rate=2.50 ingress_tx=100 ingress_bytes=5000 busy=0 "
        "breaker=closed verified=98 forged=2 vq=1\n")
    legacy = (
        "[2026-07-29T14:54:59.000Z INFO node] METRICS commits=6 "
        "commit_rate=2.60 ingress_tx=120 ingress_bytes=6000 busy=1 "
        "breaker=closed\n")
    recs = parse_node_metrics(with_suffix + legacy)
    assert len(recs) == 2
    assert recs[0]["metrics"]["verified"] == 98
    assert recs[0]["metrics"]["forged"] == 2
    assert recs[0]["metrics"]["vq"] == 1
    assert "verified" not in recs[1]["metrics"]
    assert recs[1]["metrics"]["commits"] == 6


# ---------------------------------------------------------------------------
# signed frames through the verifier's ingress-tagged bulk lane
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("users", [50, 120])
def test_signed_frames_through_the_ingress_tagged_bulk_lane(users):
    """The admission path end to end in process, at a small population:
    the seeded generator names each arrival's user, the keyring derives
    that user's key on FIRST arrival (one derivation a distinct user),
    version-2 signed frames become (digest, pk, sig) admission records,
    and INGRESS_CTX-tagged bulk requests to a host-mode engine come back
    with exactly the forged frames rejected.  OP_STATS ``ingress`` must
    count the lane wholly ingress-fed — the tag the mempool's
    admission-verify stage uses is what tells it from off-chain bulk."""
    import threading

    from hotstuff_tpu.harness.loadgen import UserLoad
    from hotstuff_tpu.sidecar import protocol as proto
    from hotstuff_tpu.sidecar import sched as vsched
    from hotstuff_tpu.sidecar.service import VerifyEngine

    n, batch = 24, 8
    load = UserLoad(rate=64.0, users=users, seed=13)
    arrivals: list = []
    tick = 0
    while len(arrivals) < n and tick < 4096:
        tick += 1
        load.arrivals(tick * 0.025, arrivals)
    arrivals = arrivals[:n]
    keyring = txsign.UserKeyring(seed=7, capacity=4096)
    forged = [i in (5, n - 1) for i in range(n)]
    frames = [
        txsign.build_signed_tx(
            keyring.get(user), nonce=i,
            payload=txsign.build_payload(
                txsign.TX_MARKER_FORGED if forged[i]
                else txsign.TX_MARKER_FILLER, i),
            flip_sig_bit=forged[i])
        for i, user in enumerate(arrivals)]
    assert 1 <= len(set(arrivals)) <= users
    assert keyring.derivations == len(set(arrivals))
    records = [txsign.admission_record(f) for f in frames]

    masks: dict = {}
    cond = threading.Condition()

    def reply_to(rid):
        def _reply(mask):
            with cond:
                masks[rid] = mask
                cond.notify_all()
        return _reply

    eng = VerifyEngine(use_host=True)
    try:
        rids = []
        for b in range(0, n, batch):
            chunk = records[b:b + batch]
            rid = 1 + b // batch
            assert eng.submit(
                proto.VerifyRequest(
                    rid, [r[0] for r in chunk], [r[1] for r in chunk],
                    [r[2] for r in chunk], ctx=txsign.INGRESS_CTX),
                reply_to(rid), cls=vsched.BULK)
            rids.append(rid)
        with cond:
            assert cond.wait_for(lambda: all(r in masks for r in rids),
                                 timeout=120.0)
        snap = eng.stats_snapshot()["ingress"]
    finally:
        eng.stop()
    flat = [bool(ok) for rid in rids for ok in masks[rid]]
    assert flat == [not f for f in forged]
    assert snap["bulk_sigs"] == n and snap["bulk_requests"] == len(rids)
    assert snap["offchain_sigs"] == 0


@pytest.mark.slow
def test_signed_ingress_e2e_local(tmp_path, monkeypatch):
    """The graftingress acceptance drill against REAL processes: a
    4-node committee with ``verify_ingress`` on, sharded signing
    clients (``client_shards=2`` per node) streaming per-user-signed
    frames with a seeded 1% forgery mix.  The run must commit, the
    admission stage must reject forgeries, and the parser's strict
    invariants (zero forged txs in any sealed batch, shard fairness)
    must hold — LogParser raises otherwise, so a clean return IS the
    assertion; the checks below pin the machine-readable evidence."""
    from conftest import NODE_BIN
    from hotstuff_tpu.harness.config import BenchParameters, NodeParameters
    from hotstuff_tpu.harness.local import LocalBench

    if not os.path.exists(NODE_BIN):
        pytest.skip("native binaries not built (scripts/native_build.sh)")
    monkeypatch.chdir(tmp_path)
    os.symlink(os.path.join(REPO, "native"), tmp_path / "native")

    params = BenchParameters({
        "faults": 0, "nodes": 4, "rate": 400, "tx_size": 64,
        "duration": 20, "verify_ingress": True, "forge_pct": 1.0,
        "client_shards": 2})
    node_params = NodeParameters.default()
    parser = LocalBench(params, node_params).run()

    ing = parser.ingress
    assert ing["signed"] and ing["verify_on"]
    assert ing["forged_sent"] >= 1, ing
    assert ing["forged_rejected"] >= 1, ing
    assert ing["forged_committed"] == 0
    assert ing["shards"] >= 2, ing      # 4 nodes x 2 shard processes
    assert any(n.startswith("Signed ingress:") for n in parser.notes)
    # The run still commits real throughput under the signed stream.
    assert "TPS:" in parser.result()
