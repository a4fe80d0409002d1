"""A committee whose Byzantine third forges votes, through the served
path: ``serve()`` in a thread, ``SidecarClient`` over the socket, the
CPU backend.  Committee 24 -> quorum 17 -> ``rlc`` bucket 32, the shape
of ``benchmark/rehearsal/configs/qc24f7.json`` (the chip's is
``benchmark/configs/qc100f33.json``: 100 -> 67 -> 128).

Every reply is held, bit for bit, to one plain reference verify per
signature; the ``bisect.*`` counters of OP_STATS and the ``bisect`` /
``bisect_step`` spans are held to what the bisection has to run."""

import hashlib
import random
import threading

import pytest

from hotstuff_tpu.crypto import ref_ed25519 as ref
from hotstuff_tpu.obs.spans import parse_spans
from hotstuff_tpu.sidecar.client import SidecarClient

COMMITTEE = 24
QUORUM = 2 * COMMITTEE // 3 + 1        # 17, the node's own formula
RLC_MIN_MSM = 4                        # crypto/eddsa.py: the bisection floor

CASES = [(kind, k) for kind in ("qc", "tc") for k in (1, 2, 6)]


def _certificate(kind: str, k: int):
    """A 17-vote certificate of a seeded 17 of the 24 validators — a QC
    (one common digest) or a TC (a message a vote) — with ``k`` votes
    forged (one bit of S flipped) at seeded rows.  Returns (msgs, pks,
    sigs, forged rows)."""
    rng = random.Random(f"byz-{kind}-{k}")
    secrets = [hashlib.sha512(b"validator-%d" % i).digest()[:32]
               for i in rng.sample(range(COMMITTEE), QUORUM)]
    tag = f"{kind}-{k}".encode()
    if kind == "qc":
        msgs = [hashlib.sha512(b"digest-" + tag).digest()[:32]] * QUORUM
    else:
        msgs = [hashlib.sha512(b"timeout-%d-" % i + tag).digest()[:32]
                for i in range(QUORUM)]
    pks = [ref.generate_keypair(sk)[1] for sk in secrets]
    sigs = [ref.sign(sk, m) for sk, m in zip(secrets, msgs)]
    forged = sorted(rng.sample(range(QUORUM), k))
    for row in forged:
        sigs[row] = sigs[row][:32] + bytes([sigs[row][32] ^ 1]) \
            + sigs[row][33:]
    return msgs, pks, sigs, forged


def _programs(rows: list, bad: set) -> tuple:
    """What resolving ``rows`` has to run, from the rule alone: (device
    programs, rows a per-signature leaf resolves).  Under RLC_MIN_MSM
    rows, one per-signature program; else one combined check and, if it
    holds a forged row, both halves."""
    if len(rows) < RLC_MIN_MSM:
        return 1, len(rows)
    if not bad & set(rows):
        return 1, 0
    mid = len(rows) // 2
    left, right = _programs(rows[:mid], bad), _programs(rows[mid:], bad)
    return 1 + left[0] + right[0], left[1] + right[1]


def _expected(forged: list) -> tuple:
    """The certificate's own (failed) launch is not a bisection program:
    the resolution starts at its two halves."""
    rows, bad = list(range(QUORUM)), set(forged)
    left = _programs(rows[:QUORUM // 2], bad)
    right = _programs(rows[QUORUM // 2:], bad)
    return left[0] + right[0], left[1] + right[1]


@pytest.fixture(scope="module")
def byz(tmp_path_factory):
    """Boot the real ``serve()`` as ``benchmark/run.py`` boots it for the
    cell (committee, ``warm_rlc``, spans on), send every case's
    certificate over the socket with an OP_STATS snapshot on either
    side, shut down, read the spans.  Yields {case: record}."""
    from hotstuff_tpu.sidecar import service

    tmp = tmp_path_factory.mktemp("byz")
    spans_path = tmp / "spans.jsonl"
    servers, errors = [], []
    ready = threading.Event()

    class Recording(service.SidecarServer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            servers.append(self)

    def run():
        try:
            service.serve(port=0, ready_event=ready, committee=COMMITTEE,
                          warm_max=32, warm_rlc=True,
                          trace_path=str(spans_path))
        except Exception as e:  # noqa: BLE001 — handed to the tests
            errors.append(e)
            ready.set()

    records = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("HOTSTUFF_TPU_COMPILE_MANIFEST",
                  str(tmp / "manifest.json"))
        mp.setattr(service, "SidecarServer", Recording)
        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        assert ready.wait(900), "serve() never became ready"
        assert not errors, errors
        try:
            with SidecarClient(port=servers[0].server_address[1],
                               timeout=300.0) as client:
                for case in CASES:
                    msgs, pks, sigs, forged = _certificate(*case)
                    before = client.stats()
                    reply = client.verify_batch(msgs, pks, sigs)
                    records[case] = dict(
                        msgs=msgs, pks=pks, sigs=sigs, forged=forged,
                        reply=reply, before=before, after=client.stats())
        finally:
            for srv in servers:
                srv.shutdown()
            thread.join(timeout=60)
        assert not thread.is_alive()
    spans, malformed = parse_spans(spans_path.read_text())
    assert malformed == 0
    # One connection, one request in flight: the i-th bisection is the
    # i-th case's.
    bisects = sorted((s for s in spans if s["stage"] == "bisect"),
                     key=lambda s: s["t0"])
    assert len(bisects) == len(CASES)
    for case, bisect in zip(CASES, bisects):
        records[case]["bisect"] = bisect
        records[case]["steps"] = [
            s for s in spans if s["stage"] == "bisect_step"
            and s["parent"] == bisect["id"]]
    assert sum(len(r["steps"]) for r in records.values()) == \
        sum(s["stage"] == "bisect_step" for s in spans)
    return records


@pytest.mark.parametrize("kind,k", CASES,
                         ids=[f"{kind}-{k}forged" for kind, k in CASES])
def test_forged_votes_are_named_and_counted(byz, kind, k):
    r = byz[(kind, k)]
    # The reply, bit for bit: one plain reference verify per signature.
    want = [bool(ref.verify(pk, m, s))
            for m, pk, s in zip(r["msgs"], r["pks"], r["sigs"])]
    assert want == [i not in r["forged"] for i in range(QUORUM)]
    assert r["reply"] == want

    programs, rows_per_sig = _expected(r["forged"])
    if k == 1:
        # One forged vote at quorum 17, whichever row: 17 -> 8/9, the
        # failing half -> 4/4 or 4/5, the failing quarter -> 2/2 or 2/3,
        # both under RLC_MIN_MSM: two per-signature leaves.  Two
        # programs a level, three levels.
        assert programs == 6 and rows_per_sig in (4, 5)

    def moved(section, key):
        return r["after"][section].get(key, 0) - \
            r["before"][section].get(key, 0)

    assert moved("bisect", "batches") == 1
    assert moved("paths", "rlc_bisect") == 1 and moved("paths", "rlc") == 1
    assert moved("bisect", "programs") == programs
    assert moved("bisect", "rows_per_sig") == rows_per_sig
    assert moved("bisect", "bad_rows") == k
    assert r["after"]["paths"].get("host", 0) == 0
    assert r["after"]["guard"]["wedges"] == 0

    bisect, steps = r["bisect"], r["steps"]
    assert bisect["launches"] == programs == len(steps)
    assert bisect["bad"] == k and bisect["n"] == QUORUM
    for s in steps:
        assert s["lid"] == bisect["lid"]
        assert bisect["t0"] <= s["t0"] and s["t"] <= bisect["t"]
        assert s["route"] == ("per_sig" if s["n"] < RLC_MIN_MSM else "rlc")
        assert s["bucket"] == (8 if s["n"] <= 8 else 16)
        assert 1 <= s["depth"] <= 3
    assert sum(s["n"] for s in steps if s["route"] == "per_sig") == \
        rows_per_sig
    # A step is `ok` exactly when it holds no forged row; the two steps
    # of depth 1 are the certificate's halves.
    assert sum(not s["ok"] for s in steps) >= 1
    assert sorted(s["n"] for s in steps if s["depth"] == 1) == [8, 9]
