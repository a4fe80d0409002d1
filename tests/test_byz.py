"""A committee whose Byzantine third forges votes, through the served
path: ``serve()`` in a thread, ``SidecarClient`` over the socket, the
CPU backend.  Committee 24 -> quorum 17 -> ``rlc`` bucket 32, the shape
of ``benchmark/rehearsal/configs/qc24f7.json`` (the chip's is
``benchmark/configs/qc100f33.json``: 100 -> 67 -> 128).

Every reply is held, bit for bit, to one plain reference verify per
signature; the ``bisect.*`` counters of OP_STATS and the ``bisect`` /
``bisect_step`` spans are held to what resolving a failed combined
check runs: ONE per-signature program over the certificate's canonical
rows, however many votes are forged and wherever they sit.  The chip's
width — 67 rows at bucket 128, a third and a half of them forged —
goes through ``eddsa.verify_batch_rlc_pack`` itself (the last test)."""

import hashlib
import random
import threading

import numpy as np
import pytest

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
from hotstuff_tpu.obs.spans import Tracer, parse_spans
from hotstuff_tpu.sidecar.client import SidecarClient
from hotstuff_tpu.sidecar.sched.stats import SchedStats

COMMITTEE = 24
QUORUM = 2 * COMMITTEE // 3 + 1        # 17, the node's own formula

# (kind, forged votes, where): seeded rows, or the first / the last row.
# 6 of 17 is the third that 22 of 67 is, 8 of 17 the half that 33 is.
CASES = [(kind, k, "seeded") for kind in ("qc", "tc") for k in (1, 2, 6)] \
    + [("qc", 1, "first"), ("qc", 1, "last"), ("tc", 8, "seeded")]
WIDE_CASES = [0, 22, 33]               # forged votes of 67, bucket 128


def _case_id(case) -> str:
    kind, k, where = case
    return f"{kind}-{k}forged" + ("" if where == "seeded" else f"-{where}")


def _certificate(kind: str, k: int, where: str = "seeded",
                 committee: int = COMMITTEE):
    """A 2N/3+1-vote certificate of a seeded quorum of the ``committee``
    validators — a QC (one common digest) or a TC (a message a vote) —
    with ``k`` votes forged (one bit of S flipped) at seeded rows, or at
    the first / the last row.  Returns (msgs, pks, sigs, forged rows)."""
    quorum = 2 * committee // 3 + 1
    rng = random.Random(f"byz-{kind}-{k}-{where}-{committee}")
    secrets = [hashlib.sha512(b"validator-%d" % i).digest()[:32]
               for i in rng.sample(range(committee), quorum)]
    tag = f"{kind}-{k}-{where}".encode()
    if kind == "qc":
        msgs = [hashlib.sha512(b"digest-" + tag).digest()[:32]] * quorum
    else:
        msgs = [hashlib.sha512(b"timeout-%d-" % i + tag).digest()[:32]
                for i in range(quorum)]
    pks = [ref.generate_keypair(sk)[1] for sk in secrets]
    sigs = [ref.sign(sk, m) for sk, m in zip(secrets, msgs)]
    forged = {"seeded": sorted(rng.sample(range(quorum), k)),
              "first": [0], "last": [quorum - 1]}[where]
    assert len(forged) == k
    for row in forged:
        sigs[row] = sigs[row][:32] + bytes([sigs[row][32] ^ 1]) \
            + sigs[row][33:]
    return msgs, pks, sigs, forged


def _reference_mask(msgs, pks, sigs) -> list:
    """One plain reference verify per signature."""
    return [bool(ref.verify(pk, m, s)) for m, pk, s in zip(msgs, pks, sigs)]


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
    # One connection, one request in flight: the i-th resolution is the
    # i-th case's.
    bisects = sorted((s for s in spans if s["stage"] == "bisect"),
                     key=lambda s: s["t0"])
    assert len(bisects) == len(CASES)
    for case, bisect in zip(CASES, bisects):
        records[case]["bisect"] = bisect
        records[case]["steps"] = [
            s for s in spans if s["stage"] == "bisect_step"
            and s["parent"] == bisect["id"]]
        # No span of its own inside a step: the benchmark's readers take
        # a step's time whole.
        records[case]["inside_steps"] = [
            s for s in spans if s.get("parent") in
            {step["id"] for step in records[case]["steps"]}]
    assert sum(len(r["steps"]) for r in records.values()) == \
        sum(s["stage"] == "bisect_step" for s in spans)
    return records


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_forged_votes_are_named_and_counted(byz, case):
    _, k, _ = case
    r = byz[case]
    # The reply, bit for bit: one plain reference verify per signature.
    want = _reference_mask(r["msgs"], r["pks"], r["sigs"])
    assert want == [i not in r["forged"] for i in range(QUORUM)]
    assert r["reply"] == want

    def moved(section, key):
        return r["after"][section].get(key, 0) - \
            r["before"][section].get(key, 0)

    # One program over every canonical row, whatever k is.
    assert moved("bisect", "batches") == 1
    assert moved("paths", "rlc_bisect") == 1 and moved("paths", "rlc") == 1
    assert moved("bisect", "programs") == 1
    assert moved("bisect", "rows_per_sig") == QUORUM
    assert moved("bisect", "bad_rows") == k
    assert r["after"]["paths"].get("host", 0) == 0
    assert r["after"]["guard"]["wedges"] == 0
    assert r["after"]["compile"]["in_service"]["count"] == 0

    bisect, (step,) = r["bisect"], r["steps"]
    assert (bisect["launches"], bisect["bad"], bisect["n"]) == (1, k, QUORUM)
    assert step["lid"] == bisect["lid"]
    assert bisect["t0"] <= step["t0"] and step["t"] <= bisect["t"]
    assert (step["n"], step["route"], step["bucket"], step["depth"],
            step["ok"]) == (QUORUM, "per_sig", 32, 0, False)
    assert r["inside_steps"] == []


class _FailedCheck:
    """What ``verify_rlc_packed_donated`` returns, as far as the fetch
    uses it, with a verdict of false."""

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(False)


@pytest.mark.parametrize("k", WIDE_CASES, ids=lambda k: f"67-{k}forged")
def test_one_program_at_the_chips_width(tmp_path, monkeypatch, k):
    """``qc100f33``'s width through the call ``service._pack`` makes: 67
    canonical rows with a third and a half of them forged and (k = 0) a
    combined check that fails though every signature verifies alone,
    which still answers all-true.  The combined check is stood in by a
    verdict of false (its program at bucket 128 is ``test_rlc``'s and
    the chip's to run); the resolution runs the real per-signature
    program at bucket 128."""
    monkeypatch.setattr(eddsa.E, "verify_rlc_packed_donated",
                        lambda rows, z: _FailedCheck())
    msgs, pks, sigs, forged = _certificate("qc", k, committee=100)
    stats = SchedStats()
    tracer = Tracer(str(tmp_path / "spans.jsonl"))
    mask = eddsa.verify_batch_rlc_pack(
        msgs, pks, sigs, on_bisect=stats.note_bisect,
        on_resolved=stats.note_bisect_resolved, trace=tracer.launch(1))()()
    tracer.close()

    want = _reference_mask(msgs, pks, sigs)
    assert len(want) == 67 and want == [i not in forged for i in range(67)]
    assert mask.tolist() == want
    snap = stats.snapshot()
    assert snap["paths"] == {"rlc_bisect": 1}
    assert snap["bisect"] == {"batches": 1, "programs": 1,
                              "rows_per_sig": 67, "bad_rows": k}
    spans, malformed = parse_spans((tmp_path / "spans.jsonl").read_text())
    assert malformed == 0
    (bisect,) = [s for s in spans if s["stage"] == "bisect"]
    (step,) = [s for s in spans if s["stage"] == "bisect_step"]
    assert (bisect["launches"], bisect["bad"], bisect["n"]) == (1, k, 67)
    assert step["parent"] == bisect["id"]
    assert (step["n"], step["route"], step["bucket"], step["depth"],
            step["ok"]) == (67, "per_sig", 128, 0, k == 0)
    assert not [s for s in spans if s.get("parent") == step["id"]]
