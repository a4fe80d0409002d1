"""verifysched scheduler invariants: strict latency priority under a
mixed-class soak, bounded backpressure (queue-full replies), carry-over
fairness and bulk pad-fill, and the RLC-vs-per-signature verdict-mask
equivalence asserted through the FULL engine path (not the crypto
layer).
"""

import collections
import itertools
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
from hotstuff_tpu.obs.spans import NO_LAUNCH
from hotstuff_tpu.sidecar import protocol as proto
from hotstuff_tpu.sidecar import sched as vsched
from hotstuff_tpu.sidecar import service
from hotstuff_tpu.sidecar.client import SidecarClient, SidecarOverloaded
from hotstuff_tpu.sidecar.service import SidecarServer, VerifyEngine


def _req(n, tag):
    """A fake verify request of n records with distinct msg bytes (the
    engine dedups identical (msg, pk, sig) records, so scheduling tests
    must not reuse them)."""
    msgs = [b"%16d|%16d" % (tag, i) for i in range(n)]
    return SimpleNamespace(request_id=tag, msgs=msgs,
                           pks=[b"p" * 32] * n, sigs=[b"s" * 64] * n)


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


# ---------------------------------------------------------------------------
# scheduler-level policy (deterministic, single-threaded driving)
# ---------------------------------------------------------------------------

def test_latency_strict_priority_and_bulk_only_behind():
    s = vsched.Scheduler()
    order = []
    for i in range(3):
        assert s.offer(_req(600, 100 + i), order.append, cls=vsched.BULK)
    assert s.offer(_req(100, 1), order.append, cls=vsched.LATENCY)
    first = s.next_launch(block=False)
    assert first.cls == vsched.LATENCY
    assert [p.request.request_id for p in first.items] == [1]
    # bucket(100) = 128; no 600-sig bulk request fits the 28 pad slots
    assert first.fill_count == 0
    for want in (100, 101, 102):
        launch = s.next_launch(block=False)
        assert launch.cls == vsched.BULK
        assert [p.request.request_id for p in launch.items] == [want]
    assert s.next_launch(block=False) is None


def test_carry_over_keeps_fifo_and_leads_next_launch():
    s = vsched.Scheduler()
    assert s.shapes.launch_cap == eddsa.MAX_SUBBATCH
    s.offer(_req(700, 1), lambda m: None, cls=vsched.BULK)
    s.offer(_req(700, 2), lambda m: None, cls=vsched.BULK)
    s.offer(_req(100, 3), lambda m: None, cls=vsched.BULK)
    first = s.next_launch(block=False)
    # 700 + 700 > 1024: request 2 is carried over, and request 3 must
    # NOT jump the queue into the first launch (FIFO is the fairness
    # token).
    assert [p.request.request_id for p in first.items] == [1]
    second = s.next_launch(block=False)
    assert [p.request.request_id for p in second.items] == [2, 3]
    assert s.stats.snapshot()["carries"] == {"bulk": 1}


def test_oversized_single_request_still_ships():
    s = vsched.Scheduler()
    s.offer(_req(3000, 9), lambda m: None, cls=vsched.BULK)
    launch = s.next_launch(block=False)
    # Bigger than the launch cap: admitted whole (the engine dispatch
    # slices it into warmed shapes); the coalescer only bounds additions.
    assert launch.total_sigs == 3000


def test_bulk_pad_fill_drains_under_sustained_latency_load():
    s = vsched.Scheduler()
    done_bulk = []
    for i in range(10):
        assert s.offer(_req(2, 200 + i), done_bulk.append,
                       cls=vsched.BULK)
    launches = []
    # Sustained latency load: the latency queue is never empty when the
    # engine asks for work, so no bulk-only launch can ever be
    # assembled — pad-fill is the only drain.
    for i in range(12):
        s.offer(_req(4, i), lambda m: None, cls=vsched.LATENCY)
        launch = s.next_launch(block=False)
        assert launch.cls == vsched.LATENCY
        launches.append(launch)
        if s.queued_sigs(vsched.BULK) == 0:
            break
    assert s.queued_sigs(vsched.BULK) == 0, \
        "bulk starved under sustained latency load"
    # bucket(4) = 8 leaves 4 pad slots -> two 2-sig bulk requests ride
    # each latency launch for free.
    filled = [l for l in launches if l.fill_count]
    assert filled and all(l.total_sigs <= 8 for l in launches)
    snap = s.stats.snapshot()
    assert snap["bulk_fill_sigs"] == 20
    assert snap["launches_by_class"].get("bulk", 0) == 0


def test_pad_fill_room_uses_deduped_records():
    """N replicas submitting the SAME QC coalesce into one launch whose
    device shape is bucket(unique records) — fill room must be sized off
    that, or fill would grow the compiled shape and charge latency for
    bulk's ride (the raw total here is 10 -> bucket 16 -> room 6, which
    would push the unique count past bucket 8)."""
    s = vsched.Scheduler()
    s.offer(_req(5, 1), lambda m: None, cls=vsched.LATENCY)
    s.offer(_req(5, 1), lambda m: None, cls=vsched.LATENCY)  # same records
    for i in range(3):
        s.offer(_req(3, 300 + i), lambda m: None, cls=vsched.BULK)
    launch = s.next_launch(block=False)
    assert launch.cls == vsched.LATENCY
    # unique = 5 -> bucket 8 -> room 3: exactly one 3-sig bulk fill fits,
    # and unique-after-fill (8) still rides the latency batch's bucket.
    assert launch.fill_count == 1
    assert launch.total_sigs == 13  # 10 raw latency + 3 fill
    uniq = {rec for p in launch.items
            for rec in zip(p.request.msgs, p.request.pks, p.request.sigs)}
    assert len(uniq) <= 8


def test_queue_full_offer_rejects_and_counts():
    s = vsched.Scheduler(bulk_cap_sigs=8)
    assert s.offer(_req(8, 1), lambda m: None, cls=vsched.BULK)
    assert not s.offer(_req(4, 2), lambda m: None, cls=vsched.BULK)
    # the other class is unaffected by bulk saturation
    assert s.offer(_req(4, 3), lambda m: None, cls=vsched.LATENCY)
    snap = s.stats.snapshot()
    assert snap["queue_full"] == {"bulk": 1}
    assert snap["admitted"] == {"bulk": 1, "latency": 1}


# ---------------------------------------------------------------------------
# mixed-priority soak through the full engine
# ---------------------------------------------------------------------------

def test_mixed_priority_soak_through_engine():
    """Every latency-class request is launched before any bulk batch
    assembled after it.  The engine's verify is stubbed (scheduling is
    under test, not curve math) and slowed slightly so a real backlog
    forms while requests stream in."""
    engine = VerifyEngine(use_host=True)
    admit_idx = {}
    seq = itertools.count()
    launches = []

    def fake_verify_submit(msgs, pks, sigs):
        time.sleep(0.02)  # dispatch cost: lets the queues build up
        res = np.ones(len(msgs), bool)
        return lambda: res

    orig_pack = engine._pack

    def spying_pack(batch, scope):
        # _pack is the launch-admission surface of the double-buffered
        # engine (the single pack worker preserves scheduler assembly
        # order, so this records the true launch order).
        launches.append([(p.cls, admit_idx[p.request.request_id])
                         for p in batch])
        return orig_pack(batch, scope)

    engine._verify_submit = fake_verify_submit
    engine._pack = spying_pack
    try:
        replies = []
        cond = threading.Condition()

        def reply(mask):
            with cond:
                replies.append(mask)
                cond.notify()

        total = 0
        rid = itertools.count(1)
        for wave in range(6):
            for _ in range(3):
                r = _req(8, next(rid))
                admit_idx[r.request_id] = next(seq)
                assert engine.submit(r, reply, cls=vsched.BULK)
                total += 1
            for _ in range(2):
                r = _req(3, next(rid))
                admit_idx[r.request_id] = next(seq)
                assert engine.submit(r, reply, cls=vsched.LATENCY)
                total += 1
        with cond:
            assert cond.wait_for(lambda: len(replies) == total,
                                 timeout=60.0)
        # Reconstruct the invariant from the observed launch order:
        # for every latency item, no bulk-ONLY launch consisting purely
        # of later-admitted items may have launched before it.
        for i, launch in enumerate(launches):
            lat_admits = [a for cls, a in launch if cls == vsched.LATENCY]
            if not lat_admits:
                continue
            for j in range(i):
                earlier = launches[j]
                if any(cls == vsched.LATENCY for cls, _ in earlier):
                    continue
                assert min(a for _, a in earlier) < min(lat_admits), \
                    (j, earlier, i, launch)
        snap = engine.stats_snapshot()
        assert snap["launches"] == len(launches)
        assert snap["launches_by_class"].get("latency", 0) >= 1
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# wire-level backpressure
# ---------------------------------------------------------------------------

def test_queue_full_backpressure_reply_over_the_wire():
    """A saturated bulk queue is an immediate empty-mask reply that the
    client surfaces as SidecarOverloaded — never a blocked connection."""
    engine = VerifyEngine(use_host=True)

    def slow_verify_submit(msgs, pks, sigs):
        time.sleep(0.8)  # hold the engine thread so the queue stays full
        res = np.ones(len(msgs), bool)
        return lambda: res

    engine._verify_submit = slow_verify_submit
    engine._sched._queues[vsched.BULK].cap_sigs = 8
    srv = SidecarServer(("127.0.0.1", 0), engine)
    t = threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.1), daemon=True)
    t.start()
    port = srv.server_address[1]
    try:
        msgs, pks, sigs = _sigs(4, seed=31)
        results = {}

        def bg_verify(name, records, bulk):
            with SidecarClient(port=port, timeout=30.0) as c:
                m, p, s = records
                results[name] = c.verify_batch(m, p, s, bulk=bulk)

        # Plug the engine (latency launch dispatches, then sleeps)...
        plug = threading.Thread(
            target=bg_verify, args=("plug", _sigs(2, seed=32), False))
        plug.start()
        time.sleep(0.3)
        # ...fill the bulk queue to its 8-sig cap...
        filler = threading.Thread(
            target=bg_verify, args=("filler", _sigs(8, seed=33), True))
        filler.start()
        time.sleep(0.2)
        # ...and the next bulk request must shed, not block.
        with SidecarClient(port=port, timeout=30.0) as c:
            t0 = time.monotonic()
            with pytest.raises(SidecarOverloaded):
                c.verify_batch(msgs, pks, sigs, bulk=True)
            assert time.monotonic() - t0 < 5.0, \
                "queue-full reply must be immediate, not engine-paced"
        plug.join(timeout=30)
        filler.join(timeout=30)
        assert len(results["plug"]) == 2 and len(results["filler"]) == 8
        assert engine.stats_snapshot()["queue_full"].get("bulk", 0) >= 1
    finally:
        srv.shutdown()
        engine.stop()
        srv.server_close()


def test_stats_roundtrip_over_the_wire():
    engine = VerifyEngine(use_host=True)
    srv = SidecarServer(("127.0.0.1", 0), engine)
    t = threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.1), daemon=True)
    t.start()
    try:
        with SidecarClient(port=srv.server_address[1]) as c:
            msgs, pks, sigs = _sigs(5, tamper={2}, seed=41)
            assert c.verify_batch(msgs, pks, sigs) == \
                [i != 2 for i in range(5)]
            assert c.verify_batch(*_sigs(3, seed=42), bulk=True) == \
                [True] * 3
            snap = c.stats()
        assert snap["launches"] >= 2
        assert snap["launches_by_class"].get("latency", 0) >= 1
        assert set(snap["launches_by_class"]) <= {"latency", "bulk"}
        assert snap["paths"].get("host", 0) >= 2
        assert snap["queue_wait"]["latency"]["n"] >= 1
        assert snap["shapes"]["launch_cap"] == eddsa.MAX_SUBBATCH
    finally:
        srv.shutdown()
        engine.stop()
        srv.server_close()


# ---------------------------------------------------------------------------
# RLC routing through the full engine path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rlc_engine():
    """Device-path engine (CPU backend) with per-signature and RLC
    shapes warmed up to 32 — the real warmup entry points, so the
    registry state matches what `--warm-rlc` produces."""
    engine = VerifyEngine()
    service._warmup(engine, warm_max=32)
    service._warmup_rlc(engine, warm_max=32)
    yield engine
    engine.stop()


def _engine_mask(engine, msgs, pks, sigs):
    done = []
    cond = threading.Condition()

    def reply(mask):
        with cond:
            done.append(mask)
            cond.notify()

    assert engine.submit(proto.VerifyRequest(1, msgs, pks, sigs), reply)
    with cond:
        assert cond.wait_for(lambda: done, timeout=120.0)
    return done[0]


def test_engine_routes_rlc_and_masks_match_per_sig(rlc_engine):
    """Batches of n >= 16 valid-shape signatures route through
    verify_batch_rlc with verdict masks bit-identical to verify_batch —
    asserted through the engine (submit -> scheduler -> routed launch ->
    reply), across all-valid AND tampered batches (bisection path)."""
    engine = rlc_engine
    assert engine._shapes.route(16) == vsched.PATH_RLC
    assert engine._shapes.route(15) == vsched.PATH_PER_SIG
    before = engine.stats_snapshot()["paths"].get("rlc", 0)
    cases = [(16, set(), 50), (20, {3, 17}, 51), (31, {0}, 52)]
    for n, tamper, seed in cases:
        msgs, pks, sigs = _sigs(n, tamper=tamper, seed=seed)
        got = _engine_mask(engine, msgs, pks, sigs)
        want = eddsa.verify_batch(msgs, pks, sigs)
        assert got == [bool(b) for b in want], (n, tamper)
        assert got == [i not in tamper for i in range(n)]
    snap = engine.stats_snapshot()
    assert snap["paths"].get("rlc", 0) - before == len(cases)
    assert snap["paths"].get("rlc_bisect", 0) >= 2  # the tampered cases


def test_engine_small_batches_stay_per_sig(rlc_engine):
    engine = rlc_engine
    before = engine.stats_snapshot()["paths"].get("per_sig", 0)
    msgs, pks, sigs = _sigs(10, tamper={4}, seed=60)
    got = _engine_mask(engine, msgs, pks, sigs)
    assert got == [i != 4 for i in range(10)]
    assert engine.stats_snapshot()["paths"].get("per_sig", 0) == before + 1


# ---------------------------------------------------------------------------
# Mesh routing through the full engine path (8-device forced-host CPU
# mesh from conftest): sharded-RLC route selection, shard-aligned launch
# shapes, and mask bit-identity vs verify_batch incl. forced bisection.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh_engine():
    """Mesh engine with the per-signature AND sharded one-MSM warmups
    run through the real entry points (what `--mesh 8 --warm-rlc-sharded`
    produces), capped to small shapes to bound compile time.

    graftscale knobs exercised: the committee (40 -> quorum 27) floors
    the RLC warmup cap ABOVE warm_max=16, so the quorum's per-shard
    bucket (4) is warmed even though warm_max alone would stop at 2 —
    the giant-committee threshold discipline at fixture scale; and the
    warmup's scan leg (chunk counts 2 and 4 of the top bucket) raises
    the launch cap through the gated enable_bulk to the scan capacity
    8 dev x 4 chunks x 4 rows = 128 sigs."""
    engine = VerifyEngine(mesh_devices=8, committee=40)
    service._warmup(engine, warm_max=32)
    service._warmup_rlc_sharded(engine, warm_max=16, scan_chunks=4)
    yield engine
    engine.stop()


def test_mesh_route_selection(mesh_engine):
    shapes = mesh_engine._shapes
    # Warmed + >= RLC_MIN_LAUNCH -> the sharded one-MSM path; below the
    # floor the ladder path, even though its per-shard bucket is warmed.
    assert shapes.route(16) == vsched.PATH_RLC_SHARDED
    assert shapes.route(32) == vsched.PATH_RLC_SHARDED
    assert shapes.route(15) == vsched.PATH_LADDER_SHARDED
    # An unwarmed per-shard bucket must NOT route to the MSM.
    cold = vsched.ShapeRegistry(n_devices=8)
    assert cold.route(64) == vsched.PATH_LADDER_SHARDED
    # Warming is keyed per-shard: marking any size on the same bucket
    # unlocks every size that lands on it.
    cold.mark_rlc_sharded(64)
    assert cold.route(64) == vsched.PATH_RLC_SHARDED
    assert cold.route(57) == vsched.PATH_RLC_SHARDED   # same bucket (8)
    assert cold.route(128) == vsched.PATH_LADDER_SHARDED


def test_mesh_shard_aligned_capacity():
    from hotstuff_tpu.parallel.shard_shapes import (shard_aligned_rows,
                                                    shard_bucket)

    reg = vsched.ShapeRegistry(n_devices=8)
    for n in (1, 5, 16, 20, 100, 375 * 8, 3000):
        cap = reg.bucket_capacity(n)
        assert cap == shard_aligned_rows(n, 8)
        assert cap % 8 == 0, "mesh capacity must divide across devices"
        per = cap // 8
        assert per == shard_bucket(n, 8)
        assert per & (per - 1) == 0 or per % eddsa.MAX_SUBBATCH == 0, \
            "per-shard rows must be a pow2 bucket or whole chunks"
        assert cap >= n
    # The 375-row-shard regression: 3000 records on 8 devices must pad
    # to a power-of-two per-shard bucket, not ceil(3000/8)=375.
    assert reg.bucket_capacity(3000) == 8 * 512


def test_mesh_pad_fill_room_uses_shard_aligned_capacity():
    s = vsched.Scheduler(shapes=vsched.ShapeRegistry(n_devices=8))
    s.offer(_req(5, 1), lambda m: None, cls=vsched.LATENCY)
    for i in range(4):
        s.offer(_req(3, 300 + i), lambda m: None, cls=vsched.BULK)
    launch = s.next_launch(block=False)
    assert launch.cls == vsched.LATENCY
    # 5 unique -> shard-aligned capacity 8 (8 devices x 1-row bucket):
    # room for exactly one 3-sig bulk fill without growing any shard.
    assert launch.fill_count == 1
    assert launch.total_sigs == 8


def test_mesh_engine_masks_match_verify_batch(mesh_engine):
    """Engine-routed mesh launches of >= 16 unique records take the
    rlc_sharded path (visible in OP_STATS route counters), produce masks
    bit-identical to verify_batch — all-valid AND tampered (forced
    bisection) — and every launch's padded bucket divides evenly by the
    device count, landing only on warmup-marked shapes."""
    engine = mesh_engine
    before = engine.stats_snapshot()["paths"].get("rlc_sharded", 0)
    cases = [(16, set(), 70), (20, {3, 17}, 71), (31, {0}, 72)]
    for n, tamper, seed in cases:
        msgs, pks, sigs = _sigs(n, tamper=tamper, seed=seed)
        got = _engine_mask(engine, msgs, pks, sigs)
        want = eddsa.verify_batch(msgs, pks, sigs)
        assert got == [bool(b) for b in want], (n, tamper)
        assert got == [i not in tamper for i in range(n)]
    snap = engine.stats_snapshot()
    assert snap["paths"].get("rlc_sharded", 0) - before == len(cases)
    assert snap["paths"].get("rlc_bisect", 0) >= 2  # the tampered cases
    # Shard-aligned discipline, asserted via the shape registry: every
    # mesh launch's per-shard bucket must have been warmed — no shape
    # can have compiled cold after warmup.
    mesh_stats = snap["mesh"]
    assert mesh_stats["sharded_launches"] >= len(cases)
    warmed = set(snap["shapes"]["rlc_shard_buckets"]) \
        | set(snap["shapes"]["shard_buckets"])
    launched = {int(b) for b in mesh_stats["shard_buckets"]}
    assert launched and launched <= warmed, (launched, warmed)
    # pipeline telemetry exists and is consistent
    pipe = snap["pipeline"]
    assert pipe["pack_ms"] > 0
    assert 0.0 <= pipe["overlap_ratio"] <= 1.0


def test_mesh_engine_small_batches_take_ladder_path(mesh_engine):
    engine = mesh_engine
    before = engine.stats_snapshot()["paths"].get("ladder_sharded", 0)
    msgs, pks, sigs = _sigs(10, tamper={4}, seed=73)
    got = _engine_mask(engine, msgs, pks, sigs)
    assert got == [i != 4 for i in range(10)]
    snap = engine.stats_snapshot()
    assert snap["paths"].get("ladder_sharded", 0) == before + 1


# ---------------------------------------------------------------------------
# graftscale: whole-backlog chunked mesh scans + giant-committee routing
# ---------------------------------------------------------------------------


def test_warmup_scan_leg_raises_launch_cap_and_covers_quorum(mesh_engine):
    """--warm-rlc-sharded's graftscale legs, observed on the fixture:
    the scan shapes are marked and the launch cap rose through the
    gated enable_bulk to the scan capacity; the committee floor (40 ->
    quorum 27) warmed the quorum's per-shard bucket even though
    warm_max=16 alone would have stopped one bucket short."""
    shapes = mesh_engine._shapes
    assert shapes.committee == 40 and shapes.qc_sigs == 27
    snap = mesh_engine.stats_snapshot()["shapes"]
    assert snap["mesh_chunks"] == [2, 4]
    assert snap["scan_rows"] == 4
    # Raise-only enable_bulk: the fixture's scan capacity (128) sits
    # BELOW the MAX_SUBBATCH default, so the cap stays put (production
    # capacities — 16 chunks of 128 rows on 8 devices — raise it).
    assert shapes.scan_capacity() == 8 * 4 * 4
    assert snap["launch_cap"] == eddsa.MAX_SUBBATCH
    assert snap["committee"] == 40
    # The quorum's per-shard bucket (shard_bucket(27, 8) = 4) is RLC
    # warmed, so the committee's own QC batches route one-MSM.
    assert shapes.route(27) == vsched.PATH_RLC_SHARDED


def test_engine_whole_backlog_scan_one_launch(mesh_engine):
    """A coalesced bulk backlog bigger than every warmed ladder bucket
    dispatches as ONE whole-backlog scan launch: the OP_STATS ``scan``
    section shows it (and zero per-slice ladder launches), the chunk
    count is warmup-marked, and the mask is bit-identical to
    verify_batch — including device-detected invalid rows."""
    engine = mesh_engine
    before = engine.stats_snapshot()
    msgs, pks, sigs = _sigs(100, tamper={3, 77}, seed=80)
    got = _engine_mask(engine, msgs, pks, sigs)
    want = eddsa.verify_batch(msgs, pks, sigs)
    assert got == [bool(b) for b in want]
    assert got == [i not in (3, 77) for i in range(100)]
    snap = engine.stats_snapshot()
    scan = snap["scan"]
    assert scan["launches"] - before["scan"]["launches"] == 1
    assert scan["sigs"] - before["scan"]["sigs"] == 100
    # ceil(100/8)=13 rows/shard over 4-row chunks -> g=4, a warmed
    # chunk count (launched-scan-shapes subset of warmed, the scan
    # twin of the ladder buckets assertion below).
    assert scan["chunk_hist"].get("4", 0) >= 1
    launched_chunks = {int(g) for g in scan["chunk_hist"]}
    assert launched_chunks <= set(snap["shapes"]["mesh_chunks"])
    assert snap["paths"].get("scan_sharded", 0) \
        - before["paths"].get("scan_sharded", 0) == 1
    # Zero per-slice ladder launches for the backlog.
    assert snap["mesh"]["sharded_launches"] \
        == before["mesh"]["sharded_launches"]


def test_scan_route_falls_back_to_slicing_when_unwarmed():
    """An unwarmed chunk count must NOT take the scan route (it would
    be a cold XLA compile on the engine thread): the router answers the
    sliced ladder instead, and scan_shape_of says why (None)."""
    reg = vsched.ShapeRegistry(n_devices=8)
    reg.mark_bucket(8)                      # shard bucket 1 warmed
    for g in (2, 4):
        reg.mark_mesh_chunks(g, 4)
    assert reg.scan_shape_of(100) == (4, 4)
    assert reg.route(100) == vsched.PATH_SCAN_SHARDED
    # 3000 records need g=128 chunks of 4 rows — never warmed.
    assert reg.scan_shape_of(3000) is None
    assert reg.route(3000) == vsched.PATH_LADDER_SHARDED
    # A batch whose ladder bucket IS warmed keeps the ladder path.
    assert reg.route(8) == vsched.PATH_LADDER_SHARDED
    # No scan warmup at all: every size slices, as before graftscale.
    cold = vsched.ShapeRegistry(n_devices=8)
    assert cold.scan_shape_of(100) is None
    assert cold.route(100) == vsched.PATH_LADDER_SHARDED


def test_enable_bulk_gated_on_scan_shapes():
    """On a mesh registry the launch cap only rises once the
    whole-backlog scan shapes are warmed — to the warmed scan capacity,
    raise-only (a small capacity never LOWERS the cap below its current
    value); single-chip registries keep the old contract."""
    reg = vsched.ShapeRegistry(n_devices=8)
    reg.enable_bulk(16 * 1024)
    assert reg.launch_cap == eddsa.MAX_SUBBATCH  # gated: nothing warmed
    # Production-scale scan shapes (16 chunks of 128 rows on 8 devices
    # = 16384 capacity): the cap rises to min(bound, capacity).
    for g in (2, 4, 8, 16):
        reg.mark_mesh_chunks(g, 128)
    reg.enable_bulk(16 * 1024)
    assert reg.launch_cap == 16 * 1024
    # The caller's bound still wins when it is tighter.
    big = vsched.ShapeRegistry(n_devices=8)
    for g in (2, 4, 8, 16):
        big.mark_mesh_chunks(g, 1024)
    big.enable_bulk(2048)
    assert big.launch_cap == 2048
    # Single chip: ungated, as before.
    single = vsched.ShapeRegistry()
    single.enable_bulk(4096)
    assert single.launch_cap == 4096
    # Raise-only: a SMALL warmed scan capacity (8 devices x 4 chunks x
    # 4 rows = 128, the test-fixture scale) must never LOWER the cap
    # below the MAX_SUBBATCH default.
    small = vsched.ShapeRegistry(n_devices=8)
    for g in (2, 4):
        small.mark_mesh_chunks(g, 4)
    small.enable_bulk(16 * 1024)
    assert small.launch_cap == eddsa.MAX_SUBBATCH
    assert small.scan_capacity() == 8 * 4 * 4
    # One rows value per registry: a second would mean two scan
    # ladders the router cannot tell apart.
    with pytest.raises(ValueError):
        reg.mark_mesh_chunks(2, 8)


def test_ladder_slices_stay_on_warmed_buckets(mesh_engine):
    """The sliced-ladder fallback must slice at the WARMED ladder cap,
    not the scan-raised launch_cap: an oversized request whose chunk
    count is unwarmed (g=16 here) slices into launches whose per-shard
    buckets the warmup compiled — never a cold mid-run shape — and the
    whole sliced backlog records as ONE mesh launch with its per-slice
    buckets."""
    engine = mesh_engine
    shapes = engine._shapes
    # The registry arithmetic: the coalescer cap (MAX_SUBBATCH at
    # fixture scale — raise-only enable_bulk) never leaks into ladder
    # slicing, which stays at n_dev x top warmed bucket = 32.
    assert shapes.launch_cap == eddsa.MAX_SUBBATCH
    assert shapes.ladder_cap() == 8 * 4
    assert shapes.route(300) == vsched.PATH_LADDER_SHARDED
    before = engine.stats_snapshot()
    msgs, pks, sigs = _sigs(300, tamper={7, 250}, seed=81)
    got = _engine_mask(engine, msgs, pks, sigs)
    assert got == [i not in (7, 250) for i in range(300)]
    snap = engine.stats_snapshot()
    assert snap["mesh"]["sharded_launches"] \
        - before["mesh"]["sharded_launches"] == 1
    assert snap["scan"]["launches"] == before["scan"]["launches"]
    launched = {int(b) for b in snap["mesh"]["shard_buckets"]}
    warmed = set(snap["shapes"]["shard_buckets"]) \
        | set(snap["shapes"]["rlc_shard_buckets"])
    assert launched and launched <= warmed, (launched, warmed)


def test_giant_committee_threshold_routing():
    """QC-shaped latency batches for N in {100, 300, 1000} route
    through the sharded one-MSM path once their quorum bucket is
    warmed (the committee-floored warmup guarantees it is), and stay
    on the safe ladder when it is not."""
    from hotstuff_tpu.parallel.shard_shapes import shard_bucket

    assert vsched.quorum_sigs(1000) == 667
    for committee in (100, 300, 1000):
        q = vsched.quorum_sigs(committee)
        reg = vsched.ShapeRegistry(n_devices=8, committee=committee)
        assert reg.qc_sigs == q
        assert q >= vsched.RLC_MIN_LAUNCH
        assert shard_bucket(q, 8) <= eddsa.MAX_SUBBATCH, \
            "quorum must fit the one-dispatch RLC envelope"
        assert reg.route(q) == vsched.PATH_LADDER_SHARDED  # unwarmed
        reg.mark_rlc_sharded(q)
        assert reg.route(q) == vsched.PATH_RLC_SHARDED
    # N=1000: ~667 signatures land on the 128-row per-shard bucket.
    assert shard_bucket(667, 8) == 128


def test_scan_and_mesh_launch_stats_accounting():
    """note_mesh_launch counts ONE launch with per-slice buckets in the
    histogram; note_scan_launch feeds the ``scan`` section including
    the slices the old per-launch_cap path would have paid."""
    stats = vsched.SchedStats()
    stats.note_mesh_launch([4, 4, 8, None])
    snap = stats.snapshot()
    assert snap["mesh"]["sharded_launches"] == 1
    assert snap["mesh"]["shard_buckets"] == {"4": 2, "8": 1}
    stats.note_scan_launch(16, 16384, 15)
    stats.note_scan_launch(4, 300, 0)
    snap = stats.snapshot()
    assert snap["scan"] == {"launches": 2, "sigs": 16684,
                            "chunk_hist": {"4": 1, "16": 1},
                            "slices_avoided": 15}


def test_pipeline_overlap_is_a_rolling_window():
    """graftcadence satellite: the OP_STATS ``pipeline`` section answers
    for RECENT pack-boundedness (entries older than PIPE_WINDOW_S age
    out), while the lifetime accumulators survive under ``lifetime_*``
    for trend tooling."""
    from hotstuff_tpu.sidecar.sched.stats import PIPE_WINDOW_S

    now = [1000.0]
    stats = vsched.SchedStats(clock=lambda: now[0])
    for _ in range(8):
        stats.note_pack(0.010, hidden=False)
    pipe = stats.snapshot()["pipeline"]
    assert pipe["overlap_ratio"] == 0.0
    assert pipe["pack_ms"] == pytest.approx(80.0)
    # The unhealthy history ages out; only the recent packs report.
    now[0] += PIPE_WINDOW_S + 1.0
    for _ in range(4):
        stats.note_pack(0.010, hidden=True)
    pipe = stats.snapshot()["pipeline"]
    assert pipe["overlap_ratio"] == 1.0
    assert pipe["pack_ms"] == pytest.approx(40.0)
    assert pipe["window_s"] == PIPE_WINDOW_S
    # Lifetime keeps the whole story.
    assert pipe["lifetime_pack_ms"] == pytest.approx(120.0)
    assert pipe["lifetime_overlap_ratio"] == pytest.approx(0.333,
                                                           abs=1e-3)


def test_dispatch_ahead_share_is_a_rolling_window():
    """``note_dispatch``: the ``pipeline`` section counts the launches
    the staged engine dispatched and those it dispatched while another
    was in flight, over the same window as ``overlap_ratio`` (age and
    PIPE_WINDOW entries)."""
    from hotstuff_tpu.sidecar.sched.stats import PIPE_WINDOW, PIPE_WINDOW_S

    now = [1000.0]
    stats = vsched.SchedStats(clock=lambda: now[0])
    pipe = stats.snapshot()["pipeline"]
    assert (pipe["dispatches"], pipe["dispatch_ahead"],
            pipe["dispatch_ahead_share"]) == (0, 0, 0.0)
    for _ in range(4):
        stats.note_dispatch(0)
    assert stats.snapshot()["pipeline"]["dispatch_ahead_share"] == 0.0
    now[0] += PIPE_WINDOW_S + 1.0
    for ahead in (0, 1, 2, 1):
        stats.note_dispatch(ahead)
    pipe = stats.snapshot()["pipeline"]
    assert (pipe["dispatches"], pipe["dispatch_ahead"],
            pipe["dispatch_ahead_share"]) == (4, 3, 0.75)
    for _ in range(PIPE_WINDOW):
        stats.note_dispatch(1)
    pipe = stats.snapshot()["pipeline"]
    assert (pipe["dispatches"], pipe["dispatch_ahead"],
            pipe["dispatch_ahead_share"]) == (PIPE_WINDOW, PIPE_WINDOW, 1.0)


def test_a_pack_is_hidden_while_another_launch_is_in_flight():
    """``_inflight_n`` drops a launch as its drain BEGINS: a pack begun
    while the older launch drains and a younger one is still in flight
    runs beside the device (``hidden``); one begun while the only launch
    drains does not (its program has finished: the device is idle)."""
    engine = VerifyEngine(use_host=True)
    try:
        seen = []

        def pack_beside_it():
            seen.append(engine._inflight_n)
            nxt = vsched.Pending(_req(4, 2), lambda m: None, vsched.BULK)
            engine._pack([nxt])()()
            return np.ones(4, bool)

        inflight = collections.deque(
            [([vsched.Pending(_req(4, rid), lambda m: None, vsched.BULK)],
              pack_beside_it, "launch:8", NO_LAUNCH, 0.0, 0.0)
             for rid in (1, 3)])
        engine._inflight_n = len(inflight)
        engine._drain_one(inflight)
        engine._drain_one(inflight)
        assert seen == [1, 0]
        assert engine._inflight_n == 0
        window = engine._sched.stats._pack_window
        assert [hidden for _, _, hidden in window] == [True, False]
    finally:
        engine.stop()


@pytest.mark.slow
def test_giant_quorum_engine_path_n1000():
    """The N=1000 acceptance shape through the REAL engine: a
    667-signature latency batch routes sharded-RLC with its mask
    bit-identical to verify_batch, incl. a forced bisection.  Slow
    lane: the quorum floor warms per-shard buckets up to 128 (each a
    fresh XLA compile of both mesh programs on the CPU backend)."""
    engine = VerifyEngine(mesh_devices=8, committee=1000)
    service._warmup(engine, warm_max=8)
    # scan_chunks=0 skips the scan leg: this test is about the RLC
    # threshold, and the scan programs at rows=128 are another minute
    # of CPU compile the assertion doesn't need.
    service._warmup_rlc_sharded(engine, warm_max=8, scan_chunks=0)
    try:
        assert engine._shapes.qc_sigs == 667
        assert engine._shapes.route(667) == vsched.PATH_RLC_SHARDED
        msgs, pks, sigs = _sigs(667, tamper={13, 600}, seed=90)
        got = _engine_mask(engine, msgs, pks, sigs)
        want = eddsa.verify_batch(msgs, pks, sigs)
        assert got == [bool(b) for b in want]
        assert got == [i not in (13, 600) for i in range(667)]
        snap = engine.stats_snapshot()
        assert snap["paths"].get("rlc_sharded", 0) >= 1
        assert snap["paths"].get("rlc_bisect", 0) >= 1
        warmed = set(snap["shapes"]["rlc_shard_buckets"])
        assert 128 in warmed, "quorum bucket must be warmed"
        launched = {int(b) for b in snap["mesh"]["shard_buckets"]}
        assert launched and launched <= warmed \
            | set(snap["shapes"]["shard_buckets"])
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# parameter-sized admission caps (ROADMAP follow-up: committee/rate sizing
# replaces the static constants; env overrides win over everything)
# ---------------------------------------------------------------------------


def test_queue_caps_sized_from_committee_and_rate(monkeypatch):
    monkeypatch.delenv("HOTSTUFF_TPU_LATENCY_QUEUE_CAP_SIGS",
                       raising=False)
    monkeypatch.delenv("HOTSTUFF_TPU_BULK_QUEUE_CAP_SIGS", raising=False)
    # No parameters: the static defaults.
    assert vsched.size_queue_caps() == (64 * 1024, 128 * 1024)
    # Committee sizing: n * quorum * per-replica pipeline depth (64),
    # clamped to [default/4, 16x default].
    lat, blk = vsched.size_queue_caps(committee=20, client_rate=100_000)
    assert lat == 20 * (2 * 20 // 3 + 1) * 64
    assert blk == 2 * 100_000
    # Clamps: a 4-node committee floors, a silly rate ceilings.
    lat, _ = vsched.size_queue_caps(committee=4)
    assert lat == 64 * 1024 // 4
    _, blk = vsched.size_queue_caps(client_rate=10 ** 9)
    assert blk == 16 * 128 * 1024


def test_queue_caps_env_override_wins(monkeypatch):
    monkeypatch.setenv("HOTSTUFF_TPU_LATENCY_QUEUE_CAP_SIGS", "777")
    monkeypatch.setenv("HOTSTUFF_TPU_BULK_QUEUE_CAP_SIGS", "888")
    assert vsched.size_queue_caps(committee=100, client_rate=10 ** 6) \
        == (777, 888)
    # Malformed / non-positive env values fall back cleanly.
    monkeypatch.setenv("HOTSTUFF_TPU_LATENCY_QUEUE_CAP_SIGS", "soon")
    monkeypatch.setenv("HOTSTUFF_TPU_BULK_QUEUE_CAP_SIGS", "-2")
    assert vsched.size_queue_caps() == (64 * 1024, 128 * 1024)


def test_engine_applies_sized_caps_and_reports_them(monkeypatch):
    monkeypatch.delenv("HOTSTUFF_TPU_LATENCY_QUEUE_CAP_SIGS",
                       raising=False)
    monkeypatch.delenv("HOTSTUFF_TPU_BULK_QUEUE_CAP_SIGS", raising=False)
    engine = VerifyEngine(use_host=True, committee=20, client_rate=50_000)
    try:
        caps = engine.stats_snapshot()["queue_caps"]
        assert caps["latency"] == 20 * (2 * 20 // 3 + 1) * 64
        assert caps["bulk"] == 100_000
    finally:
        engine.stop()
