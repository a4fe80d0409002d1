"""The verdict cache of ``sidecar/service.VerifyEngine``: a FIFO by first
insertion under a cap, written once a launch under ``_verdicts_lock``,
whose eviction costs the same however many went before it; its
``dedup.inserts`` / ``dedup.evictions`` counters and ``cache_insert``
span."""

import gc
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from hotstuff_tpu.obs.spans import Tracer
from hotstuff_tpu.sidecar.service import VerifyEngine, _Pending


def _rec(i):
    """A distinct (msg, pk, sig) record: the cache's key."""
    return (i.to_bytes(32, "big"), b"\x07" * 32, i.to_bytes(64, "little"))


def _recs(lo, hi):
    return [_rec(i) for i in range(lo, hi)]


@pytest.fixture
def engine():
    eng = VerifyEngine(use_host=True)
    yield eng
    eng.stop()
    eng._thread.join(timeout=30)


@pytest.fixture
def small(engine):
    """The engine with a cap of 8: the code reads it through ``self``."""
    engine.VERDICT_CACHE_CAP = 8
    return engine


def _batch(records, rid=1):
    """One request carrying ``records``, as ``_pack`` takes it."""
    msgs, pks, sigs = (list(col) for col in zip(*records))
    return [_Pending(SimpleNamespace(msgs=msgs, pks=pks, sigs=sigs,
                                     request_id=rid, ctx=None),
                     lambda mask: None)]


def _verdict_by_first_byte(engine):
    """Stand the host verify in for the launch: a record is valid unless
    its signature's first byte is odd (no curve arithmetic in a cache
    test)."""
    engine._verify_submit = lambda msgs, pks, sigs, **kw: (
        lambda: np.array([s[0] % 2 == 0 for s in sigs]))


# -- order, cap, what is kept --------------------------------------------

@pytest.mark.parametrize("extra", [0, 1, 5, 8, 21])
def test_cap_plus_k_inserts_leave_exactly_the_newest_cap(small, extra):
    records = _recs(0, 8 + extra)
    evicted = small._cache_verdicts([(r, True) for r in records])
    assert evicted == extra
    assert list(small._verdicts) == records[extra:]
    assert len(small._verdicts) == 8


def test_one_at_a_time_evicts_the_oldest_first(small):
    records = _recs(0, 12)
    for i, r in enumerate(records):
        assert small._cache_verdicts([(r, True)]) == (1 if i >= 8 else 0)
        assert list(small._verdicts) == records[max(0, i - 7):i + 1]


def test_rewriting_a_held_key_evicts_nothing_and_keeps_its_place(small):
    records = _recs(0, 8)
    small._cache_verdicts([(r, True) for r in records])
    # a full cache, its OLDEST entry written again with another verdict
    assert small._cache_verdicts([(records[0], False)]) == 0
    assert list(small._verdicts) == records
    assert small._verdicts[records[0]] is False
    # ...so it is still the first to go: no move-to-back on a write
    assert small._cache_verdicts([(_rec(99), True)]) == 1
    assert list(small._verdicts) == records[1:] + [_rec(99)]


def test_a_hit_does_not_move_an_entry(small):
    records = _recs(0, 8)
    small._cache_verdicts([(r, True) for r in records])
    request = SimpleNamespace(msgs=[records[0][0]], pks=[records[0][1]],
                              sigs=[records[0][2]])
    assert small.cached_verdicts(request) == [True]
    assert small._verdicts.get(records[0]) is True
    small._cache_verdicts([(_rec(99), True)])
    assert records[0] not in small._verdicts     # read, and still oldest
    assert small.cached_verdicts(request) is None


def test_false_verdicts_are_cached(small):
    good, bad = _rec(1), _rec(2)
    small._cache_verdicts([(good, True), (bad, False)])
    assert small._verdicts[bad] is False and small._verdicts[good] is True
    request = SimpleNamespace(msgs=[bad[0], good[0]], pks=[bad[1], good[1]],
                              sigs=[bad[2], good[2]])
    assert small.cached_verdicts(request) == [False, True]


def test_readers_take_no_lock(small):
    """The lockless readers stay lockless: they answer while a writer
    holds ``_verdicts_lock``."""
    r = _rec(3)
    small._cache_verdicts([(r, True)])
    request = SimpleNamespace(msgs=[r[0], b"x"], pks=[r[1], b"y"],
                              sigs=[r[2], b"z"])
    with small._verdicts_lock:
        assert small._verdicts.get(r) is True
        assert small.cached_verdicts(request) is None   # a miss: no count
        assert len(small._verdicts) == 1


# -- the counters ---------------------------------------------------------

def test_inserts_and_evictions_count_exactly(small):
    before = small.stats_snapshot()["dedup"]
    assert (before["inserts"], before["evictions"]) == (0, 0)
    small._cache_verdicts([(r, True) for r in _recs(0, 5)])
    small._cache_verdicts([(r, True) for r in _recs(5, 11)])   # 3 over
    small._cache_verdicts([(_rec(10), False)])                 # held: 0
    small._cache_verdicts([])
    snap = small.stats_snapshot()
    assert snap["dedup"]["inserts"] == 12
    assert snap["dedup"]["evictions"] == 3
    assert snap["verdict_cache_entries"] == 8


def test_a_launch_writes_its_unique_records_once(small):
    """Through ``_pack``'s fetch: a launch of 10 records, 2 of them twice,
    counts 10 inserts (the unique ones) and evicts what the cap says; the
    mask fans the verdicts out to every index."""
    _verdict_by_first_byte(small)
    records = _recs(0, 10)
    sent = records + records[:2]
    mask = small._submit(_batch(sent))()
    assert mask == [r[2][0] % 2 == 0 for r in sent]
    dedup = small.stats_snapshot()["dedup"]
    assert (dedup["inserts"], dedup["evictions"]) == (10, 2)
    assert dedup["misses"] == 10 and dedup["inbatch_hits"] == 2
    assert list(small._verdicts) == records[2:]
    # the next launch: 4 held records (answered from the cache, not
    # written again) and 4 new ones
    mask = small._submit(_batch(records[6:] + _recs(20, 24)))()
    assert mask == [r[2][0] % 2 == 0 for r in records[6:] + _recs(20, 24)]
    dedup = small.stats_snapshot()["dedup"]
    assert (dedup["inserts"], dedup["evictions"]) == (14, 6)
    assert dedup["cache_hits"] == 4
    assert list(small._verdicts) == records[6:] + _recs(20, 24)


def test_a_bls_verdict_is_one_entry_of_the_same_cache(small):
    from hotstuff_tpu.sidecar import protocol as proto

    req = proto.BlsAggRequest(11, b"m" * 32, b"\x01" * 192, [b"\x02" * 96])
    replies = []
    small._cache_verdicts([(r, True) for r in _recs(0, 8)])
    small._execute_bls(_Pending(req, replies.append))   # a decode failure
    assert replies == [[False]]
    assert list(small._verdicts)[-1] == small.bls_cache_key(req)
    dedup = small.stats_snapshot()["dedup"]
    assert (dedup["inserts"], dedup["evictions"]) == (9, 1)


# -- two writers ----------------------------------------------------------

def test_two_threads_inserting_at_once_never_exceed_the_cap(engine):
    """The guard's disposable launch threads make two writers possible
    (a wedged launch completing late beside a fresh one's fetch)."""
    engine.VERDICT_CACHE_CAP = cap = 500
    per_thread, block = 20_480, 64
    seen = [0]
    done = threading.Event()

    def watch():
        while not done.is_set():
            seen[0] = max(seen[0], len(engine._verdicts))

    def write(base):
        for lo in range(base, base + per_thread, block):
            engine._cache_verdicts([(r, True) for r in _recs(lo, lo + block)])
            seen.append(len(engine._verdicts))

    watcher = threading.Thread(target=watch)
    writers = [threading.Thread(target=write, args=(k * 10**6,))
               for k in range(2)]
    watcher.start()
    for t in writers:
        t.start()
    for t in writers:
        t.join(timeout=120)
    done.set()
    watcher.join(timeout=30)
    assert not any(t.is_alive() for t in writers)
    assert max(seen) <= cap and len(engine._verdicts) == cap
    dedup = engine.stats_snapshot()["dedup"]
    assert dedup["inserts"] == 2 * per_thread
    assert dedup["evictions"] == 2 * per_thread - cap


# -- the cost of an eviction, at the real cap ------------------------------

def test_eviction_cost_does_not_grow_with_the_evictions_before_it(engine):
    """Fill to the real cap, then 110,000 more in blocks of 4,096: the
    slowest block stays under 5 times the first block after the fill.
    ``dict.pop(next(iter(d)))`` walks every entry deleted since the last
    resize and read ~50 times here."""
    cap = VerifyEngine.VERDICT_CACHE_CAP
    assert cap == 65_536
    block, blocks = 4_096, 27                          # 110,592 inserts
    fill = [(r, True) for r in _recs(0, cap)]
    more = [[(r, i % 2 == 0) for r in
             _recs(cap + i * block, cap + (i + 1) * block)]
            for i in range(blocks)]
    assert engine._cache_verdicts(fill) == 0
    took = []
    gc.collect()
    gc.disable()        # a collection over 175,000 live tuples is no scan
    try:
        for pairs in more:
            t0 = time.thread_time()    # this thread's CPU: no neighbour's
            evicted = engine._cache_verdicts(pairs)
            took.append(time.thread_time() - t0)
            assert evicted == block
    finally:
        gc.enable()
    assert len(engine._verdicts) == cap
    assert next(iter(engine._verdicts)) == more[-16][0][0]   # 16 blocks held
    assert max(took) < 5 * took[0], [round(t * 1e3, 2) for t in took]
    assert sum(took) < 1.0


# -- the span ---------------------------------------------------------------

def test_one_cache_insert_span_a_launch_under_its_device_span(small):
    _verdict_by_first_byte(small)
    tracer = Tracer("unused: never written", clock=time.monotonic)
    scopes = [tracer.launch(lid) for lid in (7, 8)]
    small._pack(_batch(_recs(0, 6)), scope=scopes[0])()()
    small._pack(_batch(_recs(6, 12)), scope=scopes[1])()()
    spans = [s for s in tracer._buf if s["stage"] == "cache_insert"]
    assert [(s["lid"], s["parent"], s["n"], s["evicted"]) for s in spans] \
        == [(7, scopes[0].device_id, 6, 0), (8, scopes[1].device_id, 6, 4)]
    assert all(s["t0"] <= s["t"] and isinstance(s["id"], int) for s in spans)


def test_no_cache_insert_span_and_no_clock_read_with_tracing_off(small):
    _verdict_by_first_byte(small)
    reads = []
    tracer = Tracer(None, clock=lambda: reads.append(1) or 0.0)
    mask = small._pack(_batch(_recs(0, 12)), scope=tracer.launch(7))()()
    assert mask == [r[2][0] % 2 == 0 for r in _recs(0, 12)]
    assert tracer._buf == [] and reads == []
    dedup = small.stats_snapshot()["dedup"]     # the counters are always on
    assert (dedup["inserts"], dedup["evictions"]) == (12, 4)
