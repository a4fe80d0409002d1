"""graftkern tests: Pallas kernel bit-identity vs the lax reference,
the kernel-route plumbing, the MSM window-chunk re-pin, the compile
manifest / tracker, and the bench roofline surface.

Everything here runs the kernels in INTERPRET mode (CPU backend —
conftest pins it), i.e. the exact kernel bodies a TPU would compile.
The expensive full-program paths (engine RLC bisection under
HOTSTUFF_TPU_KERN=pallas, the B=1024 window-accumulator agreement) are
slow-marked; scripts/kern_gate.sh runs them inside its stated budget.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref  # noqa: E402
from hotstuff_tpu.ops import ed25519 as E  # noqa: E402
from hotstuff_tpu.ops import field25519 as F  # noqa: E402
from hotstuff_tpu.ops import kern  # noqa: E402
from hotstuff_tpu.ops import scalar25519 as S  # noqa: E402
from hotstuff_tpu.utils.intmath import L, P  # noqa: E402
from hotstuff_tpu.utils.xla_cache import (  # noqa: E402
    CompileManifest, CompileTracker, configure_xla_cache,
    kernel_fingerprint, repo_root)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _arr(x):
    return np.asarray(x)


# ---------------------------------------------------------------------------
# Kernel 1: field_mul
# ---------------------------------------------------------------------------


class TestFieldMulKernel:
    def test_random_weak_sweep_bit_identical(self):
        rng = np.random.default_rng(11)
        for seed in range(3):
            a = rng.integers(0, 512, (32, 32)).astype(np.int32)
            b = rng.integers(0, 512, (32, 32)).astype(np.int32)
            got = _arr(kern.field_mul(jnp.asarray(a), jnp.asarray(b)))
            want = _arr(F._mul_lax(jnp.asarray(a), jnp.asarray(b)))
            assert np.array_equal(got, want), f"seed {seed}"

    def test_edge_limbs_bit_identical(self):
        # Maximal weak limbs (all 511 — the worst wrap-38 carry chains),
        # canonical p-1, zero, and one: the carry-structure edges.
        cases = [
            np.full((32,), 511, np.int32),
            F.to_limbs(P - 1),
            F.to_limbs(0),
            F.to_limbs(1),
            F.to_limbs((1 << 255) - 19 - 38),  # wrap-fold boundary
        ]
        a = np.stack([c for c in cases for _ in cases])
        b = np.stack([c for _ in cases for c in cases])
        got = _arr(kern.field_mul(jnp.asarray(a), jnp.asarray(b)))
        want = _arr(F._mul_lax(jnp.asarray(a), jnp.asarray(b)))
        assert np.array_equal(got, want)
        # And the values are right, not just mutually consistent.
        got_vals = F.batch_from_limbs(_arr(F.canonical(jnp.asarray(got))))
        want_vals = [(x * y) % P
                     for x, y in zip(F.batch_from_limbs(a),
                                     F.batch_from_limbs(b))]
        assert got_vals == want_vals

    def test_batch_shapes_and_broadcast(self):
        rng = np.random.default_rng(3)
        a = rng.integers(0, 512, (3, 4, 32)).astype(np.int32)
        b = rng.integers(0, 512, (3, 4, 32)).astype(np.int32)
        got = _arr(kern.field_mul(jnp.asarray(a), jnp.asarray(b)))
        want = _arr(F._mul_lax(jnp.asarray(a), jnp.asarray(b)))
        assert got.shape == (3, 4, 32)
        assert np.array_equal(got, want)
        # 1-D (single element) and broadcast (4,32) x (32,)
        a1 = rng.integers(0, 512, (32,)).astype(np.int32)
        b1 = rng.integers(0, 512, (32,)).astype(np.int32)
        assert np.array_equal(
            _arr(kern.field_mul(jnp.asarray(a1), jnp.asarray(b1))),
            _arr(F._mul_lax(jnp.asarray(a1), jnp.asarray(b1))))


# ---------------------------------------------------------------------------
# Kernel 3: scalar_mont_mul
# ---------------------------------------------------------------------------


class TestScalarMontKernel:
    def test_random_and_boundary_scalars_bit_identical(self):
        rng = np.random.default_rng(7)
        vals_a = [int.from_bytes(rng.bytes(32), "little") % L
                  for _ in range(12)]
        vals_b = [int.from_bytes(rng.bytes(32), "little") % L
                  for _ in range(12)]
        # Order-L boundaries, zero, one.
        vals_a[:4] = [L - 1, L - 1, 0, 1]
        vals_b[:4] = [L - 1, 1, L - 1, L - 1]
        a = np.stack([F.to_limbs(v) for v in vals_a])
        b = np.stack([F.to_limbs(v) for v in vals_b])
        got = _arr(kern.scalar_mont_mul(jnp.asarray(a), jnp.asarray(b)))
        want = _arr(S._mont_mul_lax(jnp.asarray(a), jnp.asarray(b)))
        assert np.array_equal(got, want)
        # Against python ints: mont_mul computes a*b*R^-1 mod L.
        r_inv = pow(1 << 256, -1, L)
        got_vals = F.batch_from_limbs(got)
        assert got_vals == [(x * y * r_inv) % L
                            for x, y in zip(vals_a, vals_b)]

    def test_headroom_path_bit_identical(self):
        # One input up to 2^256 - 1 while the other stays < L — the
        # reduce512_mod_l high-half contract.
        rng = np.random.default_rng(9)
        big = [2**256 - 1, 2**255 + 12345,
               int.from_bytes(rng.bytes(32), "little")]
        small = [L - 1, 7, int.from_bytes(rng.bytes(32), "little") % L]
        a = np.stack([F.to_limbs(v) for v in big])
        b = np.stack([F.to_limbs(v) for v in small])
        got = _arr(kern.scalar_mont_mul(jnp.asarray(a), jnp.asarray(b)))
        want = _arr(S._mont_mul_lax(jnp.asarray(a), jnp.asarray(b)))
        assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# Kernel 2: msm_window_accum
# ---------------------------------------------------------------------------


def _real_points(n, seed=1):
    pts = []
    for i in range(n):
        _, pk = ref.generate_keypair(bytes([seed]) * 31 + bytes([i + 1]))
        y, s = E.split_y_sign(jnp.asarray(
            np.frombuffer(pk, np.uint8)[None, :].astype(np.int32)))
        p, ok = E.decompress(y, s)
        assert bool(_arr(ok)[0])
        pts.append(_arr(p)[0])
    return jnp.asarray(np.stack(pts))


class TestMsmWindowAccumKernel:
    def test_window_sums_bit_identical(self):
        pts = _real_points(8)
        table = E.msm_table(pts)
        rng = np.random.default_rng(5)
        digits = jnp.asarray(rng.integers(0, 16, (8, 64)).astype(np.int32))
        got = _arr(kern.msm_window_accum(table, digits))
        want = _arr(E._window_sums_lax(table, digits))
        assert got.shape == (64, 4, 32)
        assert np.array_equal(got, want)

    def test_zero_digit_rows_and_b1(self):
        pts = _real_points(8)
        table = E.msm_table(pts)
        rng = np.random.default_rng(6)
        digits = rng.integers(0, 16, (8, 64)).astype(np.int32)
        digits[3, :] = 0  # excluded row: selects only identity entries
        digits[7, :] = 0
        dj = jnp.asarray(digits)
        assert np.array_equal(_arr(kern.msm_window_accum(table, dj)),
                              _arr(E._window_sums_lax(table, dj)))
        t1 = E.msm_table(pts[:1])
        d1 = jnp.zeros((1, 64), jnp.int32)
        assert np.array_equal(_arr(kern.msm_window_accum(t1, d1)),
                              _arr(E._window_sums_lax(t1, d1)))

    def test_rejects_non_pow2_batch(self):
        pts = _real_points(2)
        table = jnp.concatenate([E.msm_table(pts)] * 3, axis=0)[:3]
        with pytest.raises(ValueError, match="power of two"):
            kern.msm_window_accum(table, jnp.zeros((3, 64), jnp.int32))

    @pytest.mark.slow
    def test_n1024_agreement_sweep(self):
        # The kern_gate slow lane: the window accumulator at the B=1024
        # launch cap (10 tree levels — the deepest in-kernel fold the
        # engine can ever launch) agrees with the lax path limb for
        # limb.  Identity-padded like the real MSM: 8 real points, the
        # rest identity rows with digit 0.
        pts = _real_points(8)
        b = 1024
        full = jnp.concatenate([pts, E.identity_ext((b - 8,))], axis=0)
        table = E.msm_table(full)
        rng = np.random.default_rng(13)
        digits = np.zeros((b, 64), np.int32)
        digits[:8] = rng.integers(0, 16, (8, 64))
        dj = jnp.asarray(digits)
        assert np.array_equal(_arr(kern.msm_window_accum(table, dj)),
                              _arr(E._window_sums_lax(table, dj)))


# ---------------------------------------------------------------------------
# Route plumbing (HOTSTUFF_TPU_KERN) + the interpret probe
# ---------------------------------------------------------------------------


class TestKernRoute:
    def test_mode_default_and_validation(self):
        assert kern.mode() in ("lax", "pallas")
        with pytest.raises(ValueError):
            kern.set_mode("mosaic")

    def test_interpret_probe_and_default(self):
        # CPU backend (conftest): production kernels must interpret.
        assert kern.interpret_default() is True
        assert kern.interpret_probe() is True

    def test_field_mul_routes_through_kernel(self):
        rng = np.random.default_rng(21)
        a = jnp.asarray(rng.integers(0, 512, (8, 32)).astype(np.int32))
        b = jnp.asarray(rng.integers(0, 512, (8, 32)).astype(np.int32))
        want = _arr(F._mul_lax(a, b))
        ambient = kern.mode()
        try:
            kern.set_mode("pallas")
            assert np.array_equal(_arr(F.mul(a, b)), want)
            kern.set_mode("lax")
            assert np.array_equal(_arr(F.mul(a, b)), want)
        finally:
            kern.set_mode(ambient)

    @pytest.mark.slow
    def test_engine_rlc_bisection_mask_bit_identical(self):
        # The acceptance path: HOTSTUFF_TPU_KERN=pallas forced through
        # verify_batch_rlc, including the bisection slow path (one
        # corrupted signature), must return the exact mask the lax
        # reference computes.  Compile-bound (~2 min interpreted) —
        # kern_gate's lane.
        rng = np.random.default_rng(17)
        msgs, pks, sigs = [], [], []
        for _ in range(6):
            sk = rng.bytes(32)
            msg = rng.bytes(32)
            _, pk = ref.generate_keypair(sk)
            msgs.append(msg)
            pks.append(pk)
            sigs.append(ref.sign(sk, msg))
        bad = list(sigs)
        bad[2] = bad[2][:63] + bytes([bad[2][63] ^ 1])
        want_ok = eddsa.verify_batch(msgs, pks, sigs)
        want_bad = eddsa.verify_batch(msgs, pks, bad)
        assert want_ok.all() and not want_bad[2] and want_bad.sum() == 5
        ambient = kern.mode()
        try:
            kern.set_mode("pallas")
            got_ok = eddsa.verify_batch_rlc(msgs, pks, sigs)
            got_bad = eddsa.verify_batch_rlc(msgs, pks, bad)
        finally:
            kern.set_mode(ambient)
        assert got_ok.tolist() == want_ok.tolist()
        assert got_bad.tolist() == want_bad.tolist()


# ---------------------------------------------------------------------------
# MSM window-chunk plumbing
# ---------------------------------------------------------------------------


class TestMsmWindowChunk:
    def test_get_set_validate(self):
        default = E.msm_window_chunk()
        assert 64 % default == 0
        try:
            E.set_msm_window_chunk(16)
            assert E.msm_window_chunk() == 16
        finally:
            E.set_msm_window_chunk(default)
        for bad in (0, 5, 3, -4, 128, "8"):
            with pytest.raises(ValueError):
                E.set_msm_window_chunk(bad)
        assert E.msm_window_chunk() == default

    def test_window_sums_bit_identical_across_chunks(self):
        pts = _real_points(4, seed=2)
        rng = np.random.default_rng(8)
        digits = jnp.asarray(rng.integers(0, 16, (4, 64)).astype(np.int32))
        default = E.msm_window_chunk()
        try:
            E.set_msm_window_chunk(4)
            w4 = _arr(E.msm_window_sums(pts, digits))
            E.set_msm_window_chunk(8)
            w8 = _arr(E.msm_window_sums(pts, digits))
        finally:
            E.set_msm_window_chunk(default)
        assert np.array_equal(w4, w8)


# ---------------------------------------------------------------------------
# Kernel 4: rlc_tail
# ---------------------------------------------------------------------------


def _ext_limbs(pt) -> np.ndarray:
    """Python-int ext point (ref_ed25519) -> (4, 32) limbs, as given."""
    return np.stack([F.to_limbs(c % P) for c in pt])


def _torsion8():
    ty = int.from_bytes(eddsa._SMALL_ORDER_Y[3].tobytes(), "little")
    return ref.decode_point(ty.to_bytes(32, "little"))


def _window_sum_case(name: str) -> np.ndarray:
    """(64, 4, 32) MSB-first window sums for one case of the fold."""
    rng = np.random.default_rng(28)
    wsums = _arr(E.identity_ext((64,))).copy()
    if name == "real_msm":
        pts = np.stack([_ext_limbs(ref.scalar_mult(
            int.from_bytes(rng.bytes(32), "little") % L or 1, ref.B))
            for _ in range(6)])
        scalars = rng.integers(0, 256, (6, 32))
        scalars[:, 31] &= 0x0F                       # < 2^252 < L
        digits = E.unpack_nibbles_msb(jnp.asarray(scalars, jnp.int32))
        return _arr(E.msm_window_sums(jnp.asarray(pts), digits))
    if name == "first_window_only":
        wsums[0] = _ext_limbs(ref.scalar_mult(7, ref.B))
    elif name == "last_window_only":
        wsums[63] = _ext_limbs(ref.scalar_mult(7, ref.B))
    elif name == "torsion8":
        # every window a point of the prime-order group plus a multiple
        # of an order-8 point: the fold must carry the torsion part
        # through all 256 doublings as the complete formulas do.
        t8 = _torsion8()
        for j in range(64):
            wsums[j] = _ext_limbs(ref.pt_add(
                ref.scalar_mult(j + 2, ref.B), ref.scalar_mult(j % 8, t8)))
    elif name == "weak_bound_511":
        wsums[:] = 511                # no point: the worst carry chains
    elif name != "all_identity":
        raise AssertionError(name)
    return wsums


def _comb_entries(c: int):
    digits = jnp.asarray(F.to_limbs(c))              # base-256, LE
    return digits, jnp.asarray(E.comb_table())[jnp.arange(32), digits]


_horner_ref = jax.jit(E.msm_horner)


@pytest.mark.parametrize("case", [
    "real_msm", "all_identity", "first_window_only", "last_window_only",
    "torsion8", "weak_bound_511"])
def test_rlc_tail_fold_bit_identical_to_msm_horner(case):
    wsums = jnp.asarray(_window_sum_case(case))
    got, _ = kern.rlc_tail(E.to_cached(wsums), _comb_entries(0)[1])
    want = _arr(_horner_ref(wsums))
    assert np.array_equal(_arr(got), want)
    only = {"first_window_only": 16 ** 63, "last_window_only": 1}.get(case)
    if only:                # and the value is right, not just consistent
        x, y, z = (F.from_limbs(_arr(F.canonical(got[c]))) for c in range(3))
        assert ref.pt_equal((x, y, z, 0),
                            ref.scalar_mult(7 * only % L, ref.B))


@pytest.mark.parametrize("c", [
    0, 1, L - 1, 1 << 252,
    int.from_bytes(np.random.default_rng(29).bytes(32), "little") % L],
    ids=["0", "1", "L-1", "2^252", "random"])
def test_rlc_tail_comb_equals_comb_mul_base(c):
    digits, entries = _comb_entries(c)
    ident = E.to_cached(E.identity_ext((64,)))
    _, got = kern.rlc_tail(ident, entries)
    want = E.comb_mul_base(digits)
    # as a point: cross-multiplied, canonical (what rlc_finish compares)
    cross = _arr(F.canonical(F.mul(
        jnp.stack([got[0], want[0], got[1], want[1]]),
        jnp.stack([want[2], got[2], want[2], got[2]]))))
    assert np.array_equal(cross[0], cross[1])
    assert np.array_equal(cross[2], cross[3])
    x, y, z = (F.from_limbs(_arr(F.canonical(got[k]))) for k in range(3))
    assert ref.pt_equal((x, y, z, 0), ref.scalar_mult(c, ref.B))
    # the kernel adds the 32 entries in comb_mul_base's order, so even
    # the representative is the same
    assert np.array_equal(_arr(got), _arr(want))


def test_rlc_tail_refuses_other_shapes():
    with pytest.raises(ValueError, match="rlc_tail takes"):
        kern.rlc_tail(jnp.zeros((32, 4, 32), jnp.int32),
                      jnp.zeros((32, 4, 32), jnp.int32))


# ---------------------------------------------------------------------------
# Compile manifest + tracker (the persistent-cache accounting)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("env_dir", ["/somewhere/else/xla", None])
def test_configure_xla_cache_one_location(monkeypatch, env_dir):
    """One cache, placeable from outside: with JAX_COMPILATION_CACHE_DIR
    set jax already persists there, so nothing is configured in code
    and that path is what the manifest records; unset, the cache is the
    fixed in-checkout directory."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fixed = os.path.join(repo_root(), "results", "compile_cache", "xla")
        assert configure_xla_cache() == fixed
        assert updates == [("jax_compilation_cache_dir", fixed)]
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert configure_xla_cache() == env_dir
        assert updates == []


class TestCompileManifest:
    def test_cold_then_warm_roundtrip(self, tmp_path):
        path = str(tmp_path / "manifest.json")
        cache_dir = str(tmp_path / "xla")
        os.makedirs(cache_dir)
        clock = [0.0]

        def tick():
            return clock[0]

        # Cold boot: every shape is a miss and costs 5 "seconds".
        cold = CompileTracker(cache_dir=cache_dir, manifest_path=path,
                              clock=tick, kernel="k1")
        for key in ("warmup:8", "warmup:16", "rlc:8"):
            def thunk():
                clock[0] += 5.0
            cold.warm(key, thunk)
        cold.finish()
        assert cold.misses == 3 and cold.hits == 0
        snap = cold.snapshot()
        assert snap["warm_boot"] is False
        assert snap["shapes"] == {"rlc:8": 5.0, "warmup:8": 5.0,
                                  "warmup:16": 5.0}
        json.dumps(snap)  # OP_STATS section must be JSON-safe

        # Warm boot against the SAME manifest + cache dir: zero misses,
        # lower wall.
        warm = CompileTracker(cache_dir=cache_dir, manifest_path=path,
                              clock=tick, kernel="k1")
        for key in ("warmup:8", "warmup:16", "rlc:8"):
            def thunk():
                clock[0] += 0.2
            warm.warm(key, thunk)
        warm.finish()
        assert warm.misses == 0 and warm.hits == 3
        assert warm.snapshot()["warm_boot"] is True
        runs = CompileManifest(path).data["runs"]
        assert len(runs) == 2
        assert runs[0]["misses"] == 3 and runs[1]["misses"] == 0
        assert runs[1]["wall_s"] < runs[0]["wall_s"]
        # A DIFFERENT (or wiped) cache dir must NOT read as warm: the
        # manifest alone cannot prove the compiled programs survived.
        other = CompileTracker(cache_dir=str(tmp_path / "elsewhere"),
                               manifest_path=path, clock=tick,
                               kernel="k1")
        other.warm("warmup:8", lambda: None)
        assert other.misses == 1 and other.hits == 0
        # Cache disabled (None) is always a cold boot.
        off = CompileTracker(cache_dir=None, manifest_path=path,
                             clock=tick, kernel="k1")
        off.warm("warmup:16", lambda: None)
        assert off.misses == 1

    def test_kernel_edit_invalidates(self, tmp_path):
        path = str(tmp_path / "manifest.json")
        cache_dir = str(tmp_path / "xla")
        os.makedirs(cache_dir)
        t1 = CompileTracker(cache_dir=cache_dir, manifest_path=path,
                            kernel="old")
        t1.warm("warmup:8", lambda: None)
        t1.finish()
        t2 = CompileTracker(cache_dir=cache_dir, manifest_path=path,
                            kernel="new")
        t2.warm("warmup:8", lambda: None)
        assert t2.misses == 1  # same shape, different kernel: a miss
        # Same kernel + same dir stays a hit (the control).
        t3 = CompileTracker(cache_dir=cache_dir, manifest_path=path,
                            kernel="old")
        t3.warm("warmup:8", lambda: None)
        assert t3.hits == 1

    def test_corrupt_manifest_starts_empty(self, tmp_path):
        path = tmp_path / "manifest.json"
        path.write_text("{torn")
        m = CompileManifest(str(path))
        assert m.data["kernels"] == {} and m.data["runs"] == []

    def test_fingerprint_covers_kern_sources(self):
        base = kernel_fingerprint()
        assert len(base) == 16
        # A caller's own sources (extra) must change the hash.
        assert kernel_fingerprint(extra=("chip_smoke.py",)) != base


class TestWarmupWiring:
    class _Shapes:
        def __init__(self):
            self.buckets, self.chunks, self.rlc = [], [], []

        def mark_bucket(self, n):
            self.buckets.append(n)

        def mark_chunks(self, g):
            self.chunks.append(g)

        def mark_rlc(self, n):
            self.rlc.append(n)

    class _Engine:
        def __init__(self, tracker):
            self.compile_tracker = tracker
            self._shapes = TestWarmupWiring._Shapes()

        def _verify(self, msgs, pks, sigs):
            return [True] * len(msgs)

    def test_warm_shapes_records_per_shape(self, tmp_path):
        from hotstuff_tpu.sidecar import service

        cache_dir = str(tmp_path / "xla")
        os.makedirs(cache_dir)
        tracker = CompileTracker(
            cache_dir=cache_dir,
            manifest_path=str(tmp_path / "m.json"), kernel="k")
        engine = self._Engine(tracker)
        service._warm_shapes(engine, 8, 32, "warmup")
        assert engine._shapes.buckets == [8, 16, 32]
        assert set(tracker.shapes) == {"warmup:8", "warmup:16",
                                       "warmup:32"}
        assert tracker.misses == 3
        tracker.finish()
        # A tracker-less engine (host mode, tests) still warms.
        bare = self._Engine(None)
        service._warm_shapes(bare, 8, 8, "warmup")
        assert bare._shapes.buckets == [8]
        # Second boot, same manifest + cache dir: all hits.
        t2 = CompileTracker(cache_dir=cache_dir,
                            manifest_path=str(tmp_path / "m.json"),
                            kernel="k")
        service._warm_shapes(self._Engine(t2), 8, 32, "warmup")
        assert (t2.hits, t2.misses) == (3, 0)

    def test_stats_snapshot_carries_compile_section(self, tmp_path):
        from hotstuff_tpu.sidecar.service import VerifyEngine

        engine = VerifyEngine(use_host=True)
        try:
            assert "compile" not in engine.stats_snapshot()
            tracker = CompileTracker(
                manifest_path=str(tmp_path / "m.json"), kernel="k")
            tracker.warm("warmup:8", lambda: None)
            engine.compile_tracker = tracker
            snap = engine.stats_snapshot()
            assert snap["compile"]["misses"] == 1
            json.dumps(snap)
        finally:
            engine.stop()


# ---------------------------------------------------------------------------
# warmup_report + bench roofline surfaces
# ---------------------------------------------------------------------------


class TestWarmupReport:
    def _load(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "warmup_report", os.path.join(REPO, "scripts",
                                          "warmup_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_report_compares_latest_cold_and_warm(self):
        wr = self._load()
        manifest = {"runs": [
            {"t": 1.0, "kernel": "old", "hits": 0, "misses": 9,
             "wall_s": 100.0},
            {"t": 2.0, "kernel": "k", "hits": 0, "misses": 12,
             "wall_s": 62.0},
            {"t": 3.0, "kernel": "k", "hits": 12, "misses": 0,
             "wall_s": 3.5},
        ]}
        doc = wr.report(manifest)
        cmp_ = doc["comparison"]
        assert cmp_["kernel"] == "k"
        assert cmp_["cold_wall_s"] == 62.0
        assert cmp_["warm_wall_s"] == 3.5
        assert cmp_["saved_pct"] == pytest.approx(94.4, abs=0.1)

    def test_report_without_pair(self):
        wr = self._load()
        doc = wr.report({"runs": [
            {"t": 1.0, "kernel": "k", "hits": 0, "misses": 2,
             "wall_s": 10.0}]})
        assert doc["comparison"] is None

    def test_cli_missing_manifest(self, tmp_path):
        wr = self._load()
        assert wr.main(["--manifest", str(tmp_path / "none.json")]) == 1
