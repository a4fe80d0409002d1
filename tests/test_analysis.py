"""graftlint tests: every rule fires on a known-bad fixture and stays
quiet on a known-good one; the repaired tree lints clean; the sanitizer
wiring builds and runs (tier-2, slow-marked).
"""

import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from hotstuff_tpu.analysis import (hotpath, padshape, sanitize, sockets,
                                   timing, wirecheck)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src: str):
    return hotpath.check_sources(
        {"mod.py": textwrap.dedent(src)})


def rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# hot-path rules
# ---------------------------------------------------------------------------

def test_host_sync_in_jit_fires_on_item_and_casts():
    findings = lint("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def verify_mask(x):
            n = int(x.sum())          # host round trip
            y = x * 2
            host = np.asarray(y)      # device->host copy
            return host[:n], y.max().item()
        """)
    assert rules(findings) == {"host-sync-in-jit"}
    assert len(findings) == 3


def test_host_sync_quiet_on_host_helpers_and_static_shapes():
    findings = lint("""
        import jax
        import jax.numpy as jnp
        import numpy as np

        def to_limbs(x: int):
            return np.array([int(x) >> i for i in range(4)],
                            dtype=np.int32)

        @jax.jit
        def verify_mask(x, table=()):
            n = x.shape[0]            # static: .shape launders
            rows = int(n // 2)        # python int math, not traced
            return x.reshape(rows, -1).astype(jnp.int32)
        """)
    assert findings == []


def test_traced_branch_fires_and_static_branch_is_quiet():
    bad = lint("""
        import jax

        @jax.jit
        def f(x):
            if x.sum() > 0:           # concretization error / retrace
                return x
            return -x
        """)
    assert rules(bad) == {"traced-branch"}
    good = lint("""
        import jax

        def dbl(p, with_t: bool = True):
            if with_t:                # static python config param
                return p + p
            return p

        @jax.jit
        def f(x):
            if x.ndim == 2:           # laundered: shape metadata
                return dbl(x, with_t=False)
            return dbl(x)
        """)
    assert good == []


def test_mutable_default_arg_fires_only_on_hot_functions():
    bad = lint("""
        import jax

        @jax.jit
        def f(x, opts={}):
            return x
        """)
    assert rules(bad) == {"mutable-default-arg"}
    good = lint("""
        def host_helper(x, opts={}):   # not jit-reachable
            return x
        """)
    assert good == []


def test_f64_literal_fires_on_promotion_and_dtype():
    bad = lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = x * 1.5                       # f64 under x64
            return jnp.zeros(4, dtype=jnp.float64), y
        """)
    assert rules(bad) == {"f64-literal"}
    assert len(bad) == 2
    good = lint("""
        import jax
        import jax.numpy as jnp

        SCALE = 1.5  # host-side constant, folded at trace time

        @jax.jit
        def f(x):
            return x.astype(jnp.float32) * jnp.float32(2)
        """)
    assert good == []


def test_implicit_limb_dtype_fires_on_bare_constant_lists():
    bad = lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            bias = jnp.asarray([237, 255, 127])   # backend-dependent dtype
            return x + bias
        """)
    assert rules(bad) == {"implicit-limb-dtype"}
    good = lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            bias = jnp.asarray([237, 255, 127], dtype=jnp.int32)
            return x + bias
        """)
    assert good == []


def test_nondonated_buffer_fires_on_verify_entry_points():
    bad = lint("""
        import jax

        def verify_packed(packed):
            return packed.sum(-1)

        verify_packed_jit = jax.jit(verify_packed)
        """)
    assert rules(bad) == {"nondonated-buffer"}
    good = lint("""
        import jax

        def verify_packed(packed):
            return packed.sum(-1)

        def helper(fn):
            return jax.jit(fn)        # not a verify_* symbol

        verify_packed_jit = jax.jit(verify_packed, donate_argnums=0)
        """)
    assert good == []


def test_suppression_comment_silences_a_rule():
    findings = lint("""
        import jax

        def verify_packed(packed):
            return packed.sum(-1)

        # profiling scripts re-time one device-resident input
        # graftlint: disable=nondonated-buffer
        verify_packed_jit = jax.jit(verify_packed)
        """)
    assert findings == []


def test_taint_follows_cross_module_calls():
    """A hot function calling into a field module taints the callee's
    params — the rule fires in the callee file."""
    findings = hotpath.check_sources({
        "field.py": textwrap.dedent("""
            def mul(a, b):
                return int(a) * b       # host sync on a traced value
            """),
        "curve.py": textwrap.dedent("""
            import jax
            from . import field as F

            @jax.jit
            def verify_mask(x):
                return F.mul(x, x)
            """),
    })
    assert [(f.path, f.rule) for f in findings] == \
        [("field.py", "host-sync-in-jit")]


def test_except_handler_bodies_are_linted():
    findings = lint("""
        import jax

        @jax.jit
        def f(x):
            try:
                return x * 2
            except ValueError:
                return int(x.sum())   # host sync hidden in an error path
        """)
    assert rules(findings) == {"host-sync-in-jit"}


def test_from_jax_import_numpy_spelling_is_covered():
    findings = lint("""
        import jax
        from jax import numpy as jnp

        @jax.jit
        def f(x):
            return x + jnp.asarray([237, 255, 127])
        """)
    assert rules(findings) == {"implicit-limb-dtype"}


def test_scan_and_shard_map_bodies_are_hot():
    findings = lint("""
        import jax
        from jax import shard_map

        def _make_body(cap: int):
            def _body(a, present):
                if a.sum() > cap:     # traced branch in a shard body
                    return a
                return a * present
            return _body

        fn = shard_map(_make_body(4), in_specs=None, out_specs=None)
        checker = jax.jit(fn)
        """)
    assert rules(findings) == {"traced-branch"}


# ---------------------------------------------------------------------------
# wire/constants cross-checker (fixture trees under tmp_path)
# ---------------------------------------------------------------------------

WIRE_FILES = (wirecheck.PROTOCOL, wirecheck.SIDECAR_CLIENT,
              wirecheck.CRYPTO_HPP, wirecheck.FIELD25519,
              wirecheck.INTMATH, wirecheck.FIELD381, wirecheck.BLS12381,
              wirecheck.TXSIGN, wirecheck.TX_FRAME_HPP,
              wirecheck.DAGWIRE, wirecheck.MEMPOOL_MSG_HPP)


@pytest.fixture()
def wire_tree(tmp_path):
    """Copy of the real tree's cross-checked files: the known-good base
    every bad fixture mutates — so the tests track the real sources."""
    for rel in WIRE_FILES:
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    return tmp_path


def _mutate(tree, rel, old, new):
    path = tree / rel
    text = path.read_text()
    assert old in text, f"fixture drift: {old!r} not in {rel}"
    path.write_text(text.replace(old, new))


def test_wire_checker_quiet_on_consistent_tree(wire_tree):
    assert wirecheck.check(str(wire_tree)) == []


def test_wire_tag_mismatch_fires_on_one_sided_opcode_edit(wire_tree):
    _mutate(wire_tree, wirecheck.SIDECAR_CLIENT,
            "kOpBlsSign = 4", "kOpBlsSign = 9")
    findings = wirecheck.check(str(wire_tree))
    assert rules(findings) == {"wire-tag-mismatch"}
    assert "kOpBlsSign" in findings[0].message


def test_wire_length_mismatch_fires_on_bls_and_digest_drift(wire_tree):
    _mutate(wire_tree, wirecheck.PROTOCOL,
            "BLS_SIG_LEN = 192", "BLS_SIG_LEN = 96")
    _mutate(wire_tree, wirecheck.SIDECAR_CLIENT,
            "kDigestLen = 32", "kDigestLen = 20")
    findings = wirecheck.check(str(wire_tree))
    assert rules(findings) == {"wire-length-mismatch"}
    assert len(findings) >= 2  # kBlsSigLen drift + digest drift


def test_field_modulus_mismatch_fires_on_one_sided_edit(wire_tree):
    _mutate(wire_tree, wirecheck.FIELD25519,
            "P = 2**255 - 19", "P = 2**255 - 21")
    findings = wirecheck.check(str(wire_tree))
    assert rules(findings) == {"field-modulus-mismatch"}
    assert any(f.path == wirecheck.FIELD25519 for f in findings)


def test_field_modulus_mismatch_fires_on_cpp_hex_edit(wire_tree):
    _mutate(wire_tree, wirecheck.CRYPTO_HPP,
            "b9feffffffffaaab", "b9feffffffffaaad")
    findings = wirecheck.check(str(wire_tree))
    assert rules(findings) == {"field-modulus-mismatch"}


def test_wire_header_mismatch_fires_on_request_header_drift(wire_tree):
    """Widening msg_len to u32 in protocol.py without touching
    write_header: the exact one-sided edit the rule exists for."""
    _mutate(wire_tree, wirecheck.PROTOCOL,
            '_HDR = struct.Struct("<BIIH")',
            '_HDR = struct.Struct("<BIII")')
    findings = wirecheck.check(str(wire_tree))
    assert rules(findings) == {"wire-header-mismatch"}
    assert any("write_header" in f.message for f in findings)


def test_wire_header_mismatch_fires_on_reply_layout_drift(wire_tree):
    """Shrinking the reply request id breaks the C++ reader's raw-offset
    rid parse (reply[1..4])."""
    _mutate(wire_tree, wirecheck.PROTOCOL,
            '_REPLY_HDR = struct.Struct("<BII")',
            '_REPLY_HDR = struct.Struct("<BHI")')
    findings = wirecheck.check(str(wire_tree))
    assert rules(findings) == {"wire-header-mismatch"}


def test_wire_header_mismatch_fires_on_big_endian_format(wire_tree):
    _mutate(wire_tree, wirecheck.PROTOCOL,
            '_HDR = struct.Struct("<BIIH")',
            '_HDR = struct.Struct(">BIIH")')
    findings = wirecheck.check(str(wire_tree))
    assert "wire-header-mismatch" in rules(findings)
    assert any("little-endian" in f.message for f in findings)


# ---------------------------------------------------------------------------
# padded-bucket (launch-shape discipline)
# ---------------------------------------------------------------------------

def test_padded_bucket_fires_on_unbucketed_launch():
    findings = padshape.check_sources({"mod.py": textwrap.dedent("""
        import numpy as np

        def dispatch(rows):
            return verify_packed_donated(rows)
        """)})
    assert rules(findings) == {"padded-bucket"}


def test_padded_bucket_quiet_on_bucketed_launch_and_factories():
    findings = padshape.check_sources({"mod.py": textwrap.dedent("""
        def dispatch(rows, n):
            m = next_pow2(n)
            rows = pad(rows, m)
            return verify_packed_donated(rows)

        def cached_launch(mesh, arrays):
            m = _bucket(len(arrays))
            return _cached_verifier(mesh)(arrays[:m])

        verify_packed_donated = _jit_donated(verify_packed)
        """)})
    assert findings == []


def test_padded_bucket_quiet_on_real_tree():
    assert padshape.check(REPO) == []


# ---------------------------------------------------------------------------
# shard-misaligned-launch (mesh launch-size discipline)
# ---------------------------------------------------------------------------

MESH_MOD = padshape.MESH_TARGETS[0]


def test_shard_misaligned_fires_on_handrolled_device_math():
    findings = padshape.check_sources({MESH_MOD: textwrap.dedent("""
        import numpy as np

        def verify_over_mesh(mesh, prep, n_dev):
            n = prep.shape[0]
            m = n_dev * next_pow2(-(-n // n_dev))
            rows = np.pad(prep, m - n)
            return _cached_verifier(mesh)(rows)
        """)})
    assert rules(findings) == {"shard-misaligned-launch"}
    assert any("size math against n_dev" in f.message for f in findings)


def test_shard_misaligned_fires_on_unaligned_mesh_launch():
    # A mesh launch with NO size math at all still needs the helper —
    # whoever shaped the buffers must have aligned them.
    findings = padshape.check_sources({MESH_MOD: textwrap.dedent("""
        def launch(mesh, rows, z, n):
            m = next_pow2(n)
            return _cached_rlc_verifier(mesh)(rows[:m], z[:m])
        """)})
    assert rules(findings) == {"shard-misaligned-launch"}
    assert any("mesh launch _cached_rlc_verifier" in f.message
               for f in findings)


def test_shard_misaligned_quiet_on_helper_routed_launch():
    findings = padshape.check_sources({MESH_MOD: textwrap.dedent("""
        import numpy as np

        def verify_over_mesh(mesh, prep):
            n = prep.shape[0]
            m = shard_aligned_rows(n, mesh.devices.size)
            rows = np.pad(prep, m - n)
            return _cached_verifier(mesh)(rows)

        def registry_capacity(self, n):
            return shard_aligned_rows(n, self.n_devices)
        """)})
    assert findings == []


def test_shard_misaligned_fires_on_handrolled_scan_chunks():
    """graftscale: a whole-backlog scan launch whose chunk count comes
    from hand-rolled n_dev division instead of mesh_chunk_count is a
    finding — the (g, rows) scan shapes are warmed exactly like the
    buckets, so a free-hand g can land a never-compiled program."""
    findings = padshape.check_sources({MESH_MOD: textwrap.dedent("""
        def scan_backlog(mesh, rows_in, present, n_dev, rows):
            g = next_pow2(-(-rows_in.shape[0] // n_dev) // rows)
            return _cached_chunk_verifier(mesh, g, rows)(rows_in,
                                                         present)
        """)})
    assert rules(findings) == {"shard-misaligned-launch"}
    assert any("size math against n_dev" in f.message for f in findings)


def test_shard_misaligned_quiet_on_mesh_chunk_count_routed_scan():
    """mesh_chunk_count is one of THE shard helpers: a scan launch
    routed through it is clean."""
    findings = padshape.check_sources({MESH_MOD: textwrap.dedent("""
        import numpy as np

        def scan_backlog(mesh, prep, rows):
            n = prep.shape[0]
            n_dev = mesh.devices.size
            g = mesh_chunk_count(n, n_dev, rows)
            m = n_dev * g * rows
            padded = np.pad(prep, m - n)
            return _cached_chunk_verifier(mesh, g, rows)(padded)
        """)})
    assert findings == []


def test_shard_misaligned_quiet_on_factories_and_non_mesh_modules():
    # The donated-cache factory REFERENCES _cached_verifier without
    # launching it; a non-mesh module may do n_dev math freely (the rule
    # is scoped to the mesh-path targets).
    factory = textwrap.dedent("""
        def _cached_verifier_donated(mesh, max_subbatch):
            if backend() == "cpu":
                return _cached_verifier(mesh, max_subbatch)
            return make_sharded_verifier(mesh, max_subbatch, donate=True)
        """)
    assert padshape.check_sources({MESH_MOD: factory}) == []
    elsewhere = textwrap.dedent("""
        def partition(n, n_dev):
            return n // n_dev
        """)
    assert padshape.check_sources({"mod.py": elsewhere}) == []


# ---------------------------------------------------------------------------
# pallas-interpret-in-prod (graftkern interpreter-pin discipline)
# ---------------------------------------------------------------------------


def test_pallas_interpret_fires_on_literal_true():
    findings = padshape.check_sources({
        "hotstuff_tpu/ops/kern/fake.py": textwrap.dedent("""
            def my_kernel_entry(x):
                return pl.pallas_call(
                    body,
                    out_shape=shape,
                    interpret=True,
                )(x)
            """)})
    assert rules(findings) == {"pallas-interpret-in-prod"}
    assert "my_kernel_entry" in findings[0].message


def test_pallas_interpret_quiet_on_backend_probe_and_helper_call():
    # interpret selected off the backend probe: clean.
    clean = textwrap.dedent("""
        def entry(x):
            return pl.pallas_call(
                body, out_shape=shape,
                interpret=interpret_default(),
            )(x)
        """)
    assert padshape.check_sources(
        {"hotstuff_tpu/ops/kern/fake.py": clean}) == []
    # The backend-probe helper itself may pin the literal.
    probe = textwrap.dedent("""
        def interpret_default():
            return pl.pallas_call(k, out_shape=s, interpret=True)(x)
        """)
    assert padshape.check_sources(
        {"hotstuff_tpu/ops/kern/backend.py": probe}) == []
    # ... but ONLY in backend.py: a shim merely NAMED interpret_default
    # in another kernel module cannot claim the exemption.
    findings = padshape.check_sources(
        {"hotstuff_tpu/ops/kern/msm_accum.py": probe})
    assert rules(findings) == {"pallas-interpret-in-prod"}


def test_pallas_interpret_suppression_comment():
    src = textwrap.dedent("""
        def probe(x):
            return pl.pallas_call(
                body, out_shape=shape,
                # graftlint: disable=pallas-interpret-in-prod
                interpret=True,
            )(x)
        """)
    assert padshape.check_sources(
        {"hotstuff_tpu/ops/kern/fake.py": src}) == []


def test_pallas_interpret_quiet_on_real_kern_tree():
    # The real kern package carries exactly one forced literal — the
    # interpreter probe — behind its worked suppression.
    findings = [f for f in padshape.check(REPO)
                if f.rule == "pallas-interpret-in-prod"]
    assert findings == []


def test_padded_bucket_fires_on_warmup_floor_drift(tmp_path):
    for rel in (padshape.EDDSA, padshape.SERVICE):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    _mutate(tmp_path, padshape.SERVICE,
            "_warm_shapes(engine, 8, warm_max",
            "_warm_shapes(engine, 16, warm_max")
    findings = padshape.check(str(tmp_path), targets=())
    assert rules(findings) == {"padded-bucket"}
    assert any("_MIN_BUCKET" in f.message for f in findings)


def test_padded_bucket_fires_on_non_pow2_coalesce(tmp_path):
    for rel in (padshape.EDDSA, padshape.SERVICE):
        dst = tmp_path / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(REPO, rel), dst)
    _mutate(tmp_path, padshape.SERVICE,
            "MAX_COALESCED = 16 * MAX_SUBBATCH",
            "MAX_COALESCED = 12 * MAX_SUBBATCH")
    findings = padshape.check(str(tmp_path), targets=())
    assert rules(findings) == {"padded-bucket"}
    assert any("power-of-two" in f.message for f in findings)


def test_must_cover_gate():
    from hotstuff_tpu.analysis.__main__ import check_coverage

    # the lint_gate pins: the RLC scalar module, the verifysched package
    # (directory target), and the newly-covered crypto/BLS modules
    assert check_coverage(REPO, [
        "hotpath:hotstuff_tpu/ops/scalar25519.py",
        "hotpath:hotstuff_tpu/crypto/eddsa.py",
        "hotpath:hotstuff_tpu/offchain/bls12381.py",
        "hotpath:hotstuff_tpu/sidecar/sched/scheduler.py",
        "hotpath:hotstuff_tpu/sidecar/sched/shapes.py",
        "hotpath:hotstuff_tpu/sidecar/sched/stats.py",
        "hotpath:hotstuff_tpu/sidecar/sched/classes.py",
        # graftkern pins: the Pallas kernel modules sit inside BOTH the
        # hotpath and padshape scans
        "hotpath:hotstuff_tpu/ops/kern/field_mul.py",
        "hotpath:hotstuff_tpu/ops/kern/msm_accum.py",
        "padshape:hotstuff_tpu/ops/kern/backend.py",
        "padshape:hotstuff_tpu/ops/kern/scalar_mont.py",
        # graftchaos pins (the sockets checker's targets)
        "sockets:hotstuff_tpu/chaos/plan.py",
        "sockets:hotstuff_tpu/chaos/runner.py",
        "sockets:hotstuff_tpu/chaos/recovery.py",
        "sockets:hotstuff_tpu/harness/faults.py",
        # bare pins accept any checker — including timing (exact file
        # and glob targets) and padshape
        "hotstuff_tpu/sidecar/protocol.py",
        "hotstuff_tpu/obs/spans.py",
        "timing:hotstuff_tpu/obs/trace.py",
    ]) == []
    # Checker qualification is load-bearing: the sockets checker scans
    # sidecar/ too, but a hotpath-qualified pin on a file only sockets
    # covers must FAIL (a union would let the hot-path lint silently
    # lose a file another checker's prefix still matches).
    out = check_coverage(REPO, ["hotpath:hotstuff_tpu/sidecar/client.py"])
    assert [f.rule for f in out] == ["must-cover"]
    assert "hotpath scan targets" in out[0].message
    # an unknown checker name fails loudly, never passes silently
    out = check_coverage(REPO, ["typo:hotstuff_tpu/sidecar/client.py"])
    assert [f.rule for f in out] == ["must-cover"]
    assert "unknown checker" in out[0].message
    # a file outside every checker's targets fails the gate
    out = check_coverage(REPO, ["hotstuff_tpu/utils/intmath.py"])
    assert [f.rule for f in out] == ["must-cover"]
    # a missing file fails the gate
    out = check_coverage(REPO, ["hotstuff_tpu/ops/nonexistent.py"])
    assert [f.rule for f in out] == ["must-cover"]


# ---------------------------------------------------------------------------
# timing rule (block_until_ready inside a timed region)
# ---------------------------------------------------------------------------

def tlint(src: str):
    return timing.check_sources({"prof.py": textwrap.dedent(src)})


def test_timing_rule_fires_between_timer_reads():
    findings = tlint("""
        import time

        def stage(fn, x):
            t0 = time.perf_counter()
            out = fn(x)
            out.block_until_ready()      # not the repo's fence
            return time.perf_counter() - t0
        """)
    assert rules(findings) == {"block-until-ready-in-timing"}


def test_timing_rule_quiet_on_asarray_fence_and_warmup():
    findings = tlint("""
        import time
        import numpy as np

        def stage(fn, x):
            fn(x).block_until_ready()    # warmup fence, before the timer
            t0 = time.perf_counter()
            out = fn(x)
            np.asarray(out)              # forced D2H: the honest fence
            return time.perf_counter() - t0

        def helper(x):
            return x.block_until_ready() # never times anything
        """)
    assert findings == []


def test_timing_rule_scopes_exclude_nested_functions():
    # The nested put() blocks, but only the OUTER scope times — and the
    # block sits outside the outer scope's timed region (per-stream put
    # workers are fenced individually, the outer loop times the whole
    # fan-out).
    findings = tlint("""
        import time

        def main(bufs, put_raw):
            def put(buf):
                x = put_raw(buf)
                x.block_until_ready()
                return x
            put(bufs[0])                 # warm
            t0 = time.perf_counter()
            outs = [put(b) for b in bufs]
            dt = time.perf_counter() - t0
            return outs, dt
        """)
    assert findings == []


def test_timing_rule_suppression_comment():
    findings = tlint("""
        import time

        def stage(fn, x):
            t0 = time.perf_counter()
            # CPU backend: block_until_ready is exact here
            # graftlint: disable=block-until-ready-in-timing
            fn(x).block_until_ready()
            return time.perf_counter() - t0
        """)
    assert findings == []


def test_timing_rule_quiet_on_real_profiling_scripts():
    assert timing.check(REPO) == []


# ---------------------------------------------------------------------------
# sanitizer wiring
# ---------------------------------------------------------------------------

def test_sanitizer_wiring_quiet_on_real_tree():
    assert sanitize.check(REPO) == []


def test_sanitizer_wiring_fires_when_preset_or_script_missing(tmp_path):
    native = tmp_path / "native"
    native.mkdir()
    (native / "CMakeLists.txt").write_text(
        "project(x CXX)\n")  # no GRAFT_SANITIZE, no -fsanitize
    findings = sanitize.check(str(tmp_path))
    assert rules(findings) == {"sanitizer-wiring"}
    assert any("native_sanitize.sh missing" in f.message for f in findings)


# ---------------------------------------------------------------------------
# the gate itself
# ---------------------------------------------------------------------------

def test_gate_exits_clean_on_repaired_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "hotstuff_tpu.analysis", "--root", REPO],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftlint: clean" in proc.stdout


def test_gate_exits_nonzero_on_findings(tmp_path):
    # An empty tree is missing every anchor: the gate must fail loudly,
    # not skip silently.
    proc = subprocess.run(
        [sys.executable, "-m", "hotstuff_tpu.analysis",
         "--root", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "finding" in proc.stderr


# ---------------------------------------------------------------------------
# tier-2: native sanitizer build-and-run
# ---------------------------------------------------------------------------

@pytest.mark.slow  # full native rebuild per sanitizer: minutes
@pytest.mark.parametrize("mode", ["address", "undefined"])
def test_native_sanitize_builds_and_runs(mode):
    script = os.path.join(REPO, "scripts", "native_sanitize.sh")
    proc = subprocess.run(
        [script, mode, "serde", "store"], cwd=REPO,
        capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    assert f"all tests clean under {mode}" in proc.stdout


# ---------------------------------------------------------------------------
# sockets rule (unbounded-socket-op over sidecar/, harness/, chaos/)
# ---------------------------------------------------------------------------

def slint(src: str):
    return sockets.check_sources({"net.py": textwrap.dedent(src)})


def test_unbounded_socket_op_fires_on_bare_ops():
    findings = slint("""
        import socket
        def dial():
            c = socket.create_connection(("127.0.0.1", 7100))
            return c
        def pump(sock):
            return sock.recv(4)
        def serve(listen_sock):
            conn, _ = listen_sock.accept()
            return conn
    """)
    assert [(f.rule, f.line) for f in findings] == [
        ("unbounded-socket-op", 4),
        ("unbounded-socket-op", 7),
        ("unbounded-socket-op", 9),
    ]
    assert "create_connection" in findings[0].message
    assert ".recv()" in findings[1].message


def test_unbounded_socket_op_quiet_on_bounded_ops():
    findings = slint("""
        import socket
        def dial(timeout):
            a = socket.create_connection(("h", 1), timeout=timeout)
            b = socket.create_connection(("h", 1), 5.0)
            return a, b
        def pump(sock):
            sock.settimeout(2.0)
            return sock.recv(4)
        def serve(listen_sock):
            listen_sock.settimeout(1.0)
            conn, _ = listen_sock.accept()
            return conn
        def not_a_socket(db):
            return db.connect()
    """)
    assert findings == []


def test_unbounded_socket_op_scopes_do_not_leak():
    # A settimeout in one function does not bound another function's
    # socket of the same name.
    findings = slint("""
        def a(sock):
            sock.settimeout(1.0)
            return sock.recv(4)
        def b(sock):
            return sock.recv(4)
    """)
    assert [(f.rule, f.line) for f in findings] == [
        ("unbounded-socket-op", 6)]


def test_unbounded_socket_op_timeout_none_still_fires():
    findings = slint("""
        import socket
        def dial():
            return socket.create_connection(("h", 1), timeout=None)
    """)
    assert [f.rule for f in findings] == ["unbounded-socket-op"]


def test_unbounded_socket_op_suppression():
    findings = slint("""
        def pump(sock):
            # callers bound the socket; server readers idle by design
            # graftlint: disable=unbounded-socket-op
            return sock.recv(4)
    """)
    assert findings == []


def test_sockets_rule_quiet_on_real_tree():
    assert sockets.check(REPO) == []


# ---------------------------------------------------------------------------
# obsspan rules (grafttrace instrumentation discipline)
# ---------------------------------------------------------------------------

from hotstuff_tpu.analysis import obsspan


def olint(src: str, path: str = "hotstuff_tpu/obs/mod.py"):
    return obsspan.check_sources({path: textwrap.dedent(src)})


def test_unclosed_span_fires_without_finally():
    findings = olint("""
        def pack(tracer):
            tok = tracer.begin_span("pack")
            do_work()
            tracer.end_span(tok)   # an exception above leaks the span
    """)
    assert [f.rule for f in findings] == ["unclosed-span"]


def test_unclosed_span_quiet_on_try_finally_and_with():
    assert olint("""
        def pack(tracer):
            tok = tracer.begin_span("pack")
            try:
                do_work()
            finally:
                tracer.end_span(tok)

        def bls(tracer):
            with tracer.span("device"):
                do_work()
    """) == []


def test_unclosed_span_exempts_context_manager_enter():
    # The _SpanCtx protocol: __enter__ begins, __exit__ ends — the
    # pairing is the interpreter's job, not a finally block's.
    assert olint("""
        class Ctx:
            def __enter__(self):
                self._tok = self._tracer.begin_span(self._stage)
                return self._tok

            def __exit__(self, *exc):
                self._tracer.end_span(self._tok)
    """) == []


def test_unclosed_span_scopes_are_per_function():
    # An end_span in a DIFFERENT function does not close this one.
    findings = olint("""
        def a(tracer):
            tok = tracer.begin_span("x")
            return tok

        def b(tracer, tok):
            try:
                pass
            finally:
                tracer.end_span(tok)
    """)
    assert [f.rule for f in findings] == ["unclosed-span"]


def test_span_inline_clock_fires_in_obs_modules_only():
    src = """
        import time
        def sample(self):
            return time.time()
    """
    findings = olint(src)
    assert [f.rule for f in findings] == ["span-inline-clock"]
    # the engine module may read monotonic() for OP_STATS; the clock
    # rule is scoped to obs/
    assert olint(src, path="hotstuff_tpu/sidecar/service.py") == []


def test_span_inline_clock_allows_injected_default():
    # A clock REFERENCE as a default parameter is the sanctioned idiom.
    assert olint("""
        from time import time as _wall_clock

        class Tracer:
            def __init__(self, clock=_wall_clock):
                self._clock = clock

            def now(self):
                return self._clock()
    """) == []


def test_span_inline_clock_catches_bare_imported_names():
    findings = olint("""
        from time import monotonic
        def tick(self):
            return monotonic()
    """)
    assert [f.rule for f in findings] == ["span-inline-clock"]


def test_obsspan_suppression_comment():
    assert olint("""
        import time
        def sample(self):
            # graftlint: disable=span-inline-clock
            return time.time()
    """) == []


def test_obsspan_quiet_on_real_tree():
    assert obsspan.check(REPO) == []


def test_obs_modules_pinned_to_span_and_timing_scans():
    from hotstuff_tpu.analysis.__main__ import check_coverage

    assert check_coverage(REPO, [
        "obsspan:hotstuff_tpu/obs/__init__.py",
        "obsspan:hotstuff_tpu/obs/spans.py",
        "obsspan:hotstuff_tpu/obs/trace.py",
        "obsspan:hotstuff_tpu/obs/sampler.py",
        "obsspan:hotstuff_tpu/sidecar/service.py",
        "timing:hotstuff_tpu/obs/trace.py",
        "timing:hotstuff_tpu/obs/sampler.py",
    ]) == []
    # a module outside the obsspan targets fails its qualified pin
    out = check_coverage(REPO, ["obsspan:hotstuff_tpu/harness/logs.py"])
    assert [f.rule for f in out] == ["must-cover"]


# ---------------------------------------------------------------------------
# graftscope: obsgrammar rules (Python<->C++ log-line grammar pins)
# ---------------------------------------------------------------------------

from hotstuff_tpu.analysis import obsgrammar

_GOOD_METRICS_PY = '''
_NODE_METRICS_RE = (r"\\[(\\S+Z) \\w+ [^\\]]+\\] METRICS "
                    r"commits=(\\d+) commit_rate=([0-9.]+) "
                    r"ingress_tx=(\\d+) ingress_bytes=(\\d+) "
                    r"busy=(\\d+) breaker=(\\w+)")
'''

_GOOD_METRICS_CPP = '''
void NodeMetrics::emit_sample(double dt_s) {
  LOG_INFO("node::metrics")
      << "METRICS commits=" << commits << " commit_rate=" << rate_buf
      << " ingress_tx=" << ingress_tx << " ingress_bytes=" << ingress_bytes
      << " busy=" << busy << " breaker=" << breaker_name(tpu);
}
'''


def test_obsgrammar_clean_fixture_pair():
    assert obsgrammar.check_sources({
        "hotstuff_tpu/obs/sampler.py": _GOOD_METRICS_PY,
        "native/src/common/metrics.cpp": _GOOD_METRICS_CPP}) == []


def test_obsgrammar_renamed_cpp_key_fires():
    bad = _GOOD_METRICS_CPP.replace('" busy="', '" busyx="')
    findings = obsgrammar.check_sources({
        "hotstuff_tpu/obs/sampler.py": _GOOD_METRICS_PY,
        "native/src/common/metrics.cpp": bad})
    assert [f.rule for f in findings] == ["metrics-grammar-mismatch"]
    assert "busyx" in findings[0].message


def test_obsgrammar_reordered_keys_fire_despite_same_set():
    bad = _GOOD_METRICS_CPP.replace(
        '" ingress_tx=" << ingress_tx << " ingress_bytes=" << ingress_bytes',
        '" ingress_bytes=" << ingress_bytes << " ingress_tx=" << ingress_tx')
    findings = obsgrammar.check_sources({
        "hotstuff_tpu/obs/sampler.py": _GOOD_METRICS_PY,
        "native/src/common/metrics.cpp": bad})
    assert [f.rule for f in findings] == ["metrics-grammar-mismatch"]


def test_obsgrammar_missing_anchor_is_a_finding():
    # A python side whose regex vanished cannot be silently ignored.
    findings = obsgrammar.check_sources({
        "hotstuff_tpu/obs/sampler.py": "X = 1\n",
        "native/src/common/metrics.cpp": _GOOD_METRICS_CPP})
    assert findings and all(f.rule == "metrics-grammar-mismatch"
                            for f in findings)
    # Same for an emit site that disappeared from the C++.
    findings = obsgrammar.check_sources({
        "hotstuff_tpu/obs/sampler.py": _GOOD_METRICS_PY,
        "native/src/common/metrics.cpp": "int x;\n"})
    assert findings and "emit site" in findings[0].message


def test_obsgrammar_trace_pair_fixture():
    py = ('_NODE_TRACE_RE = (r"\\[(\\S+Z) \\w+ [^\\]]+\\] TRACE "\n'
          '                  r"stage=(\\w+) block=(\\S+) round=(\\d+)")\n')
    cpp = ('void trace_stage(const char* stage, const Block& block) {\n'
           '  LOG_INFO("consensus::core")\n'
           '      << "TRACE stage=" << stage << " block=" << d\n'
           '      << " round=" << block.round;\n'
           '}\n')
    assert obsgrammar.check_sources({
        "hotstuff_tpu/obs/trace.py": py,
        "native/src/consensus/core.cpp": cpp}) == []
    findings = obsgrammar.check_sources({
        "hotstuff_tpu/obs/trace.py": py,
        "native/src/consensus/core.cpp":
            cpp.replace('" round="', '" rnd="')})
    assert [f.rule for f in findings] == ["trace-grammar-mismatch"]


def test_obsgrammar_quiet_on_real_tree():
    assert obsgrammar.check(REPO) == []


def test_obsgrammar_pins_cover_both_grammar_sides():
    from hotstuff_tpu.analysis.__main__ import check_coverage

    assert check_coverage(REPO, [
        "obsgrammar:hotstuff_tpu/obs/trace.py",
        "obsgrammar:hotstuff_tpu/obs/sampler.py",
        "obsgrammar:native/src/consensus/core.cpp",
        "obsgrammar:native/src/common/metrics.cpp",
    ]) == []
    out = check_coverage(REPO, ["obsgrammar:hotstuff_tpu/harness/logs.py"])
    assert [f.rule for f in out] == ["must-cover"]


# ---------------------------------------------------------------------------
# graftsync: threads rules (cross-thread sharing discipline)
# ---------------------------------------------------------------------------

from hotstuff_tpu.analysis import threads as threads_checker


def thlint(src: str):
    return threads_checker.check_sources({"mod.py": textwrap.dedent(src)})


def test_unlocked_shared_write_fires_on_cross_thread_attr():
    findings = thlint("""
        import threading

        class Worker:
            def __init__(self):
                self.count = 0
                self._thread = None

            def start(self):
                self._thread = threading.Thread(target=self._run)
                self._thread.start()

            def bump(self):
                self.count += 1

            def _run(self):
                while True:
                    self.count += 1
    """)
    assert [f.rule for f in findings] == ["unlocked-shared-write"] * 2
    assert {f.line for f in findings} == {14, 18}  # bump and _run sites
    assert "self.count" in findings[0].message
    # self._thread is written from ONE side only (start) — not flagged
    assert all("_thread" not in f.message for f in findings)


def test_unlocked_shared_write_quiet_when_one_lock_covers_all_sites():
    assert thlint("""
        import threading

        class Worker:
            def __init__(self):
                self.count = 0
                self._lock = threading.Lock()
                self._stop = threading.Event()

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def bump(self):
                with self._lock:
                    self.count += 1

            def _run(self):
                while not self._stop.is_set():
                    with self._lock:
                        self.count += 1
    """) == []


def test_unlocked_shared_write_fires_when_sites_disagree_on_lock():
    findings = thlint("""
        import threading

        class Worker:
            def __init__(self):
                self.count = 0
                self._a = threading.Lock()
                self._b = threading.Lock()

            def start(self):
                threading.Thread(target=self._run).start()

            def bump(self):
                with self._a:
                    self.count += 1

            def _run(self):
                with self._b:
                    self.count += 1
    """)
    assert [f.rule for f in findings] == ["unlocked-shared-write"] * 2


def test_unlocked_shared_write_init_writes_are_exempt():
    # construction happens-before Thread.start(): __init__-only writes
    # plus thread-side writes are NOT cross-thread
    assert thlint("""
        import threading

        class Sampler:
            def __init__(self):
                self.samples = 0

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                self.samples += 1
    """) == []


def test_unlocked_shared_write_fires_across_two_entries():
    # a pool worker (submit) and a dedicated thread are distinct
    # threads; a shared container written by both needs the lock
    findings = thlint("""
        import threading

        class Engine:
            def __init__(self, pool):
                self._pool = pool
                self.jobs = []

            def start(self):
                threading.Thread(target=self._run).start()
                self._pool.submit(self._pack)

            def _run(self):
                self.jobs.append("run")

            def _pack(self):
                self.jobs.append("pack")
    """)
    assert [f.rule for f in findings] == ["unlocked-shared-write"] * 2
    assert {f.line for f in findings} == {14, 17}


def test_unlocked_shared_write_worked_suppression():
    assert thlint("""
        import threading

        class Worker:
            def __init__(self):
                self.count = 0

            def start(self):
                threading.Thread(target=self._run).start()

            def bump(self):
                # single-threaded test helper, never called live
                # graftlint: disable=unlocked-shared-write
                self.count += 1

            def _run(self):
                # graftlint: disable=unlocked-shared-write
                self.count += 1
    """) == []


def test_daemon_thread_without_stop_flag_fires():
    findings = thlint("""
        import threading

        class Poller:
            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while True:
                    pass
    """)
    assert [f.rule for f in findings] == ["daemon-thread-without-stop-flag"]
    assert findings[0].line == 6


def test_daemon_thread_with_derived_stop_flag_is_quiet():
    # the sampler idiom: the loop consults an attribute DERIVED from the
    # Event in __init__ (self._wait = wait or self._stop.wait)
    assert thlint("""
        import threading

        class Sampler:
            def __init__(self, wait=None):
                self._stop = threading.Event()
                self._wait = wait if wait is not None else self._stop.wait

            def start(self):
                threading.Thread(target=self._run, daemon=True).start()

            def _run(self):
                while True:
                    if self._wait(1.0):
                        return
    """) == []


def test_thread_loop_inline_clock_fires_only_in_clock_injected_classes():
    injected = """
        import threading
        from time import monotonic

        class Runner:
            def __init__(self, clock=monotonic):
                self._clock = clock

            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                return monotonic()
    """
    findings = thlint(injected)
    assert [f.rule for f in findings] == ["thread-loop-inline-clock"]
    # a class with NO injectable clock is out of scope (the engine's
    # monotonic() telemetry reads are the documented legitimate use)
    assert thlint("""
        import threading
        from time import monotonic

        class Engine:
            def start(self):
                threading.Thread(target=self._run).start()

            def _run(self):
                return monotonic()
    """) == []


def test_threads_rules_quiet_on_real_tree():
    # the worked suppression is the reboot thread's threading.local
    # marker (service.VerifyEngine._reboot); _cache_verdicts writes the
    # verdict cache and its counters under _verdicts_lock and needs none
    assert threads_checker.check(REPO) == []


# ---------------------------------------------------------------------------
# graftsync: cxxsync rules (GUARDED_BY discipline + atomic orders)
# ---------------------------------------------------------------------------

from hotstuff_tpu.analysis import cxxsync

GUARD_HPP = textwrap.dedent("""
    #include <mutex>
    struct Box {
      std::mutex m;
      int value = 0;  // GUARDED_BY(m)
    };
""")


def cxlint(cpp: str, hpp: str = GUARD_HPP):
    return cxxsync.check_sources({
        "guard.hpp": hpp,
        "guard.cpp": textwrap.dedent(cpp),
    })


def test_guarded_member_unlocked_fires_outside_lock_scope():
    findings = cxlint("""
        #include "guard.hpp"
        void good(Box* b) {
          std::lock_guard<std::mutex> lk(b->m);
          b->value = 1;
        }
        void bad(Box* b) {
          b->value = 2;
        }
    """)
    assert [(f.rule, f.path, f.line) for f in findings] == [
        ("guarded-member-unlocked", "guard.cpp", 8)]
    assert "GUARDED_BY(m)" in findings[0].message


def test_guarded_member_locked_suffix_function_is_exempt():
    assert cxlint("""
        #include "guard.hpp"
        void tweak_locked(Box* b) {
          b->value = 3;
        }
        static void poke_locked_(Box* b) {
          b->value = 4;
        }
    """) == []


def test_guarded_member_unique_lock_unlock_window_fires():
    findings = cxlint("""
        #include "guard.hpp"
        void window(Box* b) {
          std::unique_lock<std::mutex> lk(b->m);
          b->value = 1;
          lk.unlock();
          b->value = 2;
          lk.lock();
          b->value = 3;
        }
    """)
    assert [(f.rule, f.line) for f in findings] == [
        ("guarded-member-unlocked", 7)]


def test_guarded_member_wrong_mutex_fires():
    hpp = textwrap.dedent("""
        #include <mutex>
        struct Box {
          std::mutex m;
          std::mutex m2;
          int value = 0;   // GUARDED_BY(m)
          int extra = 0;   // GUARDED_BY(m2)
        };
    """)
    findings = cxlint("""
        #include "guard.hpp"
        void bad(Box* b) {
          std::lock_guard<std::mutex> lk(b->m2);
          b->value = 1;
        }
    """, hpp=hpp)
    assert [f.rule for f in findings] == ["guarded-member-unlocked"]
    assert "GUARDED_BY(m)" in findings[0].message


def test_guarded_member_cpp_suppression_comment():
    assert cxlint("""
        #include "guard.hpp"
        void init(Box* b) {
          // pre-thread construction: the thread-start edge orders this
          // graftlint: disable=guarded-member-unlocked
          b->value = 0;
        }
    """) == []


def test_unannotated_mutex_fires_for_members_not_locals():
    findings = cxxsync.check_sources({"bare.hpp": textwrap.dedent("""
        #include <mutex>
        struct Bare {
          std::mutex m_;
          int x = 0;
        };
        inline void local_is_fine() {
          std::mutex scratch_;
          (void)scratch_;
        }
    """)})
    assert [(f.rule, f.line) for f in findings] == [("unannotated-mutex", 4)]


def test_atomic_missing_order_fires_and_explicit_is_quiet():
    findings = cxxsync.check_sources({"at.cpp": textwrap.dedent("""
        #include <atomic>
        std::atomic<int> g{0};
        int bad() { return g.load(); }
        int bad2(std::atomic<int>* p) { return p->fetch_sub(1); }
        void good() { g.store(1, std::memory_order_relaxed); }
        int good2() { return g.load(std::memory_order_acquire); }
    """)})
    assert [(f.rule, f.line) for f in findings] == [
        ("atomic-missing-order", 4), ("atomic-missing-order", 5)]


def test_cxxsync_quiet_on_real_tree():
    # every GUARDED_BY access in the annotated subsystems is either
    # under its lock, inside a *_locked function, or carries a worked
    # suppression; every atomic op states its memory order
    assert cxxsync.check(REPO) == []


def test_graftsync_modules_pinned_to_their_scans():
    from hotstuff_tpu.analysis.__main__ import check_coverage

    assert check_coverage(REPO, [
        "threads:hotstuff_tpu/sidecar/service.py",
        "threads:hotstuff_tpu/obs/sampler.py",
        "threads:hotstuff_tpu/chaos/runner.py",
        "cxxsync:native/src/crypto/sidecar_client.cpp",
        "cxxsync:native/src/network/event_loop.hpp",
    ]) == []
    out = check_coverage(REPO, ["threads:hotstuff_tpu/ops/ed25519.py"])
    assert [f.rule for f in out] == ["must-cover"]


# ---------------------------------------------------------------------------
# graftsync: machine-readable findings (--json / --json-out)
# ---------------------------------------------------------------------------

def test_json_output_clean_tree(tmp_path):
    out = tmp_path / "findings.json"
    proc = subprocess.run(
        [sys.executable, "-m", "hotstuff_tpu.analysis", "--root", REPO,
         "--json", "--json-out", str(out)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json as _json

    doc = _json.loads(proc.stdout)
    assert doc == _json.loads(out.read_text())
    assert doc["schema"] == "graftlint-findings-v1"
    assert doc["clean"] is True and doc["findings"] == []
    assert "threads" in doc["checkers"] and "cxxsync" in doc["checkers"]


def test_json_output_carries_findings(tmp_path):
    # an empty tree is missing every anchor: the JSON document must
    # carry the findings with the documented keys, and the exit status
    # must still be the findings truth
    proc = subprocess.run(
        [sys.executable, "-m", "hotstuff_tpu.analysis",
         "--root", str(tmp_path), "--json"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    import json as _json

    doc = _json.loads(proc.stdout)
    assert doc["clean"] is False and doc["findings"]
    assert set(doc["findings"][0]) == {"rule", "file", "line", "evidence"}


# ---------------------------------------------------------------------------
# graftsync: shared parse/read caches
# ---------------------------------------------------------------------------

def test_parse_cache_returns_one_tree_per_path_source_pair():
    from hotstuff_tpu.analysis import common

    common.clear_caches()
    src_a = "x = 1\n"
    t1 = common.parse_source(src_a, "a.py")
    assert common.parse_source(src_a, "a.py") is t1
    # a DIFFERENT source under the same path (test fixtures do this
    # constantly) must not collide
    t2 = common.parse_source("x = 2\n", "a.py")
    assert t2 is not t1
    # nor the same source under a different path
    assert common.parse_source(src_a, "b.py") is not t1
    common.clear_caches()
    assert common.parse_source(src_a, "a.py") is not t1


# ---------------------------------------------------------------------------
# tier-2: the TSan gate (curated subset + clockwait shim)
# ---------------------------------------------------------------------------

@pytest.mark.slow  # instrumented native build: minutes when cold
def test_tsan_gate_runs_curated_test_clean():
    if shutil.which("g++") is None and shutil.which("cmake") is None:
        pytest.skip("no C++ toolchain in this environment")
    script = os.path.join(REPO, "scripts", "tsan_gate.sh")
    proc = subprocess.run(
        [script, "serde", "store"], cwd=REPO,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    # the gate's own success line (the "all tests clean" line below it
    # is printed only by the no-cmake g++ fallback, not the ctest path)
    assert "tsan_gate: clean in" in proc.stdout


# ---------------------------------------------------------------------------
# guard rules (graftguard: unsupervised-launch)
# ---------------------------------------------------------------------------

def _guard_findings(src, path="hotstuff_tpu/sidecar/service.py"):
    from hotstuff_tpu.analysis import guardlint

    return guardlint.check_sources({path: textwrap.dedent(src)})


def test_unsupervised_launch_flags_bare_future_wait():
    findings = _guard_findings("""
        def _dispatch_one(self, packing, inflight):
            batch, fut = packing.popleft()
            fetch = fut.result()()
    """)
    assert [f.rule for f in findings] == ["unsupervised-launch"]
    assert ".result()" in findings[0].message


def test_unsupervised_launch_flags_unbounded_event_wait():
    findings = _guard_findings("""
        def _drain(self, ev):
            ev.wait()
    """)
    assert [f.rule for f in findings] == ["unsupervised-launch"]


def test_unsupervised_launch_clean_through_guard_helper():
    assert _guard_findings("""
        def _dispatch_one(self, packing, inflight):
            batch, fut = packing.popleft()
            fetch = self._guarded("k", lambda: fut.result()())

        def _drain_one(self, inflight):
            batch, fetch, t0, key = inflight.popleft()
            mask = self._guarded(key, fetch)
    """) == []


def test_unsupervised_launch_clean_through_guard_call():
    assert _guard_findings("""
        def _canary(self):
            return self._guard.call("canary:8", lambda: fut.result())
    """) == []


def test_unsupervised_launch_bounded_waits_are_legal():
    assert _guard_findings("""
        def _run(self, packing, ev):
            packing[0][1].exception(timeout=0.25)
            ev.wait(0.2)
            fut.result(timeout=1.0)
    """) == []


def test_unsupervised_launch_suppression_needs_justification():
    src = """
        def call(self, call):
            # bounded by construction: the monitor sets the event
            # graftlint: disable=unsupervised-launch
            call.done.wait()
    """
    assert _guard_findings(src) == []
    # the same wait WITHOUT the suppression is a finding
    bare = src.replace("# graftlint: disable=unsupervised-launch\n", "")
    assert [f.rule for f in _guard_findings(bare)] == \
        ["unsupervised-launch"]


def test_unsupervised_launch_dot_call_on_non_guard_not_exempt():
    # .call on something that is not a guard supervises nothing
    findings = _guard_findings("""
        def f(self, runner, fut):
            runner.call("k", lambda: 1)
            return fut.result()
    """)
    assert [f.rule for f in findings] == ["unsupervised-launch"]


def test_guard_checker_real_tree_is_clean():
    from hotstuff_tpu.analysis import guardlint

    assert guardlint.check(REPO) == []


# ---------------------------------------------------------------------------
# ring rules (graftcadence: blocking-call-in-ring-tick)
# ---------------------------------------------------------------------------

def _ring_findings(src, path="hotstuff_tpu/sidecar/ring.py"):
    from hotstuff_tpu.analysis import ringlint

    return ringlint.check_sources({path: textwrap.dedent(src)})


def test_ring_rule_flags_unbounded_wait_in_tick_body():
    findings = _ring_findings("""
        class CadenceRing:
            def _collect_oldest(self):
                fl = self._pending.popleft()
                return fl.fetch.result()
    """)
    assert [f.rule for f in findings] == ["blocking-call-in-ring-tick"]
    assert ".result()" in findings[0].message


def test_ring_rule_flags_fresh_compile_entry_in_tick_body():
    findings = _ring_findings("""
        class CadenceRing:
            def _arm(self, launch):
                from ..crypto import eddsa
                return eddsa.verify_batch(msgs, pks, sigs)
    """)
    assert [f.rule for f in findings] == ["blocking-call-in-ring-tick"]
    assert "verify_batch" in findings[0].message
    assert "compile" in findings[0].message


def test_ring_rule_guard_entry_subtrees_are_supervised():
    assert _ring_findings("""
        class CadenceRing:
            def _arm(self, launch):
                fut = self.engine._pack_pool.submit(self.engine._pack,
                                                    launch.items)
                return self.engine._guarded("tick:8",
                                            lambda: fut.result()())

            def _collect_oldest(self):
                fl = self._pending.popleft()
                return self.engine._guarded(fl.key, fl.fetch)
    """) == []


def test_ring_rule_bounded_waits_are_legal():
    assert _ring_findings("""
        class CadenceRing:
            def run(self):
                self._wait(0.002)
                self.engine._stopped.wait(timeout=0.25)
    """) == []


def test_ring_rule_ignores_non_ring_classes():
    # The staged engine may block (its deadline class tolerates it);
    # the rule scopes to ring classes only.
    assert _ring_findings("""
        class VerifyEngine:
            def _dispatch_one(self, fut):
                return fut.result()

        def module_level(fut):
            return fut.result()
    """) == []


def test_ring_checker_registered_and_real_tree_is_clean():
    from hotstuff_tpu.analysis import ringlint
    from hotstuff_tpu.analysis.__main__ import CHECKERS

    assert "ring" in CHECKERS
    assert ringlint.check(REPO) == []


# ---------------------------------------------------------------------------
# grafttaint: verification-gate provenance (wire -> gate -> consensus sink)
# ---------------------------------------------------------------------------

from hotstuff_tpu.analysis import taint
from hotstuff_tpu.analysis.__main__ import findings_json

TAINT_FIXTURES = os.path.join(REPO, "tests", "fixtures", "taint")


def _taint_fixture(name):
    with open(os.path.join(TAINT_FIXTURES, name), encoding="utf-8") as fh:
        src = fh.read()
    if name.endswith(".py"):
        return taint.check_sources({name: src})
    return taint.check_sources({}, {name: src})


def test_taint_wire_to_verdict_sink_without_gate():
    findings = _taint_fixture("bad_sink.py")
    assert [f.rule for f in findings] == ["unverified-flow-to-sink"]
    assert "verdict-emission" in findings[0].message
    assert "bad_sink.py:14" in findings[0].message  # the read_frame origin


def test_taint_dead_gate_is_unreachable_sanitizer():
    findings = _taint_fixture("dead_gate.py")
    assert [(f.rule, f.line) for f in findings] == \
        [("unreachable-sanitizer", 9)]
    assert "check_frame" in findings[0].message


def test_taint_verify_shaped_call_needs_annotation():
    findings = _taint_fixture("unannotated.py")
    assert [f.rule for f in findings] == ["unannotated-gate"]
    assert "verify_payload" in findings[0].message


def test_taint_cxx_deserialize_to_commit_without_gate():
    findings = _taint_fixture("bad_core.cpp")
    assert [f.rule for f in findings] == ["unverified-flow-to-sink"]
    assert "commit" in findings[0].message


def test_taint_mutation_dropped_verify_fires_both_rules():
    # Deleting the one verify call produces BOTH signals: the QC flows
    # to process_qc ungated, and the declared gate is never called.
    findings = _taint_fixture("mutation_dropped_verify.cpp")
    assert sorted(f.rule for f in findings) == \
        ["unreachable-sanitizer", "unverified-flow-to-sink"]


def test_taint_mutation_reordered_admission_before_gate():
    findings = _taint_fixture("mutation_reordered.py")
    assert [f.rule for f in findings] == ["unverified-flow-to-sink"]
    assert "device-launch-pack" in findings[0].message


def test_taint_gate_call_clears_the_same_flow():
    # The un-mutated shape of mutation_reordered.py: gate first, then
    # pack — the identical sink call is now a PROVEN path, not a finding.
    with open(os.path.join(TAINT_FIXTURES, "mutation_reordered.py"),
              encoding="utf-8") as fh:
        src = fh.read()
    fixed = src.replace(
        "    engine.submit(payload, None)\n"
        "    opcode, req = decode_request(payload)\n",
        "    opcode, req = decode_request(payload)\n"
        "    engine.submit(payload, None)\n")
    assert fixed != src
    findings, mapdoc = taint.analyze_sources(
        {"mutation_reordered.py": fixed}, {})
    assert findings == []
    assert mapdoc["sinks_covered"] == {"device-launch-pack": 1}
    (path,) = mapdoc["paths"]
    assert path["gates"] == ["frame-structure"]


def test_taint_suppression_silences_with_rationale():
    with open(os.path.join(TAINT_FIXTURES, "bad_sink.py"),
              encoding="utf-8") as fh:
        src = fh.read()
    suppressed = src.replace(
        "    return proto.encode_reply(",
        "    # graftlint: disable=unverified-flow-to-sink (fixture)\n"
        "    return proto.encode_reply(")
    assert suppressed != src
    assert taint.check_sources({"bad_sink.py": suppressed}) == []


def test_taint_cxx_suppression_contract_matches_python():
    with open(os.path.join(TAINT_FIXTURES, "bad_core.cpp"),
              encoding="utf-8") as fh:
        src = fh.read()
    suppressed = src.replace(
        "  return commit(m.block);",
        "  // graftlint: disable=unverified-flow-to-sink (fixture)\n"
        "  return commit(m.block);")
    assert suppressed != src
    assert taint.check_sources({}, {"bad_core.cpp": suppressed}) == []


def test_taint_findings_json_golden():
    findings = _taint_fixture("mutation_dropped_verify.cpp")
    doc = findings_json(findings, ("taint",))
    assert doc["schema"] == "graftlint-findings-v1"
    assert doc["checkers"] == ["taint"]
    assert doc["clean"] is False
    assert [(f["rule"], f["file"], f["line"]) for f in doc["findings"]] == [
        ("unreachable-sanitizer", "mutation_dropped_verify.cpp", 8),
        ("unverified-flow-to-sink", "mutation_dropped_verify.cpp", 15),
    ]
    assert all(f["evidence"] for f in doc["findings"])


def test_taint_literal_reply_masks_are_exempt():
    # PING/CHAOS echoes reply with literal masks — not verdicts.
    assert taint.check_sources({"svc.py": textwrap.dedent("""\
        def handle(sock):
            payload = read_frame(sock)
            send(encode_reply(1, 2, []))
            send(encode_reply(1, 2, [0]))
    """)}) == []


def test_taint_cxx_digit_separator_does_not_eat_the_file():
    # 20'000 is a number, not a char literal: the functions after it
    # must still be scanned (regression: ingress.hpp lost its admit gate
    # to exactly this).
    findings = taint.check_sources({}, {"g.cpp": (
        "const size_t kBudget = 20'000;\n"
        "void Core::receive(const Bytes& raw) {\n"
        "  auto m = Message::deserialize(raw);\n"
        "  commit(m.block);\n"
        "}\n")})
    assert [f.rule for f in findings] == ["unverified-flow-to-sink"]


def test_taint_entry_meet_one_ungated_caller_poisons():
    # Two callers reach the same helper; only one gates.  The meet is
    # AND over verified-ness, so the helper's sink stays a finding.
    src = textwrap.dedent("""\
        # graftlint: sanitizes=device-verdict
        def check(req):
            return True

        def emit(req):
            return encode_reply(1, 2, req.verdicts)

        def gated(sock):
            req = read_frame(sock)
            check(req)
            return emit(req)

        def ungated(sock):
            req = read_frame(sock)
            return emit(req)
    """)
    findings = taint.check_sources({"svc.py": src})
    assert [f.rule for f in findings] == ["unverified-flow-to-sink"]
    # removing the ungated caller clears it
    clean = src[:src.index("def ungated")]
    assert taint.check_sources({"svc.py": clean}) == []


def test_taint_real_tree_is_clean():
    assert taint.check(REPO) == []


def test_taint_map_proves_the_required_sink_paths():
    py_sources, cxx_sources = {}, {}
    for rel in taint.DEFAULT_TARGETS:
        with open(os.path.join(REPO, rel), encoding="utf-8") as fh:
            src = fh.read()
        (py_sources if rel.endswith(".py") else cxx_sources)[rel] = src
    findings, mapdoc = taint.analyze_sources(py_sources, cxx_sources)
    assert findings == []
    assert mapdoc["schema"] == "grafttaint-map-v1"
    assert mapdoc["clean"] is True
    # the PR's acceptance bar: at least one PROVEN wire->gate->sink path
    # through each consensus-critical sink
    for sink in ("qc-accept", "tc-assembly", "mempool-admission",
                 "verdict-emission", "commit", "store-write",
                 "device-launch-pack"):
        assert mapdoc["sinks_covered"].get(sink, 0) >= 1, sink
    # every path names its gates and its wire origin
    for p in mapdoc["paths"]:
        assert p["gates"], p
        assert ":" in p["source"], p
        assert p["via"], p


def test_taint_must_cover_pins():
    from hotstuff_tpu.analysis.__main__ import check_coverage

    assert check_coverage(REPO, [
        "taint:native/src/consensus/core.cpp",
        "taint:hotstuff_tpu/sidecar/protocol.py",
    ]) == []
    bad = check_coverage(REPO, ["taint:hotstuff_tpu/obs.py"])
    assert [f.rule for f in bad] == ["must-cover"]


# ---------------------------------------------------------------------------
# tenant-unscoped-queue (graftfleet DRR lane discipline)
# ---------------------------------------------------------------------------

SCHED_MOD = "hotstuff_tpu/sidecar/sched/classes.py"


def test_tenant_queue_fires_on_raw_deque_ops_and_head_peek():
    from hotstuff_tpu.analysis import tenantlint

    findings = tenantlint.check_sources({SCHED_MOD: textwrap.dedent("""
        class ClassQueue:
            def pop(self):
                return self.items.popleft()

            def requeue(self, p):
                self._order.appendleft(p)

            def peek_second(self):
                return self.items[1]
        """)})
    assert [f.rule for f in findings] == ["tenant-unscoped-queue"] * 3
    assert "DRR tenant lanes" in findings[0].message
    assert "peeks past the DRR head" in findings[2].message


def test_tenant_queue_quiet_on_lane_routed_scheduler():
    from hotstuff_tpu.analysis import tenantlint

    # The real discipline: class-queue SELECTION is a dict subscript
    # (fine), ordering decisions route through the tenantq helpers,
    # and value-object containers (launch.items) are data plumbing.
    findings = tenantlint.check_sources({SCHED_MOD: textwrap.dedent("""
        class Scheduler:
            def next_launch(self):
                q = self._queues[LATENCY]
                head = q.lanes.head_locked()
                if head is None:
                    return None
                return q.lanes.pop_next_locked()

            def pad_accounting(self, launch):
                return len(launch.items[0].request.msgs)
        """)})
    assert findings == []


def test_tenant_queue_exempts_tenantq_and_honors_suppression():
    from hotstuff_tpu.analysis import tenantlint

    raw = textwrap.dedent("""
        class TenantLanes:
            def pop_next_locked(self):
                return self.order.popleft()
        """)
    # tenantq.py IS the audited lane implementation: exempt wholesale.
    assert tenantlint.check_sources(
        {"hotstuff_tpu/sidecar/sched/tenantq.py": raw}) == []
    # Elsewhere the same code fires...
    assert len(tenantlint.check_sources({SCHED_MOD: raw})) == 1
    # ...unless carrying a worked inline suppression.
    suppressed = textwrap.dedent("""
        class Drain:
            def flush(self):
                # graftlint: disable=tenant-unscoped-queue (shutdown drain-all: fairness moot)
                return self.order.popleft()
        """)
    assert tenantlint.check_sources({SCHED_MOD: suppressed}) == []


def test_tenant_queue_quiet_on_real_tree():
    from hotstuff_tpu.analysis import tenantlint

    assert tenantlint.check(REPO) == []
