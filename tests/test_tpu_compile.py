"""The chip's compiler, asked here: the verify programs the sidecar
launches and the Pallas kernels, lowered and compiled for a DESCRIBED
v5e chip (no chip attached — the `on-chip-measurement` guide, section 2
step 3).  What the TPU compiler refuses, it refuses here, at no chip
time; nothing runs, so nothing below says a word about results or speed.

One file, one process: only one process may hold the TPU library, so
the topology is described inside a module-scoped fixture (never at
import — every xdist worker imports every test file) and every compile
happens in this test's own process.  The persistent compile cache is
off around these compiles: an executable for a described chip can be
written to it but not read back here.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from hotstuff_tpu.ops import ed25519 as E
from hotstuff_tpu.ops import kern  # noqa: F401 — loads the kernel modules


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: not here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shaped(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# The lax programs of the sidecar's main path, at the widths it serves:
# B=128 is the bucket of an N=100 committee's 67-vote QC, B=1024 the
# BASELINE.json batch target and the launch cap, (2, 1024) the smallest
# chunked-scan shape _warmup_bulk compiles.  Compiled as production
# launches them off the CPU: arg 0 donated (ops/ed25519._jit_donated).
_VERIFY_PROGRAMS = {
    "verify_packed:128": (E.verify_packed, [(128, 128)]),
    "verify_packed:1024": (E.verify_packed, [(1024, 128)]),
    "verify_rlc_packed:128": (E.verify_rlc_packed, [(128, 128), (128, 32)]),
    "verify_rlc_packed:1024": (E.verify_rlc_packed,
                               [(1024, 128), (1024, 32)]),
    "verify_packed_chunked:2x1024": (E.verify_packed_chunked,
                                     [(2, 1024, 128)]),
}


@pytest.fixture
def rlc_tail_on_chip(monkeypatch):
    """The rlc programs run their serial tail as the rlc_tail kernel on
    a TPU and as the lax scans elsewhere (ops/ed25519.rlc_tail reads
    kern.interpret_default at trace time, the kernel's own module reads
    its copy).  The process here is a CPU one, so both are steered HERE,
    as for the kernels below: the programs then compile as the chip
    runs them, Mosaic kernel inside."""
    mod = sys.modules["hotstuff_tpu.ops.kern.rlc_tail"]
    monkeypatch.setattr(kern, "interpret_default", lambda: False)
    monkeypatch.setattr(mod, "interpret_default", lambda: False)
    # The launcher under a jit of its own, dropped with the patch: its
    # trace holds the route, and this process's other tests (one xdist
    # worker runs several files) must keep finding the interpreter's.
    inner = mod._tail.__wrapped__
    monkeypatch.setattr(mod, "_tail", jax.jit(lambda w, c: inner(w, c)))


@pytest.mark.parametrize("name", sorted(_VERIFY_PROGRAMS))
def test_verify_program_compiles_for_v5e(one_chip, no_persistent_cache,
                                         rlc_tail_on_chip, name):
    fn, shapes = _VERIFY_PROGRAMS[name]
    args = [_shaped(one_chip, s, jnp.uint8) for s in shapes]
    # A fresh function object: jit caches traces by function, and an
    # earlier test may have traced this one with the lax tail.
    compiled = jax.jit(lambda *a: fn(*a), donate_argnums=0) \
        .lower(*args).compile()
    # The rlc programs, and only they, hold the kernel.
    assert ("tpu_custom_call" in compiled.as_text()) == ("rlc" in name)
    mem = compiled.memory_analysis()
    # One program's footprint against one v5e chip's 16 GB of HBM.
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < 16 * 10**9


# The Pallas kernels, compiled by Mosaic at the row counts the routed
# programs hand them.  Each jitted launcher reads interpret_default()
# from its own module at trace time; off the chip that says "interpret",
# so the decision is steered HERE (no option of the program): a kernel
# that silently took the interpreter would compile to no custom call.
_KERNELS = {
    # 4 coordinates x 1024 signatures: the widest field_mul launch.
    "field_mul:4096": ("field_mul", "_mul_rows",
                       [(4096, 128), (4096, 128)]),
    # z*k and z*S of a 1024-signature RLC batch.
    "scalar_mont_mul:1024": ("scalar_mont", "_mont_rows",
                             [(1024, 128), (1024, 128)]),
    # The 2048-point MSM (A and R points) of a 1024-signature RLC
    # batch: the widest, and the one whose 16 MB table must fit VMEM.
    "msm_window_accum:2048": ("msm_accum", "_accum",
                              [(2048, 16, 4, 32), (2048, 64)]),
    # The one shape every rlc program runs: 64 cached window sums and
    # the 32 selected comb entries.
    "rlc_tail:64": ("rlc_tail", "_tail", [(64, 4, 32), (32, 4, 32)]),
}


@pytest.mark.parametrize("name", sorted(_KERNELS))
def test_pallas_kernel_compiles_under_mosaic(one_chip, no_persistent_cache,
                                             monkeypatch, name):
    module, launcher, shapes = _KERNELS[name]
    # hotstuff_tpu.ops.kern re-exports functions under the module names.
    mod = sys.modules[f"hotstuff_tpu.ops.kern.{module}"]
    monkeypatch.setattr(mod, "interpret_default", lambda: False)
    args = [_shaped(one_chip, s, jnp.int32) for s in shapes]
    # A fresh function object: the launcher's own jit may hold a trace
    # of these shapes taken in interpret mode by an earlier test.
    inner = getattr(mod, launcher).__wrapped__
    compiled = jax.jit(lambda *a: inner(*a)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_rlc_program_splits_its_arguments_four_ways(
        topo, no_persistent_cache, rlc_tail_on_chip):
    """The >1k-validator path: verify_rlc_sharded's program for a Mesh
    over the four described chips, at the per-shard bucket (256) of an
    N=1000 committee's 667-vote QC.  Code that has only seen forced-host
    devices could leave every row on device 0: each chip must hold a
    quarter of the argument bytes, and the window sums must cross chips
    through collectives."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from hotstuff_tpu.parallel import sharded_verify as shv
    from hotstuff_tpu.parallel.mesh import BATCH_AXIS

    mesh = Mesh(np.asarray(topo.devices), (BATCH_AXIS,))
    rows = len(topo.devices) * shv.shard_bucket(667, len(topo.devices))
    batched = NamedSharding(mesh, PartitionSpec(BATCH_AXIS))
    args = [jax.ShapeDtypeStruct((rows, width), jnp.uint8, sharding=batched)
            for width in (128, 32)]
    compiled = shv.make_sharded_rlc_verifier(mesh, donate=True) \
        .lower(*args).compile()
    whole = rows * (128 + 32)
    assert compiled.memory_analysis().argument_size_in_bytes == whole // 4
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
    # ... and every chip finishes with the rlc_tail kernel.
    assert "tpu_custom_call" in text
