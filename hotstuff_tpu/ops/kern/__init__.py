"""graftkern: hand-laid Pallas kernels for the ed25519 verify hot path.

The lax-op modules (ops/field25519, ops/ed25519, ops/scalar25519) leave
XLA to schedule the limb arithmetic however it likes; this layer fuses
the three dominant batched primitives into Pallas kernels tuned to the
VPU's (8, 128) tile shape, behind the EXISTING public op signatures —
the scheduler / engine / sharding stack above is untouched, and the
sharded entries in parallel/sharded_verify.py route per-shard window
sums through the same kernels because they call the same ops — and
runs the one-point tail of the RLC check as a fourth:

  field_mul        the 32-limb byte convolution + wrap-38 parallel carry
                   of field25519.mul as ONE kernel, carry-save limbs in
                   a 128-lane vector, rows batched over sublanes
                   (ops/kern/field_mul.py).
  msm_window_accum the Straus inner loop — per-window 16-entry table
                   gather (one-hot masked sum) + the masked point-add
                   tree that dominates ed25519.msm_window_sums — fused
                   so window sums never round-trip through HBM between
                   limb ops (ops/kern/msm_accum.py).
  scalar_mont_mul  the mod-L Montgomery multiply (REDC at R = 2^256)
                   of scalar25519.mont_mul (ops/kern/scalar_mont.py).
  rlc_tail         the serial tail of ed25519.rlc_finish — Horner fold
                   of the 64 window sums and the 32 comb additions of
                   [c]B, 352 point operations on ONE point — with the
                   accumulators resident (ops/kern/rlc_tail.py).  NOT
                   behind the switch below: every rlc program on a TPU
                   runs it (ed25519.rlc_tail), because as lax scans
                   that tail is 38 ms of issue latency and as this
                   kernel 0.58 ms (one v5e, PERF.md PR 28); off the
                   chip the scans are the tail.

Selection of the first three: ``HOTSTUFF_TPU_KERN=lax|pallas`` (read
ONCE, at first use; ``set_mode`` re-pins it in-process and clears the
jit caches so routed programs re-trace).  The lax implementations stay
in-tree as the bit-identical reference and fallback — every kernel is
property-tested bit-identical against them (tests/test_kern.py), and
the default stays ``lax`` until a real-device measurement re-pins it
(bench.py's ``roofline`` headline is that measurement).

CPU story: each kernel selects ``interpret=`` off the backend at trace
time (ops/kern/backend.interpret_default) — on anything but a TPU the
kernels run through the Pallas interpreter, so tier-1 stays
CPU-runnable and the property sweeps exercise the exact kernel bodies a
TPU compiles.  That all four DO compile under Mosaic is pinned by
tests/test_tpu_compile.py (a described v5e, no chip attached); on a
chip the route never falls back to lax — a kernel Mosaic refuses fails
the launch.  rlc_tail is the first to have run on a chip (PR 28:
limb for limb the lax tail there; fieldops' f_mul, f_add and f_sub
were checked exact on the same chip beside it); field_mul,
msm_window_accum and scalar_mont_mul have not.  Every pallas_call is
wrapped in its own ``jax.jit`` so the per-call-site trace cost is paid
once per shape, not once per call site (~0.4 s/site -> ~4 ms/site
measured; the verify program has hundreds of mul sites).
"""

from __future__ import annotations

import os

_VALID_MODES = ("lax", "pallas")
_mode: str | None = None


def mode() -> str:
    """The kernel route, read ONCE from HOTSTUFF_TPU_KERN at first use
    (lazy, like the backend probe: importing this package must stay
    side-effect-free)."""
    global _mode
    if _mode is None:
        raw = os.environ.get("HOTSTUFF_TPU_KERN", "lax").strip().lower()
        m = raw or "lax"
        if m not in _VALID_MODES:
            raise ValueError(
                f"HOTSTUFF_TPU_KERN must be one of {_VALID_MODES}, "
                f"got {raw!r}")
        _mode = m
    return _mode


def use_pallas() -> bool:
    """True when the routed ops (field25519.mul, ed25519.msm_window_sums,
    scalar25519.mont_mul) should dispatch the Pallas kernels.  Read at
    TRACE time by the routers, so a cached jit keeps the route it was
    traced with — which is why set_mode clears the caches."""
    return mode() == "pallas"


def set_mode(m: str) -> None:
    """Re-pin the kernel route in-process (bench.py's roofline headline
    measures both routes from one process).  Clears the global jit
    caches: every routed program read use_pallas() at trace time, so a
    stale trace would keep dispatching the old route."""
    global _mode
    if m not in _VALID_MODES:
        raise ValueError(f"kern mode must be one of {_VALID_MODES}, "
                         f"got {m!r}")
    if m != mode():
        import jax

        _mode = m
        jax.clear_caches()


from .backend import interpret_default, interpret_probe  # noqa: E402
from .field_mul import field_mul  # noqa: E402
from .msm_accum import msm_window_accum  # noqa: E402
from .rlc_tail import rlc_tail  # noqa: E402
from .scalar_mont import scalar_mont_mul  # noqa: E402

__all__ = [
    "mode", "set_mode", "use_pallas",
    "interpret_default", "interpret_probe",
    "field_mul", "msm_window_accum", "scalar_mont_mul", "rlc_tail",
]
