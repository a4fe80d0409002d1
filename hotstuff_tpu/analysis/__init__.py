"""graftlint: repo-native static analysis for the TPU hot path, the
Python<->C++ wire protocol, launch shapes, socket bounds, trace spans,
cross-thread sharing discipline, and the native tree's sanitizer wiring.

Nine checkers, each runnable standalone and together via
``python -m hotstuff_tpu.analysis`` (exit non-zero on findings;
``--json``/``--json-out`` for machine-readable output):

* :mod:`.hotpath` — AST pass over the JAX device modules flagging
  host-device sync points, retrace hazards, dtype leaks, and non-donated
  verify-loop buffers inside jitted code.
* :mod:`.wirecheck` — cross-checks the sidecar wire constants
  (``sidecar/protocol.py``) and the shared field-modulus literals against
  the C++ node sources, so a one-sided edit fails the gate instead of
  corrupting a QC on the wire.
* :mod:`.padshape` — launch sizes must route through the bucket/shard
  helpers so no un-warmed XLA shape compiles mid-traffic.
* :mod:`.timing` — no ``block_until_ready`` inside timed regions of the
  profiling scripts (the fence is a forced device->host copy).
* :mod:`.sockets` — every socket/ssh operation on the process boundary
  carries an explicit bound.
* :mod:`.obsspan` — grafttrace span pairing + injected-clock discipline
  in the obs modules.
* :mod:`.threads` — graftsync Python side: cross-thread writes need one
  shared lock, daemon threads need stop flags, clock-injected thread
  loops must not read time inline.
* :mod:`.cxxsync` — graftsync C++ side: ``GUARDED_BY`` lock-discipline
  annotations enforced by a brace-scope lexer, plus explicit
  ``std::memory_order`` on every native atomic op.
* :mod:`.sanitize` — asserts the ASan/UBSan/TSan build wiring
  (``native/CMakeLists.txt`` presets + ``scripts/native_sanitize.sh`` +
  ``scripts/tsan_gate.sh``) has not rotted; the actual sanitizer runs
  are the tier-2 slow lane.

Suppression: a finding is silenced by ``# graftlint: disable=<rule>``
(Python) or ``// graftlint: disable=<rule>`` (C++ cxxsync rules) on the
finding's line or the line above; every suppression should carry a
rationale. See ``analysis/README.md`` for the rule catalogue.
"""

from __future__ import annotations

from .common import Finding  # noqa: F401


def run_all(root, checkers=None):
    """Run the selected checkers over a repo root; returns findings.

    Kept here (delegating to ``__main__``) so callers can use
    ``hotstuff_tpu.analysis.run_all`` without triggering the runpy
    double-import warning that a module-level ``from .__main__ import``
    would cause under ``python -m hotstuff_tpu.analysis``."""
    from .__main__ import CHECKERS, run_all as _run

    return _run(root, CHECKERS if checkers is None else checkers)
