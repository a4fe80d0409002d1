#!/usr/bin/env bash
# Tier-2 graftkern gate: run the interpret-mode Pallas kernel suite —
# including the slow lane (engine-path RLC bisection under
# HOTSTUFF_TPU_KERN=pallas, and the n=1024 window-accumulator agreement
# sweep) — inside a bounded window.
#
#   scripts/kern_gate.sh [pytest-args ...]
#
# What fits the window and why (measured on this container, cold):
#
#   1. The per-kernel property sweeps are cheap (~30 s total): each
#      kernel is ONE pallas trace per shape thanks to the jit-in-jit
#      wrapping (see ops/kern/__init__.py), so the interpreter cost is
#      a handful of compiles, not one per call site.
#   2. The slow lane is compile-bound, not run-bound: the full RLC
#      program with every field mul routed through the interpreter
#      compiles in ~70 s at n=8 plus ~55 s for its bisection floor, and
#      the B=1024 window-accumulator agreement costs ~90 s — ~4 min
#      total, far inside the default 900 s budget.
#   3. The rlc_tail kernel inside whole rlc programs (tests/test_rlc.py:
#      one device, and a two-device mesh) is two more interpreter
#      compiles, ~70 s and ~60 s.
#
# KERN_GATE_BUDGET_S overrides the window; the gate FAILS (rc 124) if
# the budget is exceeded, so a kernel-compile-time regression is a loud
# CI signal, never a silently-lengthening job (same contract as
# scripts/tsan_gate.sh).
set -euo pipefail

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
BUDGET="${KERN_GATE_BUDGET_S:-900}"

# pytest only puts the CALLER's cwd on sys.path: run from the repo root
# so tests/conftest.py can import hotstuff_tpu from any invocation dir.
cd "$ROOT"

start=$(date +%s)
rc=0
timeout -k 10 "$BUDGET" env JAX_PLATFORMS=cpu HOTSTUFF_TPU_SLOW_TESTS=1 \
    python -m pytest "$ROOT/tests/test_kern.py" \
    "$ROOT/tests/test_rlc.py::test_rlc_tail_kernel_keeps_the_torsion_verdicts" \
    -q -p no:cacheprovider "$@" || rc=$?
if [ "$rc" -ne 0 ]; then
  if [ "$rc" -eq 124 ]; then
    echo "kern_gate: exceeded the ${BUDGET}s budget" >&2
  else
    echo "kern_gate: FAILED (rc=$rc)" >&2
  fi
  exit "$rc"
fi
end=$(date +%s)
echo "kern_gate: clean in $((end - start))s (budget ${BUDGET}s)"
