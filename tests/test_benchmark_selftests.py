"""The benchmark's own tests (``benchmark/tests/``), run from tier-1.

``benchmark/run.py`` is the yardstick every PR is judged by, and its
tests ran "by hand" only: a failure there could sit unnoticed (PERF.md
§7).  Each file runs as its own pytest process on the CPU, so its
``conftest.py`` and ``sys.path`` stay its own and this suite imports
nothing from ``benchmark/`` (the yardstick does not import the program
either).  No file under ``benchmark/`` is edited from here.
"""

import os
import subprocess
import sys

import pytest

from conftest import REPO

# PERF.md §7: this test pins fifteen ``layers/*.flood.json``; PR 32 added
# the sixteenth (``cache_insert_ms.flood.json``) and only a ``benchmark``
# PR may edit the line (it wants ``>= 15``).  Deselected, not skipped
# over: every other test of the file runs.
_DESELECT = {
    "test_span_tree.py": [
        "benchmark/tests/test_span_tree.py::"
        "test_every_flood_layer_file_has_its_entry"],
}

# Whole commands at rehearsal size: minutes each on the CPU.
_SLOW = ("test_byz.py", "test_correct.py", "test_gate_rehearsal.py")


@pytest.mark.parametrize("name", [
    "test_arith.py", "test_bls_votes.py", "test_gate.py", "test_manifest.py",
    "test_readers.py",
    "test_run.py", "test_span_tree.py", "test_streams.py",
    "test_trace_reduce.py",
    *(pytest.param(name, marks=pytest.mark.slow) for name in _SLOW)])
def test_benchmark_test_file_passes(name):
    cmd = [sys.executable, "-m", "pytest", f"benchmark/tests/{name}", "-q",
           "-p", "no:cacheprovider"]
    for test_id in _DESELECT.get(name, ()):
        cmd += ["--deselect", test_id]
    done = subprocess.run(cmd, cwd=REPO,
                          timeout=900 if name in _SLOW else 120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True)
    assert done.returncode == 0, done.stdout[-4000:]
