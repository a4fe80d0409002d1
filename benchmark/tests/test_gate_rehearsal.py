"""The cell ``ingress20.gate`` as a whole command on the CPU at
rehearsal size (``rehearsal/BENCHMARK.gate.json``: a ``run.py`` process,
~3 minutes, most of it the sidecar's own warm-up): six gates of 8-record
requests coalesce into launches of several tenants, every ``.gate``
reader that needs no profiler finds what it reads, and the run ends
refused for the platform alone."""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
REHEARSAL = os.path.join(BENCH, "rehearsal", "BENCHMARK.gate.json")
CELL = "ingress6.gate8"


def test_the_whole_command_runs_and_coalesces_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         REHEARSAL, "--workload", CELL, "--seed", str(2**31 + 13),
         "--seconds", "3", "--trace", "1"],
        capture_output=True, text=True, timeout=1500, env=env, cwd=REPO)
    out = got.stdout.strip().splitlines()
    assert got.returncode == 1, got.stdout[-2000:] + got.stderr[-2000:]
    assert out[-1].startswith("bench: REFUSED, no result: platform is 'cpu'")
    assert out[-1].endswith("problems besides the platform: none")
    notes = json.loads(out[-2][len("bench: notes: "):])
    assert set(notes["by_status"]) == {"ok"}
    assert set(notes["by_kind"]) <= {"batch", "batch_forged"}
    assert notes["paths"]["per_sig"] > 0 and "host" not in notes["paths"]
    assert notes["compiles_in_window"] == []

    work = os.path.join(BENCH, ".work", CELL)
    with open(os.path.join(work, "notes.json"), encoding="utf-8") as f:
        full = json.load(f)
    assert "reader_errors" not in full
    with open(os.path.join(work, "spans.jsonl"), encoding="utf-8") as f:
        spans = [json.loads(line) for line in f]
    devices = [s for s in spans if s["stage"] == "device"]
    # The mechanism: launches of several gates' requests, each a whole
    # number of 8-record requests in a warmed bucket, one gate a request.
    assert max(d["tenants"] for d in devices) >= 2
    for d in devices:
        assert d["tenants"] == d["reqs"] <= 6
        assert d["sigs"] == 8 * d["reqs"] <= d["bucket"] <= 64
    served = sum(notes["by_kind"].values())
    assert sum(d["reqs"] for d in devices) >= served
