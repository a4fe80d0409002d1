"""``correct`` shown to fail: the rest of a run driven on the CPU with the
harness's look for a chip skipped, once sound, once with an answer
altered where the engine produces it, and once as the control (the
sidecar's own host path in the device's place, which breaks the
guarantee "every verdict comes from the device").  Each is a whole
``run.py`` process at rehearsal size (about a minute each, the sound and
the altered run share the CPU compile cache)."""

import json
import os
import subprocess
import sys

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
REHEARSAL = os.path.join(BENCH, "rehearsal", "BENCHMARK.json")
CONTROL = os.path.join(BENCH, "rehearsal", "BENCHMARK.control.json")

# Runs in a process of its own: run.py IS the sidecar process.
DRIVE = r"""
import sys
sys.path.insert(0, sys.argv[1])
import run

run.chips_found = lambda devices, chips: True        # no look for a chip
run.memory_peak_bytes = lambda devices: 1            # the CPU reports none
served = run.check_served_by_device
run.check_served_by_device = lambda *a: [
    p for p in served(*a) if "device.platform" not in p]

if sys.argv[2] == "altered_answer":
    from hotstuff_tpu.crypto import eddsa

    armed = []
    ready = run.Sidecar.wait_ready
    pack = eddsa.verify_batch_pack

    def wait_ready(self, timeout_s):      # the boot's own verdicts stay true
        ready(self, timeout_s)
        armed.append(True)

    def altered(*a, **kw):
        dispatcher = pack(*a, **kw)

        def dispatch():
            fetcher = dispatcher()

            def fetch():
                mask = [bool(v) for v in fetcher()]
                if armed:
                    mask[0] = not mask[0]
                return mask
            return fetch
        return dispatch

    run.Sidecar.wait_ready = wait_ready
    eddsa.verify_batch_pack = altered

sys.exit(run.main(sys.argv[3:]))
"""


def drive(fault, manifest, workload, seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    got = subprocess.run(
        [sys.executable, "-c", DRIVE, BENCH, fault, "--manifest", manifest,
         "--workload", workload, "--seed", str(seed), "--seconds", "3",
         "--trace", "0"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert got.returncode == 0, got.stdout[-2000:] + got.stderr[-2000:]
    line = json.loads(got.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    # the same numbers close standard error, each beside its limit
    tail = got.stderr.strip().splitlines()[-len(line["checks"]):]
    assert tail == [f"check {k}: {c['value']} (limit {c['limit']})"
                    for k, c in line["checks"].items()]
    return line


@pytest.mark.parametrize("fault,manifest,workload,broken", [
    ("sound", REHEARSAL, "eddsa32.flood32", []),
    ("altered_answer", REHEARSAL, "eddsa32.flood32",
     ["unmeasured_wrong", "replies_wrong"]),
    ("control", CONTROL, "eddsa32_host.flood32",
     ["host_path_launches", "route_launches"]),
])
def test_correct_reads_false_when_the_timed_path_is_broken(
        fault, manifest, workload, broken):
    line = drive(fault, manifest, workload, seed=2147483659)
    checks = line["checks"]
    over = [k for k, c in checks.items() if not run.within(c)]
    assert over == broken
    assert line["correct"] is (not broken)
    assert line["attempted"] > 0 and "verify_sigs_per_s" in line["metrics"]
    if fault == "altered_answer":
        # one row of every launch: a request of each coalesced launch
        assert 1 <= checks["replies_wrong"]["value"] == line["failed"]
