#!/usr/bin/env python3
"""CI gate: run graftlint (python -m hotstuff_tpu.analysis) from anywhere.

Exit status is the number-of-findings truth: 0 clean, 1 findings, 2 bad
usage.  Every perf PR runs this before benching — the rules it enforces
are exactly the silent-degradation class (host syncs, retraces, wire
drift, unlocked sharing) that a green unit-test run does not catch.

All CLI flags pass through to the analysis module, so
``scripts/lint_gate.py --json-out findings.json`` emits the
machine-readable findings document next to the text output (CI and
tooling consume that instead of scraping lines).
"""

import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Suppression budget: every `graftlint: disable=` in shipped code is a
# hole in a checker, and holes must not accrete silently.  The budget is
# a RATCHET on suppressions with no same-line rationale — new disables
# must say why on the same line (the older preceding-comment style is
# grandfathered into the baseline, which may only shrink).
_SUPPRESS_SCAN_ROOTS = ("hotstuff_tpu", os.path.join("native", "src"),
                        "scripts")
_SUPPRESS_RE = re.compile(
    r"(?:#|//)\s*graftlint:\s*disable=([\w\-, ]+)(.*)")
_BASELINE = os.path.join(REPO, "scripts", "suppression_baseline.json")


def count_suppressions(repo):
    """(total, without_rationale, bare_sites) over the shipped tree —
    tests and fixtures are out of scope: a fixture's suppression is the
    thing under test, not a hole."""
    total, bare, sites = 0, 0, []
    for root in _SUPPRESS_SCAN_ROOTS:
        path = os.path.join(repo, root)
        if os.path.isfile(path):
            files = [path]
        else:
            files = [os.path.join(dp, f)
                     for dp, _dns, fns in os.walk(path)
                     for f in sorted(fns)
                     if f.endswith((".py", ".cpp", ".hpp", ".h"))]
        for fp in sorted(files):
            with open(fp, encoding="utf-8", errors="replace") as fh:
                for lineno, line in enumerate(fh, start=1):
                    m = _SUPPRESS_RE.search(line)
                    if not m:
                        continue
                    total += 1
                    if not m.group(2).strip():
                        bare += 1
                        sites.append(
                            f"{os.path.relpath(fp, repo)}:{lineno}")
    return total, bare, sites


def check_suppression_budget(repo, update=False):
    """0 if the bare-suppression count respects the baseline ratchet."""
    total, bare, sites = count_suppressions(repo)
    if update:
        with open(_BASELINE, encoding="utf-8") as fh:
            doc = json.load(fh)
        doc["total"], doc["without_rationale"] = total, bare
        with open(_BASELINE, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=2)
            fh.write("\n")
        print(f"suppression baseline updated: total={total}, "
              f"without_rationale={bare}")
        return 0
    with open(_BASELINE, encoding="utf-8") as fh:
        budget = json.load(fh)["without_rationale"]
    if bare > budget:
        print(f"suppression budget exceeded: {bare} `graftlint: "
              f"disable=` line(s) without a same-line rationale "
              f"(baseline {budget}).  Add the why after the rule list "
              f"on the same line, or consciously refresh the baseline "
              f"with --update-suppression-baseline.", file=sys.stderr)
        for s in sites:
            print(f"  bare suppression: {s}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    from hotstuff_tpu.analysis.__main__ import main

    argv = sys.argv[1:]
    if "--update-suppression-baseline" in argv:
        sys.exit(check_suppression_budget(REPO, update=True))
    if not any(a == "--root" or a.startswith("--root=") for a in argv):
        argv += ["--root", REPO]
    if not any(a == "--must-cover" or a.startswith("--must-cover=")
               for a in argv):
        # Checker-qualified pins: the RLC scalar module and every
        # verifysched module must stay inside the HOTPATH scan (the
        # sockets checker also walking sidecar/ must not satisfy them),
        # and the graftchaos modules inside the SOCKETS scan.  The gate
        # fails if any of them ever moves out of its checker's target
        # set (or is deleted without this pin being updated consciously).
        for pin in ("hotpath:hotstuff_tpu/ops/scalar25519.py",
                    # graftkern: every Pallas kernel module stays inside
                    # BOTH the hot-path taint scan and the padshape scan
                    # (which carries the pallas-interpret-in-prod rule)
                    # — a kernel module that moves out of either loses
                    # the silent-degradation net this layer rides on.
                    "hotpath:hotstuff_tpu/ops/kern/__init__.py",
                    "hotpath:hotstuff_tpu/ops/kern/backend.py",
                    "hotpath:hotstuff_tpu/ops/kern/fieldops.py",
                    "hotpath:hotstuff_tpu/ops/kern/field_mul.py",
                    "hotpath:hotstuff_tpu/ops/kern/msm_accum.py",
                    "hotpath:hotstuff_tpu/ops/kern/scalar_mont.py",
                    "padshape:hotstuff_tpu/ops/kern/__init__.py",
                    "padshape:hotstuff_tpu/ops/kern/backend.py",
                    "padshape:hotstuff_tpu/ops/kern/fieldops.py",
                    "padshape:hotstuff_tpu/ops/kern/field_mul.py",
                    "padshape:hotstuff_tpu/ops/kern/msm_accum.py",
                    "padshape:hotstuff_tpu/ops/kern/scalar_mont.py",
                    "hotpath:hotstuff_tpu/parallel/shard_shapes.py",
                    # graftscale: the whole-backlog chunked mesh scan op
                    # lives in sharded_verify — it must stay inside BOTH
                    # the hot-path taint scan and the padshape scan
                    # (which carries the shard-misaligned-launch rule
                    # over its (g, rows) chunk arithmetic).
                    "hotpath:hotstuff_tpu/parallel/sharded_verify.py",
                    "hotpath:hotstuff_tpu/sidecar/sched/__init__.py",
                    "hotpath:hotstuff_tpu/sidecar/sched/classes.py",
                    "hotpath:hotstuff_tpu/sidecar/sched/scheduler.py",
                    "hotpath:hotstuff_tpu/sidecar/sched/shapes.py",
                    "hotpath:hotstuff_tpu/sidecar/sched/stats.py",
                    "padshape:hotstuff_tpu/parallel/sharded_verify.py",
                    "padshape:hotstuff_tpu/sidecar/sched/shapes.py",
                    "sockets:hotstuff_tpu/chaos/__init__.py",
                    "sockets:hotstuff_tpu/chaos/plan.py",
                    "sockets:hotstuff_tpu/chaos/runner.py",
                    "sockets:hotstuff_tpu/chaos/recovery.py",
                    "sockets:hotstuff_tpu/chaos/netem.py",
                    "sockets:hotstuff_tpu/chaos/slo.py",
                    "sockets:hotstuff_tpu/harness/faults.py",
                    "sockets:hotstuff_tpu/harness/remote.py",
                    "sockets:hotstuff_tpu/harness/local.py",
                    "sockets:hotstuff_tpu/harness/logs.py",
                    # grafttrace: every obs module stays inside the span
                    # checker AND the timing checker's scans (the
                    # critical-path numbers those modules compute feed
                    # every future perf claim).
                    "obsspan:hotstuff_tpu/obs/__init__.py",
                    "obsspan:hotstuff_tpu/obs/spans.py",
                    "obsspan:hotstuff_tpu/obs/trace.py",
                    "obsspan:hotstuff_tpu/obs/sampler.py",
                    "obsspan:hotstuff_tpu/sidecar/service.py",
                    "timing:hotstuff_tpu/obs/trace.py",
                    "timing:hotstuff_tpu/obs/sampler.py",
                    # graftscope: both halves of each frozen node-log
                    # grammar (TRACE + METRICS) stay inside the
                    # obsgrammar cross-check — a side moving out of the
                    # scan is how a one-sided grammar edit ships.
                    "obsgrammar:hotstuff_tpu/obs/trace.py",
                    "obsgrammar:hotstuff_tpu/obs/sampler.py",
                    "obsgrammar:native/src/consensus/core.cpp",
                    "obsgrammar:native/src/common/metrics.cpp",
                    # graftsync: every threaded Python module stays
                    # inside the THREADS scan, and every annotated
                    # native file inside the CXXSYNC scan — a module
                    # that grows a thread (or a header that grows a
                    # mutex) outside these sets must consciously join
                    # the pin list.
                    "threads:hotstuff_tpu/sidecar/service.py",
                    "threads:hotstuff_tpu/sidecar/sched/scheduler.py",
                    "threads:hotstuff_tpu/sidecar/sched/classes.py",
                    # graftguard: the engine AND the supervisor must
                    # stay inside the unsupervised-launch scan (an
                    # engine wait moving out of it is how the next
                    # wedged-launch hang ships), and guard.py — which
                    # owns the monitor + disposable launch threads —
                    # inside the THREADS scan.
                    "guard:hotstuff_tpu/sidecar/service.py",
                    "guard:hotstuff_tpu/sidecar/guard.py",
                    "threads:hotstuff_tpu/sidecar/guard.py",
                    # graftcadence: the resident ring stays inside the
                    # ring checker's tick-body scan (unbounded waits /
                    # unwarmed-shape launches in the cadence loop), the
                    # guard scan (it shares the engine thread), the
                    # THREADS scan, and the hot-path taint scan.
                    "ring:hotstuff_tpu/sidecar/ring.py",
                    "guard:hotstuff_tpu/sidecar/ring.py",
                    "threads:hotstuff_tpu/sidecar/ring.py",
                    "hotpath:hotstuff_tpu/sidecar/ring.py",
                    # graftsurge: the admission controller and the load
                    # model stay inside the THREADS scan (both are
                    # called from multiple threads), and every surge
                    # module inside the new BOUNDED-INGRESS scan.
                    "threads:hotstuff_tpu/sidecar/sched/surge.py",
                    "ingress:hotstuff_tpu/sidecar/sched/surge.py",
                    "ingress:hotstuff_tpu/sidecar/sched/scheduler.py",
                    "ingress:hotstuff_tpu/sidecar/sched/classes.py",
                    "ingress:hotstuff_tpu/harness/loadgen.py",
                    "threads:hotstuff_tpu/obs/sampler.py",
                    "threads:hotstuff_tpu/chaos/runner.py",
                    "threads:hotstuff_tpu/harness/faults.py",
                    "threads:hotstuff_tpu/harness/local.py",
                    "cxxsync:native/src/network/event_loop.hpp",
                    "cxxsync:native/src/network/event_loop.cpp",
                    "cxxsync:native/src/network/reliable_sender.hpp",
                    "cxxsync:native/src/network/reliable_sender.cpp",
                    "cxxsync:native/src/store/store.hpp",
                    "cxxsync:native/src/crypto/sidecar_client.hpp",
                    "cxxsync:native/src/crypto/sidecar_client.cpp",
                    "cxxsync:native/src/consensus/mempool_driver.hpp",
                    "cxxsync:native/src/consensus/core.cpp",
                    # graftview: the optimistic timeout aggregator and
                    # the cascade-driving chaos modules stay inside
                    # their checkers' scans.
                    "cxxsync:native/src/consensus/aggregator.hpp",
                    "cxxsync:native/src/consensus/aggregator.cpp",
                    "cxxsync:native/src/mempool/ingress.hpp",
                    "cxxsync:native/src/common/metrics.hpp",
                    "cxxsync:native/src/common/metrics.cpp",
                    # grafttaint: the consensus core and the sidecar wire
                    # codec anchor the verification-gate provenance scan
                    # — either moving out of the TAINT target set means
                    # the no-unverified-bytes proof silently stops
                    # covering the paths it exists for.
                    "taint:native/src/consensus/core.cpp",
                    "taint:hotstuff_tpu/sidecar/protocol.py",
                    # graftingress: the admission-verify stage and the
                    # signed-tx codec twins must stay inside the taint
                    # and cxxsync scans — the tx-signature gate proof
                    # and the frame-constant cross-check both die
                    # silently if either side drops out.
                    "taint:native/src/mempool/tx_verify.cpp",
                    "taint:native/src/mempool/tx_verify.hpp",
                    "taint:hotstuff_tpu/crypto/txsign.py",
                    # graftdag: the certified-batch mempool modules stay
                    # inside the taint scan — the batch-certificate gate
                    # (signed-ACK assembly/verification) and the
                    # cert-driven prefetch sink both lose their
                    # provenance proof if any of these drops out.
                    "taint:native/src/mempool/messages.cpp",
                    "taint:native/src/mempool/quorum_waiter.cpp",
                    "taint:native/src/mempool/synchronizer.cpp",
                    "taint:native/src/consensus/mempool_driver.cpp",
                    "cxxsync:native/src/mempool/tx_verify.hpp",
                    "cxxsync:native/src/mempool/tx_verify.cpp",
                    # graftfleet: the tenant-lane implementation and the
                    # scheduler modules that consume it stay inside the
                    # tenant-unscoped-queue scan — a scheduler module
                    # moving out of it is how the next raw-deque bypass
                    # of the DRR fairness discipline ships.
                    "tenantq:hotstuff_tpu/sidecar/sched/tenantq.py",
                    "tenantq:hotstuff_tpu/sidecar/sched/scheduler.py",
                    "tenantq:hotstuff_tpu/sidecar/sched/classes.py",
                    "threads:hotstuff_tpu/sidecar/sched/tenantq.py",
                    "hotpath:hotstuff_tpu/sidecar/sched/tenantq.py"):
            argv += ["--must-cover", pin]
    rc = main(argv)
    budget_rc = check_suppression_budget(REPO)
    sys.exit(rc or budget_rc)
