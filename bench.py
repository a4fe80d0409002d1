"""Headline benchmark: Ed25519 batch verification throughput on one TPU chip.

Prints ONE JSON line (re-printed, improving, after every timed trial —
the driver's bounded run takes the last):
  {"metric": "ed25519-batch-verify", "value": <sigs/sec on TPU>,
   "unit": "sigs/sec", "vs_baseline": <TPU / single-core-CPU>}

The baseline is the same machine's single-core CPU verifying the same
signatures one-by-one through the `cryptography` library (OpenSSL's
optimized C/asm Ed25519) — the honest stand-in for the reference's
ed25519-dalek verify path (crypto/src/lib.rs:204-208), measured fresh at
every run.  North star (BASELINE.json): >= 10x single-core CPU, measured
here over rounds of 16 sub-batches of 1024 (the sidecar's own maximum
bulk launch, MAX_COALESCED = 16 * MAX_SUBBATCH).

Measurement shape: G sub-batches of 1024 distinct (key, message,
signature) triples are verified by ONE jitted program per round
(lax.scan over sub-batches, mask all-reduced in-program so only ONE byte
returns per round), with host preparation AND the host->device transfer
of round i+1 running on a prep thread while the device executes round i.
What h2d, a fetch and the program itself cost on the chip: not measured
(ROADMAP S0 replaces this file; PERF.md holds what has been measured).

Outage resilience: every improving trial persists the measured line to
results/headline_cache.json.  If the driver's bounded run finds no
answering device, the bench emits the best previously MEASURED line,
tagged "source": "cached-measurement" with its timestamp, instead of a
zero.

The cache is namespaced by a hash of the kernel sources (bench.py, the
ops/crypto files the measurement exercises): a best recorded by OLD code
can never answer for regressed HEAD — after any kernel edit the cache
starts empty.  When a live run completes, the LIVE measurement is always
the headline `value`; a higher best-on-record (same kernel hash, i.e.
run-to-run weather) rides along as `best_on_record` so the artifact shows
both without the ratchet hiding a regression (round-5 ADVICE.md high).

RLC headline (`"rlc"` field): per-signature vs random-linear-combination
verification (crypto/eddsa.verify_batch_rlc — one MSM per quorum) at
quorum sizes n in {4, 16, 64, 256}.  Per size:
  {"per_sig_sigs_per_s": float, "rlc_sigs_per_s": float,
   "speedup": float}          — or {"skipped": true} if the size budget
(HOTSTUFF_TPU_RLC_BUDGET seconds, default 300) ran out first.

Mesh RLC headline (`"mesh_rlc"` field): ENGINE-path mesh verification
throughput — per-signature-sharded (the ladder across every device) vs
RLC-sharded (one Straus MSM whose window sums shard over the mesh) — at
quorum sizes n in {64, 256, 1024}, measured through the same
pack -> dispatch -> fetch stages the sidecar engine drives, in a
subprocess pinned to an 8-device forced-host CPU mesh (a one-chip
machine has no mesh; a pod run reuses the same probe).  Per size:
  {"per_sig_sharded_sigs_per_s": float, "rlc_sharded_sigs_per_s": float,
   "speedup": float}         — or {"skipped"/"error": ...}
(HOTSTUFF_TPU_MESH_RLC_BUDGET seconds, default 240, bounds the stage).

Committee-scale headline (`"committee_scale"` field, graftscale —
ROADMAP item 4): QC-shaped verify batches of 2f+1 votes for committee
sizes N in {100, 300, 1000}, measured through the engine-path mesh
entries — per-signature-sharded vs RLC-sharded vs the whole-backlog
chunked scan — in the same forced-host 8-device CPU-mesh subprocess as
mesh_rlc, reported as sigs/sec/CHIP.  Per committee:
  {"NX": {"quorum": int, "per_sig_sharded_sigs_per_s_chip": float,
   "rlc_sharded_sigs_per_s_chip": float, "scan_sigs_per_s_chip": float,
   "rlc_speedup": float}}    — or {"skipped"/"error": ...}
(HOTSTUFF_TPU_COMMITTEE_BUDGET seconds, default 240, bounds the stage;
the field rides BOTH the live and degraded JSON lines under the same
budget-derate/emit-or-die watchdog discipline as mesh_rlc/roofline).

MSM window-chunk sweep (`"msm_window_chunk"` field): RLC throughput at
n=256 with the Straus window chunk re-pinned to 4, 8 and 16 IN-PROCESS
(ops/ed25519.set_msm_window_chunk clears the jit caches per value — no
more subprocess per value).  Per chunk:
  {"chunkC": {"rlc_sigs_per_s": float}}   — or {"skipped"/"error": ...}.
PR 2 chose the default (8) by conv-group arithmetic; this field gives a
real v5e run the measurement to settle it (HOTSTUFF_TPU_MSM_SWEEP_BUDGET
seconds, default 180, bounds the sweep).

graftkern roofline (`"roofline"` field): measured sigs/sec/chip for the
LAX vs PALLAS kernel routes (ops/kern — HOTSTUFF_TPU_KERN) through
verify_batch_rlc at n in {64, 256, 1024}, next to an arithmetic int-op
roofline estimate per chip (roofline_estimate: per-sig op model +
HOTSTUFF_TPU_CHIP_INT_OPS), so kernel speedups are attributable as a
fraction of the same ceiling on every run.  Emitted on BOTH the live
and degraded lines; off-TPU pallas entries carry "interpreted": true
(the Pallas interpreter is not kernel performance and must never read
as it).  HOTSTUFF_TPU_ROOFLINE_BUDGET seconds (default 300) bounds the
stage; sizes/routes that miss it report {"skipped": true}.

graftview (`"viewchange"` field): batched vs per-signature TC assembly
latency at committee sizes N in {20, 100, 300} — the quorum's (2N/3+1)
timeout votes over the SHARED (round, high_qc_round) digest verified as
ONE eddsa.verify_batch launch (the QC-shaped batch the consensus core
now dispatches at view-change time) vs one reference verify per sender
(the old inline handle_timeout path, the N=100 fault-path wall).  Per
committee: {"quorum", "batched_ms", "per_sig_ms", "batched_sigs_per_s",
"per_sig_sigs_per_s", "speedup"} — or {"skipped"/"error": ...}; plus an
"eject" sub-field proving a tampered candidate fails the batch and the
per-signature fallback names exactly the signer set per-sig verification
rejects (acceptance bar in "ok").  HOTSTUFF_TPU_VIEWCHANGE_BUDGET
seconds (default 240) bounds the stage; emitted on BOTH the live and
degraded lines under the usual emit-or-die stage watchdogs.

Scheduler telemetry (`"sched"` field): the verifysched STATS counters of
a tiny in-process host-mode engine exercise (one latency QC + one bulk
batch through the real scheduler), round-tripped through the OP_STATS
wire encoding (protocol.encode_stats_reply -> decode_stats_body) so the
headline proves the telemetry pipeline end to end.  Schema:
sidecar/sched/stats.py snapshot().

graftchaos (`"chaos"` field): the fault timeline + per-event recovery
latencies of a fault plan (--fault-plan PATH|SPEC, or the
HOTSTUFF_TPU_FAULT_PLAN env, else a miniature default) run through the
real plan parser, PlanRunner, the logs/chaos-events.json round trip,
and hotstuff_tpu/chaos/recovery.summarize_recovery — the exact pipeline
a live `harness local --fault-plan` run reports through its summary.
Keys: plan_events, executed, recovered, injected_ok, max_recovery_ms,
events[] (each with t/target/action/wall/recovery_ms).

graftwan rides in the same field: `"chaos"."slo"` judges the probe's
recovery latencies against the per-fault-class SLO table (--slo
PATH|SPEC / HOTSTUFF_TPU_SLO, else chaos/slo.DEFAULT_SLO_MS) through
the same chaos/slo.judge the LogParser raises on, and `"chaos"."wan"`
proves the link-shape pipeline: the WAN spec (--wan PATH|SPEC /
HOTSTUFF_TPU_WAN, else a miniature default link) is parsed, compiled to
its per-host tc-netem command list, and realized by a real loopback
WanProxy whose shaped round trip, partition black-hole, and heal are
measured.  Keys: links, tc_commands, proxy_roundtrip_ms (one successful
shaped round trip; null when the shape defeats every attempt),
roundtrip_ok, partition_enforced, healed.

graftsurge (`"surge"` field): the overload-robustness pipeline proven
end to end — a seeded heavy-tailed multi-user generator
(harness/loadgen.py) offers 4x a modeled drain capacity into the REAL
verifysched scheduler + surge admission controller on a virtual clock,
with shed bulk feeding BUSY backoff hints back into the generator; plus
the OP_BUSY wire round trip (protocol v4) and the metrics-driven
recovery-to-baseline SLO judge on a synthetic blackout series.  Keys:
offered_x, latency {offered, shed, wait_p99_ms}, bulk {offered,
admitted, shed, deferred_by_busy}, fairness_violations,
bulk_before_latency, derate, busy_roundtrip, baseline_slo, and the
acceptance-bar "ok" (>=3x overload, consensus p99 bounded, sheds
bulk-before-latency, baseline SLO PASS).  Emitted on BOTH the live and
degraded lines.

graftguard (`"guard"` field): the supervised-verify-engine ladder proven
end to end — a host-mode VerifyEngine under a real LaunchGuard with
tight deadlines takes a scripted launch wedge (the chaos hook's `wedge`
knob, the same OP_CHAOS path a `sidecar wedge` fault-plan event drives),
answers the wedged latency batch with a mask bit-identical to
verify_batch, sheds bulk to BUSY during the crash-only reboot, re-warms,
passes the canary, and resumes device routing.  Keys: wedges, reboots,
canary_passes, quarantined_records, poisoned_records,
host_fallback_records, busy_during_reboot, busy_retry_after_ms,
masks_bit_identical, rewarmed, reboot_wall_ms, recovered, and the
acceptance bar "ok".  Emitted on BOTH the live and degraded lines.
Kill-proof emit rides with it: every emitted line is written to
results/last_line.json CACHE-FIRST, and SIGTERM/SIGALRM re-emit the best
line already measured before dying — an rc=124 round still yields a
parseable artifact.

grafttrace (`"trace"` field): the cross-layer tracing pipeline proven
end to end — synthetic replica logs with a known clock skew run
through the real node-TRACE parser, the RTT-midpoint offset estimator,
per-block stitching (one deliberately partial trace), the critical-path
p50/p99 breakdown, the graftscope protocol-v5 ctx join (one block with
a full sidecar chain, one verify-traced block without — join_rate 0.5,
verify:device sub-segment present), and a Chrome-trace JSON round trip
(the exact pipeline a live run's logs/trace.json artifact and "Commit
critical path" parser note come from).  Keys: blocks, complete,
segments ({name: {n, p50_ms, p99_ms}}), join ({committed, with_verify,
joined, rate}), join_rate, chrome_events, offset_applied_ms,
roundtrip_ok.

graftingress (`"users"` field): the signed-transaction ingress tier at
population scale — per user-population U in {1e5, 1e6}, the seeded
heavy-tailed generator (harness/loadgen.py, the C++ UserLoadModel's
twin) names which user each arrival belongs to, the probe derives that
user's Ed25519 keypair on first arrival through the bounded
crypto/txsign.UserKeyring LRU (exactly the client's derive-on-demand
discipline: 1e6 users never means 1e6 resident keys), signs each frame
with a seeded ~1% forgery mix, and drives the admission records through
a host-mode VerifyEngine as INGRESS_CTX-tagged OP_VERIFY_BULK batches —
the same (digest, pk, sig) triples and bulk-lane class the mempool
admission stage ships.  Per point: {"users", "txs", "distinct_users",
"key_derivations", "keyring_capacity", "forged_sent",
"forged_rejected", "forgery_rejection_rate", "verified",
"verified_goodput_sigs_per_s", "busy_rejected", "bulk_ingress_requests",
"bulk_ingress_sigs", "bulk_ingress_share"} — or {"skipped": true} past
the budget (HOTSTUFF_TPU_USERS_BUDGET seconds, default 240); acceptance
bar in "ok" (every forged rejected, every honest verified, the bulk
lane 100% ingress-fed).  Emitted on BOTH the live and degraded lines.

Degraded mode (`"degraded": true`): the device probe is capped at
HOTSTUFF_TPU_PROBE_ATTEMPTS tries (default 3) inside a
HOTSTUFF_TPU_PROBE_WINDOW-second window (default 600) AND inside the
remaining outer budget (HOTSTUFF_TPU_BENCH_DEADLINE seconds of total
wall clock, default 3000, minus elapsed and a fixed emit slack — the
round-5 fix: the driver's own hard timeout must never close on probe
retries, BENCH_r05.json rc=124).  When no device answers, the bench
falls back to JAX_PLATFORMS=cpu, measures the RLC + mesh_rlc headlines
there (CPU-backend sigs/sec — NOT comparable to TPU numbers, hence the
flag), and always emits one parseable JSON line before exiting 0.  A
device that does not answer can delay the artifact, never lose it.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

# Outer-budget bookkeeping: the driver wraps this bench in a hard
# `timeout` (rc=124 is the artifact-eating failure mode), so every
# internal retry window must be capped against what is LEFT of that
# budget, not just its own env knob.  HOTSTUFF_TPU_BENCH_DEADLINE is the
# total wall-clock budget in seconds, measured from process start
# (module import); the default assumes the driver's observed ~55-minute
# window minus margin.  _DEADLINE_SLACK is reserved so the degraded
# fallback can still measure and emit its JSON line INSIDE the window —
# the round-5 regression (BENCH_r05.json) was nine probe retries
# consuming the entire budget with nothing printed.
_BENCH_T0 = time.monotonic()
_DEADLINE_SLACK = 120.0


def bench_budget_s() -> float:
    raw = os.environ.get("HOTSTUFF_TPU_BENCH_DEADLINE", "").strip()
    try:
        return float(raw) if raw else 3000.0
    except ValueError:
        return 3000.0


def budget_left_s(now=time.monotonic) -> float:
    """Seconds of the outer budget left (can go negative)."""
    return bench_budget_s() - (now() - _BENCH_T0)

N = 1024          # sub-batch size; asserted == eddsa.MAX_SUBBATCH below
G = 16            # sub-batches per device dispatch
ROUNDS = 20       # timed pipelined rounds per trial: pipeline fill +
                  # final fetch are pure overhead, and more rounds
                  # amortize them (their share: not measured on the chip)
TRIALS = 4        # best-of: a shared host CPU drifts with neighbor load;
                  # best-of-n measures the hardware, not the neighbors

CACHE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "results", "headline_cache.json")

def kernel_fingerprint() -> str:
    """Hash of the kernel sources (the shared utils/xla_cache scheme —
    ops + crypto + the graftkern Pallas modules — plus bench.py itself);
    namespaces the headline cache so a stale best can only ever answer
    for the code that produced it.  The compile-cache manifest uses the
    same scheme, so one kernel edit invalidates both records together."""
    from hotstuff_tpu.utils.xla_cache import kernel_fingerprint as _kf

    return _kf(extra=("bench.py",))


def load_cache():
    try:
        with open(CACHE_PATH) as f:
            c = json.load(f)
        if c.get("value", 0) > 0 and \
                c.get("kernel") == kernel_fingerprint():
            return c
    except (OSError, ValueError):
        pass
    return None


def save_cache(value: float, vs_baseline: float, cpu: float):
    cached = load_cache()
    if cached and cached["value"] >= value:
        return
    # Honesty guard: a CPU-contended host (anything else running) starves
    # the single-core baseline and INFLATES the ratio.  Never store a
    # ratio whose baseline is far below the best baseline on record —
    # a contended run can only under-measure the TPU, never over-claim.
    if cached and cpu < 0.8 * cached.get("cpu_baseline", 0):
        return
    tmp = CACHE_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump({
            "metric": "ed25519-batch-verify",
            "value": round(value, 1),
            "unit": "sigs/sec",
            "vs_baseline": round(vs_baseline, 3),
            "cpu_baseline": round(cpu, 1),
            "kernel": kernel_fingerprint(),
            "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                         time.gmtime()),
        }, f)
    os.replace(tmp, CACHE_PATH)


# Kill-proof emit (graftguard satellite; the round-5 review's top-next
# "kill-proof BENCH emit"): every emitted line is remembered in-process AND written
# to disk CACHE-FIRST (before stdout), so a driver timeout that SIGKILLs
# mid-print — or an rc=124 round that never reaches the final emit —
# still leaves results/last_line.json as a parseable artifact, and the
# SIGTERM/SIGALRM handlers re-emit the best line already measured
# before dying (install_kill_handlers, called first thing in main()).
_LINE_CACHE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "results", "last_line.json")
_LAST_LINE = None


def emit(value: float, vs_baseline: float, **extra):
    global _LAST_LINE
    line = {"metric": "ed25519-batch-verify", "value": round(value, 1),
            "unit": "sigs/sec", "vs_baseline": round(vs_baseline, 3)}
    line.update(extra)
    _LAST_LINE = line
    try:
        tmp = _LINE_CACHE_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(line, f)
        os.replace(tmp, _LINE_CACHE_PATH)
    except OSError:
        pass  # the disk copy is belt-and-braces, never fatal
    print(json.dumps(line), flush=True)


def install_kill_handlers(exit=os._exit, signums=None):
    """SIGTERM/SIGALRM -> re-emit the best headline line this process
    already measured, then exit 0: the driver's bounded window closing
    (its `timeout` sends SIGTERM before the rc=124 SIGKILL) must never
    eat an artifact a wedged stage already earned.  Preference order:
    the last line THIS run emitted (partial stages included), else the
    best cached measurement for this exact kernel, else an explicit
    error line — always exactly one parseable JSON line.  ``exit`` is
    injectable for the regression test; returns the handler."""
    import signal as _signal

    def _handler(signum, frame):
        name = _signal.Signals(signum).name
        if _LAST_LINE is not None:
            out = dict(_LAST_LINE)
            out["killed"] = name
        else:
            cached = load_cache()
            if cached:
                out = {"metric": "ed25519-batch-verify",
                       "value": cached["value"], "unit": "sigs/sec",
                       "vs_baseline": cached["vs_baseline"],
                       "source": "cached-measurement",
                       "measured_at": cached.get("measured_at",
                                                 "unknown"),
                       "note": f"killed by {name} before any emit",
                       "killed": name}
            else:
                out = {"metric": "ed25519-batch-verify", "value": 0,
                       "unit": "sigs/sec", "vs_baseline": 0,
                       "killed": name,
                       "error": f"killed by {name} before any "
                                "measurement"}
        # ONE os.write of pre-encoded bytes, with a LEADING newline:
        # the signal may have interrupted emit() mid-print, and
        # appending to that torn prefix would weld two lines into one
        # unparseable last line.  The newline closes any partial line
        # first, so the handler's line is always whole — the driver
        # takes the last parseable line, and the torn fragment simply
        # fails parse.  (No buffered print here: os._exit would drop
        # it, and print() re-enters the interrupted stream machinery.)
        try:
            os.write(1, ("\n" + json.dumps(out) + "\n").encode("utf-8"))
        except OSError:
            pass
        exit(0)

    if signums is None:
        signums = (_signal.SIGTERM, _signal.SIGALRM)
    for s in signums:
        _signal.signal(s, _handler)
    return _handler


def emit_cached(cached, note: str, **extra):
    """The one shape for a cached-measurement line (dead-device fallback
    AND slow-live-run fallback emit through here)."""
    emit(cached["value"], cached["vs_baseline"],
         source="cached-measurement",
         measured_at=cached.get("measured_at", "unknown"),
         note=note, **extra)


def emit_final(tpu: float, cpu: float, **extra):
    """Final emit after a completed live run: the LIVE measurement is the
    headline `value` — the driver records the last line, and a number
    this run's code did not achieve must never stand in for it.  A
    higher best-on-record (same kernel fingerprint, so the difference is
    run-to-run weather, not code) rides along as secondary fields."""
    cached = load_cache()
    if cached and cached["value"] > round(tpu, 1):
        emit(tpu, tpu / cpu,
             best_on_record=cached["value"],
             best_vs_baseline=cached["vs_baseline"],
             best_measured_at=cached.get("measured_at", "unknown"),
             note="live run below best on record for this exact kernel "
                  "(run-to-run weather)", **extra)
    else:
        emit(tpu, tpu / cpu, **extra)


def emit_cached_or_fail(reason: str, code: int = 3):
    """A dead device should surface the best MEASURED number on record,
    not a zero: the cache only ever holds values a real run produced."""
    cached = load_cache()
    if cached:
        emit_cached(cached, reason)
        os._exit(0)
    emit(0, 0, error=reason)
    os._exit(code)


def rlc_compare(sizes=(4, 16, 64, 256), repeats: int = 2,
                budget_s: float | None = None) -> dict:
    """Time per-signature vs RLC batch verify at quorum sizes -> the
    headline ``rlc`` dict (see module docstring for the field schema).

    Signatures come from the pure-python reference signer — no external
    dependency, so the degraded CPU path can always run this.  Each
    size's first calls warm/compile both programs OUTSIDE the timed
    region; ``budget_s`` bounds the whole sweep (a cold XLA compile per
    shape is the dominant cost), and sizes that miss the budget report
    ``{"skipped": true}`` instead of stalling the bench window.
    """
    from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref

    t0 = time.perf_counter()
    rng = np.random.default_rng(7)
    nmax = max(sizes)
    msgs, pks, sigs = [], [], []
    for _ in range(nmax):
        sk = rng.bytes(32)
        msg = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        msgs.append(msg)
        pks.append(pk)
        sigs.append(ref.sign(sk, msg))

    out = {}
    for n in sizes:
        if budget_s is not None and time.perf_counter() - t0 > budget_s:
            out[f"n{n}"] = {"skipped": True}
            continue
        m, p, s = msgs[:n], pks[:n], sigs[:n]
        stats = {}
        for name, fn in (("per_sig", eddsa.verify_batch),
                         ("rlc", eddsa.verify_batch_rlc)):
            # Explicit raise, not assert: python -O must not strip the
            # warmup call (the first timed round would eat the compile)
            # or the correctness guard.
            if not fn(m, p, s).all():         # warm/compile + correctness
                raise RuntimeError(f"{name} verify failed at n={n}")
            best = 0.0
            for _ in range(repeats):
                t = time.perf_counter()
                mask = fn(m, p, s)
                dt = time.perf_counter() - t
                if not mask.all():
                    raise RuntimeError(f"{name} verify failed at n={n}")
                best = max(best, n / dt)
            stats[f"{name}_sigs_per_s"] = round(best, 1)
        stats["speedup"] = round(
            stats["rlc_sigs_per_s"] / stats["per_sig_sigs_per_s"], 3)
        out[f"n{n}"] = stats
    return out


def _make_ref_sigs(n: int, seed: int = 11):
    """n distinct (msg, pk, sig) triples via the pure-python reference
    signer — no external dependency (the `cryptography` lib is not
    guaranteed on this image), so every bench mode can run this."""
    from hotstuff_tpu.crypto import ref_ed25519 as ref

    rng = np.random.default_rng(seed)
    msgs, pks, sigs = [], [], []
    for _ in range(n):
        sk = rng.bytes(32)
        msg = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        msgs.append(msg)
        pks.append(pk)
        sigs.append(ref.sign(sk, msg))
    return msgs, pks, sigs


def _rlc_best_sigs_per_s(msgs, pks, sigs, n: int, repeats: int) -> float:
    """Warm/compile + correctness guard, then best-of-``repeats``
    verify_batch_rlc throughput at quorum size n — the one timing
    discipline the msm_window_chunk and roofline headlines share (a
    future change to it lands in both)."""
    from hotstuff_tpu.crypto import eddsa

    m, p, s = msgs[:n], pks[:n], sigs[:n]
    # Explicit raise, not assert: python -O must not strip the warmup
    # call or the correctness guard.
    if not eddsa.verify_batch_rlc(m, p, s).all():
        raise RuntimeError(f"RLC verify failed at n={n}")
    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        mask = eddsa.verify_batch_rlc(m, p, s)
        dt = time.perf_counter() - t0
        if not mask.all():
            raise RuntimeError(f"RLC verify failed at n={n}")
        best = max(best, n / dt)
    return best


def msm_chunk_sweep(chunks=(4, 8, 16), n: int = 256,
                    budget_s: float = 240.0) -> dict:
    """RLC throughput at quorum size n under each MSM window-chunk
    value, IN-PROCESS: ops/ed25519.set_msm_window_chunk re-pins the
    constant and clears the jit caches, so the sweep no longer re-execs
    a subprocess per value (the old shape; the constant used to bind at
    import).  Results are bit-identical across chunk values — only the
    conv-group/scan-depth trade moves — so the sweep is pure timing.
    Chunks that miss the budget report {"skipped": true}; a failed
    measurement reports {"error": ...} and the default chunk is always
    restored — the sweep never takes the headline down with it.

    The sweep PINS the lax kernel route for its duration: the chunk
    knob only exists on the lax chunked-scan path (the pallas window
    accumulator grids single windows — ed25519.msm_window_sums
    documents the knob as inapplicable there), so sweeping under
    HOTSTUFF_TPU_KERN=pallas would measure one identical program three
    times and read as "chunk doesn't matter"."""
    from hotstuff_tpu.ops import ed25519 as E
    from hotstuff_tpu.ops import kern

    t0 = time.perf_counter()
    default_chunk = E.msm_window_chunk()
    ambient_mode = kern.mode()
    msgs, pks, sigs = _make_ref_sigs(n)
    out = {}
    try:
        kern.set_mode("lax")
        for chunk in chunks:
            left = budget_s - (time.perf_counter() - t0)
            if left <= 0:
                out[f"chunk{chunk}"] = {"skipped": True}
                continue
            try:
                E.set_msm_window_chunk(chunk)
                best = _rlc_best_sigs_per_s(msgs, pks, sigs, n, repeats=2)
                out[f"chunk{chunk}"] = {"rlc_sigs_per_s": round(best, 1)}
            except Exception as e:  # noqa: BLE001 — per-chunk isolation
                out[f"chunk{chunk}"] = {"error": f"{e!r:.200}"}
    finally:
        E.set_msm_window_chunk(default_chunk)
        kern.set_mode(ambient_mode)
    return out


def roofline_estimate() -> dict:
    """Arithmetic int-op roofline for one chip — the yardstick the
    ``roofline`` headline measures the lax and pallas paths against.

    Per-signature integer-op model of the RLC verify path (the
    quorum-certificate steady state), from the op counts the ops/
    modules document:

      * one field mul = 32x63 MAC pairs (conv) + the wrap-38 fold +
        4 parallel carry steps over 32 limbs (~4 ops each);
      * decompression: ~265 muls per point (the pow_p58 chain dominates)
        x 2 points (A, R) per signature;
      * MSM: per-point 16-entry table build (14 point adds x 8 muls +
        16 to_cached muls = 128 muls/point) + 64 windows of amortized
        ~1 tree add/point (8 muls + amortized to_cached ~0.5) x
        2 points/sig; scalar mod-L products are noise next to these.

    The per-chip int-op rate defaults to a v5e-class VPU estimate
    (8 x 128 lanes x 2 int ops/cycle x ~0.94 GHz ~= 1.9e12); override
    with HOTSTUFF_TPU_CHIP_INT_OPS (and name the chip via
    HOTSTUFF_TPU_CHIP) when benching other silicon.  An estimate with
    stated knobs, not a measurement — its job is making measured
    sigs/sec/chip numbers attributable as a fraction of the ceiling."""
    ops_per_mul = 32 * 63 * 2 + 63 + 4 * 32 * 4          # ~4.6e3
    muls_decompress = 2 * 265                            # A and R
    muls_table = 2 * (14 * 8 + 16)
    muls_windows = 2 * 64 * (8 + 4)  # tree add + amortized cached/horner
    muls_per_sig = muls_decompress + muls_table + muls_windows
    int_ops_per_sig = muls_per_sig * ops_per_mul
    chip = os.environ.get("HOTSTUFF_TPU_CHIP", "v5e")
    try:
        chip_int_ops = float(
            os.environ.get("HOTSTUFF_TPU_CHIP_INT_OPS", "1.9e12"))
    except ValueError:
        chip_int_ops = 1.9e12
    return {
        "model": "rlc-straus int-op estimate",
        "field_muls_per_sig": muls_per_sig,
        "int_ops_per_sig": int_ops_per_sig,
        "chip": chip,
        "chip_int_ops_per_s": chip_int_ops,
        "roofline_sigs_per_s_chip": round(chip_int_ops / int_ops_per_sig,
                                          1),
    }


def roofline_headline(sizes=(64, 256, 1024), repeats: int = 2,
                      budget_s: float | None = None) -> dict:
    """The headline ``roofline`` field: measured sigs/sec/chip for the
    LAX vs PALLAS kernel routes at quorum sizes n, next to the
    arithmetic roofline estimate — so a graftkern speedup (or
    regression) is attributable against the same ceiling on every run.

    Measures verify_batch_rlc (the QC hot path) per route via
    ops/kern.set_mode, which clears the jit caches between routes so
    each measurement compiles its own programs; the ambient mode is
    restored afterwards.  Off-TPU the pallas route runs the kernel
    INTERPRETER — orders of magnitude slower and flagged per-entry as
    ``interpreted`` so a degraded line can never pass interpreter
    numbers off as kernel performance.  Budget-capped like every
    headline stage (HOTSTUFF_TPU_ROOFLINE_BUDGET, default 300 s):
    sizes/routes that miss the budget report {"skipped": true}; a
    failed route reports {"error": ...}.  Emitted on BOTH the live and
    degraded JSON lines."""
    from hotstuff_tpu.ops import kern

    if budget_s is None:
        budget_s = float(
            os.environ.get("HOTSTUFF_TPU_ROOFLINE_BUDGET", "300"))
    est = roofline_estimate()
    out = {"est": est, "chips": 1, "kern_default": kern.mode()}
    if budget_s <= 0:
        out["skipped"] = True
        return out
    t0 = time.perf_counter()
    msgs, pks, sigs = _make_ref_sigs(max(sizes), seed=29)
    ambient = kern.mode()
    interpreted = kern.interpret_default()
    roof = est["roofline_sigs_per_s_chip"]
    try:
        for n in sizes:
            stats = {}
            for route in ("lax", "pallas"):
                if time.perf_counter() - t0 > budget_s:
                    stats[route] = {"skipped": True}
                    continue
                try:
                    kern.set_mode(route)
                    best = _rlc_best_sigs_per_s(msgs, pks, sigs, n,
                                                repeats)
                    entry = {"sigs_per_s_chip": round(best, 1),
                             "pct_of_roofline": round(100.0 * best / roof,
                                                      2)}
                    if route == "pallas" and interpreted:
                        entry["interpreted"] = True
                    stats[route] = entry
                except Exception as e:  # noqa: BLE001 — route isolation
                    stats[route] = {"error": f"{e!r:.200}"}
            lax_v = stats.get("lax", {}).get("sigs_per_s_chip")
            pal_v = stats.get("pallas", {}).get("sigs_per_s_chip")
            if lax_v and pal_v:
                stats["pallas_speedup"] = round(pal_v / lax_v, 3)
            out[f"n{n}"] = stats
    finally:
        kern.set_mode(ambient)
    return out


def mesh_rlc_probe(n_devices: int = 8, sizes=(64, 256, 1024),
                   repeats: int = 2, budget_s: float = 240.0):
    """Child half of the ``mesh_rlc`` headline: measure ENGINE-path mesh
    throughput — per-signature-sharded (verify_batch_sharded_pack, the
    ladder across every device) vs RLC-sharded (verify_rlc_sharded_pack,
    one Straus MSM whose window sums shard over the mesh) — at quorum
    sizes n, through the same pack -> dispatch -> fetch stages the
    sidecar engine drives (host preparation included in the timed
    region, exactly as the engine pays it).  Prints one JSON line.
    Run via a subprocess pinned to a forced-host CPU mesh (the parent,
    mesh_rlc_headline, sets JAX_PLATFORMS=cpu +
    --xla_force_host_platform_device_count)."""
    from hotstuff_tpu.crypto import eddsa
    from hotstuff_tpu.parallel import sharded_verify as shv
    from hotstuff_tpu.parallel.mesh import make_mesh
    from hotstuff_tpu.utils.xla_cache import configure_xla_cache

    configure_xla_cache()
    t0 = time.perf_counter()
    mesh = make_mesh(n_devices)
    msgs, pks, sigs = _make_ref_sigs(max(sizes), seed=17)
    def emit_progress(out):
        # One line per size (completed OR skipped): if the parent's
        # subprocess timeout kills this child mid-compile, everything
        # decided so far still reaches the headline (the parent parses
        # the LAST parseable line of the partial stdout).
        print(json.dumps({"mesh_rlc": out, "n_devices": n_devices}),
              flush=True)

    out = {}
    for n in sizes:
        if time.perf_counter() - t0 > budget_s:
            out[f"n{n}"] = {"skipped": True}
            emit_progress(out)
            continue
        stats = {}
        for name, pack in (
                ("per_sig_sharded",
                 lambda p: shv.verify_batch_sharded_pack(mesh, p)),
                ("rlc_sharded",
                 lambda p: shv.verify_rlc_sharded_pack(mesh, p))):
            # Warm/compile + correctness guard outside the timed region
            # (explicit raise: python -O must not strip either).
            prep = eddsa.prepare_batch(msgs[:n], pks[:n], sigs[:n])
            if not pack(prep)()().all():
                raise RuntimeError(f"{name} verify failed at n={n}")
            best = 0.0
            for _ in range(repeats):
                t = time.perf_counter()
                prep = eddsa.prepare_batch(msgs[:n], pks[:n], sigs[:n])
                mask = pack(prep)()()
                dt = time.perf_counter() - t
                if not mask.all():
                    raise RuntimeError(f"{name} verify failed at n={n}")
                best = max(best, n / dt)
            stats[f"{name}_sigs_per_s"] = round(best, 1)
        stats["speedup"] = round(stats["rlc_sharded_sigs_per_s"]
                                 / stats["per_sig_sharded_sigs_per_s"], 3)
        out[f"n{n}"] = stats
        emit_progress(out)
    if not out:
        emit_progress(out)


def _forced_host_mesh_headline(field: str, probe_call: str,
                               n_devices: int, budget_s: float) -> dict:
    """Shared parent of the forced-host CPU-mesh probe headlines
    (``mesh_rlc``, ``committee_scale``): run the named probe in a
    subprocess pinned to an n-device virtual mesh (identical program
    structure to a real mesh, but CPU numbers: counts and correctness
    only, never a device rate; a real pod run reuses the same probes),
    parse the LAST parseable
    progress line, and salvage a partial measurement when the child
    times out mid-compile.  Failures degrade to an ``error`` entry,
    never take the headline down."""
    import re
    import subprocess
    import sys

    if budget_s <= 0:
        return {"skipped": True}
    root = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    # Belt to the environment's braces: the child also pins the
    # platform via jax.config before any backend-initializing call
    # (same as dryrun_multichip).
    code = ("import jax; jax.config.update('jax_platforms', 'cpu')\n"
            f"import bench; bench.{probe_call}\n")
    def _last_line(stdout):
        if isinstance(stdout, bytes):
            stdout = stdout.decode("utf-8", "replace")
        lines = (stdout or "").strip().splitlines()
        return json.loads(lines[-1]) if lines else None

    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=root, env=env,
            capture_output=True, text=True, timeout=budget_s + 120.0,
            check=True)
        line = _last_line(proc.stdout)
        if line is None:
            return {"error": "probe child printed nothing"}
        return line[field]
    except subprocess.TimeoutExpired as e:
        # The child emits one line per completed size: salvage whatever
        # it finished before the timeout (first-boot XLA compiles can
        # eat the whole budget; the persistent cache makes the next run
        # complete) — a partial measurement beats none.
        try:
            line = _last_line(e.stdout)
            if line is not None:
                out = line[field]
                out["timeout"] = True
                return out
        except (ValueError, KeyError, TypeError):
            pass
        return {"error": f"{e!r:.160}"}
    except Exception as e:  # noqa: BLE001 — headline isolation
        detail = ""
        if isinstance(e, subprocess.CalledProcessError):
            detail = (e.stderr or "")[-200:]
        return {"error": f"{e!r:.120}{detail}"}


def mesh_rlc_headline(n_devices: int = 8,
                      budget_s: float | None = None) -> dict:
    """Parent half of the ``mesh_rlc`` headline field: run
    :func:`mesh_rlc_probe` on the forced-host CPU mesh (see
    :func:`_forced_host_mesh_headline` for the subprocess contract)."""
    if budget_s is None:
        budget_s = float(
            os.environ.get("HOTSTUFF_TPU_MESH_RLC_BUDGET", "240"))
    return _forced_host_mesh_headline(
        "mesh_rlc", f"mesh_rlc_probe({n_devices}, budget_s={budget_s})",
        n_devices, budget_s)


def committee_scale_probe(n_devices: int = 8,
                          committees=(100, 300, 1000),
                          repeats: int = 2,
                          budget_s: float = 240.0) -> dict:
    """Child half of the ``committee_scale`` headline (graftscale):
    sweep QC-shaped verify batches — 2f+1 votes for committee sizes
    N — through the ENGINE-path mesh entries, per route:

      * ``per_sig_sharded``  — verify_batch_sharded_pack, the scalar
        ladder data-parallel across every device;
      * ``rlc_sharded``      — verify_rlc_sharded_pack, ONE Straus MSM
        whose window sums shard over the mesh (the path the scheduler
        routes a warmed giant-committee QC batch down);
      * ``scan``             — verify_sharded_chunked_pack, the
        whole-backlog chunked mesh scan draining the batch in ONE
        dispatch (the graftscale bulk route).

    Each measurement pays the full pack -> dispatch -> fetch stages the
    sidecar engine drives (host preparation included), reported as
    sigs/sec/CHIP so committee sizes compare on one axis.  Prints one
    JSON progress line per completed committee (the parent salvages a
    partial sweep) and returns the dict (the in-process schema test).
    Committee sizes that miss ``budget_s`` report {"skipped": true}."""
    from hotstuff_tpu.crypto import eddsa
    from hotstuff_tpu.parallel import sharded_verify as shv
    from hotstuff_tpu.parallel.mesh import make_mesh
    from hotstuff_tpu.sidecar.sched.shapes import quorum_sigs
    from hotstuff_tpu.utils.xla_cache import configure_xla_cache

    configure_xla_cache()
    t0 = time.perf_counter()
    mesh = make_mesh(n_devices)
    nmax = quorum_sigs(max(committees))
    msgs, pks, sigs = _make_ref_sigs(nmax, seed=19)
    # The scan column must measure the MULTI-chunk whole-backlog
    # structure the engine's scan route dispatches (a rows=None default
    # would collapse every quorum to a degenerate one-chunk scan): pick
    # the chunk rows so the batch drains as SCAN_CHUNKS chunks, the
    # same g-chunks-of-warmed-rows program shape _warmup_mesh_scan
    # compiles.
    SCAN_CHUNKS = 4

    def scan_rows_for(n):
        from hotstuff_tpu.parallel.shard_shapes import shard_bucket

        return shard_bucket(-(-n // SCAN_CHUNKS), n_devices)

    def emit_progress(out):
        print(json.dumps({"committee_scale": out,
                          "n_devices": n_devices}), flush=True)

    out = {}
    for committee in committees:
        n = quorum_sigs(committee)
        if time.perf_counter() - t0 > budget_s:
            out[f"N{committee}"] = {"quorum": n, "skipped": True}
            emit_progress(out)
            continue
        stats = {"quorum": n}
        for name, pack in (
                ("per_sig_sharded",
                 lambda p: shv.verify_batch_sharded_pack(mesh, p)),
                ("rlc_sharded",
                 lambda p: shv.verify_rlc_sharded_pack(mesh, p)),
                ("scan",
                 lambda p, r=scan_rows_for(n):
                 shv.verify_sharded_chunked_pack(mesh, p, rows=r))):
            # Warm/compile + correctness guard outside the timed region
            # (explicit raise: python -O must not strip either).
            prep = eddsa.prepare_batch(msgs[:n], pks[:n], sigs[:n])
            if not pack(prep)()().all():
                raise RuntimeError(
                    f"{name} verify failed at quorum {n}")
            best = 0.0
            for _ in range(repeats):
                t = time.perf_counter()
                prep = eddsa.prepare_batch(msgs[:n], pks[:n], sigs[:n])
                mask = pack(prep)()()
                dt = time.perf_counter() - t
                if not mask.all():
                    raise RuntimeError(
                        f"{name} verify failed at quorum {n}")
                best = max(best, n / dt)
            stats[f"{name}_sigs_per_s_chip"] = round(best / n_devices, 1)
        stats["rlc_speedup"] = round(
            stats["rlc_sharded_sigs_per_s_chip"]
            / stats["per_sig_sharded_sigs_per_s_chip"], 3)
        out[f"N{committee}"] = stats
        emit_progress(out)
    if not out:
        emit_progress(out)
    return out


def committee_scale_headline(n_devices: int = 8,
                             budget_s: float | None = None) -> dict:
    """Parent half of the ``committee_scale`` headline field
    (graftscale, ROADMAP item 4): run :func:`committee_scale_probe`
    for N in {100, 300, 1000} on the forced-host CPU mesh (see
    :func:`_forced_host_mesh_headline` for the subprocess contract;
    HOTSTUFF_TPU_COMMITTEE_BUDGET seconds, default 240, bounds the
    stage)."""
    if budget_s is None:
        budget_s = float(
            os.environ.get("HOTSTUFF_TPU_COMMITTEE_BUDGET", "240"))
    return _forced_host_mesh_headline(
        "committee_scale",
        f"committee_scale_probe({n_devices}, budget_s={budget_s})",
        n_devices, budget_s)


def trace_headline_probe() -> dict:
    """The headline's ``trace`` field: prove the grafttrace pipeline end
    to end without booting a committee.  Synthetic replica logs with a
    KNOWN clock skew run through the REAL node-TRACE parser
    (obs/trace.py — the exact regex that mines live node logs), the
    RTT-midpoint offset estimator, per-block stitching (one block's
    trace is deliberately partial: a dropped span must degrade the
    sample count, not the breakdown), the critical-path percentiles,
    the graftscope ctx join (block aaa= carries a full sidecar chain,
    block ccc= verifies but has none — join_rate must come out 0.5 and
    the device sub-segment must appear), and a Chrome-trace JSON
    serialization round trip.  Keys: blocks, complete, segments
    ({name: {n, p50_ms, p99_ms}}), join ({committed, with_verify,
    joined, rate}), join_rate, chrome_events, offset_applied_ms,
    roundtrip_ok."""
    import json as _json

    from hotstuff_tpu.obs import trace as obstrace

    def line(sec, stage, block, rnd):
        return (f"[2026-08-03T12:00:{sec:06.3f}Z INFO consensus::core] "
                f"TRACE stage={stage} block={block} round={rnd}")

    # Replica 0: the reference clock.  Block bbb='s trace is partial
    # (no verify stages — the cached-certificate path); block ccc=
    # verifies but its sidecar chain is deliberately MISSING (every
    # replica answered from the verdict-cache fast path), so the join
    # rate must degrade, not the trace.
    log_a = "\n".join([
        line(1.000, "proposal", "aaa=", 2),
        line(1.010, "verify_submit", "aaa=", 2),
        line(1.034, "verify_reply", "aaa=", 2),
        line(1.050, "commit", "aaa=", 2),
        line(1.100, "proposal", "bbb=", 3),
        line(1.180, "commit", "bbb=", 3),
        line(1.200, "proposal", "ccc=", 4),
        line(1.210, "verify_submit", "ccc=", 4),
        line(1.230, "verify_reply", "ccc=", 4),
        line(1.260, "commit", "ccc=", 4),
    ])
    # Replica 1: same events observed later, stamped by a clock running
    # a known skew AHEAD — alignment must bring them back onto (not
    # before) the reference observations.
    skew_s = 0.125
    log_b = "\n".join([
        line(1.020 + skew_s, "proposal", "aaa=", 2),
        line(1.060 + skew_s, "commit", "aaa=", 2),
    ])
    spans = obstrace.parse_node_trace(log_a, host="node-0.log")
    spans_b = obstrace.parse_node_trace(log_b, host="node-1.log")
    # Offset probe with synthetic stamps: local sends at t, the skewed
    # host answers mid-flight, local receives at t + rtt.
    rtt = 0.004
    probes = [(t, t + rtt / 2 + skew_s, t + rtt) for t in (5.0, 6.0, 7.0)]
    offset = obstrace.estimate_offset(probes)
    spans += obstrace.apply_offset(spans_b, offset)
    traces = obstrace.stitch_blocks(spans)
    summary = obstrace.critical_path(traces)
    # Sidecar chain for block aaa= only: per-request spans tagged ctx,
    # the launch-level device span tagged ctxs — the protocol-v5 schema
    # the live sidecar emits.
    sidecar_spans = [
        {"stage": "request", "t0": 1785751201.005, "t": 1785751201.04,
         "dur_ms": 35.0, "rid": 1, "cls": "latency", "ctx": "aaa="},
        {"stage": "queue", "t0": 1785751201.0085, "t": 1785751201.01,
         "dur_ms": 1.5, "rid": 1, "cls": "latency", "ctx": "aaa="},
        {"stage": "device", "t0": 1785751201.002, "t": 1785751201.02,
         "dur_ms": 18.0, "rid": 1, "ctxs": ["aaa="]},
        {"stage": "reply", "t0": 1785751201.0395, "t": 1785751201.04,
         "dur_ms": 0.5, "rid": 1, "cls": "latency", "ctx": "aaa="},
    ]
    join, joined = obstrace.join_blocks(
        traces, obstrace.chain_spans(sidecar_spans))
    if joined:
        summary["segments"][obstrace.DEVICE_SEGMENT] = \
            obstrace.device_subsegment(joined)
    chrome = obstrace.chrome_trace(traces, sidecar_spans, joined=joined)
    decoded = _json.loads(_json.dumps(chrome))
    events = decoded.get("traceEvents", [])
    roundtrip_ok = (
        len(events) == len(chrome["traceEvents"])
        and all(e.get("ph") in ("X", "M") for e in events)
        and all(isinstance(e.get("ts", 0), (int, float)) for e in events)
        # the joined chain must land nested in the block's row
        and any(e.get("name") == "sidecar:device"
                and e.get("args", {}).get("block") == "aaa="
                for e in events))
    return {
        "blocks": summary["blocks"],
        "complete": summary["complete"],
        "segments": summary["segments"],
        "join": join,
        "join_rate": join["rate"],
        "chrome_events": len(events),
        "offset_applied_ms": round(offset * 1e3, 3),
        "roundtrip_ok": roundtrip_ok,
    }


def sched_headline_probe() -> dict:
    """Round-trip the verifysched STATS counters through the wire
    encoding and return the decoded snapshot for the headline's "sched"
    field: a host-mode VerifyEngine verifies one latency-class QC and one
    bulk-class batch through the real scheduler, then the snapshot goes
    protocol.encode_stats_reply -> decode_reply_raw -> decode_stats_body
    — the exact bytes a sidecar client would see."""
    import threading

    from hotstuff_tpu.sidecar import protocol as proto
    from hotstuff_tpu.sidecar import sched as vsched
    from hotstuff_tpu.sidecar.service import VerifyEngine

    msgs, pks, sigs = _make_ref_sigs(6, seed=23)
    engine = VerifyEngine(use_host=True)
    try:
        done = []
        cond = threading.Condition()

        def reply(mask):
            with cond:
                done.append(mask)
                cond.notify()

        engine.submit(proto.VerifyRequest(1, msgs[:4], pks[:4], sigs[:4]),
                      reply, cls=vsched.LATENCY)
        engine.submit(proto.VerifyRequest(2, msgs[4:], pks[4:], sigs[4:]),
                      reply, cls=vsched.BULK)
        with cond:
            cond.wait_for(lambda: len(done) == 2, timeout=60.0)
        frame = proto.encode_stats_reply(7, engine.stats_snapshot())
        opcode, rid, body = proto.decode_reply_raw(frame[4:])
        if (opcode, rid) != (proto.OP_STATS, 7):
            raise RuntimeError("stats reply framing mismatch")
        return proto.decode_stats_body(body)
    finally:
        engine.stop()


# --fault-plan/--wan/--slo pass-through (set by main(); run_degraded
# reads them so the degraded line carries the same chaos field as a
# healthy one).
_FAULT_PLAN = None
_WAN_SPEC = None
_SLO_SPEC = None

# Miniature default plan for the headline probe: one of every fault
# class — including a graftsurge flash crowd — timed inside a tenth of
# a (virtual) second.
_DEFAULT_CHAOS_SPEC = ("0.01 sidecar kill; 0.04 sidecar restart; "
                       "0.02 node:1 pause; 0.05 node:1 resume; "
                       "0.06 sidecar degrade shed=1; "
                       "0.07 client:0 surge x5 for 0.02")

# Miniature default WAN spec for the headline probe: one shaped
# node->sidecar link, small enough that the loopback proxy round trip
# stays in the tens of milliseconds.
_DEFAULT_WAN_SPEC = "node:0>sidecar latency_ms=5 name=probe-link"


def wan_headline_probe(wan_spec=None) -> dict:
    """The ``chaos.wan`` sub-field: prove the graftwan pipeline end to
    end without a committee or root.  The spec (--wan, or a miniature
    default) runs through the REAL parser, is compiled to the per-host
    ``tc netem`` command list a fleet run would install, and is then
    realized by a real loopback WanProxy: a byte round-trips through the
    shaped link (paying its latency both ways), ``partition()`` must
    black-hole a fresh connection, and ``heal()`` must restore it — the
    exact executors a live ``--wan`` run uses, local and remote."""
    import socket as _socket
    import threading as _threading

    from hotstuff_tpu.chaos import WanProxy, parse_wan
    from hotstuff_tpu.chaos.netem import tc_setup_commands

    spec = parse_wan(wan_spec if wan_spec else _DEFAULT_WAN_SPEC)
    peers = {"node:0": "10.0.0.10", "node:1": "10.0.0.11",
             "sidecar": "10.0.0.99"}
    tc_commands = sum(
        len(tc_setup_commands(spec, f"node:{i}", peers)) for i in range(2))

    # Loopback echo server the proxy forwards to.
    server = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
    server.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
    server.bind(("127.0.0.1", 0))
    server.listen(4)
    server.settimeout(10.0)

    def _echo():
        try:
            while True:
                conn, _ = server.accept()
                conn.settimeout(10.0)
                try:
                    data = conn.recv(64)
                    if data:
                        conn.sendall(data)
                finally:
                    conn.close()
        except OSError:
            pass

    _threading.Thread(target=_echo, daemon=True).start()
    shape = spec.links[0].shape if spec.links else None
    proxy = WanProxy(server.getsockname(), shape=shape)
    proxy.start()
    try:
        if not proxy.wait_ready(10.0):
            raise RuntimeError("WanProxy readiness gate never passed")
        def _roundtrip():
            with _socket.create_connection(("127.0.0.1", proxy.port),
                                           timeout=10.0) as c:
                c.settimeout(10.0)
                c.sendall(b"ping")
                return c.recv(64)

        def _try_roundtrip(attempts=5):
            # A lossy shape DROPS connections by design (see WanProxy);
            # a dialing peer just reconnects, so the probe does too.  A
            # spec lossy enough to defeat every attempt reports
            # ok/healed False rather than erroring the whole sub-field.
            # Returns the RTT of the one SUCCESSFUL attempt (None if
            # all fail): timing the whole retry loop would fold failed
            # dials and dropped attempts into the published number.
            for _ in range(attempts):
                try:
                    t0 = time.perf_counter()
                    if _roundtrip() == b"ping":
                        return (time.perf_counter() - t0) * 1e3
                except OSError:
                    pass
            return None

        rtt_ms = _try_roundtrip()
        proxy.partition()
        try:
            partitioned = _roundtrip() != b"ping"
        except OSError:
            partitioned = True  # dropped connection IS the black-hole
        proxy.heal()
        healed = _try_roundtrip() is not None
        return {
            "links": spec.link_names(),
            "tc_commands": tc_commands,
            "proxy_roundtrip_ms": round(rtt_ms, 3)
            if rtt_ms is not None else None,
            "roundtrip_ok": rtt_ms is not None,
            "partition_enforced": partitioned,
            "healed": healed,
        }
    finally:
        proxy.stop()
        server.close()


def chaos_headline_probe(plan_spec=None, wan_spec=None,
                         slo_spec=None) -> dict:
    """The headline's ``chaos`` field: prove the graftchaos pipeline end
    to end without booting a committee.  The fault plan (the passed
    ``--fault-plan``, or a miniature default) runs through the REAL
    parser and PlanRunner against a recording injector on a virtual
    clock (instant, regardless of the plan's timescale); the executed
    events round-trip through the JSON contract the harness writes to
    logs/chaos-events.json; and recovery latencies come from the same
    ``summarize_recovery`` the LogParser folds into a live run summary —
    commits are synthesized 250 ms after each event, so a healthy
    pipeline reports ``recovered: true`` with per-event latencies.

    graftwan: the recoveries are additionally judged against the
    per-fault-class SLO table (``slo`` sub-field, chaos/slo.judge — the
    same verdicts the LogParser raises on), and the WAN link-shape
    pipeline is proven by ``wan_headline_probe`` (``wan`` sub-field)."""
    import json as _json

    from hotstuff_tpu.chaos import PlanRunner, judge, parse_plan, \
        parse_slos, summarize_recovery

    plan = parse_plan(plan_spec if plan_spec else _DEFAULT_CHAOS_SPEC)

    class _NullInjector:
        def apply(self, event):
            pass  # the probe measures the pipeline, not real processes

    base_wall = 1_700_000_000.0
    now = [0.0]

    def clock():
        return now[0]

    def sleep(dt):
        now[0] += dt

    runner = PlanRunner(plan, _NullInjector(), clock=clock, sleep=sleep,
                        wall=lambda: base_wall + now[0])
    runner.start(t0=0.0)
    runner.join(timeout=60.0)
    # The on-disk/wire contract: what the harness persists is what the
    # parser reads back.
    events = _json.loads(_json.dumps(runner.events()))
    commits = [e["wall"] + 0.25 for e in events]
    summary = summarize_recovery(events, commits)
    slo_verdict = judge(summary, parse_slos(slo_spec))
    try:
        wan = wan_headline_probe(wan_spec)
    except Exception as e:  # noqa: BLE001 — sub-probe isolation
        wan = {"error": f"{e!r:.120}"}
    return {
        "plan_events": len(plan.events),
        "executed": len(events),
        "recovered": summary["recovered"],
        "injected_ok": summary["injected_ok"],
        "max_recovery_ms": summary["max_recovery_ms"],
        "events": summary["events"],
        "slo": slo_verdict,
        "wan": wan,
    }


def surge_headline_probe(offered_x: float = 4.0,
                         seconds: float = 3.0) -> dict:
    """The headline's ``surge`` field: prove the graftsurge overload
    pipeline end to end without booting a committee.

    A seeded heavy-tailed multi-user generator (harness/loadgen.py, the
    python twin of the C++ client's UserLoadModel) offers ``offered_x``
    times a modeled drain capacity of BULK verify work, plus a steady
    consensus-class stream, into the REAL verifysched scheduler with its
    REAL surge admission controller on a virtual clock.  Shed bulk
    requests feed BUSY backoff hints back into the generator — the full
    backpressure loop.  The probe then proves the OP_BUSY wire round
    trip (protocol v4 encode -> decode -> SidecarOverloaded with the
    retry hint attached) and the metrics-driven recovery-to-baseline SLO
    judge on a synthetic sampled series with a blackout.

    The acceptance bar rides in ``ok``: at >= 3x offered overload the
    consensus-class wait p99 stays bounded (no queue collapse), sheds
    are bulk-before-latency (zero latency sheds, zero fairness
    violations), and the surge event is judged PASS by the
    recovery-to-baseline judge."""
    from hotstuff_tpu.chaos import judge_baseline_recovery
    from hotstuff_tpu.harness.loadgen import UserLoad
    from hotstuff_tpu.sidecar import protocol as proto
    from hotstuff_tpu.sidecar import sched as vsched
    from hotstuff_tpu.sidecar.client import SidecarClient, \
        SidecarOverloaded

    TICK_S = 0.01
    CAP_SIGS_PER_TICK = 128       # modeled device drain per tick
    QC_SIGS = 16                  # one consensus verify
    LAT_PER_TICK = 2              # consensus offers per tick (well
                                  # under capacity: it must never shed)
    BULK_REQ_SIGS = 32
    cap_sigs_per_s = CAP_SIGS_PER_TICK / TICK_S
    bulk_req_rate = offered_x * cap_sigs_per_s / BULK_REQ_SIGS

    sched = vsched.Scheduler(latency_cap_sigs=4 * 1024,
                             bulk_cap_sigs=8 * 1024)
    # Coalesce at the modeled per-tick drain so launch granularity and
    # drain capacity speak the same units (the real engine's cap is the
    # compiled-shape budget; here the "device" IS the tick budget).
    sched.shapes.launch_cap = CAP_SIGS_PER_TICK
    adm = sched.admission
    load = UserLoad(rate=bulk_req_rate, users=200, seed=11)

    rid = [0]

    def request(n):
        rid[0] += 1
        recs = [rid[0].to_bytes(6, "big") + i.to_bytes(2, "big")
                for i in range(n)]
        return proto.VerifyRequest(rid[0], recs, recs, recs)

    offered_at = {}
    lat_waits = []
    lat_offered = bulk_offered = 0
    ticks = int(round(seconds / TICK_S))
    for k in range(1, ticks + 1):
        t = k * TICK_S
        for _ in range(LAT_PER_TICK):
            req = request(QC_SIGS)
            offered_at[req.request_id] = t
            lat_offered += 1
            sched.offer(req, lambda m: None, cls=vsched.LATENCY)
        for _ in range(load.arrivals(t)):
            bulk_offered += 1
            if not sched.offer(request(BULK_REQ_SIGS), lambda m: None,
                               cls=vsched.BULK):
                # The generator honors the BUSY hint: per-user backoff.
                load.busy(t, sched.retry_after_ms(vsched.BULK) / 1e3)
        budget = CAP_SIGS_PER_TICK
        while budget > 0:
            launch = sched.next_launch(block=False)
            if launch is None:
                break
            for p in launch.items:
                if p.cls == vsched.LATENCY:
                    lat_waits.append(
                        (t - offered_at.pop(p.request.request_id, t))
                        * 1e3)
            budget -= launch.total_sigs
            # Pipeline evidence for the derate controller: a tick whose
            # offered load exceeds drain capacity packs in the open
            # (overlap collapsed) — exactly the surge regime.
            adm.note_pack(0.001, hidden=offered_x <= 1.0)
    snap = adm.snapshot()
    lat_waits.sort()
    wait_p99 = lat_waits[int(0.99 * (len(lat_waits) - 1))] \
        if lat_waits else 0.0

    # OP_BUSY wire round trip: server encode -> client decode -> the
    # typed overload error with the retry hint attached.
    frame = proto.encode_busy_reply(9, 137)
    opcode, brid, body = proto.decode_reply_raw(frame[4:])
    try:
        SidecarClient._unwrap(opcode, body)
        busy_ok, hint = False, None
    except SidecarOverloaded as e:
        hint = e.retry_after_ms
        busy_ok = brid == 9 and hint == 137

    # Metrics-driven recovery-to-baseline judge on a synthetic series:
    # steady 1000 sigs/s, a surge-window blackout, then recovery.
    base_wall = 1_700_000_000.0
    samples = []
    launched = 0
    for s in range(31):
        t = base_wall + s
        if 10 <= s < 13:
            samples.append({"t": t, "ok": False, "error": "surge"})
            continue
        launched += 1000
        samples.append({"t": t, "ok": True,
                        "stats": {"sigs_launched": launched}})
    surge_event = {"t": 10.0, "target": "client:0", "action": "surge",
                   "wall": base_wall + 10, "ok": True,
                   "params": {"x": 5, "for": 3}}
    baseline = judge_baseline_recovery(samples, [surge_event])

    ok = (offered_x >= 3.0
          and wait_p99 <= 3 * TICK_S * 1e3
          and snap["shed"].get(vsched.LATENCY, 0) == 0
          and snap["shed"].get(vsched.BULK, 0) > 0
          and snap["fairness_violations"] == 0
          and busy_ok
          and baseline["ok"] and baseline["judged"] == 1)
    return {
        "offered_x": offered_x,
        "ticks": ticks,
        "latency": {
            "offered": lat_offered,
            "shed": snap["shed"].get(vsched.LATENCY, 0),
            "wait_p99_ms": round(wait_p99, 3),
        },
        "bulk": {
            "offered": bulk_offered,
            "admitted": snap["admitted"].get(vsched.BULK, 0),
            "shed": snap["shed"].get(vsched.BULK, 0),
            "deferred_by_busy": load.deferred,
        },
        "fairness_violations": snap["fairness_violations"],
        "bulk_before_latency": snap["shed"].get(vsched.LATENCY, 0) == 0,
        "derate": snap["derate"],
        "busy_roundtrip": {"ok": busy_ok, "retry_after_ms": hint},
        "baseline_slo": baseline,
        "ok": ok,
    }


def guard_headline_probe() -> dict:
    """The headline's ``guard`` field: prove the graftguard wedge ->
    recover ladder end to end without a device.

    A host-mode VerifyEngine runs under a REAL LaunchGuard whose
    deadlines are tiny (tens of milliseconds — the virtual-clock
    equivalent for a monitor that must actually preempt a hung thread),
    and the chaos hook's ``wedge`` knob hangs the next launch exactly
    as a ``sidecar wedge`` fault-plan event does over OP_CHAOS.  The
    probe asserts the full ladder: the wedged latency batch is answered
    with a mask BIT-IDENTICAL to ``verify_batch`` (one tampered
    signature pins the comparison), bulk offered during the crash-only
    reboot is shed to BUSY with a retry-after hint, the injected rewarm
    runs, the canary passes, and device routing resumes with the guard
    counters (wedges / reboots / quarantine / canary) accounting for
    all of it.  The acceptance bar rides in ``ok``.  Emitted on BOTH
    the live and degraded JSON lines."""
    import threading

    from hotstuff_tpu.crypto import eddsa
    from hotstuff_tpu.sidecar import protocol as proto
    from hotstuff_tpu.sidecar import sched as vsched
    from hotstuff_tpu.sidecar.guard import LaunchDeadlines, LaunchGuard
    from hotstuff_tpu.sidecar.service import ChaosState, VerifyEngine

    msgs, pks, sigs = _make_ref_sigs(8, seed=31)
    sigs = list(sigs)
    sigs[3] = sigs[3][:1] + bytes([sigs[3][1] ^ 0xFF]) + sigs[3][2:]
    chaos = ChaosState()
    # warm launch deadlines at 0.2 s (the injected hang is infinite, so
    # any deadline catches it fast); the compile-class budget — which
    # the reboot canary always gets — stays generous so a contended
    # host can never false-wedge the recovery the probe asserts on.
    guard = LaunchGuard(deadlines=LaunchDeadlines(
        warm_boot=True, compile_budget_s=5.0, warm_grace_s=0.2,
        min_deadline_s=0.05))
    rewarmed = []

    def rewarm():
        rewarmed.append(1)
        time.sleep(0.2)  # an observable reboot window for the BUSY leg

    engine = VerifyEngine(use_host=True, guard=guard, chaos=chaos,
                          rewarm_fn=rewarm)
    try:
        done = {}
        cond = threading.Condition()

        def reply_to(rid):
            def _reply(mask):
                with cond:
                    done[rid] = mask
                    cond.notify_all()
            return _reply

        expect = [bool(b) for b in eddsa.verify_batch(msgs, pks, sigs)]
        chaos.configure({"wedge": 1})
        engine.submit(proto.VerifyRequest(1, msgs, pks, sigs),
                      reply_to(1), cls=vsched.LATENCY)
        with cond:
            cond.wait_for(lambda: 1 in done, timeout=30.0)
        # Bulk offered while the engine re-warms must shed to BUSY.
        busy_shed = None
        t0 = time.monotonic()
        while time.monotonic() - t0 < 5.0:
            if engine._rebooting:
                busy_shed = not engine.submit(
                    proto.VerifyRequest(2, msgs, pks, sigs),
                    reply_to(2), cls=vsched.BULK)
                break
            time.sleep(0.002)
        retry_ms = engine.retry_after_ms(vsched.BULK)
        t0 = time.monotonic()
        while (engine._rebooting or not engine._device_ok) \
                and time.monotonic() - t0 < 20.0:
            time.sleep(0.01)
        engine.submit(proto.VerifyRequest(3, msgs, pks, sigs),
                      reply_to(3), cls=vsched.LATENCY)
        with cond:
            cond.wait_for(lambda: 3 in done, timeout=30.0)
        snap = engine.stats_snapshot().get("guard", {})
        masks_ok = done.get(1) == expect and done.get(3) == expect
        recovered = bool(snap.get("device_ok")) \
            and not snap.get("rebooting")
        ok = (masks_ok and busy_shed is True and bool(rewarmed)
              and snap.get("wedges", 0) >= 1
              and snap.get("reboots", 0) >= 1
              and snap.get("canary_passes", 0) >= 1
              and snap.get("suspect_records", 0) >= 1
              and recovered)
        return {
            "wedges": snap.get("wedges", 0),
            "reboots": snap.get("reboots", 0),
            "canary_passes": snap.get("canary_passes", 0),
            "quarantined_records": snap.get("suspect_records", 0),
            "poisoned_records": snap.get("poisoned_records", 0),
            "host_fallback_records": snap.get("host_fallback_records", 0),
            "busy_during_reboot": busy_shed,
            "busy_retry_after_ms": retry_ms,
            "masks_bit_identical": masks_ok,
            "rewarmed": bool(rewarmed),
            "reboot_wall_ms": round(
                snap.get("last_reboot_wall_s", 0.0) * 1e3, 1),
            "recovered": recovered,
            "ok": ok,
        }
    finally:
        engine.stop()
        guard.close()


def fleet_headline_probe(window_s: float = 0.8) -> dict:
    """The headline's ``fleet`` field: graftfleet goodput across a
    kill-primary failover plus a seeded greedy-tenant flood, in-process
    and host-mode (no device, no subprocesses).

    Two REAL SidecarServers front two REAL host-mode VerifyEngines; a
    sticky endpoint ladder (the python twin of the C++ TpuVerifier's
    ordered list) drives tenant-tagged verify traffic at the primary,
    the primary is killed mid-run, and the ladder re-homes to the
    survivor — goodput is measured on both sides of the kill, every
    reply held bit-identical to the reference (one tampered signature
    pins the comparison), and the host rung must never fire while a
    fleet member is alive.  A second tenant then replays the SAME
    records at the survivor (cross-tenant verdict-cache sharing: the QC
    gossiped to N replicas is verified once fleet-wide), and a seeded
    greedy-tenant flood runs against the survivor with the REAL
    LogParser holding the strict verdict — ``tenant_starvation == 0``
    and the victim's queue-wait p99 within the 2x bound.  The
    acceptance bar rides in ``ok``.  Emitted on BOTH the live and
    degraded JSON lines."""
    import threading

    from hotstuff_tpu.sidecar.client import SidecarClient
    from hotstuff_tpu.sidecar.service import SidecarServer, VerifyEngine

    # A pool of distinct reference batches, each with one tampered
    # signature so the expected mask is never the trivial all-True.
    POOL, BATCH = 6, 16
    pool, expects = [], []
    for k in range(POOL):
        msgs, pks, sigs = _make_ref_sigs(BATCH, seed=700 + k)
        sigs = list(sigs)
        sigs[k % BATCH] = (sigs[k % BATCH][:1]
                           + bytes([sigs[k % BATCH][1] ^ 0xFF])
                           + sigs[k % BATCH][2:])
        pool.append((msgs, pks, sigs))
        expects.append([i != (k % BATCH) for i in range(BATCH)])

    servers = []
    for _ in range(2):
        eng = VerifyEngine(use_host=True)
        srv = SidecarServer(("127.0.0.1", 0), eng)
        threading.Thread(target=srv.serve_forever,
                         kwargs=dict(poll_interval=0.05),
                         daemon=True).start()
        servers.append((srv, eng))
    ports = [srv.server_address[1] for srv, _ in servers]

    class _Ladder:
        """Sticky-until-unhealthy ordered endpoint list; host path is
        the LAST rung and counts as a fallback, never a peer."""

        def __init__(self, tenant):
            self.tenant = tenant
            self.active = 0
            self.rehomes = 0
            self.host_fallbacks = 0
            self._clients = {}

        def _client(self, ix):
            c = self._clients.get(ix)
            if c is None:
                c = SidecarClient(port=ports[ix], timeout=5.0)
                c.hello(self.tenant)
                self._clients[ix] = c
            return c

        def drop(self, ix):
            c = self._clients.pop(ix, None)
            if c is not None:
                try:
                    c.close()
                except OSError:
                    pass

        def verify(self, msgs, pks, sigs):
            while self.active < len(ports):
                try:
                    return self._client(self.active).verify_batch(
                        msgs, pks, sigs)
                except OSError:
                    self.drop(self.active)
                    self.active += 1
                    self.rehomes += 1
            self.host_fallbacks += 1
            from hotstuff_tpu.crypto import eddsa
            return [bool(b) for b in
                    eddsa.verify_batch(msgs, pks, sigs)]

        def close(self):
            for ix in list(self._clients):
                self.drop(ix)

    killed = [False]

    def kill_primary():
        srv0, eng0 = servers[0]
        srv0.shutdown()
        eng0.stop()
        srv0.server_close()
        killed[0] = True

    ladder = _Ladder("replica-0")
    masks_ok = True
    try:
        # -- live phase: tenant-tagged goodput at the primary ----------
        live_sigs, i = 0, 0
        t0 = time.monotonic()
        while time.monotonic() - t0 < window_s:
            m, p, s = pool[i % POOL]
            masks_ok &= ladder.verify(m, p, s) == expects[i % POOL]
            live_sigs += BATCH
            i += 1
        live_goodput = live_sigs / max(time.monotonic() - t0, 1e-9)

        # -- kill the primary mid-run ----------------------------------
        # In-process stand-in for SIGKILL: the listener closes AND the
        # established connection dies (the OS closes a dead process's
        # sockets), so the ladder's next send surfaces a transport
        # error and re-homes.  The C++ in-flight-resubmit leg is
        # covered natively (test_crypto: sidecar_fleet_failover).
        t_kill = time.monotonic()
        kill_primary()
        ladder.drop(0)

        # -- failover phase: goodput on the survivor -------------------
        m, p, s = pool[0]
        masks_ok &= ladder.verify(m, p, s) == expects[0]
        rehome_ms = (time.monotonic() - t_kill) * 1e3
        fo_sigs, i = BATCH, 1
        t1 = time.monotonic()
        while time.monotonic() - t1 < window_s:
            m, p, s = pool[i % POOL]
            masks_ok &= ladder.verify(m, p, s) == expects[i % POOL]
            fo_sigs += BATCH
            i += 1
        fo_goodput = fo_sigs / max(time.monotonic() - t1, 1e-9)

        # -- cross-tenant dedup at the survivor ------------------------
        with SidecarClient(port=ports[1], timeout=5.0) as peer:
            peer.hello("replica-1")
            for k in range(POOL):
                m, p, s = pool[k]
                masks_ok &= peer.verify_batch(m, p, s) == expects[k]
        survivor = servers[1][1]
        dedup = survivor.stats_snapshot().get("dedup", {})

        # -- seeded greedy-tenant flood at the survivor ----------------
        flood = _fleet_flood(ports[1], survivor)

        ok = (masks_ok
              and ladder.rehomes >= 1
              and ladder.host_fallbacks == 0
              and ladder.active == 1
              and live_goodput > 0 and fo_goodput > 0
              and dedup.get("hit_rate", 0) > 0
              and flood.get("ok") is True)
        return {
            "endpoints": 2,
            "live_goodput_sigs_per_s": round(live_goodput, 1),
            "failover_goodput_sigs_per_s": round(fo_goodput, 1),
            "rehome_ms": round(rehome_ms, 1),
            "rehomes": ladder.rehomes,
            "host_fallbacks": ladder.host_fallbacks,
            "active_endpoint": ladder.active,
            "masks_bit_identical": masks_ok,
            "dedup": {"cache_hits": dedup.get("cache_hits", 0),
                      "hit_rate": dedup.get("hit_rate", 0.0)},
            "flood": flood,
            "ok": ok,
        }
    finally:
        ladder.close()
        for ix, (srv, eng) in enumerate(servers):
            if ix == 0 and killed[0]:
                continue
            srv.shutdown()
            eng.stop()
            srv.server_close()


# Minimal golden log pair for the fleet probe's LogParser verdict: the
# parser refuses empty inputs by contract, and the flood judge only
# needs its constructor to succeed — these are the shortest client/node
# logs it accepts (start line + node config + one commit).
_FLEET_GOLDEN_CLIENT = """\
[2026-07-29T14:54:56.456Z INFO client] Transactions size: 512 B
[2026-07-29T14:54:56.456Z INFO client] Transactions rate: 2000 tx/s
[2026-07-29T14:54:56.525Z INFO client] Start sending transactions
"""
_FLEET_GOLDEN_NODE = """\
[2026-07-29T14:54:55.100Z INFO mempool::config] Garbage collection depth set to 50 rounds
[2026-07-29T14:54:55.100Z INFO mempool::config] Sync retry delay set to 5000 ms
[2026-07-29T14:54:55.100Z INFO mempool::config] Sync retry nodes set to 3 nodes
[2026-07-29T14:54:55.100Z INFO mempool::config] Batch size set to 15000 B
[2026-07-29T14:54:55.100Z INFO mempool::config] Max batch delay set to 100 ms
[2026-07-29T14:54:55.101Z INFO consensus::config] Timeout delay set to 1000 ms
[2026-07-29T14:54:55.101Z INFO consensus::config] Sync retry delay set to 10000 ms
[2026-07-29T14:54:57.000Z INFO consensus::core] Committed B2
"""


def _fleet_flood(port: int, engine, pre_s: float = 0.8,
                 flood_s: float = 1.2) -> dict:
    """Seeded greedy-tenant flood leg of the ``fleet`` headline: a
    victim tenant keeps a small latency-class cadence while a greedy
    tenant floods bulk batches; the per-tenant DRR quantum and
    admission caps must keep the victim's queue-wait p99 within the
    strict 2x bound with ZERO starvation events — judged by the REAL
    LogParser verdict (``note_tenant_flood``), same as the chaos
    drill."""
    import threading

    from hotstuff_tpu.harness.logs import LogParser
    from hotstuff_tpu.sidecar.client import SidecarClient, \
        SidecarOverloaded

    # One reference batch per role; per-iteration msg mutation keeps
    # every record UNIQUE (so the verdict-cache fast path never
    # short-circuits the queue this leg is measuring) while pks stay
    # valid curve points — full verify work, masks all-False.
    vm, vp, vs = _make_ref_sigs(4, seed=881)
    gm, gp, gs = _make_ref_sigs(32, seed=887)
    errors = []

    def _mut(msgs, tag, i):
        return [tag + i.to_bytes(4, "big") + j.to_bytes(4, "big")
                + m[12:] for j, m in enumerate(msgs)]

    def victim(stop, period_s=0.005):
        try:
            with SidecarClient(port=port, timeout=30.0) as c:
                c.hello("victim")
                i = 0
                while not stop.is_set():
                    mask = c.verify_batch(_mut(vm, b"vict", i), vp, vs)
                    assert len(mask) == len(vm)
                    i += 1
                    time.sleep(period_s)
        except Exception as e:  # noqa: BLE001 — surfaced in the verdict
            errors.append(repr(e))

    def greedy(stop, seed):
        try:
            with SidecarClient(port=port, timeout=30.0) as c:
                c.hello("greedy")
                i = 0
                while not stop.is_set():
                    try:
                        c.verify_batch(_mut(gm, b"gr%02d" % seed, i),
                                       gp, gs)
                    except SidecarOverloaded:
                        time.sleep(0.002)  # honor the tenant-cap BUSY
                    i += 1
        except Exception as e:  # noqa: BLE001 — surfaced in the verdict
            errors.append(repr(e))

    def _phase(n_greedy, seconds, base_seed):
        stop = threading.Event()
        threads = [threading.Thread(target=victim, args=(stop,),
                                    daemon=True)]
        threads += [threading.Thread(target=greedy,
                                     args=(stop, base_seed + k),
                                     daemon=True)
                    for k in range(n_greedy)]
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=30.0)
        return json.loads(json.dumps(engine.stats_snapshot()))

    pre = _phase(1, pre_s, 1)
    post = _phase(3, flood_s, 2)
    if errors:
        return {"ok": False, "errors": errors[:3]}

    parser = LogParser([_FLEET_GOLDEN_CLIENT], [_FLEET_GOLDEN_NODE],
                       faults=0)
    try:
        parser.note_tenant_flood(pre, post, "victim", strict=True)
    except Exception as e:  # noqa: BLE001 — strict ParseError -> not ok
        return {"ok": False, "error": f"{e!r:.200}",
                "verdict": getattr(parser, "tenant_flood", None)}
    verdict = dict(parser.tenant_flood or {})
    verdict["ok"] = bool(verdict.get("ok")) and bool(verdict.get("judged"))
    return verdict


def cadence_probe(n_devices: int = 8, budget_s: float = 240.0) -> dict:
    """Child half of the ``cadence`` headline (graftcadence): ring vs
    staged sigs/sec at a FIXED offered load, swept across ring depth
    k in {2, 4, 8} (knob hygiene: the trained depth-k supersedes the
    staged depth-2 constant, and this sweep is where a measurement pin
    would come from), queue-wait p99 from the OP_STATS ``cadence``
    section under a seeded surge-style load through the REAL cadence
    engine, and the mesh leg: ``ring_slot_pack`` — the pre-donated
    fixed-shape resident entry a mesh ring slot arms — proven
    bit-identical to ``verify_batch`` on the forced-host n-device mesh.

    The engine legs run host-mode (pure-python reference verify), so
    ring-vs-staged numbers measure PIPELINE overheads honestly relative
    to each other but are never comparable to device throughput.  The
    acceptance bar rides in ``ok``: staged stays the default (a
    default-built engine has no ring), every reply bit-identical to the
    reference (one tampered signature pins the comparison), every
    cadence dispatch guard-supervised under the ``tick:`` deadline
    class, queue-wait percentiles present, and the mesh slot
    bit-identical.  Prints one JSON progress line per completed leg
    (the parent salvages partials) and returns the dict."""
    import threading

    from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
    from hotstuff_tpu.harness.loadgen import UserLoad
    from hotstuff_tpu.parallel import sharded_verify as shv
    from hotstuff_tpu.parallel.mesh import make_mesh
    from hotstuff_tpu.sidecar import protocol as proto
    from hotstuff_tpu.sidecar import sched as vsched
    from hotstuff_tpu.sidecar.guard import LaunchDeadlines, LaunchGuard
    from hotstuff_tpu.sidecar.ring import CadenceRing, RingDepth
    from hotstuff_tpu.sidecar.service import VerifyEngine
    from hotstuff_tpu.utils.xla_cache import configure_xla_cache

    t0 = time.perf_counter()
    out = {"n_devices": n_devices}

    def emit_progress():
        print(json.dumps({"cadence": out}), flush=True)

    # Fixed offered load shared by every engine leg: REQS requests of
    # REQ_SIGS records, one tampered signature pinning the bit-identity
    # comparison on every single reply.
    REQS, REQ_SIGS = 12, 8
    msgs, pks, sigs = _make_ref_sigs(REQ_SIGS, seed=41)
    sigs = list(sigs)
    sigs[3] = sigs[3][:1] + bytes([sigs[3][1] ^ 0xFF]) + sigs[3][2:]
    expect = [bool(ref.verify(pk, m, s))
              for m, pk, s in zip(msgs, pks, sigs)]

    def drive(engine):
        """Submit the fixed load, wait out every reply; (sigs/s, ok)."""
        done = {}
        cond = threading.Condition()

        def reply_to(rid):
            def _reply(mask):
                with cond:
                    done.setdefault(rid, []).append(mask)
                    cond.notify_all()
            return _reply

        t = time.perf_counter()
        for rid in range(1, REQS + 1):
            engine.submit(proto.VerifyRequest(rid, msgs, pks, sigs),
                          reply_to(rid), cls=vsched.LATENCY)
        with cond:
            cond.wait_for(lambda: len(done) == REQS, timeout=120.0)
        dt = time.perf_counter() - t
        masks_ok = (len(done) == REQS
                    and all(v == [expect] for v in done.values()))
        return round(REQS * REQ_SIGS / dt, 1), masks_ok

    # Staged stays the DEFAULT: a default-built engine has no ring; the
    # ring engages only behind --cadence / HOTSTUFF_TPU_CADENCE.
    probe_engine = VerifyEngine(use_host=True)
    staged_default = probe_engine._ring is None
    probe_engine.stop()
    out["staged_default"] = staged_default

    masks = {}
    eng = VerifyEngine(use_host=True)
    try:
        rate, masks["staged"] = drive(eng)
    finally:
        eng.stop()
    out["staged_sigs_per_s"] = rate
    emit_progress()

    tick_supervised = True
    for k in RingDepth.DEPTHS:
        if time.perf_counter() - t0 > budget_s:
            out[f"ring_k{k}"] = {"skipped": True}
            continue
        guard = LaunchGuard(deadlines=LaunchDeadlines(warm_boot=True))
        eng = VerifyEngine(
            use_host=True, guard=guard,
            ring_factory=lambda e, k=k: CadenceRing(
                e, depth=RingDepth(pinned=k)))
        try:
            rate, masks[f"ring_k{k}"] = drive(eng)
            snap = eng.stats_snapshot()["cadence"]
            deadlines = guard.snapshot()["deadlines"]
        finally:
            eng.stop()
            guard.close()
        # Supervision evidence: the guard's deadline trainer saw the
        # tick class — every cadence dispatch went through guard.call.
        ticked = any(dkey.startswith("tick:") and v.get("n", 0) >= 1
                     for dkey, v in deadlines.items())
        tick_supervised = tick_supervised and ticked
        out[f"ring_k{k}"] = {
            "sigs_per_s": rate,
            "dispatch_ticks": snap["dispatch_ticks"],
            "tick_rate_hz": snap["tick_rate_hz"],
            "pad_fill_ratio": snap["pad_fill"]["ratio"],
            "queue_wait_p99_ms": snap["queue_wait"]["p99_ms"],
            "generation_drops": snap["generation"]["drops"],
            "guard_tick_launches": ticked,
        }
        emit_progress()
    out["tick_launches_supervised"] = tick_supervised

    # Queue-wait p99 under the seeded surge-style plan: the loadgen's
    # heavy-tailed multi-user generator (the surge headline's seeded
    # twin of the C++ client's UserLoadModel) offers bulk bursts over a
    # steady consensus-class stream into the REAL cadence engine, BUSY
    # backoff honored; the reported percentiles are the OP_STATS
    # ``cadence.queue_wait`` reservoir — admission to cadence dispatch.
    if time.perf_counter() - t0 > budget_s:
        out["surge_wait"] = {"skipped": True}
    else:
        guard = LaunchGuard(deadlines=LaunchDeadlines(warm_boot=True))
        eng = VerifyEngine(
            use_host=True, guard=guard,
            ring_factory=lambda e: CadenceRing(
                e, depth=RingDepth(pinned=4)))
        try:
            done = []
            cond = threading.Condition()

            def _reply(mask):
                with cond:
                    done.append(1)
                    cond.notify_all()

            load = UserLoad(rate=40.0, users=50, seed=11)
            TICK_S, TICKS = 0.02, 25
            rid = 1000
            accepted = 0
            t_load = time.perf_counter()
            for i in range(1, TICKS + 1):
                t_rel = i * TICK_S
                rid += 1
                accepted += 1
                eng.submit(proto.VerifyRequest(rid, msgs, pks, sigs),
                           _reply, cls=vsched.LATENCY)
                for _ in range(load.arrivals(t_rel)):
                    rid += 1
                    if eng.submit(
                            proto.VerifyRequest(rid, msgs, pks, sigs),
                            _reply, cls=vsched.BULK):
                        accepted += 1
                    else:
                        load.busy(t_rel,
                                  eng.retry_after_ms(vsched.BULK) / 1e3)
                sleep_left = t_load + t_rel - time.perf_counter()
                if sleep_left > 0:
                    time.sleep(sleep_left)
            with cond:
                cond.wait_for(lambda: len(done) >= accepted,
                              timeout=120.0)
            snap = eng.stats_snapshot()["cadence"]
        finally:
            eng.stop()
            guard.close()
        out["surge_wait"] = {
            "accepted_reqs": accepted,
            "answered": len(done),
            "deferred_by_busy": load.deferred,
            "queue_wait_p50_ms": snap["queue_wait"]["p50_ms"],
            "queue_wait_p99_ms": snap["queue_wait"]["p99_ms"],
            "occupancy_hist": snap["occupancy_hist"],
        }
        emit_progress()

    # Mesh leg: the fixed-shape pre-donated resident entry a mesh ring
    # slot arms (parallel.sharded_verify.ring_slot_pack), bit-identical
    # to verify_batch on the forced-host n-device mesh.
    if time.perf_counter() - t0 > budget_s:
        out["mesh_ring_slot"] = {"skipped": True}
    else:
        try:
            configure_xla_cache()
            mesh = make_mesh(n_devices)
            n = 16
            mm, mp, ms = _make_ref_sigs(n, seed=43)
            ms = list(ms)
            ms[5] = ms[5][:1] + bytes([ms[5][1] ^ 0xFF]) + ms[5][2:]
            want = [bool(b) for b in eddsa.verify_batch(mm, mp, ms)]
            rows = shv.shard_aligned_rows(n, n_devices,
                                          eddsa.MAX_SUBBATCH)
            prep = eddsa.prepare_batch(mm, mp, ms)
            got = [bool(b)
                   for b in shv.ring_slot_pack(mesh, prep, rows)()()]
            out["mesh_ring_slot"] = {"rows": rows,
                                     "bit_identical": got == want}
        except Exception as e:  # noqa: BLE001 — leg isolation
            out["mesh_ring_slot"] = {"error": f"{e!r:.160}"}
        emit_progress()

    masks_ok = bool(masks) and all(masks.values())
    ring_rates = [v.get("sigs_per_s", 0.0) for kk, v in out.items()
                  if kk.startswith("ring_k") and isinstance(v, dict)
                  and not v.get("skipped")]
    sw = out.get("surge_wait", {})
    wait_ok = bool(sw.get("skipped")) or \
        sw.get("queue_wait_p99_ms") is not None
    mr = out.get("mesh_ring_slot", {})
    mesh_ok = bool(mr.get("skipped")) or mr.get("bit_identical") is True
    out["masks_bit_identical"] = masks_ok
    out["ok"] = (staged_default and masks_ok and tick_supervised
                 and bool(ring_rates)
                 and all(r > 0 for r in ring_rates)
                 and wait_ok and mesh_ok)
    emit_progress()
    return out


def cadence_headline(n_devices: int = 8,
                     budget_s: float | None = None) -> dict:
    """Parent half of the ``cadence`` headline field (graftcadence,
    ROADMAP item 6): run :func:`cadence_probe` on the forced-host CPU
    mesh (see :func:`_forced_host_mesh_headline` for the subprocess
    contract; HOTSTUFF_TPU_CADENCE_BUDGET seconds, default 240, bounds
    the stage).  Emitted on BOTH the live and degraded lines."""
    if budget_s is None:
        budget_s = float(
            os.environ.get("HOTSTUFF_TPU_CADENCE_BUDGET", "240"))
    return _forced_host_mesh_headline(
        "cadence", f"cadence_probe({n_devices}, budget_s={budget_s})",
        n_devices, budget_s)


def users_headline_probe(populations=(100_000, 1_000_000),
                         txs_per_point: int = 96,
                         budget_s: float | None = None) -> dict:
    """The headline ``users`` field (graftingress): the signed ingress
    tier at user-population scale, end to end in process.

    Per population U the seeded generator names which user each arrival
    belongs to (``UserLoad.arrivals(out_users=...)`` — the same contract
    the C++ client's UserLoadModel grew), the probe derives that user's
    Ed25519 keypair on FIRST arrival through the bounded
    ``txsign.UserKeyring`` LRU, builds version-2 signed frames with a
    seeded ~1% forgery mix (at least one forged frame per point, so the
    rejection rate is always a measured number), turns each frame into
    its admission (digest, pk, sig) record, and submits QC-shaped
    batches to a host-mode VerifyEngine as INGRESS_CTX-tagged bulk
    requests — the exact class + ctx tag the mempool admission-verify
    stage uses, so the engine's OP_STATS ``ingress`` section must report
    the lane 100% ingress-fed.  Key generation and signing run OUTSIDE
    the timed region; ``verified_goodput_sigs_per_s`` times only the
    verify drive (host-mode reference verify: honest relative to the
    other points, never comparable to device throughput).

    Populations that miss ``budget_s`` report ``{"skipped": true}``.
    Acceptance bar in ``ok``: every forged frame rejected, every honest
    frame verified, goodput positive, and the bulk lane fully
    ingress-fed on every completed point."""
    import random
    import threading

    from hotstuff_tpu.crypto import txsign
    from hotstuff_tpu.harness.loadgen import UserLoad
    from hotstuff_tpu.sidecar import protocol as proto
    from hotstuff_tpu.sidecar import sched as vsched
    from hotstuff_tpu.sidecar.service import VerifyEngine

    if budget_s is None:
        budget_s = float(
            os.environ.get("HOTSTUFF_TPU_USERS_BUDGET", "240"))
    t0 = time.perf_counter()
    out = {"mix_forge_pct": 1.0, "txs_per_point": txs_per_point}
    BATCH = 32

    for pop in populations:
        key = f"u{pop}"
        if time.perf_counter() - t0 > budget_s:
            out[key] = {"skipped": True}
            continue
        # Arrival stream on a virtual clock: with U users at a fixed
        # aggregate rate, a short window touches ~txs_per_point DISTINCT
        # users (per-user gaps are U/rate seconds) — the population knob
        # stresses the key-derivation path, not the verify path.
        load = UserLoad(rate=64.0, users=pop, seed=13)
        arrivals: list = []
        tick = 0
        while len(arrivals) < txs_per_point and tick < 4096:
            tick += 1
            load.arrivals(tick * 0.025, arrivals)
        arrivals = arrivals[:txs_per_point]
        keyring = txsign.UserKeyring(seed=7, capacity=4096)
        mix = random.Random(2024 + pop)
        frames, forged = [], []
        for i, user in enumerate(arrivals):
            forge = mix.random() < 0.01
            marker = (txsign.TX_MARKER_FORGED if forge
                      else txsign.TX_MARKER_FILLER)
            frames.append(txsign.build_signed_tx(
                keyring.get(user), nonce=i,
                payload=txsign.build_payload(marker, i),
                flip_sig_bit=forge))
            forged.append(forge)
        if not any(forged):  # seeded mix, floored at one forged frame
            frames[-1] = txsign.build_signed_tx(
                keyring.get(arrivals[-1]), nonce=len(arrivals) - 1,
                payload=txsign.build_payload(
                    txsign.TX_MARKER_FORGED, len(arrivals) - 1),
                flip_sig_bit=True)
            forged[-1] = True
        records = [txsign.admission_record(f) for f in frames]

        masks: dict = {}
        cond = threading.Condition()

        def reply_to(rid, masks=masks, cond=cond):
            def _reply(mask):
                with cond:
                    masks[rid] = mask
                    cond.notify_all()
            return _reply

        eng = VerifyEngine(use_host=True)
        busy_rejected = 0
        try:
            t_drive = time.perf_counter()
            rids = []
            for b in range(0, len(records), BATCH):
                chunk = records[b:b + BATCH]
                rid = 1 + b // BATCH
                req = proto.VerifyRequest(
                    rid,
                    [r[0] for r in chunk], [r[1] for r in chunk],
                    [r[2] for r in chunk], ctx=txsign.INGRESS_CTX)
                for attempt in range(8):
                    if eng.submit(req, reply_to(rid), cls=vsched.BULK):
                        rids.append(rid)
                        break
                    busy_rejected += 1
                    time.sleep(eng.retry_after_ms(vsched.BULK) / 1e3)
            with cond:
                cond.wait_for(
                    lambda: all(r in masks for r in rids), timeout=120.0)
            dt = time.perf_counter() - t_drive
            snap = eng.stats_snapshot().get("ingress", {})
        finally:
            eng.stop()

        flat = []
        for rid in rids:
            flat.extend(masks.get(rid) or [])
        answered = len(flat)
        verified = sum(1 for ok, f in zip(flat, forged) if ok and not f)
        forged_sent = sum(forged)
        forged_rejected = sum(
            1 for ok, f in zip(flat, forged) if f and not ok)
        honest = len(frames) - forged_sent
        total_bulk_sigs = (snap.get("bulk_sigs", 0)
                           + snap.get("offchain_sigs", 0))
        out[key] = {
            "users": pop,
            "txs": len(frames),
            "distinct_users": len(set(arrivals)),
            "key_derivations": keyring.derivations,
            "keyring_capacity": keyring.capacity,
            "forged_sent": forged_sent,
            "forged_rejected": forged_rejected,
            "forgery_rejection_rate": round(
                forged_rejected / forged_sent, 3) if forged_sent else 0.0,
            "verified": verified,
            "verified_goodput_sigs_per_s": round(verified / dt, 1)
            if dt > 0 else 0.0,
            "busy_rejected": busy_rejected,
            "bulk_ingress_requests": snap.get("bulk_requests", 0),
            "bulk_ingress_sigs": snap.get("bulk_sigs", 0),
            "bulk_ingress_share": round(
                snap.get("bulk_sigs", 0) / total_bulk_sigs, 3)
            if total_bulk_sigs else 0.0,
            "answered": answered,
            "point_ok": (answered == len(frames)
                         and verified == honest
                         and forged_rejected == forged_sent
                         and snap.get("bulk_sigs", 0) == total_bulk_sigs
                         > 0),
        }
    done = [v for k, v in out.items()
            if k.startswith("u") and isinstance(v, dict)
            and not v.get("skipped")]
    out["ok"] = bool(done) and all(v["point_ok"] for v in done)
    return out


def viewchange_headline(committees=(20, 100, 300), repeats: int = 2,
                        budget_s: float | None = None) -> dict:
    """The headline ``viewchange`` field (graftview): batched vs
    per-signature TC assembly latency at committee sizes N.

    Per committee, the quorum's (2N/3+1) timeout votes of one view
    change — every vote signing the SHARED (round, high_qc_round)
    digest, the QC-shaped batch the consensus core now dispatches as ONE
    sidecar launch — are verified two ways: one signature at a time
    through the pure-python reference verifier (the per-sender host path
    the old handle_timeout ran inline, the N=100 fault-path wall), and
    as one eddsa.verify_batch launch.  The probe also proves the EJECT
    contract once per run: a tampered candidate fails the batch, and the
    per-signature fallback identifies EXACTLY the signers per-signature
    verification rejects (the accept/reject set equivalence the native
    test pins, re-proven through the python engine).

    Budget-capped like every stage (HOTSTUFF_TPU_VIEWCHANGE_BUDGET,
    default 240 s): committees that miss the budget report
    {"skipped": true}.  Emitted on BOTH the live and degraded lines.
    """
    from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
    # The node's own quorum formula, single-homed (sched/shapes; the
    # committee_scale headline uses the same helper).
    from hotstuff_tpu.sidecar.sched.shapes import quorum_sigs

    if budget_s is None:
        budget_s = float(
            os.environ.get("HOTSTUFF_TPU_VIEWCHANGE_BUDGET", "240"))
    out = {"committees": list(committees)}
    if budget_s <= 0:
        out["skipped"] = True
        return out
    t0 = time.perf_counter()
    rng = np.random.default_rng(37)
    # One shared digest: all honest timeouts of a round carry the same
    # (round, high_qc_round), which is what makes the batch QC-shaped.
    shared = rng.bytes(32)
    max_q = quorum_sigs(max(committees))
    pks, sigs = [], []
    for _ in range(max_q):
        sk = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        pks.append(pk)
        sigs.append(ref.sign(sk, shared))

    for n in committees:
        if time.perf_counter() - t0 > budget_s:
            out[f"n{n}"] = {"skipped": True}
            continue
        q = quorum_sigs(n)
        m, p, s = [shared] * q, pks[:q], sigs[:q]
        try:
            # Batched: warm/compile outside the timed region, then the
            # one-launch path the core's TC batch rides.
            if not eddsa.verify_batch(m, p, s).all():
                raise RuntimeError(f"batched TC verify failed at q={q}")
            batched_ms = None
            for _ in range(repeats):
                t = time.perf_counter()
                mask = eddsa.verify_batch(m, p, s)
                dt = (time.perf_counter() - t) * 1e3
                if not mask.all():
                    raise RuntimeError(f"batched TC verify failed at q={q}")
                batched_ms = dt if batched_ms is None else min(batched_ms,
                                                               dt)
            # Per-signature: the old inline host path, one verify per
            # arriving timeout (single repeat — pure-python point math).
            t = time.perf_counter()
            for mi, pi, si in zip(m, p, s):
                if not ref.verify(pi, mi, si):
                    raise RuntimeError(f"per-sig TC verify failed at q={q}")
            per_sig_ms = (time.perf_counter() - t) * 1e3
            out[f"n{n}"] = {
                "quorum": q,
                "batched_ms": round(batched_ms, 2),
                "per_sig_ms": round(per_sig_ms, 2),
                "batched_sigs_per_s": round(q / (batched_ms / 1e3), 1),
                "per_sig_sigs_per_s": round(q / (per_sig_ms / 1e3), 1),
                "speedup": round(per_sig_ms / batched_ms, 3),
            }
        except Exception as e:  # noqa: BLE001 — per-size isolation
            out[f"n{n}"] = {"error": f"{e!r:.200}"}

    # Eject-path equivalence at the smallest committee: one tampered
    # candidate -> the batch rejects, and the per-sig fallback names
    # exactly the same signer set the batch mask does.
    try:
        q = quorum_sigs(min(committees))
        bad_i = q // 2
        bad_sigs = list(sigs[:q])
        bad_sigs[bad_i] = bad_sigs[bad_i][:1] + \
            bytes([bad_sigs[bad_i][1] ^ 0xFF]) + bad_sigs[bad_i][2:]
        mask = [bool(b) for b in
                eddsa.verify_batch([shared] * q, pks[:q], bad_sigs)]
        per_sig = [ref.verify(pk, shared, sg)
                   for pk, sg in zip(pks[:q], bad_sigs)]
        out["eject"] = {
            "tampered_index": bad_i,
            "batch_rejected": not all(mask),
            "ejected": [i for i, ok in enumerate(mask) if not ok],
            "match_per_sig": mask == per_sig,
        }
    except Exception as e:  # noqa: BLE001
        out["eject"] = {"error": f"{e!r:.200}"}

    measured = [v for k, v in out.items()
                if k.startswith("n") and isinstance(v, dict)
                and "speedup" in v]
    out["ok"] = bool(measured) and \
        out.get("eject", {}).get("match_per_sig") is True and \
        out.get("eject", {}).get("batch_rejected") is True
    return out


def probe_device(window: float | None = None,
                 max_attempts: int | None = None, run=None,
                 sleep=time.sleep, now=time.monotonic):
    """Bounded subprocess probe of the (wedgeable) device ->
    ``(ok, reason)``.

    Caps the retry loop THREE ways: an attempt cap
    (HOTSTUFF_TPU_PROBE_ATTEMPTS, default 3), the probe's own window
    (HOTSTUFF_TPU_PROBE_WINDOW, default 600 s), and — the round-5 fix —
    the REMAINING outer bench budget (HOTSTUFF_TPU_BENCH_DEADLINE minus
    elapsed) less _DEADLINE_SLACK, so the degraded fallback always has
    the slack left to measure and emit its JSON line inside the driver's
    hard timeout.  The regression this prevents (BENCH_r05.json): the
    driver granted a window larger than its own timeout, nine probe
    retries consumed everything, rc=124, no artifact.  ``run``/``sleep``/
    ``now`` are injectable for the regression test (a fake always-failing
    probe on a virtual clock)."""
    import subprocess
    import sys

    if run is None:
        run = subprocess.run
    if window is None:
        window = float(os.environ.get("HOTSTUFF_TPU_PROBE_WINDOW", "600"))
    if max_attempts is None:
        max_attempts = max(
            1, int(os.environ.get("HOTSTUFF_TPU_PROBE_ATTEMPTS", "3")))
    budget_window = max(0.0, budget_left_s(now) - _DEADLINE_SLACK)
    window = min(window, budget_window)
    probe = ("import jax, jax.numpy as jnp, numpy as np;"
             "np.asarray(jnp.ones((8, 8)) @ jnp.ones((8, 8)))")
    deadline = now() + window
    attempt = 0
    proc_errors = 0
    last_err = "device wedged (probe timeouts)"
    while True:
        remaining = deadline - now()
        if remaining <= 0 and attempt > 0:
            break
        attempt += 1
        retry_sleep = 30.0
        try:
            run([sys.executable, "-c", probe],
                timeout=min(75.0, max(5.0, remaining)),
                check=True, capture_output=True)
            return True, ""
        except subprocess.TimeoutExpired:
            proc_errors = 0
            last_err = "device wedged (probe timeouts)"
        except subprocess.CalledProcessError as e:
            # A probe that exits nonzero (bad install, import error) is
            # deterministic — only timeouts are worth waiting out, so
            # retry these quickly and give up after a few in a row.
            proc_errors += 1
            retry_sleep = 5.0
            last_err = (e.stderr or b"").decode("utf-8", "replace")[-300:]
            if proc_errors >= 4:
                return False, (f"device probe errored {proc_errors}x in "
                               f"a row (not a wedge): {last_err}")
        remaining = deadline - now()
        if attempt >= max_attempts or remaining <= 0:
            break
        print(f"bench: device probe attempt {attempt} failed; retrying "
              f"({remaining:.0f}s left in window)", file=sys.stderr)
        sleep(min(retry_sleep, max(0.0, remaining)))
    return False, (f"device probe failed {attempt}x (cap {max_attempts}, "
                   f"window {window:.0f}s, outer budget "
                   f"{bench_budget_s():.0f}s): {last_err}")


def run_degraded(reason: str):
    """No usable accelerator: fall back to JAX_PLATFORMS=cpu, measure the
    RLC headline there, and ALWAYS emit one parseable JSON line tagged
    ``"degraded": true`` before exiting 0 — a degraded measurement of a
    degraded environment is a successful bench run, and the driver's
    bounded window must never close on silence (BENCH_r05.json).
    ``value`` is the largest completed per-signature CPU-backend
    throughput: NOT comparable to TPU numbers, which is what the flag
    says."""
    import threading

    emitted = threading.Event()

    def _bail():
        if emitted.is_set():
            return
        cached = load_cache()
        if cached:
            emit_cached(cached, f"degraded watchdog: {reason}",
                        degraded=True)
        else:
            emit(0, 0, degraded=True,
                 error=f"degraded watchdog: {reason}")
        os._exit(0)

    # The degraded stage itself must fit the REMAINING outer budget with
    # slack for the emit: the whole point of capping the probe window is
    # that this path still lands its line inside the driver's timeout.
    # Cap raised 480 -> 900 with the roofline stage (a pallas-interpret
    # measurement is compile-bound, ~2-4 min for one size on CPU), then
    # 900 -> 1200 with the committee_scale stage (another bounded
    # forced-host-mesh subprocess); the budget_left guard, not the cap,
    # is what keeps the emit inside the driver's window.
    left = max(30.0, budget_left_s() - 60.0)
    watchdog = threading.Timer(min(1200.0, left), _bail)
    watchdog.daemon = True
    watchdog.start()
    try:
        import jax

        # Mirrors tests/conftest.py: jax may already be imported, so the
        # env var can be too late — flip the platform through jax.config
        # before any backend initializes.  If a backend already initialized (the
        # degraded call came after a successful probe), keep it: it is
        # reachable by definition.
        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:  # noqa: BLE001
            pass
        from hotstuff_tpu.utils.xla_cache import configure_xla_cache

        configure_xla_cache()
        # All four headline sizes; the budget guard marks whatever the
        # CPU backend can't fit as {"skipped": true} instead of stalling.
        rlc = rlc_compare(repeats=1,
                          budget_s=min(300.0, max(20.0, left - 120.0)))
        value = 0.0
        for stats in rlc.values():
            value = max(value, stats.get("per_sig_sigs_per_s", 0.0))
        try:
            mesh_rlc = mesh_rlc_headline(budget_s=min(
                float(os.environ.get("HOTSTUFF_TPU_MESH_RLC_BUDGET",
                                     "240")),
                max(0.0, budget_left_s() - 90.0)))
        except Exception as e:  # noqa: BLE001 — headline isolation
            mesh_rlc = {"error": f"{e!r:.120}"}
        # graftscale committee_scale on the same forced-host mesh: the
        # giant-committee sweep rides the degraded line too (same
        # bounded-subprocess emit-or-die discipline as mesh_rlc) — a
        # degraded environment still proves the N in {100, 300, 1000}
        # routing story, just on CPU-backend numbers.
        try:
            committee_scale = committee_scale_headline(budget_s=min(
                float(os.environ.get("HOTSTUFF_TPU_COMMITTEE_BUDGET",
                                     "240")),
                max(0.0, budget_left_s() - 90.0)))
        except Exception as e:  # noqa: BLE001 — headline isolation
            committee_scale = {"error": f"{e!r:.120}"}
        # graftkern roofline on the CPU backend: the estimate is always
        # present; measured entries are CPU-backend (and the pallas
        # route interpreter-flagged) — comparable to each other, never
        # to TPU numbers, which the degraded flag already says.  One
        # size: a pallas-interpret measurement is compile-bound
        # (~2-4 min) and the larger sizes belong to a live device run
        # (the budget check is per-route, so an in-flight measurement
        # is never preempted — the size list is what bounds this
        # stage under the degraded watchdog).
        try:
            roofline = roofline_headline(
                sizes=(64,), repeats=1,
                budget_s=min(240.0, max(0.0, budget_left_s() - 180.0)))
        except Exception as e:  # noqa: BLE001 — headline isolation
            roofline = {"est": roofline_estimate(),
                        "error": f"{e!r:.120}"}
        # graftview viewchange on the CPU backend: batched vs per-sig TC
        # assembly plus the eject-equivalence check — CPU-backend
        # latencies (never comparable to device numbers, the degraded
        # flag says so), but the eject contract and the field's schema
        # are proven on every line.
        try:
            viewchange = viewchange_headline(
                repeats=1,
                budget_s=min(
                    float(os.environ.get("HOTSTUFF_TPU_VIEWCHANGE_BUDGET",
                                         "240")),
                    max(0.0, budget_left_s() - 90.0)))
        except Exception as e:  # noqa: BLE001 — headline isolation
            viewchange = {"error": f"{e!r:.120}"}
        try:
            sched = sched_headline_probe()
        except Exception as e:  # noqa: BLE001 — telemetry is best-effort
            sched = {"error": f"{e!r:.120}"}
        try:
            chaos = chaos_headline_probe(_FAULT_PLAN, _WAN_SPEC,
                                         _SLO_SPEC)
        except Exception as e:  # noqa: BLE001 — chaos probe is best-effort
            chaos = {"error": f"{e!r:.120}"}
        try:
            trace = trace_headline_probe()
        except Exception as e:  # noqa: BLE001 — trace probe is best-effort
            trace = {"error": f"{e!r:.120}"}
        try:
            surge = surge_headline_probe()
        except Exception as e:  # noqa: BLE001 — surge probe is best-effort
            surge = {"error": f"{e!r:.120}"}
        try:
            guard = guard_headline_probe()
        except Exception as e:  # noqa: BLE001 — guard probe is best-effort
            guard = {"error": f"{e!r:.120}"}
        # graftcadence ring-vs-staged on the forced-host mesh: the same
        # bounded-subprocess emit-or-die discipline as mesh_rlc — the
        # ring story (depth sweep, queue-wait p99, resident-slot
        # bit-identity) is proven on the degraded line too.
        try:
            cadence = cadence_headline(budget_s=min(
                float(os.environ.get("HOTSTUFF_TPU_CADENCE_BUDGET",
                                     "240")),
                max(0.0, budget_left_s() - 90.0)))
        except Exception as e:  # noqa: BLE001 — headline isolation
            cadence = {"error": f"{e!r:.120}"}
        # graftingress user-population sweep: host-mode in-process (no
        # device), so the degraded line proves the same signed-ingress
        # story as the live one.
        try:
            users = users_headline_probe(budget_s=min(
                float(os.environ.get("HOTSTUFF_TPU_USERS_BUDGET",
                                     "240")),
                max(0.0, budget_left_s() - 90.0)))
        except Exception as e:  # noqa: BLE001 — headline isolation
            users = {"error": f"{e!r:.120}"}
        # graftfleet failover + flood isolation: host-mode in-process,
        # so the degraded line carries the same fleet story as the
        # live one.
        try:
            fleet = fleet_headline_probe()
        except Exception as e:  # noqa: BLE001 — fleet probe is best-effort
            fleet = {"error": f"{e!r:.120}"}
        # The watchdog stays armed until the moment of the real emit: a
        # stall anywhere above (including the sched probe) must still
        # produce a parseable line, which is this path's whole contract.
        emitted.set()
        # Report the backend that actually ran (an already-initialized
        # device backend wins over the cpu config flip above).
        emit(value, 0.0, degraded=True, backend=jax.default_backend(),
             note=reason, rlc=rlc, mesh_rlc=mesh_rlc,
             committee_scale=committee_scale, roofline=roofline,
             viewchange=viewchange, sched=sched, chaos=chaos, trace=trace,
             surge=surge, guard=guard, cadence=cadence, users=users,
             fleet=fleet)
    except Exception as e:  # noqa: BLE001 — the line must still be emitted
        emitted.set()
        emit(0, 0, degraded=True,
             error=f"{reason}; degraded run failed: {e!r:.200}")
    os._exit(0)


def make_batch():
    """G*N fully distinct (key, message, signature) triples — no repetition,
    so the headline number is honest about per-signature cost.  Generated
    through OpenSSL (deterministic Ed25519: bit-identical to the pure-python
    reference, ~100x faster for 16k keypairs)."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )
    from cryptography.hazmat.primitives.serialization import (
        Encoding, PublicFormat,
    )

    rng = np.random.default_rng(2024)
    msgs, pks, sigs = [], [], []
    for _ in range(G * N):
        key = Ed25519PrivateKey.from_private_bytes(rng.bytes(32))
        pk = key.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)
        msg = rng.bytes(64)
        msgs.append(msg)
        pks.append(pk)
        sigs.append(key.sign(msg))
    return msgs, pks, sigs


def cpu_baseline(msgs, pks, sigs) -> float:
    """Single-core verifies/sec via OpenSSL (cryptography lib)."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    keys = [Ed25519PublicKey.from_public_bytes(pk) for pk in pks]
    # warmup
    keys[0].verify(sigs[0], msgs[0])
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for k, m, s in zip(keys, msgs, sigs):
            k.verify(s, m)
        dt = time.perf_counter() - t0
        best = max(best, len(msgs) / dt)
    return best


def tpu_throughput(msgs, pks, sigs, on_trial=None) -> float:
    """End-to-end pipelined verifies/sec.  Every timed round pays full host
    preparation AND the h2d transfer for all G*N signatures; both run on a
    prep thread overlapping the device compute of the previous round (the
    SHA-512 loop releases the GIL; the transfer blocks in C).  The
    (G, N) mask is all-reduced in-program, so each round returns one byte,
    and verdicts are fetched after the last round — per-fetch latency
    (not measured on the chip) is paid once per trial, not once per
    round."""
    import jax
    import jax.numpy as jnp

    from hotstuff_tpu.crypto import eddsa
    from hotstuff_tpu.ops import ed25519 as E

    assert N == eddsa.MAX_SUBBATCH
    verify_chunked = E.verify_packed_chunked  # (G, N, 128) -> (G, N)
    # Donate each round's device buffer (consumed exactly once below), so
    # the headline measures the same donation behavior the sidecar's
    # production launches use; CPU doesn't implement donation (debug runs
    # would only warn per launch).
    donate = {} if jax.default_backend() == "cpu" \
        else dict(donate_argnums=0)
    verify_all = jax.jit(lambda arr: verify_chunked(arr).all(), **donate)

    def prep_round():
        rows = []
        for g in range(G):
            prep = eddsa.prepare_batch(msgs[g * N:(g + 1) * N],
                                       pks[g * N:(g + 1) * N],
                                       sigs[g * N:(g + 1) * N])
            assert prep["host_ok"].all()
            rows.append(prep["packed"])
        return np.stack(rows)

    out = verify_all(jax.device_put(prep_round()))   # compile + warmup
    assert bool(np.asarray(out)), "benchmark signatures must verify"

    from concurrent.futures import ThreadPoolExecutor

    # Three-stage pipeline on two helper threads: prep (CPU-bound SHA-512,
    # releases the GIL) and h2d transfer (blocks in C) run as separate
    # stages so the transfer of round i+1 overlaps the device compute of
    # round i WITHOUT waiting behind round i+2's prep (each stage's cost
    # on the chip: not measured).
    best = 0.0
    # HOTSTUFF_TPU_XFER_STREAMS=2 runs two concurrent h2d transfers —
    # worth it ONLY if scripts/exp_xfer_streams.py shows h2d bandwidth
    # is a per-stream limit rather than a physical one; with a physical
    # limit two streams just split it (never measured: ROADMAP S2).
    try:
        xfer_streams = max(
            1, int(os.environ.get("HOTSTUFF_TPU_XFER_STREAMS", "1").strip()))
    except ValueError:
        raise SystemExit("HOTSTUFF_TPU_XFER_STREAMS must be an integer")
    with ThreadPoolExecutor(1) as prep_pool, \
         ThreadPoolExecutor(xfer_streams) as xfer_pool:
        lead = xfer_streams  # transfers in flight ahead of compute
        for _ in range(TRIALS):
            t0 = time.perf_counter()
            preps = [prep_pool.submit(prep_round) for _ in range(1 + lead)]
            devs = [xfer_pool.submit(
                        lambda f=preps[i]: jax.device_put(f.result()))
                    for i in range(lead)]
            verdicts = []
            for r in range(ROUNDS):
                if r + 1 + lead < ROUNDS:
                    preps.append(prep_pool.submit(prep_round))
                if r + lead < ROUNDS:
                    devs.append(xfer_pool.submit(
                        lambda f=preps[r + lead]: jax.device_put(f.result())))
                verdicts.append(verify_all(devs[r].result()))
            oks = [bool(np.asarray(v)) for v in verdicts]  # forces the work
            dt = time.perf_counter() - t0
            assert all(oks), "benchmark signatures must verify"
            best = max(best, G * N * ROUNDS / dt)
            if on_trial:
                on_trial(best)
    return best


def main(argv=None):
    # --fault-plan rides through to the chaos headline probe (a path to a
    # JSON plan or an inline DSL spec; the HOTSTUFF_TPU_FAULT_PLAN env is
    # the no-argv channel).  parse_known_args: the driver may pass flags
    # this bench does not own.
    import argparse

    # Kill-proof emit FIRST (graftguard satellite): from here on, the
    # driver's window closing (SIGTERM ahead of the rc=124 SIGKILL) or
    # a stage alarm re-emits the best line already measured instead of
    # dying silently; every emit below also lands cache-first on disk.
    install_kill_handlers()

    global _FAULT_PLAN, _WAN_SPEC, _SLO_SPEC
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--fault-plan", default=None)
    ap.add_argument("--wan", default=None)
    ap.add_argument("--slo", default=None)
    known, _ = ap.parse_known_args(argv)
    _FAULT_PLAN = known.fault_plan \
        or os.environ.get("HOTSTUFF_TPU_FAULT_PLAN") or None
    _WAN_SPEC = known.wan or os.environ.get("HOTSTUFF_TPU_WAN") or None
    _SLO_SPEC = known.slo or os.environ.get("HOTSTUFF_TPU_SLO") or None

    # Watchdog: a device call can wedge indefinitely.  A hung bench is worse than a failed
    # one — the driver's round-end run must always terminate.
    import threading

    # Capped probe: a wedged device hangs ANY device call indefinitely,
    # and only a subprocess can be timed out reliably.  probe_device bounds the retry loop by
    # attempts, its own window, AND the remaining outer bench budget
    # (HOTSTUFF_TPU_BENCH_DEADLINE) — round 5 spent its ENTIRE driver
    # window on nine probe retries and emitted nothing (BENCH_r05.json
    # rc=124).  When any cap is hit, fall back to a JAX_PLATFORMS=cpu
    # degraded measurement: a parseable line always lands, with slack to
    # spare inside the driver's hard timeout.
    ok, probe_reason = probe_device()
    if not ok:
        run_degraded(probe_reason)

    # Persistent XLA compilation cache BEFORE anything compiles in this
    # process (the in-process msm sweep below is the first compiler; the
    # old subprocess children configured the cache themselves).
    from hotstuff_tpu.utils.xla_cache import configure_xla_cache

    configure_xla_cache()

    # MSM window-chunk sweep, IN-PROCESS (set_msm_window_chunk re-pins
    # the constant and clears the jit caches — no more subprocess per
    # value; this process now binds the device here, which is fine: the
    # probe subprocesses have exited and every later stage runs in this
    # same process anyway).  Budget-guarded per chunk; failures degrade
    # to per-chunk error entries.  The budget only checks BETWEEN
    # chunks, and the subprocess-per-value timeout that used to bound a
    # wedged compile is gone — so the stage runs under its own watchdog:
    # a stalled compile emits the best cached measurement (or
    # an error line) instead of eating the whole artifact (the rc=124
    # failure mode the module header documents).
    def _msm_abort():
        emit_cached_or_fail("msm chunk sweep wedged (stage watchdog)")

    msm_budget = float(
        os.environ.get("HOTSTUFF_TPU_MSM_SWEEP_BUDGET", "180"))
    msm_watchdog = threading.Timer(
        min(msm_budget + 120.0,
            max(60.0, budget_left_s() - _DEADLINE_SLACK)), _msm_abort)
    msm_watchdog.daemon = True
    msm_watchdog.start()
    try:
        msm = msm_chunk_sweep(budget_s=msm_budget)
    except Exception as e:  # noqa: BLE001
        msm = {"error": f"{e!r:.200}"}
    msm_watchdog.cancel()

    # mesh_rlc headline: a forced-host CPU-mesh subprocess (no device
    # contention with the stages below), budgeted so the main headline
    # measurement keeps at least its usual window of the outer budget.
    mesh_rlc = mesh_rlc_headline(budget_s=min(
        float(os.environ.get("HOTSTUFF_TPU_MESH_RLC_BUDGET", "240")),
        max(0.0, budget_left_s() - 900.0)))

    # committee_scale headline (graftscale): the giant-committee sweep
    # on the same forced-host mesh — also a bounded subprocess, also
    # budgeted against what the main measurement must keep.
    committee_scale = committee_scale_headline(budget_s=min(
        float(os.environ.get("HOTSTUFF_TPU_COMMITTEE_BUDGET", "240")),
        max(0.0, budget_left_s() - 900.0)))

    def _abort():
        emit_cached_or_fail(
            "watchdog: TPU unresponsive for 900s after a healthy probe")

    watchdog = threading.Timer(
        min(900.0, max(60.0, budget_left_s() - _DEADLINE_SLACK)), _abort)
    watchdog.daemon = True
    watchdog.start()

    from hotstuff_tpu.ops import field25519

    field25519.mul_selfcheck()  # trip fast if this backend's conv is inexact
    try:
        msgs, pks, sigs = make_batch()
        cpu = cpu_baseline(msgs, pks, sigs)
    except Exception as e:  # e.g. `cryptography` missing: no OpenSSL
        watchdog.cancel()   # baseline — degrade rather than die silently
        run_degraded(f"headline prerequisites failed: {e!r:.200}")
        return

    def on_trial(best):
        # Capture-on-every-improving-trial: the line is on stdout (and the
        # cache on disk) the moment the FIRST trial lands, so a mid-run
        # wedge or driver timeout still leaves a parseable measurement.
        save_cache(best, best / cpu, cpu)
        emit(best, best / cpu)

    try:
        tpu = tpu_throughput(msgs, pks, sigs, on_trial=on_trial)
    except Exception as e:  # device died mid-measurement
        watchdog.cancel()
        emit_cached_or_fail(f"measurement aborted: {e!r:.300}")
        return
    save_cache(tpu, tpu / cpu, cpu)
    watchdog.cancel()
    # RLC headline under its OWN bounded watchdog: the headline number is
    # already measured and cached, so a wedge in this stage must neither
    # relabel the run "unresponsive" nor drop the measurement — it just
    # ships the line with the rlc field marked aborted.  (budget_s only
    # checks between sizes; a single stalled compile needs the timer.)
    def _rlc_abort():
        emit_final(tpu, cpu, rlc={"error": "rlc stage watchdog (420s)"},
                   msm_window_chunk=msm, mesh_rlc=mesh_rlc,
                   committee_scale=committee_scale,
                   roofline={"est": roofline_estimate(),
                             "skipped": True,
                             "note": "rlc stage watchdog fired first"})
        os._exit(0)

    rlc_watchdog = threading.Timer(420.0, _rlc_abort)
    rlc_watchdog.daemon = True
    rlc_watchdog.start()
    try:
        rlc = rlc_compare(budget_s=float(
            os.environ.get("HOTSTUFF_TPU_RLC_BUDGET", "300")))
    except Exception as e:  # noqa: BLE001 — headline must not die on rlc
        rlc = {"error": f"{e!r:.200}"}
    rlc_watchdog.cancel()
    # graftkern roofline: lax vs pallas sigs/sec/chip against the
    # arithmetic ceiling, derated against what is left of the outer
    # budget.  A Mosaic failure on new silicon degrades to a per-route
    # error entry; a Mosaic compile that WEDGES needs the timer (the
    # budget only checks between routes) — on fire, the already-measured
    # fields still ship instead of dying with the stage.
    def _roofline_abort():
        emit_final(tpu, cpu, rlc=rlc, msm_window_chunk=msm,
                   mesh_rlc=mesh_rlc, committee_scale=committee_scale,
                   roofline={"est": roofline_estimate(),
                             "error": "roofline stage watchdog"})
        os._exit(0)

    roofline_budget = min(
        float(os.environ.get("HOTSTUFF_TPU_ROOFLINE_BUDGET", "300")),
        max(0.0, budget_left_s() - _DEADLINE_SLACK))
    roofline_watchdog = threading.Timer(
        min(max(60.0, roofline_budget + 180.0),
            max(60.0, budget_left_s() - 60.0)), _roofline_abort)
    roofline_watchdog.daemon = True
    roofline_watchdog.start()
    try:
        roofline = roofline_headline(budget_s=roofline_budget)
    except Exception as e:  # noqa: BLE001 — headline isolation
        roofline = {"error": f"{e!r:.200}"}
    roofline_watchdog.cancel()
    # graftview viewchange: batched vs per-sig TC assembly.  Compile-
    # bound (fresh verify_batch buckets), so it gets the same stage-
    # watchdog discipline as rlc/roofline — on fire, the already-measured
    # fields ship with the stage marked instead of eating the line.
    def _viewchange_abort():
        emit_final(tpu, cpu, rlc=rlc, msm_window_chunk=msm,
                   mesh_rlc=mesh_rlc, committee_scale=committee_scale,
                   roofline=roofline,
                   viewchange={"error": "viewchange stage watchdog"})
        os._exit(0)

    viewchange_budget = min(
        float(os.environ.get("HOTSTUFF_TPU_VIEWCHANGE_BUDGET", "240")),
        max(0.0, budget_left_s() - _DEADLINE_SLACK))
    viewchange_watchdog = threading.Timer(
        min(max(60.0, viewchange_budget + 120.0),
            max(60.0, budget_left_s() - 60.0)), _viewchange_abort)
    viewchange_watchdog.daemon = True
    viewchange_watchdog.start()
    try:
        viewchange = viewchange_headline(budget_s=viewchange_budget)
    except Exception as e:  # noqa: BLE001 — headline isolation
        viewchange = {"error": f"{e!r:.200}"}
    viewchange_watchdog.cancel()
    try:
        sched = sched_headline_probe()
    except Exception as e:  # noqa: BLE001 — telemetry is best-effort
        sched = {"error": f"{e!r:.120}"}
    try:
        chaos = chaos_headline_probe(_FAULT_PLAN, _WAN_SPEC, _SLO_SPEC)
    except Exception as e:  # noqa: BLE001 — chaos probe is best-effort
        chaos = {"error": f"{e!r:.120}"}
    try:
        trace = trace_headline_probe()
    except Exception as e:  # noqa: BLE001 — trace probe is best-effort
        trace = {"error": f"{e!r:.120}"}
    try:
        surge = surge_headline_probe()
    except Exception as e:  # noqa: BLE001 — surge probe is best-effort
        surge = {"error": f"{e!r:.120}"}
    try:
        guard = guard_headline_probe()
    except Exception as e:  # noqa: BLE001 — guard probe is best-effort
        guard = {"error": f"{e!r:.120}"}
    # graftcadence: ring vs staged on the forced-host mesh — a bounded
    # subprocess like mesh_rlc (its own watchdog discipline), budgeted
    # against what is left of the outer window.
    try:
        cadence = cadence_headline(budget_s=min(
            float(os.environ.get("HOTSTUFF_TPU_CADENCE_BUDGET", "240")),
            max(0.0, budget_left_s() - 60.0)))
    except Exception as e:  # noqa: BLE001 — headline isolation
        cadence = {"error": f"{e!r:.120}"}
    # graftingress user-population sweep: in-process host-mode engine,
    # no device contention with anything above.
    try:
        users = users_headline_probe(budget_s=min(
            float(os.environ.get("HOTSTUFF_TPU_USERS_BUDGET", "240")),
            max(0.0, budget_left_s() - 60.0)))
    except Exception as e:  # noqa: BLE001 — headline isolation
        users = {"error": f"{e!r:.120}"}
    # graftfleet: kill-primary failover goodput + greedy-tenant flood
    # isolation, in-process host-mode (no device contention).
    try:
        fleet = fleet_headline_probe()
    except Exception as e:  # noqa: BLE001 — fleet probe is best-effort
        fleet = {"error": f"{e!r:.120}"}
    emit_final(tpu, cpu, rlc=rlc, msm_window_chunk=msm,
               mesh_rlc=mesh_rlc, committee_scale=committee_scale,
               roofline=roofline, viewchange=viewchange, sched=sched,
               chaos=chaos, trace=trace, surge=surge, guard=guard,
               cadence=cadence, users=users, fleet=fleet)


if __name__ == "__main__":
    main()
