#!/usr/bin/env python3
"""chip_smoke: the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls: boots
``python -m hotstuff_tpu.sidecar`` as a child (the command the harness
builds), speaks to it over its socket with the frames the C++ node sends
(HELLO, then OP_VERIFY_BATCH / OP_VERIFY_BULK), and holds every verdict
mask to the plain host reference (``crypto/ref_ed25519``, one verify per
signature).  Then it reads OP_STATS and FAILS unless the answers came
from the device: platform ``tpu``, no host path, no wedge, no host
fallback.  A second boot against the same compile cache must be warm.

    python chip_smoke.py              one chip: N=100 committee, QC/TC/bulk
    python chip_smoke.py --chips 4    only the --mesh 4 path: N=1000, 667-vote QC

One process may hold a chip, so this parent calls no JAX function (the
imports below pull jax in but initialise no backend): the sidecar child
is the only process that touches the device.  With ``JAX_PLATFORMS=cpu``
everything up to the stats check passes and the platform check fails —
the rehearsal.  Exit code 0 and a last line
``{"ok": true, "device": {...}}`` only when every check held.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import socket
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from hotstuff_tpu.crypto import ref_ed25519 as ref  # noqa: E402
from hotstuff_tpu.harness.local import LocalBench  # noqa: E402
from hotstuff_tpu.harness.logs import LogParser  # noqa: E402
from hotstuff_tpu.harness.utils import log_tail  # noqa: E402
from hotstuff_tpu.sidecar.client import SidecarClient  # noqa: E402
# Engine-path RLC floor: batches of this many unique records and more
# must take the one-MSM route.
from hotstuff_tpu.sidecar.sched.shapes import RLC_MIN_LAUNCH  # noqa: E402
from hotstuff_tpu.utils.xla_cache import xla_cache_dir  # noqa: E402

# Sidecar logs and stats snapshots: what is too long for stdout.
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
# The contract: exit 0 within 1200 s, compilation included.
TIME_LIMIT_S = 1150
# --chips 4 is run by a builder, never by the driver: its cold boot
# compiles two mesh programs for each of eight per-shard buckets and
# four scan shapes, about twenty minutes of compiling.
MESH_TIME_LIMIT_S = 1800
# Rounds of one-chip requests: the first pays whatever a launch shape's
# first use costs, the later ones are the steady state.
ROUNDS = 3


class SmokeFailure(RuntimeError):
    """A phase failed; the message says which check and why."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# The sidecar child
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Sidecar:
    """One ``python -m hotstuff_tpu.sidecar`` child in its own process
    group, its output in ``log_path``; stopped on exit, whatever happened."""

    def __init__(self, flags: list[str], log_path: str):
        self.port = free_port()
        self.log_path = log_path
        self.cmd = [sys.executable, "-m", "hotstuff_tpu.sidecar",
                    "--port", str(self.port), *flags]
        self._proc = None
        self._log = None

    def __enter__(self):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        self._log = open(self.log_path, "w")
        say("boot: " + " ".join(self.cmd[1:]))
        self._proc = subprocess.Popen(
            self.cmd, cwd=REPO, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)
        return self

    def __exit__(self, *exc):
        proc = self._proc
        if proc is not None and proc.poll() is None:
            os.killpg(proc.pid, signal.SIGTERM)
            try:
                proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
        self._log.close()

    def wait_ready(self, deadline: float) -> float:
        """Block until the sidecar answers a PING (it binds after its
        warmup, so reachable == warmed); returns the wall seconds.  An
        exited child or a passed deadline is a failure carrying the log
        tail."""
        t0 = time.monotonic()
        while True:
            try:
                with SidecarClient(port=self.port, timeout=5.0) as c:
                    c.ping()
                return time.monotonic() - t0
            except (OSError, ConnectionError):
                pass
            rc = self._proc.poll()
            if rc is not None or time.monotonic() > deadline:
                why = f"exited with code {rc}" if rc is not None else \
                    f"not ready after {time.monotonic() - t0:.0f}s"
                raise SmokeFailure(
                    f"sidecar {why}; tail of {self.log_path}:\n"
                    f"{log_tail(self.log_path)}")
            time.sleep(0.5)


# ---------------------------------------------------------------------------
# Workload: certificates of a seeded committee, and their reference masks
# ---------------------------------------------------------------------------


def _h(*parts) -> bytes:
    return hashlib.sha512(
        b"|".join(str(p).encode() for p in parts)).digest()[:32]


def make_validators(seed: int, n: int) -> list:
    """n (secret seed, public key) pairs, a pure function of ``seed``."""
    out = []
    for i in range(n):
        sk = _h("validator", seed, i)
        out.append((sk, ref.generate_keypair(sk)[1]))
    return out


def quorum(n: int) -> int:
    """The node's own formula (native/src/consensus/config.hpp)."""
    return 2 * n // 3 + 1


def certificate(validators, msgs) -> tuple:
    """Vote i: validator i signs msgs[i] -> (msgs, pks, sigs)."""
    pks = [validators[i][1] for i in range(len(msgs))]
    sigs = [ref.sign(validators[i][0], m) for i, m in enumerate(msgs)]
    return list(msgs), pks, sigs


def forge_vote(cert, k: int) -> tuple:
    """Vote k's signature with one bit of S flipped."""
    msgs, pks, sigs = cert
    sig = sigs[k]
    sigs = list(sigs)
    sigs[k] = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
    return msgs, pks, sigs


def wrong_key(cert, k: int) -> tuple:
    """Vote k presented under its neighbour's (valid, wrong) key."""
    msgs, pks, sigs = cert
    pks = list(pks)
    pks[k] = pks[(k + 1) % len(pks)]
    return msgs, pks, sigs


def reference_mask(cert) -> list:
    """The plain host reference: one ref_ed25519.verify per signature."""
    return [bool(ref.verify(pk, m, s)) for m, pk, s in zip(*cert)]


def one_chip_requests(validators, seed: int, rnd: int) -> list:
    """One round of what an N=100 deployment's sidecar sees, plus the
    quorums of the reference's smaller committees: (name, cert, bulk,
    bad rows).  Every digest is fresh per round, so nothing is answered
    from the verdict cache."""
    q = quorum(100)

    def qc(tag, votes):
        return certificate(validators, [_h(tag, seed, rnd)] * votes)

    k = (7 * rnd + 3) % q
    reqs = [
        ("qc67", qc("qc", q), False, ()),
        ("tc67", certificate(
            validators, [_h("tc", seed, rnd, i) for i in range(q)]),
         False, ()),
    ]
    for n in (4, 10, 20, 50):
        reqs.append((f"qc{quorum(n)}", qc(f"qc-n{n}", quorum(n)), False, ()))
    reqs += [
        ("bulk128", certificate(
            validators * 2, [_h("bulk", seed, rnd, i) for i in range(128)]),
         True, ()),
        ("qc67_forged_vote", forge_vote(qc("qc-forged", q), k), False, (k,)),
        ("qc67_wrong_key", wrong_key(qc("qc-wrongkey", q), k), False, (k,)),
    ]
    return reqs


def mesh_requests(validators, seed: int) -> list:
    """The >1k-validator case: an N=1000 committee's 667-vote QC, valid
    and with one forged vote."""
    q = quorum(len(validators))
    valid = certificate(validators, [_h("giant-qc", seed)] * q)
    forged = forge_vote(
        certificate(validators, [_h("giant-qc-forged", seed)] * q), q // 2)
    return [(f"qc{q}", valid, False, ()),
            (f"qc{q}_forged_vote", forged, False, (q // 2,))]


def send_and_check(client, reqs, latencies: dict) -> None:
    """Every reply must equal the host reference's verdict mask: forged
    rows false, all others true."""
    for name, cert, bulk, bad in reqs:
        want = reference_mask(cert)
        if [i for i, ok in enumerate(want) if not ok] != list(bad):
            raise SmokeFailure(
                f"{name}: the host reference itself disagrees with the "
                f"planted forgeries {bad}")
        msgs, pks, sigs = cert
        t0 = time.monotonic()
        got = client.verify_batch(msgs, pks, sigs, bulk=bulk,
                                  ctx=None if bulk else msgs[0])
        latencies.setdefault(name, []).append(
            (time.monotonic() - t0) * 1e3)
        if got != want:
            wrong = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
            raise SmokeFailure(
                f"{name}: sidecar mask differs from the host reference "
                f"at rows {wrong[:8]} ({len(wrong)} of {len(want)})")


# ---------------------------------------------------------------------------
# The stats check: did the DEVICE answer?
# ---------------------------------------------------------------------------


def check_stats(stats: dict, *, count: int, rlc_path: str, rlc_launches: int,
                warmed_shapes: int | None) -> list:
    """Hold one OP_STATS snapshot to "the device answered"; returns the
    violated conditions (empty list = pass).

    count          devices the engine must be launching on
    rlc_path       the one-MSM route's key in ``paths`` (``rlc``, or
                   ``rlc_sharded`` on a mesh)
    rlc_launches   batches of >= RLC_MIN_LAUNCH records that were sent:
                   each must have taken that route
    warmed_shapes  shapes the boot was asked to warm (None: not held)
    """
    bad = []
    device = stats.get("device")
    if not isinstance(device, dict):
        bad.append("no `device` section: the sidecar holds no device "
                   "(host crypto?)")
    else:
        if device.get("platform") != "tpu":
            bad.append(f"device.platform is {device.get('platform')!r}, "
                       "not 'tpu'")
        if device.get("count") != count:
            bad.append(f"device.count is {device.get('count')!r}, "
                       f"not {count}")
    paths = stats.get("paths") or {}
    if paths.get("host", 0):
        bad.append(f"paths has a host entry: {paths}")
    if paths.get(rlc_path, 0) < rlc_launches:
        bad.append(f"paths[{rlc_path!r}] is {paths.get(rlc_path, 0)}, "
                   f"expected >= {rlc_launches}: {paths}")
    guard = stats.get("guard") or {}
    for key in ("wedges", "host_fallback_records", "poison_host_verified"):
        if guard.get(key, 0):
            bad.append(f"guard.{key} is {guard.get(key)}")
    if not guard.get("device_ok", False):
        bad.append("guard.device_ok is not true")
    compile_ = stats.get("compile") or {}
    if warmed_shapes is not None:
        seen = compile_.get("hits", 0) + compile_.get("misses", 0)
        if seen != warmed_shapes:
            bad.append(f"compile.hits + compile.misses is {seen}, "
                       f"expected {warmed_shapes} warmed shapes")
    return bad


def require_stats(stats: dict, **expect) -> dict:
    bad = check_stats(stats, **expect)
    if bad:
        raise SmokeFailure("stats check failed:\n  " + "\n  ".join(bad))
    return stats["device"]


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def dir_populated(path: str) -> bool:
    try:
        return any(os.scandir(path))
    except OSError:
        return False


def summarize(latencies: dict) -> dict:
    return {name: {"first_ms": round(ms[0], 2),
                   "steady_ms": round(statistics.median(ms[1:]), 2)
                   if len(ms) > 1 else None}
            for name, ms in latencies.items()}


def boot_and_drive(tag: str, flags: list, rounds: list, out_dir: str,
                   deadline: float) -> dict:
    """One sidecar lifetime: boot with ``flags``, wait for its warmup,
    HELLO as the node does, send every round of requests and hold each
    reply to the host reference; returns the OP_STATS snapshot taken
    before the child is stopped (also left in ``stats-<tag>.json``, the
    child's output in ``sidecar-<tag>.log``)."""
    latencies: dict = {}
    with Sidecar(flags, os.path.join(out_dir, f"sidecar-{tag}.log")) as sc:
        ready_s = sc.wait_ready(deadline)
        with SidecarClient(port=sc.port, timeout=300.0) as client:
            client.hello("chip_smoke")
            for reqs in rounds:
                send_and_check(client, reqs, latencies)
            stats = client.stats()
    with open(os.path.join(out_dir, f"stats-{tag}.json"), "w") as f:
        json.dump(stats, f, indent=1, sort_keys=True)
    compiled = stats.get("compile") or {}
    say(f"{tag} boot: ready after {ready_s:.1f}s, warmup "
        f"{compiled.get('warmup_wall_s')}s, {compiled.get('misses')} "
        f"miss(es), {compiled.get('hits')} hit(s), "
        f"warm_boot={compiled.get('warm_boot')}, cache "
        f"{compiled.get('cache_dir')}")
    say(f"{tag} boot: all {sum(map(len, rounds))} replies equal the host "
        f"reference; paths {json.dumps(stats.get('paths'), sort_keys=True)}")
    say(f"{tag} boot: latency ms (first, steady median) "
        f"{json.dumps(summarize(latencies), sort_keys=True)}")
    return stats


def one_chip_phase(args, out_dir: str, deadline: float) -> dict:
    buckets = [n for n in (8, 16, 32, 64, 128, 256, 512, 1024)
               if n <= args.warm]
    flags = ["--committee", "100", "--warm", str(args.warm), "--warm-rlc"]
    validators = make_validators(args.seed, 100)
    rounds = [one_chip_requests(validators, args.seed, r)
              for r in range(ROUNDS)]
    # (<= args.warm: a rehearsal that warms less routes the wider
    # batches down the per-signature ladder, as the registry must.)
    rlc_launches = sum(RLC_MIN_LAUNCH <= len(cert[0]) <= args.warm
                       for reqs in rounds for _, cert, _, _ in reqs)
    cold_cache = not dir_populated(xla_cache_dir())
    say("compile cache " + xla_cache_dir() + " is "
        + ("empty" if cold_cache else "already populated"))
    stats = boot_and_drive("first", flags, rounds, out_dir, deadline)
    device = require_stats(
        stats, count=1, rlc_path="rlc", rlc_launches=rlc_launches,
        # the ladder and the RLC program, one per bucket
        warmed_shapes=2 * len(buckets))
    # Second boot, same cache: every shape a hit, nothing compiled anew.
    first = stats["compile"]
    second = boot_and_drive("second", flags, [rounds[0][:1]], out_dir,
                            deadline)["compile"]
    if not second["warm_boot"]:
        raise SmokeFailure(
            f"second boot against {second['cache_dir']} was not warm: "
            f"{second['misses']} miss(es)")
    if cold_cache and \
            not second["warmup_wall_s"] < first["warmup_wall_s"]:
        raise SmokeFailure(
            f"warm boot warmed up in {second['warmup_wall_s']}s, no "
            f"faster than the cold boot's {first['warmup_wall_s']}s")
    return device


def mesh_phase(args, out_dir: str, deadline: float,
               validators_n: int = 1000) -> dict:
    """Only the --mesh 4 path and what it is compared with.  --warm 8:
    the sharded warmup floors its ceiling at the committee's quorum, so
    every per-shard bucket up to the 667-vote QC's is compiled anyway
    and a larger --warm would only add ladder shapes nothing here
    sends."""
    flags = ["--mesh", "4", "--committee", str(validators_n), "--warm", "8",
             "--warm-rlc-sharded"]
    reqs = mesh_requests(make_validators(args.seed, validators_n), args.seed)
    stats = boot_and_drive("mesh", flags, [reqs], out_dir, deadline)
    return require_stats(stats, count=4, rlc_path="rlc_sharded",
                         rlc_launches=len(reqs), warmed_shapes=None)


def committee_phase(args, out_dir: str, deadline: float) -> dict:
    """The node <-> device sidecar path on the chip host: a 4-node
    committee of the real C++ replicas (built by the harness inside this
    command — native/build/ is not committed) verifying its QCs through
    one device sidecar for ~20 s.  Requires a zero exit (the log parser's
    safety check raised nothing), commits, and the same stats conditions
    as the one-chip phase.  The harness's sidecar warms the ladder to
    the launch cap (no --warm), eight shapes."""
    log_path = os.path.join(out_dir, "committee.log")
    cmd = [sys.executable, "-m", "hotstuff_tpu.harness", "local",
           "--nodes", "4", "--rate", "2000", "--duration", "20",
           "--tpu-sidecar"]
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    say("committee: " + " ".join(cmd[1:]))
    with open(log_path, "w") as log:
        try:
            rc = subprocess.run(
                cmd, cwd=REPO, env=env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
                timeout=max(1.0, deadline - time.monotonic())).returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
            # The harness died before its own teardown: run its sweep
            # of nodes, clients and the sidecar (each its own session).
            sweeper = LocalBench.__new__(LocalBench)
            sweeper._procs = []
            sweeper._kill_nodes()
    if rc != 0:
        raise SmokeFailure(f"harness local ended with {rc}; tail of "
                           f"{log_path}:\n{log_tail(log_path)}")
    logs = os.path.join(REPO, "logs")
    parser = LogParser.process(logs, faults=0)   # re-runs the safety check
    say(f"committee: {len(parser.commits)} batch(es) committed, safety held")
    if not parser.commits:
        raise SmokeFailure("the committee committed nothing")
    with open(os.path.join(logs, "sidecar-stats.json")) as f:
        stats = json.load(f)
    say(f"paths: {json.dumps(stats.get('paths'), sort_keys=True)}")
    # N=4: a QC is 3 votes, so every launch is a per-signature ladder.
    return require_stats(stats, count=1, rlc_path="rlc", rlc_launches=0,
                         warmed_shapes=8)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the validator keys and digests")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the --mesh 4 path (N=1000)")
    ap.add_argument("--committee", action="store_true",
                    help="run only the 4-node committee against one "
                         "device sidecar (builds the native plane)")
    ap.add_argument("--warm", type=int, default=128,
                    help="largest bucket the one-chip sidecar warms "
                         "(128 holds an N=100 quorum; smaller only for "
                         "a CPU rehearsal)")
    args = ap.parse_args(argv)
    os.makedirs(OUT_DIR, exist_ok=True)
    deadline = time.monotonic() + (
        MESH_TIME_LIMIT_S if args.chips == 4 else TIME_LIMIT_S)
    try:
        phase = mesh_phase if args.chips == 4 else \
            committee_phase if args.committee else one_chip_phase
        device = phase(args, OUT_DIR, deadline)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
