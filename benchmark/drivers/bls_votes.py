"""Driver ``bls_votes``: BLS aggregate-QC certificates sent to the
sidecar's socket as ``OP_BLS_VERIFY_VOTES`` frames, the frame a
``scheme=bls`` replica ships (``native/src/crypto/crypto.cpp``), in a
closed loop, one certificate in flight a connection.

The process plumbing is ``cert_stream``'s (imported: its ``Handle``, the
child -> runner events and the runner -> child order line); what differs
is the child.  It builds the pool (``yardstick/bls_streams.py``) in
worker processes while the sidecar warms up, checks a seeded sample and
every planted forgery against the plain reference, and, told the port,
sends every certificate of the pool once, in order, with
``SidecarClient.bls_verify_votes``: a certificate is never sent twice,
so no verdict can come from the sidecar's verdict cache.  A pool that
runs out before the window ends refuses the run: the child then reports
``exhausted`` in place of ``result``.

Before it starts the child, ``start()`` checks that the program speaks
the frame (``SidecarClient.bls_verify_votes``): a program without it
cannot run the cell, and the run ends there, before the chip is touched.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for _p in (REPO, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from drivers.cert_stream import DriverError, Handle, _say  # noqa: E402


def start(cell: dict, seed: int, work_dir: str) -> Handle:
    """Start the load generator for ``cell``; refuses a program that has
    no client for the VOTES frame."""
    from hotstuff_tpu.sidecar.client import SidecarClient

    if not hasattr(SidecarClient, "bls_verify_votes"):
        raise DriverError("this program's SidecarClient has no "
                          "bls_verify_votes: it cannot send the cell's "
                          "OP_BLS_VERIFY_VOTES certificates")
    err_path = os.path.join(work_dir, "generator.err")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, BENCH, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    with open(err_path, "w") as err:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             cell["config_path"], cell["mix_path"], str(int(seed))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
            env=env, text=True, bufsize=1)
    return Handle(proc, err_path)


def workers() -> int:
    """Worker processes for signing: the host's cores but two, for the
    sidecar's boot beside them."""
    return max(1, min(8, (os.cpu_count() or 1) - 2))


def _send(client, req: dict, mix: dict) -> bool:
    ctx = req["msg"] if mix.get("ctx") == "digest" else None
    return client.bls_verify_votes(req["msg"], req["pks"], req["sigs"],
                                   ctx=ctx)


def _loop(client, pool: list, mix: dict, t_end: float, records: list):
    """The closed loop: certificate i + 1 is sent when the reply to i
    is in; each certificate once.  Returns whether the pool ran out
    before ``t_end``."""
    from hotstuff_tpu.sidecar.client import SidecarOverloaded
    from yardstick import bls_streams

    for i, req in enumerate(pool):
        now = time.monotonic()
        if now >= t_end:
            return False
        rec = {"conn": 0, "index": i, "kind": req["kind"],
               "sigs": len(req["sigs"]), "t_send": now, "t_reply": None,
               "status": "unanswered"}
        records.append(rec)
        try:
            got = _send(client, req, mix)
            rec["t_reply"] = time.monotonic()
            rec["status"] = "ok" if got == bls_streams.expected(req) \
                else "mismatch"
        except SidecarOverloaded:
            rec["t_reply"] = time.monotonic()
            rec["status"] = "refused"
        except Exception as e:  # noqa: BLE001 — counted, and the loop ends
            rec["status"] = "error"
            rec["detail"] = f"{type(e).__name__}: {e}"[:200]
            return False
    return time.monotonic() < t_end


def child(config_path: str, mix_path: str, seed: int) -> int:
    from yardstick import bls_streams

    with open(config_path, encoding="utf-8") as f:
        config = json.load(f)
    with open(mix_path, encoding="utf-8") as f:
        mix = json.load(f)
    if int(mix["connections"]) != 1:
        raise ValueError("bls_votes drives one connection")
    t0 = time.monotonic()
    gen = bls_streams.Generator(mix, config, seed)
    n = workers()
    pool = gen.build(gen.plan(), n)
    warm = gen.build(gen.warmup(), n)
    t_pool = time.monotonic()
    sample = bls_streams.check_sample(pool, seed, int(mix["sample"]), n)
    by_kind: dict = {}
    for r in pool:
        by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + 1
    _say({"event": "pool", "requests": len(pool),
          "records": sum(len(r["sigs"]) for r in pool),
          "sigs_per_request": gen.votes, "by_kind": by_kind,
          "distinct_digests": len({r["msg"] for r in pool + warm}),
          "pool_s": t_pool - t0, "sample_s": time.monotonic() - t_pool,
          "sample": sample})

    from hotstuff_tpu.sidecar.client import SidecarClient

    order = json.loads(sys.stdin.readline())
    port, seconds = int(order["port"]), float(order["seconds"])
    drain_s = float(mix.get("drain_s", 5))
    client = SidecarClient(port=port, timeout=60.0)
    try:
        client.hello(f"{mix['name']}-0")
        unmeasured_wrong = sum(_send(client, req, mix)
                               != bls_streams.expected(req) for req in warm)
        with SidecarClient(port=port, timeout=60.0) as sc:
            stats_start = sc.stats()
        t_start = time.monotonic()
        t_end = t_start + seconds
        _say({"event": "window", "t_start": t_start, "t_end": t_end,
              "t_wall": time.time(), "unmeasured": len(warm),
              "unmeasured_wrong": unmeasured_wrong})
        records: list = []
        ran_out = []
        thread = threading.Thread(
            target=lambda: ran_out.append(
                _loop(client, pool, mix, t_end, records)),
            daemon=True, name="conn-0")
        thread.start()
        thread.join(timeout=max(0.0, t_end + drain_s - time.monotonic()))
        undrained = int(thread.is_alive())
        with SidecarClient(port=port, timeout=60.0) as sc:
            stats_end = sc.stats()
    finally:
        client.close()
    if ran_out and ran_out[0]:
        _say({"event": "exhausted", "requests": len(pool),
              "detail": "the pool ran out before the window ended: "
                        "raise pool_blocks in the mix"})
        return 1
    flat = [dict(r) for r in list(records)]
    _say({"event": "result", "t_start": t_start, "t_end": t_end,
          "undrained_connections": undrained, "requests": flat,
          "stats_start": stats_start, "stats_end": stats_end})
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 5 and sys.argv[1] == "--child":
        sys.exit(child(sys.argv[2], sys.argv[3], int(sys.argv[4])))
    sys.exit("bls_votes.py is started by benchmark/run.py")
