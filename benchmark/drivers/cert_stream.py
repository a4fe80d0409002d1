"""Driver ``cert_stream``: certificates or signature batches sent to the
sidecar's socket in a closed loop, one request in flight a connection.

Two halves in one file.  ``start()`` runs in the runner, before it
touches JAX: it starts one child (this file with ``--child``) that holds
no device.  The child builds the cell's request pool from the seed
(``yardstick/streams.py``) while the sidecar warms up, checks a seeded
sample against the plain reference, and — told the port — speaks to the
socket with ``hotstuff_tpu.sidecar.client.SidecarClient`` (HELLO, then
``verify_batch``), the frames the C++ node sends.  One thread a
connection; each request's send and reply times are taken on the child's
monotonic clock (system-wide on Linux, so the runner's clock too), and
each reply is held to the generator's ground truth.

Child -> runner, one JSON object a line on stdout:
  {"event": "pool", ...}      the pool is built and the sample checked
  {"event": "window", ...}    unmeasured requests done, the window opens
  {"event": "result", ...}    every request record, OP_STATS before/after
Runner -> child, one line on stdin: {"port": ..., "seconds": ...}.
"""

from __future__ import annotations

import json
import os
import queue
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


# ---------------------------------------------------------------------------
# Runner side
# ---------------------------------------------------------------------------


class DriverError(RuntimeError):
    pass


class Handle:
    """The running child, as the runner sees it."""

    def __init__(self, proc, err_path: str):
        self._proc = proc
        self._err_path = err_path
        self._events: queue.Queue = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True,
                                        name="cert-stream-reader")
        self._reader.start()

    def _read(self):
        for line in self._proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                self._events.put(json.loads(line))
        self._events.put(None)

    def expect(self, event: str, timeout_s: float) -> dict:
        """The child's next event, which has to be ``event``."""
        try:
            got = self._events.get(timeout=timeout_s)
        except queue.Empty:
            raise DriverError(
                f"load generator: no {event!r} event after {timeout_s:.0f}s "
                f"(its errors: {self._err_path})") from None
        if got is None or got.get("event") != event:
            raise DriverError(
                f"load generator: expected {event!r}, got "
                f"{'its exit' if got is None else got.get('event')!r} "
                f"(its errors: {self._err_path})")
        return got

    def go(self, port: int, seconds: float):
        self._proc.stdin.write(
            json.dumps({"port": port, "seconds": seconds}) + "\n")
        self._proc.stdin.flush()

    def stop(self):
        """Stop the child and wait until it has ended."""
        proc = self._proc
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
        proc.wait()
        for pipe in (proc.stdin, proc.stdout):
            try:
                pipe.close()
            except OSError:
                pass
        self._reader.join(timeout=5)


def start(cell: dict, seed: int, work_dir: str) -> Handle:
    """Start the load generator for ``cell`` ({"config_path",
    "mix_path", ...}); it initialises no JAX backend."""
    err_path = os.path.join(work_dir, "generator.err")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, BENCH, env.get("PYTHONPATH", "")])
    # The child never calls JAX; if anything it imports ever did, it
    # must not be the process that takes the chip.
    env["JAX_PLATFORMS"] = "cpu"
    with open(err_path, "w") as err:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--child",
             cell["config_path"], cell["mix_path"], str(int(seed))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
            env=env, text=True, bufsize=1)
    return Handle(proc, err_path)


# ---------------------------------------------------------------------------
# Child side
# ---------------------------------------------------------------------------


def _say(obj: dict):
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _send(client, req: dict, mix: dict) -> list:
    bulk = mix["class"] == "bulk"
    ctx = req["msgs"][0] if mix.get("ctx") == "digest" and not bulk else None
    return client.verify_batch(req["msgs"], req["pks"], req["sigs"],
                               bulk=bulk, ctx=ctx)


def _connection(k: int, client, pool: list, mix: dict, t_end: float,
                records: list):
    """Closed loop of connection k: requests k, k+C, k+2C, ... of the
    pool, replayed in order, the next one sent when the reply is in."""
    from hotstuff_tpu.sidecar.client import SidecarOverloaded
    from yardstick import streams

    stride = int(mix["connections"])
    i = k
    while True:
        req = pool[i % len(pool)]
        now = time.monotonic()
        if now >= t_end:
            return
        rec = {"conn": k, "index": i, "kind": req["kind"],
               "sigs": len(req["msgs"]), "t_send": now, "t_reply": None,
               "status": "unanswered"}
        records.append(rec)
        try:
            got = _send(client, req, mix)
            rec["t_reply"] = time.monotonic()
            rec["status"] = "ok" if got == streams.expected_mask(req) \
                else "mismatch"
        except SidecarOverloaded:
            rec["t_reply"] = time.monotonic()
            rec["status"] = "refused"
        except Exception as e:  # noqa: BLE001 — counted, and the loop ends
            rec["status"] = "error"
            rec["detail"] = f"{type(e).__name__}: {e}"[:200]
            return
        i += stride


def child(config_path: str, mix_path: str, seed: int) -> int:
    from hotstuff_tpu.sidecar.client import SidecarClient
    from yardstick import streams

    with open(config_path, encoding="utf-8") as f:
        config = json.load(f)
    with open(mix_path, encoding="utf-8") as f:
        mix = json.load(f)
    conns = int(mix["connections"])
    t0 = time.monotonic()
    gen = streams.Generator(mix, config, seed)
    pool = gen.pool()
    warm = [gen.warmup(k) for k in range(conns)]
    t_pool = time.monotonic()
    sample = streams.check_sample(pool, seed, int(mix.get("sample", 256)))
    by_kind: dict = {}
    for r in pool:
        by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + 1
    _say({"event": "pool", "requests": len(pool),
          "records": sum(len(r["msgs"]) for r in pool),
          "sigs_per_request": gen.votes, "by_kind": by_kind,
          "pool_s": t_pool - t0, "sample_s": time.monotonic() - t_pool,
          "sample": sample})

    order = json.loads(sys.stdin.readline())
    port, seconds = int(order["port"]), float(order["seconds"])
    drain_s = float(mix.get("drain_s", 5))
    clients = [SidecarClient(port=port, timeout=60.0) for _ in range(conns)]
    try:
        for k, c in enumerate(clients):
            c.hello(f"{mix['name']}-{k}")
        unmeasured_wrong = 0
        for k, c in enumerate(clients):
            for req in warm[k]:
                unmeasured_wrong += \
                    _send(c, req, mix) != streams.expected_mask(req)
        with SidecarClient(port=port, timeout=60.0) as sc:
            stats_start = sc.stats()
        t_start = time.monotonic()
        t_end = t_start + seconds
        _say({"event": "window", "t_start": t_start, "t_end": t_end,
              "t_wall": time.time(),
              "unmeasured": sum(map(len, warm)),
              "unmeasured_wrong": unmeasured_wrong})
        records = [[] for _ in range(conns)]
        threads = [threading.Thread(
            target=_connection, args=(k, clients[k], pool, mix, t_end,
                                      records[k]),
            daemon=True, name=f"conn-{k}") for k in range(conns)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(0.0, t_end + drain_s - time.monotonic()))
        undrained = sum(t.is_alive() for t in threads)
        with SidecarClient(port=port, timeout=60.0) as sc:
            stats_end = sc.stats()
    finally:
        for c in clients:
            c.close()
    # Copies: a thread that is still waiting may yet write to its last
    # record, and that request stays "unanswered".
    flat = [dict(r) for recs in records for r in list(recs)]
    _say({"event": "result", "t_start": t_start, "t_end": t_end,
          "undrained_connections": undrained, "requests": flat,
          "stats_start": stats_start, "stats_end": stats_end})
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 5 and sys.argv[1] == "--child":
        sys.exit(child(sys.argv[2], sys.argv[3], int(sys.argv[4])))
    sys.exit("cert_stream.py is started by benchmark/run.py")
