"""Test configuration: force an 8-device virtual CPU mesh so all sharding /
multi-chip code paths run (and are validated) without TPU hardware, per the
framework's multi-chip design (hotstuff_tpu/parallel/).

The suite runs on the CPU backend: the sandbox has no accelerator, and on a
machine that has one the chip belongs to one process at a time (xdist
workers and the sidecar children the tests spawn would fight over it).  The
platform is pinned twice — through the environment for child processes, and
through jax.config for this one, which holds whatever the interpreter
imported before this file ran (backends initialise lazily, so the update
still takes).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache (the same dir the sidecar uses): the
# suite's wall-clock is dominated by lax.scan ladder compiles
# that are identical run to run — cache them across sessions.  The
# min-compile-time floor keeps trivial programs out of the cache dir.
from hotstuff_tpu.utils.xla_cache import configure_xla_cache  # noqa: E402

configure_xla_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


# ---------------------------------------------------------------------------
# Slow-lane gating: tests marked @pytest.mark.slow (the two multichip
# dryruns, which duplicate the driver's own per-round dryrun_multichip
# check, and the exhaustive A/B flag-variant sweep) are skipped unless
# HOTSTUFF_TPU_SLOW_TESTS=1.  They account for ~215 s of a ~385 s
# warm-cache full run; the default lane stays under 5 minutes while CI's
# dedicated job exports the env and runs everything.
# ---------------------------------------------------------------------------


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavyweight test, skipped unless HOTSTUFF_TPU_SLOW_TESTS=1")


def pytest_collection_modifyitems(config, items):
    import pytest

    if os.environ.get("HOTSTUFF_TPU_SLOW_TESTS") == "1":
        return
    skip = pytest.mark.skip(
        reason="slow lane: set HOTSTUFF_TPU_SLOW_TESTS=1 to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


# ---------------------------------------------------------------------------
# Shared integration-test scaffolding (node/client/sidecar process testbed).
# Used by test_integration*.py; lives here so the spawn/teardown and log
# helpers exist exactly once.
# ---------------------------------------------------------------------------

import signal as _signal
import socket as _socket
import subprocess as _subprocess
import time as _time

import pytest as _pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NODE_BIN = os.path.join(REPO, "native", "build", "node")
CLIENT_BIN = os.path.join(REPO, "native", "build", "client")


def free_port():
    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def count_in_log(path, needle):
    try:
        with open(path, "r", errors="replace") as f:
            return f.read().count(needle)
    except OSError:
        return 0


def wait_commits(log_files, minimum, deadline_s):
    start = _time.monotonic()
    while _time.monotonic() - start < deadline_s:
        counts = [count_in_log(p, "Committed B") for p in log_files]
        if all(c >= minimum for c in counts):
            return counts
        _time.sleep(0.5)
    return [count_in_log(p, "Committed B") for p in log_files]


def wait_sidecar_ping(port, deadline_s=30):
    from hotstuff_tpu.sidecar.client import SidecarClient

    start = _time.monotonic()
    while _time.monotonic() - start < deadline_s:
        try:
            with SidecarClient(port=port, timeout=2.0) as c:
                c.ping()
            return True
        except (OSError, ConnectionError):
            _time.sleep(0.2)
    return False


def make_committee(tmp_path, nodes, timeout_delay_ms, batch_size=1000,
                   sidecar_port=None, scheme=None):
    """Generate keys + committee + parameters files; returns (keys,
    committee, params)."""
    from hotstuff_tpu.harness.config import Key, LocalCommittee, NodeParameters

    keys = []
    for i in range(nodes):
        _subprocess.run([NODE_BIN, "keys", "--filename", f".node-{i}.json"],
                        cwd=tmp_path, check=True)
        keys.append(Key.from_file(str(tmp_path / f".node-{i}.json")))
    committee = LocalCommittee([k.name for k in keys], free_port())
    committee.print(str(tmp_path / ".committee.json"))
    params = NodeParameters.default(
        tpu_sidecar=(f"127.0.0.1:{sidecar_port}" if sidecar_port else None),
        scheme=scheme)
    params.json["consensus"]["timeout_delay"] = timeout_delay_ms
    params.json["mempool"]["batch_size"] = batch_size
    params.print(str(tmp_path / ".parameters.json"))
    return keys, committee, params


def boot_without_serving(monkeypatch, tmp_path, **serve_args):
    """Run ``service.serve(**serve_args)`` through its whole boot — the
    engine, the guard, every warm-up leg the arguments ask for — up to
    the point where it would listen, and return the engine it built.
    The socket server is a stand-in whose ``serve_forever`` returns at
    once, so serve() goes on through its own ``finally``.  The warm-up
    manifest goes to ``tmp_path``: a test's stubbed shapes must never
    make a later real boot look warm.  The caller stubs what it does not
    want compiled BEFORE calling this."""
    from hotstuff_tpu.sidecar import service

    engines = []

    class NeverListens:
        def __init__(self, address, engine, chaos=None):
            self.server_address = address
            engines.append(engine)

        def serve_forever(self, poll_interval=None):
            pass

        def server_close(self):
            pass

    monkeypatch.setenv("HOTSTUFF_TPU_COMPILE_MANIFEST",
                       str(tmp_path / "manifest.json"))
    monkeypatch.setattr(service, "SidecarServer", NeverListens)
    service.serve(port=0, **serve_args)
    (engine,) = engines
    return engine


@_pytest.fixture
def testbed(tmp_path):
    procs = []

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def spawn(cmd, log_name):
        log = open(tmp_path / log_name, "w")
        p = _subprocess.Popen(cmd, cwd=tmp_path, stdout=log, stderr=log,
                              env=env)
        procs.append((p, log))
        return p

    yield tmp_path, spawn
    for p, log in procs:
        if p.poll() is None:
            p.send_signal(_signal.SIGTERM)
    for p, log in procs:
        try:
            p.wait(timeout=10)
        except _subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        log.close()
