"""Persistent compiled-program cache shared by every TPU-touching
entrypoint (sidecar, bench): cold processes reuse compiled programs
instead of paying tens of seconds of compile per shape.

Two layers:

* The XLA compilation cache (:func:`configure_xla_cache`): jax persists
  compiled executables to a shared on-disk dir, so a warm boot's
  "compile" is a fast deserialization.
* The warmed-shape manifest (:class:`CompileManifest`,
  ``results/compile_cache/manifest.json``): records which (shape key,
  kernel-source hash) pairs a warmup has already compiled — keyed on
  the SAME kernel-source hash scheme bench.py uses for its headline
  cache (:func:`kernel_fingerprint`), so a kernel edit invalidates the
  record exactly when it invalidates the programs.  The sidecar's
  warmup walks its shapes through :class:`CompileTracker`, which counts
  manifest hits/misses and per-shape wall time into the OP_STATS
  ``compile`` section; ``scripts/warmup_report.py`` turns the recorded
  runs into the cold-vs-warm boot comparison.
"""

from __future__ import annotations

import glob
import hashlib
import json
import logging
import os
import threading
import time

log = logging.getLogger("xla-cache")

MANIFEST_SCHEMA = "hotstuff-tpu-compile-manifest-v1"
_MAX_RUNS = 50

# The sources whose edits can change what a compiled verify program
# does: a manifest entry (and a cached bench headline) is only
# comparable to a boot built from the same kernel.  The kern glob keeps
# new Pallas modules inside the hash automatically.
KERNEL_SOURCES = (
    "hotstuff_tpu/ops/ed25519.py",
    "hotstuff_tpu/ops/field25519.py",
    "hotstuff_tpu/ops/scalar25519.py",
    "hotstuff_tpu/crypto/eddsa.py",
)
KERNEL_SOURCE_GLOBS = ("hotstuff_tpu/ops/kern/*.py",)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def kernel_fingerprint(extra=()) -> str:
    """Hash of the kernel sources (plus any caller-specific ``extra``
    repo-relative files — bench.py adds itself); namespaces the manifest
    and the bench headline cache so a stale record can only ever answer
    for the code that produced it."""
    root = repo_root()
    rels = list(KERNEL_SOURCES)
    for pattern in KERNEL_SOURCE_GLOBS:
        rels += sorted(
            os.path.relpath(p, root)
            for p in glob.glob(os.path.join(root, pattern)))
    rels += list(extra)
    h = hashlib.sha256()
    for rel in rels:
        try:
            with open(os.path.join(root, rel), "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(b"<missing>")
        h.update(b"\x00")
    return h.hexdigest()[:16]


def xla_cache_dir() -> str:
    """Where the one on-disk XLA compilation cache lives (no jax
    needed to ask): ``JAX_COMPILATION_CACHE_DIR`` where it is set, else
    the fixed git-ignored ``results/compile_cache/xla`` of this checkout
    (fixed: the path is part of the cache key, a directory that moves
    never hits)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        repo_root(), "results", "compile_cache", "xla")


def configure_xla_cache() -> str:
    """Make jax persist compiled programs in :func:`xla_cache_dir` and
    return that directory (the CompileTracker records it per warmed
    shape).  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax already
    persists there, and nothing is configured here."""
    cache_dir = xla_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def default_manifest_path() -> str:
    return os.environ.get(
        "HOTSTUFF_TPU_COMPILE_MANIFEST",
        os.path.join(repo_root(), "results", "compile_cache",
                     "manifest.json"))


class CompileManifest:
    """The warmed-shape manifest: which (kernel hash, shape key) pairs
    have been compiled, plus a bounded history of warmup runs.  Load is
    tolerant (a corrupt or missing file starts empty); save is atomic
    (tmp + replace) so a killed sidecar can never leave a torn file."""

    def __init__(self, path: str | None = None):
        self.path = path or default_manifest_path()
        self.data = self._load()

    def _load(self) -> dict:
        try:
            with open(self.path, encoding="utf-8") as f:
                data = json.load(f)
            if isinstance(data, dict) and \
                    data.get("schema") == MANIFEST_SCHEMA and \
                    isinstance(data.get("kernels"), dict) and \
                    isinstance(data.get("runs"), list):
                return data
        except (OSError, ValueError):
            pass
        return {"schema": MANIFEST_SCHEMA, "kernels": {}, "runs": []}

    def seen(self, kernel: str, key: str,
             cache_dir: str | None = None) -> bool:
        """True when this (kernel, key) pair was warmed before AND — if
        ``cache_dir`` is given — it was warmed against that same XLA
        cache dir, which still exists on disk.  The dir checks keep the
        warm-boot claim honest: a manifest alone cannot prove the
        compiled programs survived (a wiped or different cache dir
        means this boot recompiles everything regardless of what the
        manifest remembers)."""
        entry = self.data["kernels"].get(kernel, {}) \
            .get("shapes", {}).get(key)
        if entry is None:
            return False
        if cache_dir is None:
            return True
        return entry.get("cache_dir") == cache_dir and \
            os.path.isdir(cache_dir)

    def shape_walls(self, kernel: str) -> dict:
        """``{shape key: last_wall_s}`` for every shape warmed under
        this kernel hash — what graftguard's LaunchDeadlines reads to
        decide warm-boot deadlines (empty dict = cold boot: no record
        of any compiled shape for this exact kernel)."""
        shapes = self.data["kernels"].get(kernel, {}).get("shapes", {})
        out = {}
        for key, entry in shapes.items():
            if isinstance(entry, dict) and \
                    isinstance(entry.get("last_wall_s"), (int, float)):
                out[key] = float(entry["last_wall_s"])
        return out

    def cold_wall_s(self) -> float | None:
        """Wall time of the most expensive recorded COLD warmup run —
        the max wall among runs that paid at least one miss (None when
        no such run is on record).  graftguard's acceptance bar compares
        the crash-only reboot's re-warm wall against half of this."""
        walls = [r.get("wall_s") for r in self.data["runs"]
                 if isinstance(r, dict) and r.get("misses")
                 and isinstance(r.get("wall_s"), (int, float))]
        return max(walls) if walls else None

    def record(self, kernel: str, key: str, wall_s: float,
               now: float | None = None,
               cache_dir: str | None = None) -> None:
        shapes = self.data["kernels"].setdefault(
            kernel, {"shapes": {}})["shapes"]
        entry = shapes.setdefault(key, {
            "first_warmed_at": now if now is not None else time.time()})
        entry["last_wall_s"] = round(wall_s, 3)
        entry["cache_dir"] = cache_dir

    def record_run(self, kernel: str, hits: int, misses: int,
                   wall_s: float, now: float | None = None) -> None:
        self.data["runs"].append({
            "t": now if now is not None else time.time(),
            "kernel": kernel,
            "hits": hits,
            "misses": misses,
            "wall_s": round(wall_s, 3),
        })
        del self.data["runs"][:-_MAX_RUNS]

    def save(self) -> None:
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(self.data, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        except OSError as e:  # manifest is an optimization, never fatal
            log.warning("compile manifest save failed: %r", e)


class CompileTracker:
    """Warmup-time compile accounting against the persistent manifest.

    The sidecar wraps every warmup shape in :meth:`warm`: a shape whose
    (kernel hash, key) pair the manifest already holds is a cache HIT —
    the XLA disk cache deserializes instead of compiling — anything
    else is a MISS that this boot pays for and records.  A second boot
    against a populated cache therefore reports ``misses == 0`` with a
    measurably lower warmup wall time, which is exactly what the
    OP_STATS ``compile`` section (:meth:`snapshot`) and
    ``scripts/warmup_report.py`` surface.

    What a shape's seconds WERE comes from jax itself: the tracker
    registers one ``jax.monitoring`` duration listener and, while a shape
    is inside :meth:`warm`, adds the events to that shape — ``lower_s``
    (jaxpr tracing + lowering to MLIR) and ``backend_s`` (the backend
    compile, or the read-back from the persistent cache).  After
    :meth:`finish` every backend-compile event counts under
    ``in_service``: a program built while serving, the operator's answer
    to "which step recompiled".  ``clock`` and ``register`` (what takes
    the listener; default ``jax.monitoring``'s) are injectable for
    tests."""

    LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")
    BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, cache_dir: str | None = None,
                 manifest_path: str | None = None,
                 clock=None, kernel: str | None = None, register=None):
        self.cache_dir = cache_dir
        self._clock = clock or time.monotonic
        self.kernel = kernel or kernel_fingerprint()
        self.manifest = CompileManifest(manifest_path)
        self.hits = 0
        self.misses = 0
        self.shapes: dict[str, dict] = {}
        self._t0 = self._clock()
        self._wall_s: float | None = None
        # Monitoring events arrive on whichever thread compiles.
        self._events_lock = threading.Lock()
        self._warming: dict | None = None   # the shape inside warm()
        self._boot_split = {"lower_s": 0.0, "backend_s": 0.0}
        self.in_service = {"count": 0, "seconds": 0.0, "last_at": None}
        self._listens_to_jax = register is None
        if register is None:
            from jax import monitoring

            register = monitoring.register_event_duration_secs_listener
        register(self._on_event)

    def close(self):
        """Take the listener off ``jax.monitoring`` again (idempotent):
        a process that boots ``serve()`` more than once keeps one
        listener a live tracker."""
        if self._listens_to_jax:
            from jax import monitoring

            self._listens_to_jax = False
            monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        """The ``jax.monitoring`` duration listener."""
        if event == self.BACKEND_EVENT:
            field = "backend_s"
        elif event in self.LOWER_EVENTS:
            field = "lower_s"
        else:
            return
        with self._events_lock:
            if self._wall_s is not None:
                if field == "backend_s":
                    self.in_service["count"] += 1
                    self.in_service["seconds"] += duration
                    self.in_service["last_at"] = time.time()
                return
            self._boot_split[field] += duration
            if self._warming is not None:
                self._warming[field] += duration

    def warm(self, key: str, thunk):
        """Run one warmup shape under hit/miss + wall-time accounting;
        returns the thunk's result.  A hit requires the manifest entry
        AND the matching, still-present XLA cache dir (a boot with the
        cache disabled or re-pointed counts every shape as a miss —
        it IS recompiling; CompileManifest.seen documents the residual:
        a dir whose files were purged but recreated can still read as
        warm)."""
        hit = self.manifest.seen(self.kernel, key,
                                 cache_dir=self.cache_dir
                                 if self.cache_dir is not None else "")
        split = {"lower_s": 0.0, "backend_s": 0.0}
        with self._events_lock:
            self._warming = split
        t0 = self._clock()
        try:
            out = thunk()
        finally:
            with self._events_lock:
                self._warming = None
        dt = self._clock() - t0
        if hit:
            self.hits += 1
        else:
            self.misses += 1
        self.shapes[key] = {"s": round(dt, 3), "hit": hit,
                            "lower_s": round(split["lower_s"], 3),
                            "backend_s": round(split["backend_s"], 3)}
        self.manifest.record(self.kernel, key, dt,
                             cache_dir=self.cache_dir)
        return out

    def wall_s(self) -> float:
        if self._wall_s is not None:
            return self._wall_s
        return self._clock() - self._t0

    def finish(self) -> None:
        """Close out the warmup: stamp the run into the manifest and
        persist it (idempotent)."""
        if self._wall_s is None:
            with self._events_lock:
                self._wall_s = self._clock() - self._t0
            self.manifest.record_run(self.kernel, self.hits, self.misses,
                                     self._wall_s)
            self.manifest.save()

    def snapshot(self) -> dict:
        """The OP_STATS ``compile`` section (JSON-safe)."""
        return {
            "kernel": self.kernel,
            "cache_dir": self.cache_dir,
            "manifest": self.manifest.path,
            "hits": self.hits,
            "misses": self.misses,
            "warm_boot": self.misses == 0 and (self.hits > 0),
            "warmup_wall_s": round(self.wall_s(), 3),
            "shapes": {k: v["s"] for k, v in sorted(self.shapes.items())},
            "lower_s": round(self._boot_split["lower_s"], 3),
            "backend_s": round(self._boot_split["backend_s"], 3),
            "split": {k: [v["lower_s"], v["backend_s"]]
                      for k, v in sorted(self.shapes.items())},
            "in_service": dict(self.in_service,
                               seconds=round(self.in_service["seconds"], 3)),
        }
