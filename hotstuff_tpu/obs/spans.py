"""Span records + the buffered JSONL tracer the sidecar hot path writes
through.

One span = one JSON object on its own line::

    {"stage": "pack", "id": 31, "t0": 1722600000.1188, "t": 1722600000.1230,
     "dur_ms": 4.2, "lid": 9, "parent": null, "rids": [17], ...}

One meaning, however the span was written (``record``,
``begin_span``/``end_span`` or ``span``): ``t`` is the span's END and
``t0`` its START, both epoch seconds on the tracer's one clock, and
``dur_ms`` = (``t`` - ``t0``) * 1e3.  ``benchmark/yardstick/
trace_reduce.span_intervals`` and ``obs/trace.chrome_trace`` read them
so.

One clock: a tracer takes ONE anchor at construction — the wall-clock
epoch and the monotonic clock together — and ``now()`` returns
``anchor_wall + (monotonic - anchor_mono)``: stamps are epoch seconds
(the merger aligns wall clocks across hosts, the benchmark maps them onto
the device trace) that a wall-clock step during a run cannot move against
each other.  An injected ``clock`` replaces the pair (virtual-clock
tests); instrumented code reads time through ``now()`` only.

Cause and identity: every span has an integer ``id`` (``next_id()``
hands them out, so a child written first can name a parent written
later).  Per-request spans (``request``, ``decode``, ``queue``,
``reply``) carry ``rid`` and ``parent`` = the id of the request's
``request`` span; launch-scope spans carry ``lid``, the engine's launch
counter — ``pack``, ``dispatch`` and ``device`` with ``parent`` null,
their children (``h2d``, ``fetch_wait``, ``d2h``, ``bisect``) with
``parent`` = the id of the launch's ``device`` span
(:class:`LaunchScope`), and a ``bisect``'s own children
(``bisect_step``, one a device program the resolution ran) with
``parent`` = the id of that ``bisect`` span.  A request's ``queue``
span names the ``lid`` it left for and a ``pack`` span the ``rids`` it
coalesced, so a request can be followed through a coalesced launch.
Everything else is free-form tags.

The sink keeps spans in memory: a record is appended to a list under the
lock — no ``json.dumps``, no I/O under it — and ``close()`` writes them.
The buffer is bounded at ``BUFFER_SPANS`` records (8192: a traced 20 s
window of one closed-loop connection leaves ~4,400, so no write-out
falls inside it; ~1.4 MB of JSONL).  The call that fills it swaps the
list out under the lock and serialises and writes it OUTSIDE the lock,
under a separate I/O lock it only ever tries for: that one thread pays
the write-out (PERF.md §6 has the measured cost), and a call that finds
a write-out under way appends and returns, so no span site waits on
another thread's ``json.dumps`` or file write — the buffer overshoots
the bound by what arrives meanwhile.  A long committee run cannot grow
without limit.  A sidecar killed outright (SIGKILL, or SIGTERM before
it serves) loses at most what the buffer held — the chaos drills read
spans for notes, not verdicts; SIGTERM on a serving sidecar process
reaches ``close()`` through ``serve()``'s ``finally``
(``sidecar/service.py`` ``_ExitOnSigterm``).

Discipline (enforced mechanically by graftlint's ``unclosed-span``
checker over the obs-instrumented modules):

  * a ``begin_span`` must reach its ``end_span`` on every return path —
    use the ``span()`` context manager, or pair them in a ``finally``;
  * timestamps come from the INJECTED clock only (``clock=`` at
    construction), never an inline ``time.time()`` — virtual-clock
    tests and the trace merger's offset math both depend on one
    substitutable time source per process.

Telemetry is best-effort by contract: a tracer whose sink fails (disk
full, path unwritable) disables itself when it finds out — at the first
write-out — counts what it held as ``dropped``, and the engine keeps
verifying: spans must never take the data plane down with them.
"""

from __future__ import annotations

import itertools
import json
import threading
from time import monotonic as _mono_clock
from time import time as _wall_clock


class SpanError(ValueError):
    """Malformed span record (parse-side only; writers never raise)."""


def anchored_clock(wall=_wall_clock, mono=_mono_clock):
    """Epoch seconds that advance with the monotonic clock: one anchor
    of both, taken now."""
    anchor_wall, anchor_mono = wall(), mono()
    return lambda: anchor_wall + (mono() - anchor_mono)


class _NullStage:
    """What a disabled scope's ``stage()`` hands out: enters to None,
    reads no clock, allocates nothing."""

    __slots__ = ()
    id = None

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_STAGE = _NullStage()


class Tracer:
    """Thread-safe buffered JSONL span writer.

    ``Tracer(None)`` (or ``Tracer.disabled()``) is the null tracer:
    every call is a cheap no-op, so instrumented code needs no
    ``if tracing:`` guards at the call sites (sites that would build
    tags or read ``now()`` themselves do gate on ``enabled``).

    ``annotation`` is an optional ``(name, **kw) -> context manager``
    factory — ``jax.profiler.TraceAnnotation`` on device boots — that
    :meth:`LaunchScope.stage` opens beside each launch-scope span, so a
    profiler session holds the sidecar's stages on the trace's own clock.
    """

    BUFFER_SPANS = 8192

    def __init__(self, path: str | None, clock=None, annotation=None):
        self._path = path
        self._clock = clock if clock is not None else anchored_clock()
        self._annotation = annotation
        self._lock = threading.Lock()
        self._io_lock = threading.Lock()  # one write-out at a time, in order
        self._buf: list = []
        self._ids = itertools.count(1)
        self.enabled = path is not None
        self.dropped = 0  # spans lost to sink failures (telemetry)

    @classmethod
    def disabled(cls) -> "Tracer":
        return cls(None)

    # -- recording -----------------------------------------------------------

    def now(self) -> float:
        """The tracer's clock (for cross-thread duration bookkeeping —
        the one sanctioned way instrumented code reads time)."""
        return self._clock()

    def next_id(self) -> int:
        """A fresh span id, for a parent that is written after its
        children (a ``request`` or ``device`` span)."""
        return next(self._ids)

    def record(self, stage: str, t0: float, t: float | None = None,
               id: int | None = None, **tags):  # noqa: A002 — the field
        """Write one span that started at ``t0`` (a ``now()`` reading)
        and ended at ``t`` (default: now)."""
        if not self.enabled:
            return
        if t is None:
            t = self._clock()
        rec = {"stage": stage,
               "id": id if id is not None else next(self._ids),
               "t0": t0, "t": t, "dur_ms": round((t - t0) * 1e3, 3)}
        rec.update(tags)
        self._write(rec)

    def begin_span(self, stage: str, **tags) -> dict:
        """Open a span; the returned token MUST reach :meth:`end_span`
        on every return path (use :meth:`span` where control flow
        allows)."""
        if not self.enabled:
            return {}
        token = {"stage": stage, "id": next(self._ids),
                 "t0": self._clock()}
        token.update(tags)
        return token

    def end_span(self, token: dict, **tags):
        """Close a span begun by :meth:`begin_span` and write it."""
        if not self.enabled or not token:
            return
        rec = dict(token)
        rec.update(tags)
        rec["t"] = self._clock()
        rec["dur_ms"] = round((rec["t"] - rec["t0"]) * 1e3, 3)
        self._write(rec)

    def span(self, stage: str, **tags):
        """``with tracer.span("pack", rid=7): ...`` — begin/end pairing
        the interpreter guarantees."""
        return _SpanCtx(self, stage, tags)

    def launch(self, lid: int) -> "LaunchScope":
        """This tracer bound to launch ``lid`` (the null scope when
        disabled)."""
        if not self.enabled:
            return NO_LAUNCH
        return LaunchScope(self, lid)

    # -- sink ----------------------------------------------------------------

    def _write(self, rec: dict):
        with self._lock:
            if not self.enabled:
                return
            self._buf.append(rec)
            full = len(self._buf) >= self.BUFFER_SPANS
        if full and self._io_lock.acquire(blocking=False):
            try:
                self._write_out()
            finally:
                self._io_lock.release()

    def _write_out(self):
        """Swap the buffer out under the lock; serialise and append it to
        the file outside it (the caller holds the I/O lock, so write-outs
        reach the file in the order they were swapped)."""
        with self._lock:
            buf, self._buf = self._buf, []
        lines = []
        lost = 0
        for rec in buf:
            try:
                lines.append(json.dumps(rec, sort_keys=True))
            except (TypeError, ValueError):
                lost += 1
        dead = False
        if lines:
            try:
                with open(self._path, "a", encoding="utf-8") as f:
                    f.write("\n".join(lines) + "\n")
            except OSError:
                dead = True
        if lost or dead:
            with self._lock:
                self.dropped += lost
                if dead:
                    # Sink gone: disable forever, never stall the engine.
                    self.dropped += len(lines) + len(self._buf)
                    self._buf = []
                    self.enabled = False

    def close(self):
        with self._io_lock:
            if self.enabled:
                self._write_out()
            with self._lock:
                self.enabled = False
                self.dropped += len(self._buf)  # raced in behind the swap
                self._buf = []


class LaunchScope:
    """A tracer bound to one launch: its ``lid`` and the id its
    ``device`` span will carry, so the launch's children — written from
    the pack worker and the guard's launch threads, before the ``device``
    span exists — can name it.  The engine makes one a launch
    (:meth:`Tracer.launch`) and hands it down to the pack functions;
    ``crypto/`` sees nothing else of the sidecar.  ``pack_end`` is the
    one stamp that crosses threads through it: the pack worker leaves the
    end of the ``pack`` span there, and the dispatch site reads from it
    how long it waited for the pack.  ``bucket`` crosses the same way:
    the padded rows of the program the pack staged, for the ``device``
    span."""

    __slots__ = ("_tracer", "lid", "device_id", "enabled", "pack_end",
                 "bucket")

    def __init__(self, tracer: Tracer | None, lid: int | None):
        self._tracer = tracer
        self.lid = lid
        self.enabled = tracer is not None
        self.device_id = tracer.next_id() if tracer is not None else None
        self.pack_end = None
        self.bucket = None

    def now(self) -> float:
        return self._tracer.now()

    def record(self, stage: str, t0: float, t: float | None = None,
               id: int | None = None, **tags):  # noqa: A002 — the field
        """A launch-scope span with ``parent`` null (``pack``,
        ``dispatch``, ``device``)."""
        if self.enabled:
            self._tracer.record(stage, t0, t, id=id, lid=self.lid,
                                parent=None, **tags)

    def stage(self, stage: str, parent: int | None = None):
        """``with scope.stage("h2d") as tags:`` — one child span of the
        launch's ``device`` span around the block, with the profiler
        annotation ``sidecar:<stage>`` beside it where the tracer has
        one.  ``tags`` is a dict the block may add to (``tags["bytes"]
        = ...``), or None when tracing is off: guard tag building on
        it.  The object handed out has the span's ``id`` (None when
        tracing is off), which a grandchild names as its ``parent``: a
        ``bisect_step`` is the child of its ``bisect`` span."""
        if not self.enabled:
            return _NULL_STAGE
        return _StageCtx(self, stage, parent)

    def annotate(self, stage: str):
        """The profiler annotation alone (for a span the caller records
        itself), or a no-op."""
        if self.enabled and self._tracer._annotation is not None:
            return self._tracer._annotation(f"sidecar:{stage}",
                                            lid=self.lid)
        return _NULL_STAGE


NO_LAUNCH = LaunchScope(None, None)


class _StageCtx:
    __slots__ = ("_scope", "_stage", "_tags", "_t0", "_annot", "_parent",
                 "id")

    def __init__(self, scope: LaunchScope, stage: str,
                 parent: int | None = None):
        self._scope = scope
        self._stage = stage
        self._parent = parent if parent is not None else scope.device_id
        self.id = scope._tracer.next_id()
        self._tags: dict = {}
        self._t0 = 0.0
        self._annot = None

    def __enter__(self):
        self._annot = self._scope.annotate(self._stage)
        self._annot.__enter__()
        self._t0 = self._scope.now()
        return self._tags

    def __exit__(self, exc_type, exc, tb):
        scope = self._scope
        t = scope.now()
        self._annot.__exit__(exc_type, exc, tb)
        if exc_type:
            self._tags["error"] = True
        scope._tracer.record(self._stage, self._t0, t, id=self.id,
                             lid=scope.lid, parent=self._parent,
                             **self._tags)
        return False


class _SpanCtx:
    __slots__ = ("_tracer", "_stage", "_tags", "_token")

    def __init__(self, tracer: Tracer, stage: str, tags: dict):
        self._tracer = tracer
        self._stage = stage
        self._tags = tags
        self._token = {}

    def __enter__(self):
        self._token = self._tracer.begin_span(self._stage, **self._tags)
        return self._token

    def __exit__(self, exc_type, exc, tb):
        self._tracer.end_span(self._token,
                              **({"error": True} if exc_type else {}))
        return False


def parse_jsonl(text: str, valid):
    """JSONL text -> ``(records, malformed)`` with ``valid(rec)`` as the
    per-record predicate (records are always dicts by the time it runs).

    This is THE torn-line tolerance contract for the whole obs package
    (spans and metrics share it): concurrent writers, or a chaos SIGKILL
    mid-line, can tear lines; torn/garbage lines are skipped and
    counted, never raised — the same contract as the LogParser's log
    sanitizer."""
    records = []
    malformed = 0
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            malformed += 1
            continue
        if not isinstance(rec, dict) or not valid(rec):
            malformed += 1
            continue
        records.append(rec)
    return records, malformed


def parse_spans(text: str):
    """JSONL span text -> ``(spans, malformed)`` (torn lines skipped and
    counted; see :func:`parse_jsonl`).  A span has a ``stage`` and both
    stamps, ``t0`` and ``t``."""
    return parse_jsonl(
        text,
        lambda rec: "stage" in rec
        and isinstance(rec.get("t"), (int, float))
        and isinstance(rec.get("t0"), (int, float)))
