"""CPU time of the sidecar's Python threads by role, read at OP_STATS
time and never on the request path.

Each sidecar thread body enters the registry with its role and leaves it
as it ends:

  ``engine``  the ``verify-engine`` thread;
  ``launch``  every disposable ``guard-launch-*`` thread
              (``sidecar/guard.py``): the jitted calls' host work and
              the fetches (the verdicts' ``bool``, ``cache_insert``,
              the fan-out);
  ``pack``    the ``verify-pack`` worker (the executor's initializer);
  ``conn``    a connection's handler thread (``_Handler.setup`` /
              ``finish``) and its ``sidecar-conn-writer``.

Entering takes the thread's CPU clock id (``time.pthread_getcpuclockid``
of its own ident, while it runs); leaving reads the thread's CPU time
once and adds it to the role's retired total.  That is all a thread pays
while nobody asks.  :meth:`ThreadCpu.snapshot` adds to the retired
totals ``time.clock_gettime`` of each live thread's clock id.  The read
goes by the id alone, never through a thread descriptor, so a thread
that ended without leaving reads as ``OSError`` and keeps the last value
read for it.  Threads the registry does not know (the runner's, the
profiler's, XLA's native ones) are not the sidecar's Python and are not
counted.

A thread's CPU clock runs while the thread is on a core, in user or
kernel mode (a socket write, a futex), whether it holds the GIL or not
(a jitted call that released it counts).  So the
roles' CPU seconds over the wall clock, ``python_cpu_share``, say how
much of one core the sidecar's Python threads used, and can pass 1.0;
they are not the share of time the GIL was held.
"""

from __future__ import annotations

import threading
import time

ROLES = ("engine", "launch", "pack", "conn")


class ThreadCpu:
    """The registry: the role and clock id of each live thread, the CPU
    seconds of the retired ones.  ``thread_clock`` (the calling thread's
    CPU seconds) and ``read`` (a clock id's seconds) are injectable for
    tests."""

    def __init__(self, thread_clock=time.thread_time,
                 read=time.clock_gettime):
        self._thread_clock = thread_clock
        self._read = read
        self._lock = threading.Lock()
        self._live: dict = {}  # ident -> [role, clock id, last read]
        self._retired = dict.fromkeys(ROLES, 0.0)

    def enter(self, role: str):
        """The calling thread's body starts, as ``role``."""
        ident = threading.get_ident()
        entry = [role, time.pthread_getcpuclockid(ident), 0.0]
        with self._lock:
            self._live[ident] = entry

    def leave(self):
        """The calling thread's body ends: its CPU seconds go to its
        role's retired total."""
        now = self._thread_clock()
        with self._lock:
            entry = self._live.pop(threading.get_ident(), None)
            if entry is not None:
                self._retired[entry[0]] += now

    def snapshot(self) -> dict:
        """CPU seconds by role: the retired threads' plus the live
        threads' as of now."""
        with self._lock:
            for ident, entry in list(self._live.items()):
                try:
                    entry[2] = self._read(entry[1])
                except OSError:  # ended without leave(): its last read
                    del self._live[ident]
                    self._retired[entry[0]] += entry[2]
            out = dict(self._retired)
            for role, _, last in self._live.values():
                out[role] += last
        return out


class CpuWindow:
    """The OP_STATS ``host`` section over one registry: CPU seconds by
    role and the engine's launch counter since boot, and an ``interval``
    over the time since the previous call, from any client (so the
    benchmark's snapshot at a window's end covers exactly the window
    since its snapshot at the start, as long as nobody else asks
    between them).  The cumulative fields alone are stateless: a
    reader that holds two snapshots can difference them itself."""

    def __init__(self, cpu: ThreadCpu, wall=time.monotonic):
        self._cpu = cpu
        self._wall = wall
        self._lock = threading.Lock()
        self._prev = (wall(), 0, cpu.snapshot())

    def section(self, launches: int) -> dict:
        with self._lock:
            now, cpu = self._wall(), self._cpu.snapshot()
            (t0, launches0, cpu0), self._prev = self._prev, \
                (now, launches, cpu)
        wall_s, n = now - t0, launches - launches0
        used = {role: cpu[role] - cpu0[role] for role in ROLES}
        python_s = sum(used.values())
        interval = {"wall_s": round(wall_s, 6), "launches": n}
        if wall_s > 0:
            interval["python_cpu_share"] = round(python_s / wall_s, 4)
        if n > 0:
            for role in ROLES:
                interval[f"{role}_cpu_ms_per_launch"] = \
                    round(used[role] * 1e3 / n, 4)
            interval["python_cpu_ms_per_launch"] = \
                round(python_s * 1e3 / n, 4)
        return {"cpu_s": {role: round(cpu[role], 6) for role in ROLES},
                "launches": launches, "interval": interval}
