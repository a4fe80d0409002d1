"""graftlint guard checker: launch supervision discipline (graftguard).

The verify engine's wedge protection rests on ONE structural invariant:
no engine-side code may block unboundedly on a staged device launch —
every dispatch/fetch future wait must route through the guard's
deadline helper (``VerifyEngine._guarded`` / ``LaunchGuard.call``), so
a hung device call becomes a declared wedge plus the
degradation ladder, never a parked engine thread with every queued
consensus verify behind it.  The type system cannot hold that
invariant; this checker holds it mechanically.

Rule:
  unsupervised-launch   an UNBOUNDED wait call — ``.result()``,
                        ``.exception()``, or ``.wait()`` with neither a
                        positional timeout nor a ``timeout=`` keyword —
                        in a guard-scanned module, outside the
                        argument subtree of a ``self._guarded(...)`` or
                        ``<...guard...>.call(...)`` call.  A bounded
                        wait (any timeout) is legal: the engine's
                        pipeline uses bounded slices precisely so
                        ``stop()`` stays observable.  Waits lexically
                        inside the thunks handed TO the guard are by
                        definition supervised (the monitor preempts
                        them), so the argument subtrees are exempt.

Worked suppressions in the real tree (both carry their evidence
inline): ``LaunchGuard.call``'s ``call.done.wait()`` — bounded by
construction, the monitor thread sets the event at every deadline
overrun — and the chaos wedge drill's deliberate
``threading.Event().wait()`` in ``VerifyEngine._guarded``, which IS the
injected hang and runs on a disposable launch thread.
"""

from __future__ import annotations

import ast
import glob as _glob
import os

from .common import Finding, apply_suppressions, parse_source, \
    read_source

# The engine and the guard itself: the two modules whose blocking
# behavior decides whether a wedge hangs the sidecar.
DEFAULT_TARGETS = (
    "hotstuff_tpu/sidecar/service.py",
    "hotstuff_tpu/sidecar/guard.py",
    # graftcadence: the ring shares the engine thread, so its blocking
    # discipline is the engine's (the ring checker adds the tick-body
    # rules on top).
    "hotstuff_tpu/sidecar/ring.py",
)

_WAIT_ATTRS = {"result", "exception", "wait"}


def _is_unbounded_wait(node: ast.Call) -> bool:
    func = node.func
    if not isinstance(func, ast.Attribute) or func.attr not in _WAIT_ATTRS:
        return False
    if node.args:
        return False  # positional timeout (Event.wait(t), cv.wait(t))
    if any(kw.arg == "timeout" for kw in node.keywords):
        return False
    return True


def _names_guard(node: ast.expr) -> bool:
    """True when an attribute/name chain mentions a guard (the
    ``self._guard`` receiver of ``.call``)."""
    while isinstance(node, ast.Attribute):
        if "guard" in node.attr.lower():
            return True
        node = node.value
    return isinstance(node, ast.Name) and "guard" in node.id.lower()


def _is_guard_entry(node: ast.Call) -> bool:
    """A call that supervises its argument thunks: ``self._guarded(...)``
    or ``<...guard...>.call(...)``."""
    func = node.func
    if isinstance(func, ast.Attribute):
        if func.attr == "_guarded":
            return True
        if func.attr == "call" and _names_guard(func.value):
            return True
    return isinstance(func, ast.Name) and func.id == "_guarded"


def check_source(path: str, source: str) -> list:
    findings = []
    tree = parse_source(source, path)
    supervised: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_guard_entry(node):
            for arg in list(node.args) + [kw.value for kw in
                                          node.keywords]:
                for child in ast.walk(arg):
                    supervised.add(id(child))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or id(node) in supervised:
            continue
        if _is_unbounded_wait(node):
            findings.append(Finding(
                path, node.lineno, "unsupervised-launch",
                f"unbounded .{node.func.attr}() wait outside the "
                "guard's deadline helper: a hung device call here "
                "parks the engine thread and every queued consensus "
                "verify behind it — route the wait through "
                "self._guarded(...) / LaunchGuard.call(...), or bound "
                "it with a timeout"))
    return findings


def check_sources(sources: dict) -> list:
    """Lint a {path: source} mapping (the unit-test entry point)."""
    findings = []
    for path, src in sources.items():
        findings += check_source(path, src)
    return sorted(apply_suppressions(findings, sources),
                  key=lambda f: (f.path, f.line))


def check(root: str, targets=DEFAULT_TARGETS) -> list:
    sources = {}
    for target in targets:
        for path in sorted(_glob.glob(os.path.join(root, target))):
            if not path.endswith(".py"):
                continue
            sources[os.path.relpath(path, root)] = read_source(path)
    return check_sources(sources)
