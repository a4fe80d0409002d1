"""graftlint timing checker: ``block_until_ready`` must not be the
synchronization inside a timed region of the measurement code.

The repo convention for a stage timed as ``t0 = perf_counter(); out =
fn(); <fence>; dt = perf_counter() - t0`` is to fence with a forced
device->host copy (``np.asarray(out)``): the timed region then ends
when the host HOLDS the result, which is what the engine's fetch stage
pays, and a data dependency cannot return early on any backend.
Whether ``block_until_ready()`` alone is a sound fence here: not
measured on the chip.  This rule keeps the convention mechanically in
the code that times device work (``DEFAULT_TARGETS``), so its numbers
stay comparable.

Rule:
  block-until-ready-in-timing   a ``.block_until_ready()`` call lexically
                                inside a timed region — between the first
                                and last ``time.perf_counter()`` /
                                ``time.monotonic()`` reads of the same
                                function scope (nested functions and
                                lambdas are their own scopes, so warmup
                                fences outside the timer and helpers that
                                never time anything stay legal)

Scope model is deliberately lexical, not dataflow: a timer read before
and after a statement is what makes it "timed", and the scanned
files are straight-line enough that this has no false positives on
the tree (fixtures in tests/test_analysis.py pin both
directions).
"""

from __future__ import annotations

import ast
import glob as _glob
import os

from .common import Finding, apply_suppressions, parse_source, \
    read_source

# Files whose clock reads feed optimization decisions, relative to the
# repo root (globs allowed).
DEFAULT_TARGETS = (
    # grafttrace: the obs package computes the numbers every future perf
    # claim cites — a bogus fence there poisons ALL attribution.
    "hotstuff_tpu/obs/*.py",
)

_TIMER_READS = {"perf_counter", "monotonic", "perf_counter_ns",
                "monotonic_ns"}


def _scopes(tree: ast.Module):
    """Yield (scope node, direct statements/expressions) with nested
    function/lambda bodies cut out — each function times (or doesn't)
    on its own."""
    nested = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

    def direct_nodes(root):
        out = []
        stack = [iter(ast.iter_child_nodes(root))]
        while stack:
            try:
                node = next(stack[-1])
            except StopIteration:
                stack.pop()
                continue
            if isinstance(node, nested):
                continue  # its body is a separate scope
            out.append(node)
            stack.append(iter(ast.iter_child_nodes(node)))
        return out

    yield tree, direct_nodes(tree)
    for node in ast.walk(tree):
        if isinstance(node, nested):
            yield node, direct_nodes(node)


def check_source(path: str, source: str) -> list:
    findings = []
    tree = parse_source(source, path)
    for _scope, nodes in _scopes(tree):
        timer_lines = []
        blockers = []
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute):
                if func.attr in _TIMER_READS:
                    timer_lines.append(node.lineno)
                elif func.attr == "block_until_ready":
                    blockers.append(node)
            elif isinstance(func, ast.Name) and func.id in _TIMER_READS:
                timer_lines.append(node.lineno)
        if len(timer_lines) < 2:
            continue
        lo, hi = min(timer_lines), max(timer_lines)
        for node in blockers:
            if lo < node.lineno < hi:
                findings.append(Finding(
                    path, node.lineno, "block-until-ready-in-timing",
                    "block_until_ready() inside a timed region: the repo "
                    "convention is to time until the host holds the "
                    "result; fence with a forced D2H copy — "
                    "np.asarray(out) — instead"))
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
