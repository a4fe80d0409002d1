"""graftlint CLI: ``python -m hotstuff_tpu.analysis [options]``.

Runs every registered checker (hot path, wire, sanitizer wiring, launch
shapes, timing fences, socket bounds, trace spans, thread discipline,
C++ lock discipline, verification-gate taint provenance); prints one
line per finding — or the
``graftlint-findings-v1`` JSON document under ``--json``/``--json-out``
— and exits non-zero when anything fires.  ``scripts/lint_gate.py`` is
the CI entry point.
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import os
import sys

CHECKERS = ("hotpath", "wire", "sanitize", "padshape", "timing", "sockets",
            "obsspan", "obsgrammar", "threads", "cxxsync", "ingress",
            "guard", "ring", "taint", "tenantq")


def run_all(root: str, checkers=CHECKERS) -> list:
    from . import cxxsync, guardlint, hotpath, ingress, obsgrammar, \
        obsspan, padshape, ringlint, sanitize, sockets, taint, \
        tenantlint, threads, timing, wirecheck

    findings = []
    if "hotpath" in checkers:
        findings += hotpath.check(root)
    if "wire" in checkers:
        findings += wirecheck.check(root)
    if "sanitize" in checkers:
        findings += sanitize.check(root)
    if "padshape" in checkers:
        findings += padshape.check(root)
    if "timing" in checkers:
        findings += timing.check(root)
    if "sockets" in checkers:
        findings += sockets.check(root)
    if "obsspan" in checkers:
        findings += obsspan.check(root)
    if "obsgrammar" in checkers:
        findings += obsgrammar.check(root)
    if "threads" in checkers:
        findings += threads.check(root)
    if "cxxsync" in checkers:
        findings += cxxsync.check(root)
    if "ingress" in checkers:
        findings += ingress.check(root)
    if "guard" in checkers:
        findings += guardlint.check(root)
    if "ring" in checkers:
        findings += ringlint.check(root)
    if "taint" in checkers:
        # CLI runs refresh the wire→gate→sink proof artifact alongside
        # the findings (tests call taint.check() directly, no write)
        findings += taint.check(root, map_out=taint.MAP_OUT)
    if "tenantq" in checkers:
        findings += tenantlint.check(root)
    # checkers may anchor the same missing constant from two rule paths
    seen, unique = set(), []
    for f in findings:
        key = (f.path, f.line, f.rule, f.message)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    return unique


def check_coverage(root: str, must_cover) -> list:
    """Assert each repo-relative file exists and is scanned — the gate
    for 'this new module MUST be linted' requirements.

    A pin may be checker-qualified (``hotpath:path``, ``sockets:path``,
    ``timing:path``, ``padshape:path``, ``threads:path``,
    ``cxxsync:path``) to demand coverage by THAT
    checker's target set: a device module pinned to hotpath stays
    covered-by-hotpath even though the sockets checker happens to scan
    the same directory (a union would let the hot-path scan silently
    lose a file another checker's prefix still matches).  A bare path
    accepts any checker.  scripts/lint_gate.py pins the RLC scalar
    module and the verifysched modules to hotpath, and the graftchaos
    modules to sockets."""
    from . import cxxsync, guardlint, hotpath, ingress, obsgrammar, \
        obsspan, padshape, ringlint, sockets, taint, tenantlint, \
        threads, timing
    from .common import Finding

    target_sets = {
        "hotpath": tuple(hotpath.DEFAULT_TARGETS),
        "sockets": tuple(sockets.DEFAULT_TARGETS),
        "timing": tuple(timing.DEFAULT_TARGETS),
        "padshape": tuple(padshape.DEFAULT_TARGETS),
        "obsspan": tuple(obsspan.DEFAULT_TARGETS),
        "obsgrammar": tuple(obsgrammar.DEFAULT_TARGETS),
        "threads": tuple(threads.DEFAULT_TARGETS),
        "cxxsync": tuple(cxxsync.DEFAULT_TARGETS),
        "ingress": tuple(ingress.DEFAULT_TARGETS),
        "guard": tuple(guardlint.DEFAULT_TARGETS),
        "ring": tuple(ringlint.DEFAULT_TARGETS),
        "taint": tuple(taint.DEFAULT_TARGETS),
        "tenantq": tuple(tenantlint.DEFAULT_TARGETS),
    }
    findings = []
    for pin in must_cover:
        checker, _, rel = pin.rpartition(":")
        if checker and checker not in target_sets:
            findings.append(Finding(
                rel or pin, 1, "must-cover",
                f"unknown checker {checker!r} in --must-cover pin "
                f"(have {', '.join(sorted(target_sets))})"))
            continue
        scan_targets = target_sets[checker] if checker else tuple(
            t for ts in target_sets.values() for t in ts)
        norm = rel.replace(os.sep, "/")
        if not os.path.isfile(os.path.join(root, rel)):
            findings.append(Finding(
                rel, 1, "must-cover",
                "required module is missing from the tree"))
            continue
        # Targets are files, directories, or globs (timing's
        # "hotstuff_tpu/obs/*.py"); a pin matches any of the three shapes.
        covered = any(
            norm == t or norm.startswith(t.rstrip("/") + "/")
            or fnmatch.fnmatch(norm, t)
            for t in scan_targets)
        if not covered:
            where = f"the {checker} scan targets" if checker \
                else "every lint scan target"
            findings.append(Finding(
                rel, 1, "must-cover",
                f"file is outside {where} "
                f"({', '.join(scan_targets)}); add it to the checker's "
                "DEFAULT_TARGETS or move it"))
    return findings


def _default_root() -> str:
    # hotstuff_tpu/analysis/__main__.py -> repo root
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m hotstuff_tpu.analysis",
        description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=_default_root(),
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--checker", action="append", choices=CHECKERS,
                    help="run only this checker (repeatable; default all)")
    ap.add_argument("--must-cover", action="append",
                    metavar="[CHECKER:]RELPATH",
                    help="fail unless this repo-relative file exists AND "
                         "lies inside a lint scan target — of the named "
                         "checker (hotpath/sockets) when qualified, of "
                         "any checker when bare (guards against a module "
                         "silently escaping its lint; repeatable)")
    ap.add_argument("--json", action="store_true",
                    help="print machine-readable findings JSON to stdout "
                         "instead of one line per finding (exit status "
                         "unchanged: 0 clean, 1 findings)")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="additionally write the findings JSON to PATH "
                         "(CI artifact; text output stays on stdout)")
    args = ap.parse_args(argv)
    checkers = tuple(args.checker) if args.checker else CHECKERS
    findings = run_all(args.root, checkers)
    findings += check_coverage(args.root, args.must_cover or ())
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    if args.json or args.json_out:
        doc = findings_json(findings, checkers)
        if args.json_out:
            with open(args.json_out, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, indent=2, sort_keys=True)
                fh.write("\n")
    if args.json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    else:
        for f in findings:
            print(f.render())
        if not findings:
            print(f"graftlint: clean [checkers: {', '.join(checkers)}]")
    if findings:
        print(f"graftlint: {len(findings)} finding(s) "
              f"[checkers: {', '.join(checkers)}]", file=sys.stderr)
        return 1
    return 0


def findings_json(findings, checkers) -> dict:
    """The machine-readable findings document (``--json``/``--json-out``):
    CI and future tooling consume this instead of scraping the text
    renderer, so the schema is part of the gate's contract — additive
    changes only."""
    return {
        "schema": "graftlint-findings-v1",
        "checkers": list(checkers),
        "clean": not findings,
        "findings": [
            {"rule": f.rule, "file": f.path, "line": f.line,
             "evidence": f.message}
            for f in findings
        ],
    }


if __name__ == "__main__":
    sys.exit(main())
