"""The documents against the tree.

``README.md`` says how the system is laid out and run, ``PERF.md`` §3
says where every span is written: both name files, functions and
environment variables, and a name that has left the tree misleads every
later reader (the README sent them to run ``python bench.py`` for ten
PRs after ``benchmark/run.py`` had replaced it).
"""

import ast
import os
import re

import pytest

from conftest import REPO

PKG = os.path.join(REPO, "hotstuff_tpu")
ENV_NAME = re.compile(r"HOTSTUFF_TPU_[A-Z0-9_]*[A-Z0-9]")


def _read(*parts):
    with open(os.path.join(REPO, *parts)) as f:
        return f.read()


def _py_files(path):
    """The file itself, or every ``.py`` under the directory."""
    if os.path.isfile(path):
        return [path]
    return [os.path.join(dp, f) for dp, _, fs in os.walk(path) for f in fs
            if f.endswith(".py")]


def _resolve(path, bare=False):
    """A file as the documents write it: from the repo's root or from
    ``hotstuff_tpu/``; with ``bare``, also a base name that is unique
    under ``hotstuff_tpu/``."""
    for base in (REPO, PKG):
        if os.path.isfile(os.path.join(base, path)):
            return os.path.join(base, path)
    if bare and "/" not in path:
        hits = [p for p in _py_files(PKG) if os.path.basename(p) == path]
        if len(hits) == 1:
            return hits[0]
    return None


def test_every_file_the_readme_names_exists():
    named = set(re.findall(
        r"(?<![\w/*.-])([\w./-]+\.(?:py|cpp|hpp|sh|md|json|jsonl|yml))\b",
        _read("README.md")))
    assert len(named) > 30  # the pattern still finds the README's paths
    assert sorted(p for p in named if _resolve(p) is None) == []


def _env_names_read_under(*roots):
    names = set()
    for root in roots:
        for path in _py_files(os.path.join(REPO, root)):
            names |= set(ENV_NAME.findall(_read(path)))
    return names


def test_every_env_name_in_the_readme_is_read_by_the_code():
    in_readme = set(ENV_NAME.findall(_read("README.md")))
    assert in_readme <= _env_names_read_under(
        "hotstuff_tpu", "chip_smoke.py", os.path.join("tests", "conftest.py"))


def test_every_env_name_the_package_reads_is_in_the_readme():
    in_readme = set(ENV_NAME.findall(_read("README.md")))
    assert _env_names_read_under("hotstuff_tpu") <= in_readme


def _span_rows():
    """(span, [(file, dotted name), ...]) for each row of PERF.md §3's
    span table; only sites written `` `file.py:Name` `` are checked."""
    rows, inside = [], False
    for line in _read("PERF.md").splitlines():
        if line.startswith("| span | site"):
            inside = True
        elif inside and not line.startswith("|"):
            break
        elif inside and not line.startswith("| ---"):
            span, site = [c.strip() for c in line.split("|")[1:3]]
            sites = re.findall(r"`([\w/]+\.py):([\w.]+)`", site)
            rows.append(pytest.param(sites, id=re.findall(r"`(\w+)`", span)[0]))
    return rows


def _defines(tree, dotted):
    body = tree.body
    for part in dotted.split("."):
        found = [n for n in body if isinstance(
            n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))
            and n.name == part]
        if not found:
            return False
        body = found[0].body
    return True


@pytest.mark.parametrize("sites", _span_rows())
def test_span_table_site_exists(sites):
    assert sites, "the row's site names no `file.py:Name`"
    for path, dotted in sites:
        resolved = _resolve(path, bare=True)
        assert resolved, f"{path} is not a file of the tree"
        with open(resolved) as f:
            assert _defines(ast.parse(f.read()), dotted), \
                f"{path} defines no {dotted}"
