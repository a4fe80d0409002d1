"""The seam between a deployment's file and the sidecar.

``benchmark/configs/*.json`` (read here, never edited; a new file joins
every test by itself) hands its ``sidecar`` section to ``serve()`` as
keyword arguments and states the ``route`` its traffic must take.  These
tests boot ``serve()`` with exactly those arguments and the verify entry
points stubbed to all-true masks, so nothing compiles, and hold the
warm-up plan, the warmed-shape registry and the route to what the file
and PERF.md §4 state.  The plan tests are the net under any rewrite of
warm-up (ROADMAP D14).
"""

import glob
import inspect
import json
import os

import numpy as np
import pytest

from conftest import REPO, boot_without_serving
from hotstuff_tpu.crypto import eddsa
from hotstuff_tpu.ops import bls381
from hotstuff_tpu.sidecar import service
from hotstuff_tpu.sidecar.sched.shapes import quorum_sigs

BENCH = os.path.join(REPO, "benchmark")


def _load(path):
    with open(path) as f:
        return json.load(f)


CONFIGS = {c["name"]: c for c in map(
    _load, sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))))}
RLC_CONFIGS = sorted(n for n, c in CONFIGS.items() if c["route"] == "rlc")

# PERF.md §4: "ten shapes" (qc100, and qc100f33 whose programs are
# qc100's), "eight ladder shapes" (eddsa1024, and ingress20 whose
# programs are eddsa1024's), "two shapes" (qc100bls: the ladder at 8 and
# the pairing program).
STATED_PLAN_SIZE = {"qc100": 10, "qc100f33": 10, "eddsa1024": 8,
                    "ingress20": 8, "qc100bls": 2}


def _widths(name):
    """Signatures a request of each of this configuration's cells: the
    traffic's ``votes``, a number or the committee's quorum."""
    out = []
    for cell in _load(os.path.join(REPO, "BENCHMARK.json"))["workloads"]:
        if cell["config"] != name:
            continue
        votes = _load(os.path.join(
            BENCH, "traffic", cell["traffic"] + ".json"))["votes"]
        out.append(quorum_sigs(CONFIGS[name]["sidecar"]["committee"])
                   if votes == "quorum" else int(votes))
    return out


def _stated_plan(sidecar):
    """The keys of PERF.md §4's plan: the per-signature ladder 8 ..
    ``warm_max``, then under ``warm_bls`` the pairing program, then under
    ``warm_rlc`` the one-MSM program at the ladder's buckets."""
    buckets = [8]
    while buckets[-1] * 2 <= sidecar["warm_max"]:
        buckets.append(buckets[-1] * 2)
    keys = [f"warmup:{n}" for n in buckets]
    if sidecar.get("warm_bls"):
        keys.append("bls:pairing")
    if sidecar.get("warm_rlc"):
        keys += [f"rlc:{n}" for n in buckets]
    return keys


@pytest.fixture
def booted(request, monkeypatch, tmp_path):
    """(the configuration's name; the keys ``_warmed`` was called with,
    in order; the engine) after a boot with the configuration's own
    ``serve()`` arguments."""
    def all_true(*args, **kw):
        msgs = args[-3]
        return np.ones(len(msgs), bool)

    monkeypatch.setattr(service.VerifyEngine, "_verify", all_true)
    monkeypatch.setattr(eddsa, "verify_batch_rlc", all_true)
    monkeypatch.setattr(bls381, "selfcheck", lambda: None)
    monkeypatch.setattr(bls381, "verify_common_apk",
                        lambda apk, msg, agg: True)
    keys = []
    warmed = service._warmed

    def recording(engine, key, thunk):
        keys.append(key)
        return warmed(engine, key, thunk)

    monkeypatch.setattr(service, "_warmed", recording)
    engine = boot_without_serving(
        monkeypatch, tmp_path, **CONFIGS[request.param]["sidecar"])
    return request.param, keys, engine


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_sidecar_key_is_a_serve_parameter(name):
    assert set(CONFIGS[name]["sidecar"]) <= \
        set(inspect.signature(service.serve).parameters)


@pytest.mark.parametrize("booted", sorted(CONFIGS), indirect=True)
def test_warm_up_plan_is_the_stated_one(booted):
    name, keys, engine = booted
    assert keys == _stated_plan(CONFIGS[name]["sidecar"])
    assert len(keys) == STATED_PLAN_SIZE.get(name, len(keys))
    # Every shape went through the compile tracker: OP_STATS ``compile``
    # and the manifest account for the whole plan.
    assert list(engine.compile_tracker.shapes) == keys


@pytest.mark.parametrize("booted", sorted(CONFIGS), indirect=True)
def test_traffic_width_takes_the_configured_route(booted):
    name, keys, engine = booted
    widths = _widths(name)
    assert widths, f"no cell of BENCHMARK.json runs {name}"
    if CONFIGS[name]["route"] == "bls_pairing":
        # A BLS certificate takes the pairing program by its opcode,
        # whatever its width: the program has to be warm.
        assert "bls:pairing" in keys
        return
    for n in widths:
        assert engine._shapes.route(n) == CONFIGS[name]["route"], n


def _resolution_reach(m):
    """(route, bucket) of every device program a certificate with m
    canonical rows may run (``eddsa.verify_batch_rlc_pack``): under
    RLC_MIN_MSM the per-signature program from the start; else the
    combined check and, when it fails, ONE per-signature program over
    the same rows, at the same bucket."""
    if m < eddsa.RLC_MIN_MSM:
        return {("per_sig", eddsa._bucket(m))} if m else set()
    return {("rlc", eddsa._bucket(m)), ("per_sig", eddsa._bucket(m))}


@pytest.mark.parametrize("booted", RLC_CONFIGS, indirect=True)
def test_every_shape_a_bisection_can_reach_is_warmed(booted):
    """A failed combined check must never build a program while serving
    (``compile.in_service`` stays 0): the per-signature bucket of the
    traffic's width is a shape the boot warmed — and so are both
    programs at every narrower bucket, which a certificate reaches when
    the host refuses some of its rows as non-canonical."""
    name, keys, engine = booted
    for width in _widths(name):
        if width == 67:
            assert _resolution_reach(width) == {("rlc", 128),
                                                ("per_sig", 128)}
        reach = set().union(*map(_resolution_reach, range(width + 1)))
        assert {b for _, b in reach} == set(engine._shapes.buckets)
        for route, bucket in sorted(reach):
            if route == "rlc":
                assert f"rlc:{bucket}" in keys
                assert bucket in engine._shapes.rlc_buckets
            else:
                assert f"warmup:{bucket}" in keys
                assert bucket in engine._shapes.buckets
