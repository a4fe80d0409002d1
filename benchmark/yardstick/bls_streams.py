"""The BLS request generator: a traffic mix (data) + a configuration
(data) + a seed -> the pool of aggregate-QC certificates a cell sends,
with the verdict each reply has to equal.

One certificate is what a ``scheme=bls`` replica ships as
``OP_BLS_VERIFY_VOTES`` (``native/src/crypto/crypto.cpp``): one 32-byte
digest and, for each of a seeded quorum of the committee's validators,
its 96-byte G1 key and its 192-byte G2 vote over that digest.  Every
certificate carries a fresh digest.  A forged certificate carries one
vote, at a seeded row, that its validator signed over ANOTHER digest: on
the curve and in the subgroup, so it passes every decode and only the
pairing rejects it.

Signing is ~11 ms a vote in Python integers, so the pool is built in
worker processes (``workers``), and so is the sample check, which holds
a seeded sample of certificates and every planted forgery to the plain
reference (``ref_bls12381.verify_votes``).

A mix's keys (all data, ``benchmark/traffic/<mix>.json``): ``votes``
("quorum" or a number), ``block`` ({kind: count}, the pool is whole
blocks of it in a seeded order), ``kinds`` ({kind: {"forged": 0|1}}),
``pool_blocks`` (blocks in the pool) and ``sample`` (certificates the
sample check verifies besides the forged ones).
"""

from __future__ import annotations

import multiprocessing
import random

from . import ref_bls12381 as ref
from .streams import _h, quorum, schedule

_GEN = None  # the generator the worker processes inherit


class Generator:
    """Certificates of one cell, a pure function of (mix, config, seed)."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        n = int(config["sidecar"]["committee"])
        votes = mix["votes"]
        self.votes = quorum(n) if votes == "quorum" else int(votes)
        self.sks = [int.from_bytes(_h("validator", self.seed, i), "big")
                    % (ref.R - 1) + 1 for i in range(n)]
        self.pks = [ref.g1_encode(ref.g1_mul(ref.g1_generator(), sk))
                    for sk in self.sks]

    def request(self, tag: str, index: int, kind: str) -> dict:
        """One certificate: {"kind", "msg", "pks", "sigs", "bad"};
        ``bad`` are the rows whose vote was signed over another digest,
        and the verdict has to be false exactly when there is one."""
        spec = self.mix["kinds"][kind]
        rng = random.Random(_h("request", tag, self.seed, index))
        signers = rng.sample(range(len(self.sks)), self.votes)
        msg = _h("digest", tag, self.seed, index)
        bad = sorted(rng.sample(range(self.votes), int(spec.get("forged", 0))))
        h = ref.hash_to_g2(msg)
        other = ref.hash_to_g2(_h("other digest", tag, self.seed, index)) \
            if bad else None
        sigs = [ref.g2_encode(ref.g2_mul(other if row in bad else h,
                                         self.sks[v]))
                for row, v in enumerate(signers)]
        return {"kind": kind, "msg": msg,
                "pks": [self.pks[v] for v in signers], "sigs": sigs,
                "bad": bad}

    def plan(self) -> list:
        """(tag, index, kind) of every pool certificate, in send order."""
        blocks = int(self.mix["pool_blocks"])
        return [("pool", i, kind) for i, kind in
                enumerate(schedule(self.mix, self.seed, blocks))]

    def warmup(self) -> list:
        """(tag, index, kind): one unmeasured certificate of every kind,
        with digests the pool does not hold."""
        return [("warmup", i, kind)
                for i, kind in enumerate(sorted(self.mix["kinds"]))]

    def build(self, plan: list, workers: int) -> list:
        """The certificates of ``plan``, signed in ``workers`` processes
        (forked: the workers inherit this generator)."""
        global _GEN
        _GEN = self
        if workers <= 1:
            return [_one(item) for item in plan]
        with multiprocessing.get_context("fork").Pool(workers) as pool:
            return pool.map(_one, plan, chunksize=1)


def _one(item):
    return _GEN.request(*item)


def expected(request: dict) -> bool:
    """The verdict of a certificate: true iff no vote is forged."""
    return not request["bad"]


def _verify(request: dict) -> bool:
    return ref.verify_votes(request["msg"], request["pks"], request["sigs"])


def check_sample(pool: list, seed: int, sample: int, workers: int) -> dict:
    """Hold ``sample`` seeded certificates and every forged one to the
    plain reference.  Returns {"checked", "forged", "disagreements"}
    (pool indices whose reference verdict differs from ``expected``)."""
    forged = [i for i, r in enumerate(pool) if r["bad"]]
    rest = [i for i in range(len(pool)) if not pool[i]["bad"]]
    picked = forged + random.Random(_h("sample", seed)).sample(
        rest, min(sample, len(rest)))
    requests = [pool[i] for i in picked]
    if workers <= 1:
        got = [_verify(r) for r in requests]
    else:
        with multiprocessing.get_context("fork").Pool(workers) as p:
            got = p.map(_verify, requests, chunksize=1)
    return {"checked": len(picked), "forged": len(forged),
            "disagreements": [i for i, ok in zip(picked, got)
                              if ok != expected(pool[i])]}
