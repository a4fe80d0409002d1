"""The one general request generator: a traffic mix (data) + a
configuration (data) + a seed -> the pool of verify requests a cell
replays, with the verdict mask each reply has to equal.

Modelled on ``chip_smoke.py`` (``make_validators``, ``certificate``,
``forge_vote``); copied, not imported, so the yardstick does not move
with the program.  Signing uses ``cryptography`` (OpenSSL Ed25519: RFC
8032 signatures are deterministic, so it gives the bytes the plain
reference's ``sign`` gives, ~50 us against ~3 ms); what decides
``correct`` is the plain reference (``ref_ed25519.verify``) over a
seeded sample, and the planted forgeries.

A mix's keys (all data, ``benchmark/traffic/<mix>.json``):

  votes       "quorum" (2N/3+1 of the configuration's committee, the
              node's own formula) or a number of signatures a request
  keys        "committee": each request is signed by a seeded subset of
              the committee's N validators; "distinct": a fresh key a
              signature
  block       {kind: count}: the pool is whole blocks, every block holds
              exactly these counts in a seeded order, so every seed and
              every stretch of the stream carries the same work
  kinds       {kind: {"messages": "common" | "distinct", "forged": 0|1}}
              common: one fresh 32-byte digest a request (a QC);
              distinct: a message a signature (a TC, an off-chain batch);
              forged: signatures with one bit of S flipped, at seeded rows
  pool_min_records   the pool is the least number of whole blocks whose
              records exceed this (twice the sidecar's verdict cache, so
              a replayed record has always been evicted)
"""

from __future__ import annotations

import hashlib
import random

from cryptography.hazmat.primitives import serialization as _ser
from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PrivateKey

from . import ref_ed25519 as ref


def _h(*parts) -> bytes:
    return hashlib.sha512(
        b"|".join(str(p).encode() for p in parts)).digest()[:32]


def quorum(n: int) -> int:
    """The node's own formula (native/src/consensus/config.hpp)."""
    return 2 * n // 3 + 1


class Signer:
    """One Ed25519 key from a 32-byte secret seed."""

    def __init__(self, secret: bytes):
        self._key = Ed25519PrivateKey.from_private_bytes(secret)
        self.pk = self._key.public_key().public_bytes(
            _ser.Encoding.Raw, _ser.PublicFormat.Raw)

    def sign(self, msg: bytes) -> bytes:
        return self._key.sign(msg)


def forge(sig: bytes) -> bytes:
    """The signature with one bit of S flipped."""
    return sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]


def votes_per_request(mix: dict, config: dict) -> int:
    votes = mix["votes"]
    if votes == "quorum":
        return quorum(int(config["sidecar"]["committee"]))
    return int(votes)


def pool_blocks(mix: dict, votes: int) -> int:
    """Least number of whole blocks whose records exceed
    ``pool_min_records``."""
    per_block = sum(mix["block"].values()) * votes
    need = int(mix["pool_min_records"])
    return max(1, -(-(need + 1) // per_block))


def schedule(mix: dict, seed: int, blocks: int) -> list:
    """The kinds of the pool's requests in order: ``blocks`` blocks, each
    the mix's counts shuffled by the seed."""
    kinds = []
    for b in range(blocks):
        block = [k for k, n in sorted(mix["block"].items())
                 for _ in range(int(n))]
        random.Random(_h("order", seed, b)).shuffle(block)
        kinds += block
    return kinds


class Generator:
    """Requests of one cell, a pure function of (mix, config, seed)."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self.votes = votes_per_request(mix, config)
        self.committee = None
        if mix["keys"] == "committee":
            n = int(config["sidecar"]["committee"])
            self.committee = [Signer(_h("validator", self.seed, i))
                              for i in range(n)]
        elif mix["keys"] != "distinct":
            raise ValueError(f"unknown keys rule {mix['keys']!r}")

    def request(self, tag: str, index: int, kind: str) -> dict:
        """One request: {"kind", "msgs", "pks", "sigs", "bad"}; ``bad``
        are the rows whose verdict has to be false."""
        spec = self.mix["kinds"][kind]
        rng = random.Random(_h("request", tag, self.seed, index))
        n = self.votes
        if self.committee is not None:
            signers = rng.sample(self.committee, n)
        else:
            signers = [Signer(_h("key", tag, self.seed, index, i))
                       for i in range(n)]
        if spec["messages"] == "common":
            msgs = [_h("digest", tag, self.seed, index)] * n
        elif spec["messages"] == "distinct":
            msgs = [_h("message", tag, self.seed, index, i)
                    for i in range(n)]
        else:
            raise ValueError(f"unknown messages rule {spec['messages']!r}")
        sigs = [s.sign(m) for s, m in zip(signers, msgs)]
        bad = sorted(rng.sample(range(n), int(spec.get("forged", 0))))
        for row in bad:
            sigs[row] = forge(sigs[row])
        return {"kind": kind, "msgs": msgs, "pks": [s.pk for s in signers],
                "sigs": sigs, "bad": bad}

    def pool(self) -> list:
        blocks = pool_blocks(self.mix, self.votes)
        return [self.request("pool", i, kind) for i, kind in
                enumerate(schedule(self.mix, self.seed, blocks))]

    def warmup(self, connection: int) -> list:
        """One unmeasured request of every kind of the mix for one
        connection, from records the pool does not hold."""
        return [self.request(f"warmup{connection}", i, kind)
                for i, kind in enumerate(sorted(self.mix["kinds"]))]


def expected_mask(request: dict) -> list:
    bad = set(request["bad"])
    return [i not in bad for i in range(len(request["msgs"]))]


def check_sample(pool: list, seed: int, sample: int = 256) -> dict:
    """Hold a seeded sample of at least ``sample`` records, every planted
    forgery among them, to the plain reference: a forged row must verify
    false and every other row true.  Returns {"checked", "forged",
    "disagreements"}."""
    rows = [(i, j) for i, r in enumerate(pool) for j in r["bad"]]
    forged = len(rows)
    rng = random.Random(_h("sample", seed))
    while len(rows) < forged + sample:
        i = rng.randrange(len(pool))
        rows.append((i, rng.randrange(len(pool[i]["msgs"]))))
    disagreements = []
    for i, j in rows:
        r = pool[i]
        want = j not in r["bad"]
        if bool(ref.verify(r["pks"][j], r["msgs"][j], r["sigs"][j])) != want:
            disagreements.append([i, j])
    return {"checked": len(rows), "forged": forged,
            "disagreements": disagreements}
