"""Probe of the BLS pairing program (ops/bls381.py pairings_check) on
whatever device this process gets, against the benchmark's plain BLS
reference (benchmark/yardstick/ref_bls12381.py).

    python3 scripts/probe_bls_pairing.py [--certs 64]

Two parts, one JSON line at the end on standard output, exit 1 unless
everything agrees:

* ``exact``: ``dbls.selfcheck()``, the Fermat inversion and an Fq12
  product at batch shape () and (1,) against Python integers, and the
  raw convolution behind a lone product at each precision;
* ``verdicts``: ``--certs`` valid certificates and as many bad ones (one
  forged vote, the wrong digest, one key swapped, in turn) through the
  sidecar's decode and served entry (``aggregate_keys``, then
  ``verify_common_apk``), each verdict held to the reference's; and for
  8 of them the final-exponentiated Fq12 value of the device, integer
  for integer, against the reference's (the device's Miller loop skips
  the BLS_X sign, so its value is the inverse of the reference's).

It times nothing: the program's and the host half's times are the
benchmark's (``qc100bls.votes``).  The certificates and the reference's
verdicts are computed in forked worker processes before this process
touches JAX.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmark")]

from yardstick import bls_streams, ref_bls12381 as ref  # noqa: E402
from yardstick.streams import _h  # noqa: E402

MIX = {"votes": "quorum", "block": {"qc": 1},
       "kinds": {"qc": {"forged": 0}, "qc_forged": {"forged": 1}}}
CONFIG = {"sidecar": {"committee": 100}}
BAD = ("forged_vote", "wrong_digest", "key_swapped")
FE_CASES = 8
FE_VALID = (6, 10, 51, 53)


def certificates(gen, n: int, workers: int) -> list:
    """n valid and n bad certificates, each {"msg", "pks", "sigs",
    "case"}."""
    plan = [("probe", i, "qc") for i in range(n)] + \
        [("probe", n + i, "qc_forged" if i % 3 == 0 else "qc")
         for i in range(n)]
    built = gen.build(plan, workers)
    out = []
    for i, cert in enumerate(built):
        case = "valid" if i < n else BAD[(i - n) % 3]
        if case == "wrong_digest":
            cert["msg"] = _h("wrong digest", i)
        elif case == "key_swapped":
            signers = set(cert["pks"])
            cert["pks"][0] = next(p for p in gen.pks if p not in signers)
        cert["case"] = case
        out.append(cert)
    return out


def _reference(cert):
    ok = ref.verify_votes(cert["msg"], cert["pks"], cert["sigs"])
    fe = None
    if cert.get("fe"):
        keys = [ref.g1_decode(p) for p in cert["pks"]]
        agg = ref.aggregate([ref.g2_decode(s) for s in cert["sigs"]])
        fe = ref.fq12_inv(ref.common_exponentiated(keys, cert["msg"], agg))
    return ok, fe


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--certs", type=int, default=64)
    ap.add_argument("--workers", type=int,
                    default=max(1, min(12, (os.cpu_count() or 2) - 1)))
    args = ap.parse_args()
    gen = bls_streams.Generator(MIX, CONFIG, 3900000001)
    certs = certificates(gen, args.certs, args.workers)
    # The valid ones: four whose norm, before pow_const reduced its
    # input, broke the final exponentiation at this seed (PERF.md §6).
    valid = [i for i in FE_VALID if i < args.certs][:FE_CASES // 2]
    valid += [i for i in range(args.certs) if i not in valid]
    half = min(FE_CASES // 2, args.certs)
    for i in valid[:half] + list(range(args.certs, args.certs + half)):
        certs[i]["fe"] = True
    with multiprocessing.get_context("fork").Pool(args.workers) as pool:
        refs = pool.map(_reference, certs, chunksize=1)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hotstuff_tpu.offchain import bls12381 as bls
    from hotstuff_tpu.ops import bls381 as dbls
    from hotstuff_tpu.ops import field381 as F
    from hotstuff_tpu.utils.xla_cache import configure_xla_cache

    configure_xla_cache()
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}}

    # Exactness where the final exponentiation runs: batch shape ().
    rng = np.random.default_rng(39)
    exact = {}
    try:
        dbls.selfcheck()
        exact["selfcheck"] = True
    except AssertionError as e:
        exact["selfcheck"] = repr(e)
    xs = [int.from_bytes(rng.bytes(48), "little") % F.Q for _ in range(4)]
    for shape in ((), (1,)):
        good = True
        for x in xs:
            a = jnp.asarray(F.to_limbs(x * F.R % F.Q)).reshape(*shape, 48)
            got = F.from_limbs(np.asarray(F.from_mont(F.inv(a))))
            good &= got == pow(x, F.Q - 2, F.Q)
        exact[f"inv_batch_{shape}"] = bool(good)
        x12 = tuple(int.from_bytes(rng.bytes(48), "little") % F.Q
                    for _ in range(12))
        y12 = tuple(int.from_bytes(rng.bytes(48), "little") % F.Q
                    for _ in range(12))
        dx = jnp.asarray(dbls.host_fq12_to_mont_limbs(x12)).reshape(
            *shape, 12, 48)
        dy = jnp.asarray(dbls.host_fq12_to_mont_limbs(y12)).reshape(
            *shape, 12, 48)
        prod = np.asarray(F.from_mont(dbls.fq12_mul(dx, dy))).reshape(12, 48)
        exact[f"fq12_mul_batch_{shape}"] = tuple(
            F.from_limbs(r) for r in prod) == bls.fq12_mul(x12, y12)
    # The raw convolution behind a lone product, at the precision a
    # batch keeps (HIGH) and at the one a lone product takes (HIGHEST),
    # with one group and with four, on weak limbs (< 2^9; 1,540 in one).
    limbs = rng.integers(0, 512, (4, 48)).astype(np.float32)
    limbs[:, 47] = 1540
    want = np.stack([np.convolve(r, r) for r in limbs])
    for prec in ("HIGH", "HIGHEST"):
        for groups in (1, 4):
            got = jax.lax.conv_general_dilated(
                jnp.asarray(limbs[:groups].reshape(1, groups, 48)),
                jnp.asarray(limbs[:groups, ::-1].reshape(groups, 1, 48)),
                window_strides=(1,), padding=[(47, 47)],
                dimension_numbers=("NCH", "OIH", "NCH"),
                feature_group_count=groups,
                precision=getattr(jax.lax.Precision, prec))
            exact[f"conv_{prec}_groups_{groups}"] = bool(np.array_equal(
                np.asarray(got).reshape(groups, 95), want[:groups]))
    out["exact"] = exact

    # Verdicts through the sidecar's decode and the device program.
    def decode(c):
        return ([bls.g1_decode(p) for p in c["pks"]],
                bls.aggregate([bls.g2_decode_lax(s) for s in c["sigs"]]))

    def lines_of(c):
        keys, agg = decode(c)
        return np.stack([
            dbls.miller_lines(dbls.aggregate_keys(keys),
                              bls.hash_to_g2(c["msg"])),
            dbls.miller_lines(bls.g1_neg(bls.g1_generator()), agg)])

    disagree, by_case = [], {}
    for i, (c, (want, _)) in enumerate(zip(certs, refs)):
        keys, agg = decode(c)
        apk = dbls.aggregate_keys(keys)
        got = bls.g2_in_subgroup(agg) and apk is not None and \
            dbls.verify_common_apk(apk, c["msg"], agg)
        by_case.setdefault(c["case"], [0, 0])[0] += 1
        if bool(got) != bool(want):
            disagree.append([i, c["case"], bool(got), bool(want)])
        else:
            by_case[c["case"]][1] += 1

    def exponentiated(lines):
        fs = dbls.miller_accumulate(jnp.moveaxis(lines, -5, 0))
        return F.from_mont(dbls.final_exponentiate(
            dbls.fq12_mul(fs[0], fs[1])))

    fe_jit = jax.jit(exponentiated)
    fe_equal = []
    for c, (_, want) in zip(certs, refs):
        if want is None:
            continue
        got = np.asarray(fe_jit(jnp.asarray(lines_of(c))))
        fe_equal.append(tuple(F.from_limbs(r) for r in got) == want)
    out["verdicts"] = {"certificates": len(certs), "by_case": by_case,
                       "disagreements": disagree,
                       "references_agree_with_cases":
                       all(bool(w) == (c["case"] == "valid")
                           for c, (w, _) in zip(certs, refs)),
                       "fe_values_equal": fe_equal}
    # A lone convolution at HIGH may be inexact (it is on the v5e): that
    # is why a lone product takes HIGHEST, so it is reported, not failed.
    out["ok"] = (all(v is True for k, v in exact.items()
                     if not k.startswith("conv_HIGH_"))
                 and not disagree and all(fe_equal)
                 and len(fe_equal) == 2 * half)
    print(json.dumps(out, default=str), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
