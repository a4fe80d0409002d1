"""Probe of the rlc_tail kernel on whatever device this process gets:
the serial tail of ops/ed25519.rlc_finish as the two lax scans
(msm_horner + comb_mul_base) against ops/kern/rlc_tail, alone and inside
verify_rlc_packed at the 67-vote quorum's bucket (128).

    chiprun --timeout 900 -- python3 scripts/probe_rlc_tail.py

Prints one JSON line (also chiprun_out/probe_rlc_tail.json): ms a call
behind block_until_ready, whether the kernel's two points equal the lax
ones limb for limb ON THIS DEVICE, and the verdicts of a valid and a
forged certificate through both tails.  A time from a run whose
``device.platform`` is not "tpu" is the interpreter's, not a device time.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref  # noqa: E402
from hotstuff_tpu.ops import ed25519 as E  # noqa: E402
from hotstuff_tpu.ops import field25519 as F  # noqa: E402
from hotstuff_tpu.ops import kern  # noqa: E402
from hotstuff_tpu.ops import scalar25519 as S  # noqa: E402

VOTES, BUCKET = 67, 128


def timed(fn, *args, calls):
    out = jax.block_until_ready(fn(*args))      # compile, warm
    t = time.perf_counter()
    for _ in range(calls):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t) / calls * 1e3, out


def certificate(forge: bool):
    msg = b"probe-rlc-tail block digest"
    keys = [ref.generate_keypair(bytes([i + 1]) * 32) for i in range(VOTES)]
    sigs = [ref.sign(sk, msg) for sk, _ in keys]
    msgs = [msg] * VOTES
    if forge:
        msgs[VOTES // 2] = b"another block digest"
    rows = eddsa.prepare_batch(msgs, [pk for _, pk in keys], sigs)["packed"]
    z = np.zeros((BUCKET, 32), np.uint8)
    z[:VOTES] = eddsa._rlc_coeffs(rows, b"")
    return jnp.asarray(np.pad(rows, [(0, BUCKET - VOTES), (0, 0)])), \
        jnp.asarray(z)


def main() -> int:
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}}
    valid, forged = certificate(False), certificate(True)

    wsums, u_sum, _bad = jax.jit(E.rlc_partials)(*valid)
    c = jax.jit(S.reduce_limbsum_mod_l)(u_sum)
    lax_ms, (m_lax, c_lax) = timed(
        jax.jit(lambda w, d: (E.msm_horner(w), E.comb_mul_base(d))),
        wsums, c, calls=100)
    comb = jnp.asarray(E.comb_table())
    w_cached = jax.jit(E.to_cached)(wsums)
    entries = comb[jnp.arange(32), c]
    kern_ms, (m_k, c_k) = timed(jax.jit(kern.rlc_tail), w_cached, entries,
                               calls=100)
    out["tail_ms"] = {"lax": lax_ms, "kernel": kern_ms}

    # One dispatch costs about as much as the kernel: k kernels chained
    # in one program (each fed by the one before), so that (t_k - t_1) /
    # (k - 1) is the kernel's own time on the device.
    def chained(k):
        def run(w, e):
            for _ in range(k):
                m, _ = kern.rlc_tail(w, e)
                w = w.at[0].set(m)
            return w
        return jax.jit(run)

    t1, _ = timed(chained(1), w_cached, entries, calls=100)
    t9, _ = timed(chained(9), w_cached, entries, calls=100)
    out["tail_ms"]["kernel_on_device"] = (t9 - t1) / 8
    out["tail_ms"]["dispatch"] = timed(
        jax.jit(lambda x: x + 1), w_cached, calls=100)[0]
    out["limb_for_limb"] = bool(
        np.array_equal(np.asarray(m_lax), np.asarray(m_k))
        and np.array_equal(np.asarray(c_lax), np.asarray(c_k)))

    # field25519.mul at batch shape () is ONE conv at precision HIGH
    # (three bf16 passes): exact while limbs fit 8 bits, which the
    # chains above keep them to but for rare carries; full-range weak
    # limbs (< 2^9, odd ones need 9 bits) are what the kernel's HIGHEST
    # pass is exact on and this may not be.
    rng = np.random.default_rng(28)
    a, b = (rng.integers(0, 512, (32,)).astype(np.int32) for _ in range(2))
    got = F.from_limbs(np.asarray(jax.jit(F.canonical)(jax.jit(F.mul)(a, b))))
    out["lax_mul_batch1_exact_on_9bit_limbs"] = \
        got == F.from_limbs(a) * F.from_limbs(b) % F.P

    # The whole program, tail as the kernel and as the lax scans.  A
    # lambda each: jit caches by function, and E.rlc_tail reads its
    # route at trace time.
    real = kern.interpret_default
    for name, interp in (("kernel", real), ("lax", lambda: True)):
        kern.interpret_default = interp
        prog = jax.jit(lambda p, z: E.verify_rlc_packed(p, z))
        ms, ok = timed(prog, *valid, calls=30)
        out[f"verify_rlc_packed_{BUCKET}_{name}"] = {
            "ms": ms, "valid": bool(ok), "forged": bool(prog(*forged))}
    kern.interpret_default = real

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_rlc_tail.json", "w") as f:
        json.dump(out, f)
    print(json.dumps(out))
    good = out["limb_for_limb"] and all(
        v["valid"] and not v["forged"]
        for k, v in out.items() if k.startswith("verify_rlc_packed"))
    return 0 if good else 1


if __name__ == "__main__":
    sys.exit(main())
