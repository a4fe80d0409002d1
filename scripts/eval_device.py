"""Reliable device-cost eval for verify_packed: slope between G=2 and G=10
chunked-scan calls (cancels fixed per-dispatch overhead), min over trials
(cancels latency spikes).  Prints one number: device ms per 1024-batch.

--trace DIR additionally captures a jax.profiler trace of one chunked
dispatch (SURVEY §5.1: device-side profiling for the verify kernel) for
TensorBoard / xprof inspection.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
from hotstuff_tpu.ops import ed25519 as E

N = 1024


def make_big(packed_np, G):
    return jnp.asarray(np.broadcast_to(packed_np, (G, N, 128)).copy())


def measure(packed_np, G, trials=5, reps=3):
    verify_chunked = E.verify_packed_chunked_jit  # the shipped program

    big = make_big(packed_np, G)
    out = verify_chunked(big)
    assert np.asarray(out).all()
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = verify_chunked(big)
        np.asarray(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", metavar="DIR",
                    help="also write a jax.profiler trace of one chunked "
                         "dispatch to DIR")
    args = ap.parse_args()

    rng = np.random.default_rng(7)
    msgs, pks, sigs = [], [], []
    for _ in range(N):
        sk = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        m = rng.bytes(64)
        msgs.append(m)
        pks.append(pk)
        sigs.append(ref.sign(sk, m))
    prep = eddsa.prepare_batch(msgs, pks, sigs)
    packed_np = prep["packed"]

    t2 = measure(packed_np, 2)
    t10 = measure(packed_np, 10)
    slope = (t10 - t2) / 8
    print(f"G2 {t2*1e3:.2f} ms, G10 {t10*1e3:.2f} ms")
    print(f"DEVICE {slope*1e3:.2f} ms/1024  ({N/slope:,.0f} sigs/s ceiling)")

    if args.trace:
        # Trace the G=10 shape measure() already compiled, so the capture
        # holds ONE warm device dispatch — not a cold XLA compile.
        big = make_big(packed_np, 10)
        with jax.profiler.trace(args.trace):
            np.asarray(E.verify_packed_chunked_jit(big))
        print(f"profiler trace written to {args.trace}")


if __name__ == "__main__":
    main()
