"""Device-side profiling of the Ed25519 verify kernel (SURVEY §5.1 TPU add).

Times each stage of the verification pipeline separately on the real chip:
host preparation, H2D transfer, decompression, the digit unpack, the
256-step ladder, and the full fused program — to locate where the batch
latency actually goes before optimizing.  Run: python scripts/profile_verify.py
Optionally dumps a jax profiler trace with --trace (view offline).
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp
import numpy as np


def timeit(fn, *args, reps=10, warmup=2):
    # Force a D2H copy to synchronize: the repo's timing fence
    # (analysis/timing.py) — the region ends when the host holds the
    # result.
    for _ in range(warmup):
        out = fn(*args)
    np.asarray(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    np.asarray(out)
    return (time.perf_counter() - t0) / reps


def main():
    from hotstuff_tpu.crypto import eddsa, ref_ed25519 as ref
    from hotstuff_tpu.ops import ed25519 as E
    from hotstuff_tpu.ops import field25519 as F

    N = 1024
    rng = np.random.default_rng(7)
    msgs, pks, sigs = [], [], []
    for _ in range(N):
        sk = rng.bytes(32)
        _, pk = ref.generate_keypair(sk)
        m = rng.bytes(64)
        msgs.append(m)
        pks.append(pk)
        sigs.append(ref.sign(sk, m))

    # --- host prep ---
    t0 = time.perf_counter()
    prep = eddsa.prepare_batch(msgs, pks, sigs)
    t_prep = time.perf_counter() - t0
    print(f"host prepare_batch      : {t_prep*1e3:8.2f} ms  "
          f"({N/t_prep:,.0f} sigs/s host-bound)")

    packed_np = prep["packed"]

    # --- H2D transfer ---
    t = timeit(lambda x: jnp.asarray(x).block_until_ready(), packed_np)
    print(f"H2D transfer (128B/sig) : {t*1e3:8.2f} ms")

    packed = jnp.asarray(packed_np)
    ay, a_sign = E.split_y_sign(packed[:, 0:32].astype(jnp.int32))
    ry, r_sign = E.split_y_sign(packed[:, 32:64].astype(jnp.int32))

    # --- decompress (x2 points) ---
    dec = jax.jit(lambda y, s: E.decompress(y, s)[0])
    t = timeit(dec, ay, a_sign)
    print(f"decompress one point    : {t*1e3:8.2f} ms")

    # --- digit unpack ---
    unp = jax.jit(E.unpack_nibbles_msb)
    t = timeit(unp, packed[:, 96:128])
    print(f"unpack_nibbles_msb      : {t*1e3:8.2f} ms")

    # --- comb + ladder + final eq, given points ---
    s_digits = packed[:, 64:96].astype(jnp.int32)
    k_digits = unp(packed[:, 96:128])

    def ladder_only(ay, a_sign, ry, r_sign, s_digits, k_digits):
        return E.verify_prepared(ay, a_sign, ry, r_sign, s_digits, k_digits)

    t = timeit(jax.jit(ladder_only), ay, a_sign, ry, r_sign, s_digits,
               k_digits)
    print(f"verify_prepared (full)  : {t*1e3:8.2f} ms")

    # --- single field mul at batch (N,32) ---
    a = jnp.asarray(rng.integers(0, 512, (N, 32)), jnp.int32)
    b = jnp.asarray(rng.integers(0, 512, (N, 32)), jnp.int32)
    t = timeit(jax.jit(F.mul), a, b)
    print(f"one field mul (N,32)    : {t*1e6:8.1f} us")
    t4 = timeit(jax.jit(lambda x, y: F.mul(F.mul(x, y), F.mul(y, x))), a, b)
    print(f"three chained muls      : {t4*1e6:8.1f} us")

    # --- full verify_packed ---
    t = timeit(E.verify_packed_jit, packed)
    print(f"verify_packed (device)  : {t*1e3:8.2f} ms  "
          f"({N/t:,.0f} sigs/s device-bound)")

    if "--trace" in sys.argv:
        with jax.profiler.trace("/tmp/jax-trace"):
            E.verify_packed_jit(packed).block_until_ready()
        print("trace written to /tmp/jax-trace")


if __name__ == "__main__":
    main()
