"""Where does verify_packed's device time go?  Repeat each stage R times
inside one program (chained so XLA can't dedupe) and fit slope between two R
values — device cost per stage at batch 1024, immune to fixed per-call
noise.

Stages: decompress(A), ladder (64x4dbl+add vs table), comb (32 adds + gather),
final combine+eq.
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
from hotstuff_tpu.ops import field25519 as F


def timeit(fn, reps=8):
    np.asarray(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    np.asarray(out)
    return (time.perf_counter() - t0) / reps


def slope(make, lo=1, hi=5):
    f_lo, f_hi = make(lo), make(hi)
    t_lo = timeit(lambda: f_lo())
    t_hi = timeit(lambda: f_hi())
    return (t_hi - t_lo) / (hi - lo), t_lo, t_hi


def main():
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
    prep = eddsa.prepare_batch(msgs, pks, sigs)
    packed = jnp.asarray(prep["packed"])
    ay, a_sign = E.split_y_sign(packed[:, 0:32].astype(jnp.int32))
    s_digits = packed[:, 64:96].astype(jnp.int32)
    k_digits = E.unpack_nibbles_msb(packed[:, 96:128])
    ay = jnp.asarray(ay)
    a_pt, _ = jax.jit(E.decompress)(ay, a_sign)
    a_pt = jnp.asarray(np.asarray(a_pt))

    # --- stage: decompress, chained via feeding x back as y ---------------
    def mk_dec(R):
        @jax.jit
        def f(y, s):
            def body(y, _):
                pt, _ok = E.decompress(y, s)
                # feed the X row back (depends on the full pow chain); the
                # Y row is the input verbatim and would let XLA DCE the
                # whole stage
                return pt[..., 0, :] & 0xFF, None
            out, _ = jax.lax.scan(body, y, None, length=R)
            return out
        return lambda: f(ay, a_sign)
    s_, lo, hi = slope(mk_dec)
    print(f"decompress      : {s_*1e3:8.3f} ms/stage (R1 {lo*1e3:.2f}, R5 {hi*1e3:.2f})")

    # --- stage: ladder ----------------------------------------------------
    def mk_ladder(R):
        @jax.jit
        def f(pt, kd):
            def body(p0, _):
                ax, ay_l, az, at = p0[..., 0, :], p0[..., 1, :], p0[..., 2, :], p0[..., 3, :]
                neg_a_ext = jnp.stack([F.neg(ax), ay_l, az, F.neg(at)], axis=-2)
                neg_a_cached = E.to_cached(neg_a_ext)
                entries = [E.identity_ext((N,)), neg_a_ext]
                for _ in range(2, 16):
                    entries.append(E.point_add(entries[-1], neg_a_cached))
                table = jnp.stack([E.to_cached(e) for e in entries], axis=-3)

                def ladder_body(p, digit_row):
                    p = E.point_dbl(p, with_t=False)
                    p = E.point_dbl(p, with_t=False)
                    p = E.point_dbl(p, with_t=False)
                    p = E.point_dbl(p)
                    p = E.point_add(p, E._digit_select(table, digit_row))
                    return p, None

                ka, _ = jax.lax.scan(ladder_body, E.identity_ext((N,)),
                                     jnp.moveaxis(kd, -1, 0))
                return ka, None
            out, _ = jax.lax.scan(body, pt, None, length=R)
            return out
        return lambda: f(a_pt, k_digits)
    s_, lo, hi = slope(mk_ladder, 1, 3)
    print(f"ladder+table    : {s_*1e3:8.3f} ms/stage (R1 {lo*1e3:.2f}, R3 {hi*1e3:.2f})")

    # --- stage: comb ------------------------------------------------------
    def mk_comb(R):
        comb = jnp.asarray(E.comb_table())
        @jax.jit
        def f(sd):
            def body(acc0, _):
                def comb_body(acc, xs):
                    comb_j, digit_row = xs
                    entry = jnp.take(comb_j, digit_row, axis=0)
                    return E.point_add(acc, entry), None
                sb, _ = jax.lax.scan(comb_body, acc0,
                                     (comb, jnp.moveaxis(sd, -1, 0)))
                return sb, None
            out, _ = jax.lax.scan(body, E.identity_ext((N,)), None, length=R)
            return out
        return lambda: f(s_digits)
    s_, lo, hi = slope(mk_comb)
    print(f"comb (32 gthr+add): {s_*1e3:7.3f} ms/stage (R1 {lo*1e3:.2f}, R5 {hi*1e3:.2f})")


if __name__ == "__main__":
    main()
