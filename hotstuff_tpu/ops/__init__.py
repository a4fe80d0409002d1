"""Device-side cryptographic kernels (the JAX/TPU compute substrate).

Modules:
  field25519    GF(2^255-19) limb arithmetic (radix-2^8 int32 limbs; the
                schoolbook product is a depthwise conv on the MXU).
  scalar25519   Arithmetic mod the Ed25519 group order L (Montgomery
                reduction at the byte-aligned R = 2^256) — the scalar
                half of the RLC batch check.
  ed25519       Curve ops and the two batch-verification programs:
                per-signature (comb + windowed ladder per vote) and the
                random-linear-combination (RLC) one-MSM path.
  field381 / bls381   BLS12-381 field + pairing kernels (QC aggregate
                verification under scheme=bls).

The RLC check in one paragraph: per-signature verification proves
[S_i]B == R_i + [k_i]A_i once per vote.  Drawing coefficients z_i from a
deterministic PRF over the batch content and summing z_i*(eq_i) collapses
a quorum to ONE equation, [sum z_i S_i]B == sum [z_i]R_i + [z_i k_i]A_i,
whose variable half is a single 2n-point multi-scalar multiplication
(Straus shared 4-bit windows + a masked binary-tree batch reduction —
see ops/ed25519.msm_window_sums).  All-valid batches — the steady state
of quorum-certificate verification — pay one MSM instead of 2n ladders;
a failed combined check is resolved on the per-signature path, so a bad
vote is still pinpointed and the returned mask is bit-identical to
verify_batch's.  Coefficients must be >= 128 bits: an adversary who can
cancel a defect against the z-weighted sum forges a batch verdict, and
the cancellation probability is 2^-(coefficient bits) — shorter
coefficients would make the combined check the system's weakest link,
below the curve's ~2^126 security level.

Torsion handling: E(Fp) is Z/8 x Z/L, and a scalar acts mod 8 on a
point's 8-torsion component — so the MSM scalars are CRT-lifted to the
full-group exponent 8L (ops/scalar25519.add_small_multiple_of_l) so that
every row's torsion defect enters the combined sum with exactly the
coefficient the per-signature cofactorless equation uses.  A single
defective row (including any mixed-order A or R an adversary crafts —
small-order points are already rejected host-side) therefore passes or
fails the combined check exactly as verify_batch would.  Known residual:
two or more colluding rows whose 8-torsion defects cancel exactly can
make the combined check accept where per-signature verification rejects
each row — inherent to any deterministic-coefficient cofactorless batch
check (cf. Chalkias et al., "Taming the many EdDSAs"); committees that
must exclude it should subgroup-check authority keys at registration
([L]A == identity, one-time per key).
"""
