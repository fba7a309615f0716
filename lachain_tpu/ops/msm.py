"""Windowed multi-scalar multiplication over BLS12-381 G1 — the TPU kernel.

This replaces the round-1 bit-serial double-and-add (ops/curve.py
g1_scalar_mul_bits: 256 doublings + 256 conditional complete-adds per share)
with the design the hardware actually wants:

  * 4-bit windowed scalar-mul with a per-lane table of the 16 small
    multiples: depth 14 table adds + W x (4 dbl + 1 add) instead of
    256 x (dbl + add). Scalars are 64-bit for the verification RLC (the
    verifier picks them; 2^-64 soundness) and 2 x 128-bit via the GLV
    endomorphism for the arbitrary-Fr Lagrange coefficients, so W is 16 or
    32, never 64.
  * GLV: phi(x, y) = (beta x, y) acts as multiplication by lambda on the
    r-torsion, and because lambda ~ 2^127.6 for BLS12-381, plain divmod
    k = k2 * lambda + k1 gives |k1|, |k2| < 2^128 with both parts
    non-negative — no lattice reduction needed. k*P = k1*P + k2*phi(P).
  * INCOMPLETE group ops on the loose field (ops/fpl.py): no per-op
    equality tests, no ripple carries. Infinity is an explicit boolean lane
    flag, never a Z==0 test. Doubling/equal-operand edge cases cannot occur
    for in-range scalars (the accumulator's multiplier always differs from
    the table entry's mod r), and cross-lane collisions in the tree
    reduction have probability ~2^-64 because the verifier's coefficients
    are random — a wrong sum then just fails the batch check and falls back
    to serial verification, which is the existing escape path.

Reference role: the batched replacement for the per-share MCL pairing loop
(/root/reference/src/Lachain.Crypto/TPKE/PublicKey.cs:55-92 via
HoneyBadger.cs:205-247). `tpke_era_glv_kernel` is the kernel the mesh
shards (parallel/mesh.py); one chip runs the Pallas form (ops/pg1.py).
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

import jax.numpy as jnp
from jax import lax

from . import fpl
from ..crypto import bls12381 as bls

WINDOW = 4
TABLE = 1 << WINDOW  # 16
W128 = 128 // WINDOW  # 32 windows for GLV halves
W64 = 64 // WINDOW  # 16 windows for RLC coefficients

# ---------------------------------------------------------------------------
# GLV constants — derived, then verified against the host oracle at import
# ---------------------------------------------------------------------------

_Z = 0xD201000000010000  # |z| for BLS12-381 (z itself is negative)
LAMBDA = (_Z * _Z - 1) % bls.R  # ~2^127.6, the small cube root of unity
assert (LAMBDA * LAMBDA + LAMBDA + 1) % bls.R == 0
assert LAMBDA.bit_length() <= 128


def _find_beta() -> int:
    """The cube root of unity in Fp matching LAMBDA on G1: lambda*(x,y) =
    (beta*x, y). Two candidates; pick by testing on the generator."""
    # any non-trivial cube root of unity mod p
    exp = (bls.P - 1) // 3
    g = 2
    while True:
        b = pow(g, exp, bls.P)
        if b != 1:
            break
        g += 1
    gen = bls.G1_GEN
    target = bls.g1_to_affine(bls.g1_mul(gen, LAMBDA))
    gx, gy = bls.g1_to_affine(gen)
    for cand in (b, b * b % bls.P):
        if (cand * gx % bls.P, gy) == target:
            return cand
    raise AssertionError("no beta matches lambda on G1")


BETA = _find_beta()
BETA_MONT = jnp.asarray(fpl.to_mont_host(BETA))


def glv_split(k: int) -> Tuple[int, int]:
    """k mod r -> (k1, k2) with k = k1 + k2*lambda, both in [0, 2^128)."""
    k %= bls.R
    k2, k1 = divmod(k, LAMBDA)
    return k1, k2


# ---------------------------------------------------------------------------
# incomplete Jacobian group law on the loose field
# ---------------------------------------------------------------------------


def g1_dbl(p):
    """Jacobian doubling; valid for any non-infinity point (flag-carried
    infinity lanes produce garbage that is never selected)."""
    X1, Y1, Z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    A = fpl.mont_sqr(X1)
    B = fpl.mont_sqr(Y1)
    C = fpl.mont_sqr(B)
    D = fpl.sub(fpl.sub(fpl.mont_sqr(fpl.add(X1, B)), A), C)
    D = fpl.add(D, D)
    E = fpl.mul_small(A, 3)
    F = fpl.mont_sqr(E)
    X3 = fpl.sub(F, fpl.add(D, D))
    Y3 = fpl.sub(
        fpl.mont_mul(E, fpl.sub(D, X3)), fpl.mul_small(C, 8)
    )
    Z3 = fpl.mont_mul(Y1, Z1)
    Z3 = fpl.add(Z3, Z3)
    return jnp.stack([X3, Y3, Z3], axis=-2)


def g1_add_incomplete(p, q):
    """Generic Jacobian add; REQUIRES p != +-q and both non-infinity
    (callers guarantee this by construction / flags)."""
    X1, Y1, Z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    X2, Y2, Z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]
    Z1Z1 = fpl.mont_sqr(Z1)
    Z2Z2 = fpl.mont_sqr(Z2)
    U1 = fpl.mont_mul(X1, Z2Z2)
    U2 = fpl.mont_mul(X2, Z1Z1)
    S1 = fpl.mont_mul(fpl.mont_mul(Y1, Z2), Z2Z2)
    S2 = fpl.mont_mul(fpl.mont_mul(Y2, Z1), Z1Z1)
    H = fpl.sub(U2, U1)
    Rr = fpl.sub(S2, S1)
    I = fpl.mont_sqr(fpl.add(H, H))
    J = fpl.mont_mul(H, I)
    Rr2 = fpl.add(Rr, Rr)
    V = fpl.mont_mul(U1, I)
    X3 = fpl.sub(fpl.sub(fpl.mont_sqr(Rr2), J), fpl.add(V, V))
    S1J = fpl.mont_mul(S1, J)
    Y3 = fpl.sub(fpl.mont_mul(Rr2, fpl.sub(V, X3)), fpl.add(S1J, S1J))
    Z3 = fpl.mont_mul(fpl.mont_mul(Z1, Z2), H)
    Z3 = fpl.add(Z3, Z3)
    return jnp.stack([X3, Y3, Z3], axis=-2)


def g1_add_flagged(p, fp_, q, fq):
    """Flag-aware add: infinity is an explicit bool lane, never a field
    test. p != +-q required when both flags are False."""
    r = g1_add_incomplete(p, q)
    r = jnp.where(
        fq[..., None, None], p, jnp.where(fp_[..., None, None], q, r)
    )
    return r, fp_ & fq


# ---------------------------------------------------------------------------
# windowed MSM
# ---------------------------------------------------------------------------


def _build_table(points):
    """(..., 3, L) -> (..., TABLE, 3, L): entry k holds k*P (entry 0 is
    garbage; digit==0 lanes are handled by flags).

    lax.scan over the +P chain keeps the compiled graph one-add-sized; the
    fully unrolled version produced a ~30k-op graph per call site."""
    two = g1_dbl(points)

    def step(acc, _):
        nxt = g1_add_incomplete(acc, points)
        return nxt, nxt

    _, chain = lax.scan(step, two, None, length=TABLE - 3)
    # chain: (TABLE-3, ..., 3, L) = [3P .. 15P]
    rows = jnp.concatenate(
        [
            (points * 0)[None],  # entry 0: filler, never selected
            points[None],
            two[None],
            chain,
        ],
        axis=0,
    )
    return jnp.moveaxis(rows, 0, -3)


def g1_msm_windowed(points, digits):
    """Batched windowed scalar-mul: points (..., 3, L), digits (..., W)
    int32 in [0, 16), MSB-first. Returns (result, inf_flag) with the same
    batch shape.

    Depth: 13 table adds + W * (4 dbl + 1 add) — vs 256 * (dbl + add) for
    the bit-serial scan this replaces. The window loop is a lax.scan whose
    body (4 dbl + gather + add) is large enough to amortize device-loop
    overhead — the opposite regime from the per-limb scans this design
    removed.
    """
    table = _build_table(points)  # (..., 16, 3, L)
    nw = digits.shape[-1]
    dseq = jnp.moveaxis(digits, -1, 0)  # (W, ...)

    def take(d):
        idx = d[..., None, None, None]
        entry = jnp.take_along_axis(table, idx, axis=-3)
        return entry[..., 0, :, :]

    acc0 = take(dseq[0])
    flag0 = dseq[0] == 0

    def step(carry, d):
        acc, flag = carry
        for _ in range(WINDOW):
            acc = g1_dbl(acc)
        entry = take(d)
        added = g1_add_incomplete(acc, entry)
        keep = d == 0
        acc = jnp.where(
            keep[..., None, None],
            acc,
            jnp.where(flag[..., None, None], entry, added),
        )
        return (acc, flag & keep), None

    (acc, flag), _ = lax.scan(step, (acc0, flag0), dseq[1:])
    return acc, flag


def g1_tree_reduce_flagged(points, flags, axis: int):
    """Tree-sum along `axis` with explicit infinity flags; log-depth."""
    points = jnp.moveaxis(points, axis, 0)
    flags = jnp.moveaxis(flags, axis, 0)
    n = points.shape[0]
    while n > 1:
        if n % 2:
            points = jnp.concatenate([points, points[:1] * 0], axis=0)
            flags = jnp.concatenate(
                [flags, jnp.ones_like(flags[:1])], axis=0
            )
            n += 1
        half = n // 2
        points, flags = g1_add_flagged(
            points[:half], flags[:half], points[half:n], flags[half:n]
        )
        n = half
    return points[0], flags[0]


# ---------------------------------------------------------------------------
# fixed-base path for the era-invariant verification keys
# ---------------------------------------------------------------------------


def y_fixed_base_tables(y_dev):
    """(K, 3, L) verification keys -> (K, W64, TABLE, 3, L) tables with
    T[i, w, d] = d * 16^w * Y_i.

    The Y_i are fixed for a validator set, so this runs ONCE (off the era
    hot path); per era the y-aggregates then cost only gathers plus one
    flagged tree-sum — no doublings, no scalar-mul scan at all.
    """
    rows = []
    base = y_dev
    for w in range(W64):
        rows.append(_build_table(base))  # (K, TABLE, 3, L)
        if w + 1 < W64:
            for _ in range(WINDOW):
                base = g1_dbl(base)
    # rows[w] built for 16^w; digits are MSB-first so window w weights
    # 16^(W64-1-w): reverse to index by the digit position directly
    return jnp.stack(rows[::-1], axis=1)  # (K, W64, TABLE, 3, L)


def y_agg_fixed_base(tables, rlc_digits):
    """tables (K, W64, TABLE, 3, L); rlc_digits (S, K, W64) MSB-first.
    Returns per-slot aggregates sum_i rlc[s,i] * Y_i as ((S, 3, L), (S,))."""
    s = rlc_digits.shape[0]
    idx = rlc_digits[..., None, None, None]  # (S, K, W, 1, 1, 1)
    entries = jnp.take_along_axis(tables[None], idx, axis=3)
    entries = entries[..., 0, :, :]  # (S, K, W, 3, L)
    flags = rlc_digits == 0
    k, w = entries.shape[1], entries.shape[2]
    entries = entries.reshape(s, k * w, 3, fpl.NLIMBS)
    flags = flags.reshape(s, k * w)
    return g1_tree_reduce_flagged(entries, flags, axis=1)


# ---------------------------------------------------------------------------
# the era kernel: verify-RLC aggregates + GLV Lagrange combine in ONE pass
# ---------------------------------------------------------------------------


def tpke_era_glv_kernel3(u_pts, rlc_digits, lag1_digits, lag2_digits):
    """Era kernel without the y lane group (3K lanes/slot): the verify RHS
    aggregates ride the fixed-base tables (y_agg_fixed_base) instead.
    Returns (points (S, 3grp, 3, L), flags (S, 3grp)): u_agg, comb1, comb2.
    """
    phi_u = jnp.concatenate(
        [
            fpl.mont_mul(u_pts[..., 0:1, :], BETA_MONT),
            u_pts[..., 1:3, :],
        ],
        axis=-2,
    )
    lanes = jnp.concatenate([u_pts, u_pts, phi_u], axis=1)
    digits = jnp.concatenate([rlc_digits, lag1_digits, lag2_digits], axis=1)
    acc, flags = g1_msm_windowed(lanes, digits)
    s, k3 = acc.shape[0], acc.shape[1]
    k = k3 // 3
    acc = acc.reshape(s, 3, k, 3, fpl.NLIMBS)
    flags = flags.reshape(s, 3, k)
    return g1_tree_reduce_flagged(acc, flags, axis=2)


def tpke_era_glv_kernel(u_pts, y_pts, rlc_digits, lag1_digits, lag2_digits):
    """Full-era TPKE kernel (S slots x K shares):

      u_pts, y_pts:   (S, K, 3, L) loose-Montgomery Jacobian points
      rlc_digits:     (S, K, W128) 64-bit verifier RLC coefficients,
                      zero-padded in the top W128-W64 windows
      lag1/lag2:      (S, K, W128) GLV halves of the Lagrange coefficients
                      (zero rows for shares outside the combine subset)

    One fused windowed pass over 4K lanes per slot:
      lane group 0: u * rlc     -> u_agg    (verify LHS)
      lane group 1: y * rlc     -> y_agg    (verify RHS)
      lane group 2: u * lag1    -> comb half 1
      lane group 3: phi(u)*lag2 -> comb half 2
    Host finishes with e(u_agg, H) == e(y_agg, W) per slot and XOR-pads with
    the combined point (reference PublicKey.cs:55-92 semantics).

    Returns (points (S, 4, 3, L), flags (S, 4)): u_agg, y_agg, comb1, comb2
    (comb = comb1 + comb2, added on host after canonicalization — keeping
    the kernel's output regular).
    """
    phi_u = jnp.concatenate(
        [
            fpl.mont_mul(u_pts[..., 0:1, :], BETA_MONT),
            u_pts[..., 1:3, :],
        ],
        axis=-2,
    )
    lanes = jnp.concatenate([u_pts, y_pts, u_pts, phi_u], axis=1)
    digits = jnp.concatenate(
        [rlc_digits, rlc_digits, lag1_digits, lag2_digits], axis=1
    )
    acc, flags = g1_msm_windowed(lanes, digits)  # (S, 4K, 3, L), (S, 4K)
    s, k4 = acc.shape[0], acc.shape[1]
    k = k4 // 4
    acc = acc.reshape(s, 4, k, 3, fpl.NLIMBS)
    flags = flags.reshape(s, 4, k)
    out, out_flags = g1_tree_reduce_flagged(acc, flags, axis=2)
    return out, out_flags


# ---------------------------------------------------------------------------
# host marshal: vectorized conversions (numpy, no per-bit Python loops)
# ---------------------------------------------------------------------------


def scalars_to_digits(scalars: Sequence[int], nwindows: int) -> np.ndarray:
    """List of ints -> (n, nwindows) int32 4-bit digits, MSB-first.
    Vectorized via byte decomposition."""
    nbytes = nwindows * WINDOW // 8
    buf = b"".join(int(s).to_bytes(nbytes, "big") for s in scalars)
    a = np.frombuffer(buf, dtype=np.uint8).reshape(len(scalars), nbytes)
    hi = a >> 4
    lo = a & 0xF
    out = np.empty((len(scalars), nbytes * 2), dtype=np.int32)
    out[:, 0::2] = hi
    out[:, 1::2] = lo
    return out


def era_digits(rlc_rows, lag_rows):
    """Shared era-coefficient marshal for the GLV-kernel pipelines
    (ops/verify.GlvEraPipeline and parallel/mesh.MeshEraPipeline): (S, K)
    integer coefficient rows -> (rlc64, rlc_d, lag1, lag2) digit arrays,
    with the 64-bit RLC coefficients embedded in the top W64 of W128
    windows and the Lagrange coefficients GLV-split into halves. One
    definition so window-width and split conventions cannot diverge between
    the single-device and mesh topologies."""
    s = len(rlc_rows)
    k = len(rlc_rows[0]) if s else 0
    rlc64 = np.stack([scalars_to_digits(row, W64) for row in rlc_rows])
    rlc_d = np.zeros((s, k, W128), dtype=np.int32)
    rlc_d[:, :, W128 - W64 :] = rlc64
    lag1 = np.zeros((s, k, W128), dtype=np.int32)
    lag2 = np.zeros((s, k, W128), dtype=np.int32)
    for i, row in enumerate(lag_rows):
        halves = [glv_split(v) for v in row]
        lag1[i] = scalars_to_digits([h[0] for h in halves], W128)
        lag2[i] = scalars_to_digits([h[1] for h in halves], W128)
    return rlc64, rlc_d, lag1, lag2


def combine_or_host_msm(comb, u_list, lag_list, backend):
    """Shared incomplete-add escape hatch for the era pipelines: a combine
    lane group degenerating to infinity (two equal partial sums collide in
    the incomplete add tree) has no random-coefficient soundness, so the
    ~2^-255 / adversarially-forced case falls back to the host oracle MSM."""
    if comb[2] == 0 and any(c for c in lag_list):
        return backend.g1_msm(
            [u for u, c in zip(u_list, lag_list) if c],
            [c for c in lag_list if c],
        )
    return comb


def _batch_inverse(vals: List[int], p: int) -> List[int]:
    """Montgomery's trick: n field inversions for the price of one."""
    n = len(vals)
    prefix = [1] * (n + 1)
    for i, v in enumerate(vals):
        prefix[i + 1] = prefix[i] * v % p
    inv_all = pow(prefix[n], -1, p)
    out = [0] * n
    for i in range(n - 1, -1, -1):
        out[i] = prefix[i] * inv_all % p
        inv_all = inv_all * vals[i] % p
    return out


def _ints_to_limbs_np(ints: List[int]) -> np.ndarray:
    """List of field ints -> (n, NLIMBS) int32, vectorized limb split."""
    nbytes = 48  # 384 bits covers any canonical field element
    buf = b"".join(v.to_bytes(nbytes, "little") for v in ints)
    a = np.frombuffer(buf, dtype=np.uint8).reshape(len(ints), nbytes)
    bits = np.unpackbits(a, axis=1, bitorder="little")  # (n, 384)
    nfull = 384 // fpl.BASE  # limbs fully covered by 384 bits
    limbs = bits[:, : nfull * fpl.BASE].reshape(len(ints), nfull, fpl.BASE)
    weights = (1 << np.arange(fpl.BASE, dtype=np.int64)).astype(np.int32)
    out = np.zeros((len(ints), fpl.NLIMBS), dtype=np.int32)
    out[:, :nfull] = (limbs * weights).sum(axis=2, dtype=np.int32)
    if nfull < fpl.NLIMBS and nfull * fpl.BASE < 384:
        rest = bits[:, nfull * fpl.BASE : 384]
        w = (1 << np.arange(rest.shape[1], dtype=np.int64)).astype(np.int32)
        out[:, nfull] = (rest * w).sum(axis=1, dtype=np.int32)
    return out


def g1_to_device_loose(points) -> np.ndarray:
    """Oracle Jacobian G1 tuples -> (n, 3, NLIMBS) loose Montgomery affine
    (Z=1). Batch inversion + vectorized limb packing; infinity entries get
    (0, 1, 0) — callers must flag them separately if semantically needed."""
    n = len(points)
    zs = []
    idx = []
    for i, pt in enumerate(points):
        if pt[2] != 0:
            zs.append(pt[2])
            idx.append(i)
    zinvs = _batch_inverse(zs, bls.P) if zs else []
    xs = [0] * n
    ys = [0] * n
    zcol = [0] * n
    one_m = fpl.R_MONT % bls.P  # Mont(1)
    j = 0
    for i, pt in enumerate(points):
        if pt[2] == 0:
            xs[i] = 0
            ys[i] = one_m
            zcol[i] = 0
        else:
            zi = zinvs[j]
            j += 1
            zi2 = zi * zi % bls.P
            ax = pt[0] * zi2 % bls.P
            ay = pt[1] * zi2 % bls.P * zi % bls.P
            xs[i] = ax * fpl.R_MONT % bls.P
            ys[i] = ay * fpl.R_MONT % bls.P
            zcol[i] = one_m
    out = np.stack(
        [
            _ints_to_limbs_np(xs),
            _ints_to_limbs_np(ys),
            _ints_to_limbs_np(zcol),
        ],
        axis=1,
    )
    return out


def g1_from_device_loose(arr, flags=None) -> list:
    """(n, 3, NLIMBS) loose limbs (+ optional inf flags) -> oracle tuples.
    Exact canonicalization happens here, on host ints."""
    arr = np.asarray(arr)
    rinv = pow(fpl.R_MONT, -1, bls.P)
    out = []
    for i in range(arr.shape[0]):
        if flags is not None and bool(np.asarray(flags)[i]):
            out.append(bls.G1_INF)
            continue
        x = fpl.limbs_to_int(arr[i, 0]) * rinv % bls.P
        y = fpl.limbs_to_int(arr[i, 1]) * rinv % bls.P
        z = fpl.limbs_to_int(arr[i, 2]) * rinv % bls.P
        if z == 0:
            out.append(bls.G1_INF)
        else:
            out.append((x, y, z))
    return out
