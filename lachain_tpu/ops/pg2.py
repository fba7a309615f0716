"""Pallas G2 engine: VMEM-resident Fp2/G2 kernels for the coin hot path.

Round-3 counterpart of ops/pg1.py for the OTHER half of the era's crypto:
threshold-signature share verification + Lagrange combination, where the
signatures live in G2 (Fp2 coordinates). The reference verifies each coin
share with 2 pairings and combines with a serial G2 Lagrange loop
(/root/reference/src/Lachain.Crypto/ThresholdSignature/ThresholdSigner.cs:
45-95, PublicKeySet.cs:35-44 via CommonCoin.cs:75-96); here S coins x K
shares collapse into three windowed MSM passes in one kernel launch:

  verify : e(g1, sum_j c_j sigma_j) == e(sum_j c_j Y_j, H)   per coin
  combine: sigma = sum_i lambda_i sigma_i                    per coin

sigma-aggregates are G2 MSMs (this module); the key aggregate is a G1 MSM
(reuses pg1's machinery verbatim); the host finishes with one grand
multi-pairing.

Field/kernel design is pg1's, lifted to Fp2 = Fp[i]/(i^2+1):
  * an Fp2 element is a pair of 44x10-bit signed plain-form limb vectors;
    mul is Karatsuba — 3 convs + 3 MXU fold matmuls (folding each conv
    separately keeps every int32 conv accumulator within pg1's proven
    44*2^12.1^2 < 2^29.7 bound; combining convs first would overflow);
    square is (a+b)(a-b) / 2ab — 2 convs + 2 folds.
  * G2 points are Jacobian over Fp2: (288, B) int32 blocks
    (X.c0|X.c1|Y.c0|Y.c1|Z.c0|Z.c1, one 48-row slot per component), same
    incomplete add/dbl formulas as pg1 with Fp ops replaced by Fp2 ops.
  * the MSM is the same one-pallas_call window scan with the accumulator
    and 16-entry table VMEM-resident; LANE_TILE2 = 128 keeps the resident
    table block at 16*288*128*4 B = 2.4 MB.
  * no GLV: the G2 endomorphism (untwist-Frobenius-twist) needs Fp2
    Frobenius + twist constants in-kernel; a 64-window full-scalar pass is
    ~2x the window count for a fraction of the complexity. The RLC verify
    pass stays 16 windows (64-bit coefficients).

Magnitude invariants are pg1's (fuzz-checked in tests/test_pg2.py): every
Fp2 component flows through the same _add/_sub/_fold/_crush compositions
at the same chain depths as pg1's G1 formulas.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import msm, pg1
from ..crypto import bls12381 as bls
from .pg1 import (
    NLIMBS,
    POINT_ROWS,
    TABLE,
    WINDOW,
    _add,
    _const_args,
    _consts,
    _conv,
    _fold,
    _lane_call,
    _mul_small,
    _per_platform,
    _sub,
    make_msm_scan,
)

COMP_ROWS = 48  # one Fp2 component per 48-row slot (44 limbs + 4 zero
# rows): Mosaic's lane-axis concatenate requires operands at matching
# sublane offsets, and 44-row strides would alternate slices between
# offsets 0 and 4 ("result/input offset mismatch on non-concat dimension")
POINT2_ROWS = 6 * COMP_ROWS  # 288: X.c0|X.c1|Y.c0|Y.c1|Z.c0|Z.c1
W256 = 256 // WINDOW  # 64 windows: full-scalar (Lagrange) pass
LANE_TILE2 = 128  # resident table block 16*288*128*4 = 2.4 MB VMEM


# ---------------------------------------------------------------------------
# Fp2 helpers (pairs of (44, B) limb blocks inside kernel bodies)
# ---------------------------------------------------------------------------


def _fp2_add(x, y, c):
    return (_add(x[0], y[0], c), _add(x[1], y[1], c))


def _fp2_sub(x, y, c):
    return (_sub(x[0], y[0], c), _sub(x[1], y[1], c))


def _fp2_muls(x, k: int, c):
    return (_mul_small(x[0], k, c), _mul_small(x[1], k, c))


def _fp2_mul(x, y, c):
    """Karatsuba: (a+bi)(d+ei) = (ad-be) + ((a+b)(d+e)-ad-be)i.

    The 3 independent Fp products ride ONE conv+fold on a 3x-wide lane
    block (lane-axis packing): Mosaic compile time scales with statement
    count, not tile width, so one (44, 3B) conv costs a third of three
    (44, B) convs to compile — the lever that brought the G2 kernel from
    ~300 s to double-digit compile. Each conv folds before combination so
    conv accumulators keep pg1's proven int32 bound; the 3-term imag
    combination is two crush(1) subs (same chain depth as pg1's X3/Y3)."""
    a, b = x
    d, e = y
    bcols = a.shape[-1]
    xs = jnp.concatenate([a, b, _add(a, b, c)], axis=-1)  # (44, 3B)
    ys = jnp.concatenate([d, e, _add(d, e, c)], axis=-1)
    f = _fold(_conv(xs, ys), c)  # (44, 3B)
    f_ad = f[:, :bcols]
    f_be = f[:, bcols : 2 * bcols]
    f_k = f[:, 2 * bcols :]
    real = _sub(f_ad, f_be, c)
    imag = _sub(_sub(f_k, f_ad, c), f_be, c)
    return (real, imag)


def _fp2_sqr(x, c):
    """(a+bi)^2 = (a+b)(a-b) + 2abi — one conv+fold on a 2x-wide block."""
    a, b = x
    bcols = a.shape[-1]
    xs = jnp.concatenate([_add(a, b, c), a], axis=-1)  # (44, 2B)
    ys = jnp.concatenate([_sub(a, b, c), b], axis=-1)
    f = _fold(_conv(xs, ys), c)
    real = f[:, :bcols]
    ab = f[:, bcols:]
    return (real, _add(ab, ab, c))


def _split(p):
    """(288, B) -> three Fp2 values (X, Y, Z); every slice starts on an
    8-aligned sublane offset (COMP_ROWS = 48)."""
    c = [p[COMP_ROWS * j : COMP_ROWS * j + NLIMBS] for j in range(6)]
    return ((c[0], c[1]), (c[2], c[3]), (c[4], c[5]))


def _join(x, y, z):
    b = x[0].shape[-1]
    z4 = jnp.zeros((COMP_ROWS - NLIMBS, b), jnp.int32)
    return jnp.concatenate(
        [x[0], z4, x[1], z4, y[0], z4, y[1], z4, z[0], z4, z[1], z4],
        axis=0,
    )


# ---------------------------------------------------------------------------
# in-kernel G2 group law (Jacobian over Fp2, incomplete — flags outside)
# ---------------------------------------------------------------------------


def _g2_dbl_val(p, c):
    """(288, B) -> (288, B); same a=0 Jacobian formulas as pg1._g1_dbl_val
    (oracle: crypto/bls12381.py:g2_dbl)."""
    X1, Y1, Z1 = _split(p)
    A = _fp2_sqr(X1, c)
    B = _fp2_sqr(Y1, c)
    C = _fp2_sqr(B, c)
    D = _fp2_sub(_fp2_sub(_fp2_sqr(_fp2_add(X1, B, c), c), A, c), C, c)
    D = _fp2_add(D, D, c)
    E = _fp2_muls(A, 3, c)
    F = _fp2_sqr(E, c)
    X3 = _fp2_sub(F, _fp2_add(D, D, c), c)
    Y3 = _fp2_sub(
        _fp2_mul(E, _fp2_sub(D, X3, c), c), _fp2_muls(C, 8, c), c
    )
    Z3 = _fp2_mul(Y1, Z1, c)
    Z3 = _fp2_add(Z3, Z3, c)
    return _join(X3, Y3, Z3)


def _g2_add_val(p, q, c):
    """(288, B) x (288, B) -> (288, B); requires p != +-q, both finite
    (oracle: crypto/bls12381.py:g2_add)."""
    X1, Y1, Z1 = _split(p)
    X2, Y2, Z2 = _split(q)
    Z1Z1 = _fp2_sqr(Z1, c)
    Z2Z2 = _fp2_sqr(Z2, c)
    U1 = _fp2_mul(X1, Z2Z2, c)
    U2 = _fp2_mul(X2, Z1Z1, c)
    S1 = _fp2_mul(_fp2_mul(Y1, Z2, c), Z2Z2, c)
    S2 = _fp2_mul(_fp2_mul(Y2, Z1, c), Z1Z1, c)
    H = _fp2_sub(U2, U1, c)
    Rr = _fp2_sub(S2, S1, c)
    I = _fp2_sqr(_fp2_add(H, H, c), c)
    J = _fp2_mul(H, I, c)
    Rr2 = _fp2_add(Rr, Rr, c)
    V = _fp2_mul(U1, I, c)
    X3 = _fp2_sub(
        _fp2_sub(_fp2_sqr(Rr2, c), J, c), _fp2_add(V, V, c), c
    )
    S1J = _fp2_mul(S1, J, c)
    Y3 = _fp2_sub(
        _fp2_mul(Rr2, _fp2_sub(V, X3, c), c), _fp2_add(S1J, S1J, c), c
    )
    Z3 = _fp2_mul(_fp2_mul(Z1, Z2, c), H, c)
    Z3 = _fp2_add(Z3, Z3, c)
    return _join(X3, Y3, Z3)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _dbl2_kernel(mlo_ref, mhi_ref, wrap_ref, p_ref, o_ref):
    o_ref[:] = _g2_dbl_val(p_ref[:], _consts(mlo_ref, mhi_ref, wrap_ref))


def _add2_kernel(mlo_ref, mhi_ref, wrap_ref, p_ref, q_ref, o_ref):
    o_ref[:] = _g2_add_val(
        p_ref[:], q_ref[:], _consts(mlo_ref, mhi_ref, wrap_ref)
    )


@jax.jit
def pl_dbl2(p):
    """(288, n) -> (288, n) Jacobian G2 doubling on-device."""
    return _per_platform(
        lambda p: _lane_call(_dbl2_kernel, POINT2_ROWS, p, tile=LANE_TILE2),
        lambda p: _g2_dbl_val(p, _const_args()),
        p,
    )


@jax.jit
def pl_add2(p, q):
    """(288, n) x (288, n) -> (288, n) incomplete G2 add on-device."""
    return _per_platform(
        lambda p, q: _lane_call(
            _add2_kernel, POINT2_ROWS, p, q, tile=LANE_TILE2
        ),
        lambda p, q: _g2_add_val(p, q, _const_args()),
        p,
        q,
    )


# table (16, 288, n), digits (W, 1, n) -> ((288, n), (n,) flags): pg1's
# window scan (accumulator + table blocks VMEM-resident across windows)
# over the G2 group law
_msm2_scan = make_msm_scan(_g2_dbl_val, _g2_add_val, tile=LANE_TILE2)


def build_table2(lanes):
    """(288, n) -> (16, 288, n): entry k = k*P (entry 0 never selected)."""
    two = pl_dbl2(lanes)
    rows = [jnp.zeros_like(lanes), lanes, two]
    cur = two
    for _ in range(TABLE - 3):
        cur = pl_add2(cur, lanes)
        rows.append(cur)
    return jnp.stack(rows, axis=0)


def msm2_windowed(lanes, digits):
    """Windowed G2 MSM: lanes (288, n), digits (W, n) MSB-first 4-bit."""
    table = build_table2(lanes)
    return _msm2_scan(table, digits[:, None, :])


def tree_reduce2_k(acc, flags, k: int):
    """Sum groups of k adjacent G2 lanes (k power of two) with flags."""
    assert k & (k - 1) == 0
    while k > 1:
        a, b = acc[:, 0::2], acc[:, 1::2]
        fa, fb = flags[0::2], flags[1::2]
        r = pl_add2(a, b)
        acc = jnp.where(fb[None, :], a, jnp.where(fa[None, :], b, r))
        flags = fa & fb
        k //= 2
    return acc, flags


# ---------------------------------------------------------------------------
# the coin-era kernel: G2 RLC verify + G2 Lagrange combine + G1 key RLC
# ---------------------------------------------------------------------------


def ts_era_kernel(sig, y, rlc16, lag64, k: int):
    """sig: (288, S*K) signature shares (G2 plain Jacobian limbs);
    y: (132, S*K) per-share verification keys (G1, duplicated per slot);
    rlc16: (16, S*K) 64-bit RLC digits; lag64: (64, S*K) 256-bit Lagrange
    digits. k = K (lanes per slot, power of two).

    Returns one fused (289, 3S) int32 buffer (row 288 = infinity flags):
      cols [0,   S): per-slot sigma RLC aggregates (G2)   — verify
      cols [S,  2S): per-slot sigma Lagrange combines (G2) — the signature
      cols [2S, 3S): per-slot key RLC aggregates (G1, rows 132..287 zero)
    Host finishes: e(g1, sig_agg) == e(y_agg, H) per slot via ONE grand
    multi-pairing (reference runs 2 pairings per SHARE instead:
    ThresholdSigner.cs:92-95)."""
    # one 64-window scan over duplicated lanes serves BOTH sigma passes
    # (RLC digits pad with leading zero windows — flags stay set until the
    # first nonzero digit): one table build + one Mosaic MSM instance
    # instead of two, and Mosaic kernel compiles dominate era setup time
    n = sig.shape[-1]
    rlc64 = jnp.concatenate(
        [
            jnp.zeros(
                (lag64.shape[0] - rlc16.shape[0], n), jnp.int32
            ),
            rlc16,
        ],
        axis=0,
    )
    table = build_table2(sig)
    acc, fl = _msm2_scan(
        jnp.concatenate([table, table], axis=-1),
        jnp.concatenate([rlc64, lag64], axis=1)[:, None, :],
    )
    acc_r, fl_r = acc[:, :n], fl[:n]
    acc_l, fl_l = acc[:, n:], fl[n:]
    acc_y, fl_y = pg1.msm_windowed(y, rlc16)
    out_r, ofl_r = tree_reduce2_k(acc_r, fl_r, k)
    out_l, ofl_l = tree_reduce2_k(acc_l, fl_l, k)
    out_y, ofl_y = pg1.tree_reduce_k(acc_y, fl_y, k)
    s = out_r.shape[-1]
    y_padded = jnp.concatenate(
        [out_y, jnp.zeros((POINT2_ROWS - POINT_ROWS, s), jnp.int32)], axis=0
    )
    pts = jnp.concatenate([out_r, out_l, y_padded], axis=1)  # (288, 3S)
    flags = jnp.concatenate([ofl_r, ofl_l, ofl_y]).astype(jnp.int32)[None, :]
    return jnp.concatenate([pts, flags], axis=0)  # (289, 3S)


ts_era_kernel_jit = jax.jit(ts_era_kernel, static_argnames=("k",))


def msm2_reduce(lanes, digits, k: int):
    """G2 windowed MSM + tree reduce as ONE device program (see
    pg1.msm_reduce for why). Returns (289, n/k): points + flag row."""
    acc, fl = msm2_windowed(lanes, digits)
    out, ofl = tree_reduce2_k(acc, fl, k)
    return jnp.concatenate(
        [out, ofl.astype(jnp.int32)[None, :]], axis=0
    )


msm2_reduce_jit = jax.jit(msm2_reduce, static_argnames=("k",))


# ---------------------------------------------------------------------------
# host marshal
# ---------------------------------------------------------------------------


def g2_pack(points: Sequence[tuple]) -> np.ndarray:
    """Oracle G2 Jacobian tuples -> (288, n) int32 plain limbs (one
    48-row slot per Fp2 component, rows 44..47 of each slot zero).
    Infinity maps to ((0,0),(1,0),(0,0)) — callers flag it separately."""
    comps = []
    for p in points:
        if bls.g2_is_inf(p):
            comps.append((0, 0, 1, 0, 0, 0))
        else:
            (x0, x1), (y0, y1), (z0, z1) = p
            comps.append((x0, x1, y0, y1, z0, z1))
    n = len(points)
    out = np.zeros((POINT2_ROWS, n), dtype=np.int32)
    for j in range(6):
        out[COMP_ROWS * j : COMP_ROWS * j + NLIMBS] = (
            msm._ints_to_limbs_np([c[j] for c in comps]).T
        )
    return out


def g2_unpack(arr, flags=None) -> list:
    """(288, n) limbs (+ optional flags) -> oracle G2 Jacobian tuples."""
    arr = np.asarray(arr)
    out = []
    for i in range(arr.shape[-1]):
        if flags is not None and bool(np.asarray(flags)[i]):
            out.append(bls.G2_INF)
            continue
        v = [
            pg1._limbs_int(arr[COMP_ROWS * j : COMP_ROWS * j + NLIMBS, i])
            for j in range(6)
        ]
        if v[4] == 0 and v[5] == 0:
            out.append(bls.G2_INF)
        else:
            out.append(((v[0], v[1]), (v[2], v[3]), (v[4], v[5])))
    return out
