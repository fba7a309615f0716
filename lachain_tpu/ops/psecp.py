"""Pallas secp256k1 engine: batched ECDSA public-key recovery on the MXU.

The second TPU kernel family (SURVEY.md §2a named batched ECDSA recovery as
the natural second target after the BLS12-381 era kernels). The reference
verifies receipt signatures serially on a CPU thread pool
(/root/reference/src/Lachain.Core/Blockchain/Operations/
TransactionVerifier.cs:23-72); here a whole pool-ingest batch of recoveries
runs as lane-parallel point arithmetic:

  recover_i:  Q_i = u1_i * R_i + u2_i * G
    (u1 = s/r mod n, u2 = -z/r mod n — cheap host bigints; R_i is the
     host-decompressed signature point; the two scalar multiplications are
     ~99.9% of the work and they are exactly the windowed per-lane scalar
     muls the pg1 MSM machinery already implements.)

The host finishes with batch affine conversion (one inversion amortized via
Montgomery's trick).

Field/kernel design is pg1's, re-parameterized for the secp256k1 prime:
  * 26 limbs x 10 bits (260-bit redundant signed representation over the
    256-bit field); conv length 51; fold matrix rows = limbs of
    2^(10(k+j)) mod p, split in 5-bit halves for exact f32 MXU dot
    products (153-term sums < 2^23 — exactly representable).
  * points are Jacobian (96, B) int32 blocks: 32-row component slots
    (26 limbs + 6 zero rows) keep every slice 8-sublane-aligned, the same
    constraint pg2 hit with Mosaic's concatenate.
  * magnitudes: crushed limbs <= 2^12.1, conv accumulators
    26 * 2^24.2 < 2^29 (int32 safe) — strictly smaller than the proven
    BLS bounds, same crush schedule.

Kernel layout per batch of n signatures: 2n lanes [R_0..R_{n-1} | G...G],
per-lane 64x4-bit digits [u1 | u2], one windowed scan (table of 16
per-lane multiples resident in VMEM), then a k=2 tree reduce pairs each
R-lane accumulator with its G-lane partner... lanes are interleaved so the
reduce sums adjacent pairs: lane 2i = u1_i*R_i, lane 2i+1 = u2_i*G.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..crypto import ecdsa
from .pg1 import TABLE, _lane_call, _per_platform, make_msm_scan

NLIMBS = 26
BASE = 10
MASK = (1 << BASE) - 1
CONVLEN = 2 * NLIMBS - 1  # 51
COMP_ROWS = 32  # 26 limbs + 6 zero rows: 8-aligned slices
POINT_ROWS = 3 * COMP_ROWS  # 96
P_INT = ecdsa.P
N_INT = ecdsa.N
W256 = 64  # 4-bit windows over 256-bit scalars
LANE_TILE = 256


def _int_to_limbs(v: int) -> np.ndarray:
    return np.array(
        [(v >> (BASE * i)) & MASK for i in range(NLIMBS)], dtype=np.int32
    )


_FOLD_M = np.zeros((NLIMBS, 3 * CONVLEN), dtype=np.int32)
for _j in range(3):
    for _k in range(CONVLEN):
        _FOLD_M[:, _j * CONVLEN + _k] = _int_to_limbs(
            (1 << (BASE * (_k + _j))) % P_INT
        )
# numpy, not jnp: importing this module must not open the jax backend
_FOLD_LO = (_FOLD_M & 31).astype(np.float32)
_FOLD_HI = (_FOLD_M >> 5).astype(np.float32)
_WRAP_COL = _int_to_limbs((1 << (BASE * NLIMBS)) % P_INT)[:, None]

_HIGHEST = jax.lax.Precision.HIGHEST


# -- field helpers (pg1's schedule at secp parameters) ----------------------


def _crush(t, wrap, rounds: int = 1):
    b = t.shape[-1]
    for _ in range(rounds):
        carry = t >> BASE
        top = carry[NLIMBS - 1 : NLIMBS, :]
        shifted = jnp.concatenate(
            [jnp.zeros((1, b), jnp.int32), carry[: NLIMBS - 1, :]], axis=0
        )
        t = (t & MASK) + shifted + top * wrap
    return t


def _conv(x, y):
    b = x.shape[-1]
    zpad = jnp.zeros((NLIMBS - 1, b), jnp.int32)
    ypad = jnp.concatenate([zpad, y, zpad], axis=0)  # (3*NLIMBS-2, B)
    t = jnp.zeros((CONVLEN, b), jnp.int32)
    for i in range(NLIMBS):
        t = t + x[i : i + 1, :] * ypad[NLIMBS - 1 - i : 2 * NLIMBS - 1 - i + NLIMBS - 1, :]
    return t


def _fold(t, c):
    mlo, mhi, wrap = c
    a = t & MASK
    bb = (t >> BASE) & MASK
    cc = t >> (2 * BASE)
    planes = jnp.concatenate([a, bb, cc], axis=0).astype(jnp.float32)
    lo = jnp.dot(mlo, planes, preferred_element_type=jnp.float32,
                 precision=_HIGHEST)
    hi = jnp.dot(mhi, planes, preferred_element_type=jnp.float32,
                 precision=_HIGHEST)
    r = lo.astype(jnp.int32) + (hi.astype(jnp.int32) << 5)
    return _crush(r, wrap, 3)


def _mul(x, y, c):
    return _fold(_conv(x, y), c)


def _sqr(x, c):
    return _mul(x, x, c)


def _add(x, y, c):
    return _crush(x + y, c[2], 1)


def _sub(x, y, c):
    return _crush(x - y, c[2], 1)


def _mul_small(x, k: int, c):
    return _crush(x * k, c[2], 2)


def _split(p):
    return (
        p[0:NLIMBS],
        p[COMP_ROWS : COMP_ROWS + NLIMBS],
        p[2 * COMP_ROWS : 2 * COMP_ROWS + NLIMBS],
    )


def _join(x, y, z):
    b = x.shape[-1]
    z6 = jnp.zeros((COMP_ROWS - NLIMBS, b), jnp.int32)
    return jnp.concatenate([x, z6, y, z6, z, z6], axis=0)


# -- group law (Jacobian, a = 0 curve y^2 = x^3 + 7, same shape as BLS) ----


def _pt_dbl_val(p, c):
    X1, Y1, Z1 = _split(p)
    A = _sqr(X1, c)
    B = _sqr(Y1, c)
    C = _sqr(B, c)
    D = _sub(_sub(_sqr(_add(X1, B, c), c), A, c), C, c)
    D = _add(D, D, c)
    E = _mul_small(A, 3, c)
    F = _sqr(E, c)
    X3 = _sub(F, _add(D, D, c), c)
    Y3 = _sub(_mul(E, _sub(D, X3, c), c), _mul_small(C, 8, c), c)
    Z3 = _mul(Y1, Z1, c)
    Z3 = _add(Z3, Z3, c)
    return _join(X3, Y3, Z3)


def _pt_add_val(p, q, c):
    X1, Y1, Z1 = _split(p)
    X2, Y2, Z2 = _split(q)
    Z1Z1 = _sqr(Z1, c)
    Z2Z2 = _sqr(Z2, c)
    U1 = _mul(X1, Z2Z2, c)
    U2 = _mul(X2, Z1Z1, c)
    S1 = _mul(_mul(Y1, Z2, c), Z2Z2, c)
    S2 = _mul(_mul(Y2, Z1, c), Z1Z1, c)
    H = _sub(U2, U1, c)
    Rr = _sub(S2, S1, c)
    I = _sqr(_add(H, H, c), c)
    J = _mul(H, I, c)
    Rr2 = _add(Rr, Rr, c)
    V = _mul(U1, I, c)
    X3 = _sub(_sub(_sqr(Rr2, c), J, c), _add(V, V, c), c)
    S1J = _mul(S1, J, c)
    Y3 = _sub(_mul(Rr2, _sub(V, X3, c), c), _add(S1J, S1J, c), c)
    Z3 = _mul(_mul(Z1, Z2, c), H, c)
    Z3 = _add(Z3, Z3, c)
    return _join(X3, Y3, Z3)


# -- pallas wrappers --------------------------------------------------------

_CONST_SPECS = [
    pl.BlockSpec((NLIMBS, 3 * CONVLEN), lambda *g: (0, 0),
                 memory_space=pltpu.VMEM),
    pl.BlockSpec((NLIMBS, 3 * CONVLEN), lambda *g: (0, 0),
                 memory_space=pltpu.VMEM),
    pl.BlockSpec((NLIMBS, 1), lambda *g: (0, 0), memory_space=pltpu.VMEM),
]


def _const_args():
    return (_FOLD_LO, _FOLD_HI, _WRAP_COL)


def _consts(mlo_ref, mhi_ref, wrap_ref):
    return (mlo_ref[:], mhi_ref[:], wrap_ref[:])


def _dbl_kernel(mlo, mhi, wrap, p_ref, o_ref):
    o_ref[:] = _pt_dbl_val(p_ref[:], _consts(mlo, mhi, wrap))


def _add_kernel(mlo, mhi, wrap, p_ref, q_ref, o_ref):
    o_ref[:] = _pt_add_val(p_ref[:], q_ref[:], _consts(mlo, mhi, wrap))


_SECP_CONSTS = (_const_args(), _CONST_SPECS)  # pg1._lane_call's `consts`


@jax.jit
def pl_dbl(p):
    return _per_platform(
        lambda p: _lane_call(
            _dbl_kernel, POINT_ROWS, p, tile=LANE_TILE, consts=_SECP_CONSTS
        ),
        lambda p: _pt_dbl_val(p, _const_args()),
        p,
    )


@jax.jit
def pl_add(p, q):
    return _per_platform(
        lambda p, q: _lane_call(
            _add_kernel, POINT_ROWS, p, q, tile=LANE_TILE,
            consts=_SECP_CONSTS,
        ),
        lambda p, q: _pt_add_val(p, q, _const_args()),
        p,
        q,
    )


# pg1's window scan at secp parameters
_msm_scan = make_msm_scan(
    _pt_dbl_val, _pt_add_val, tile=LANE_TILE, consts=_SECP_CONSTS
)


def build_table(lanes):
    two = pl_dbl(lanes)
    rows = [jnp.zeros_like(lanes), lanes, two]
    cur = two
    for _ in range(TABLE - 3):
        cur = pl_add(cur, lanes)
        rows.append(cur)
    return jnp.stack(rows, axis=0)


_SQRT_EXP = (P_INT + 1) // 4
_SQRT_BITS = np.array(
    [(_SQRT_EXP >> i) & 1 for i in range(253, -1, -1)], dtype=np.int32
)[:, None]  # MSB-first column


def sqrt_kernel(x_lanes, bits):
    """Per-lane y = (x^3 + 7)^((p+1)/4): candidate square roots for the
    signature points' x coordinates — the host pow() at ~300 us/lane was
    the recover pipeline's single biggest cost. Square-and-multiply with
    the STATIC exponent bit table rides a fori loop (one sqr+mul+select
    body in the trace). Non-residues produce garbage lanes the host
    rejects with the y^2 == x^3+7 check it already performs."""
    c = _const_args()
    x3 = _mul(_sqr(x_lanes, c), x_lanes, c)
    seven = jnp.zeros_like(x_lanes).at[0].set(7)
    y2 = _add(x3, seven, c)

    def step(i, acc):
        sq = _mul(acc, acc, c)
        withmul = _mul(sq, y2, c)
        return jnp.where(bits[i] != 0, withmul, sq)

    # exponent MSB is 1: start from y2 itself
    y = jax.lax.fori_loop(1, 254, step, y2)
    return y


sqrt_kernel_jit = jax.jit(sqrt_kernel)


def ints_from_limbs(arr) -> list:
    """(26, n) limb planes -> python ints mod p. Device limbs are LOOSE
    (possibly >10-bit or negative), so the shift-accumulate runs in
    python-int space per lane — 26 multiword ops/lane, ~0.15 s per 10k
    lanes, a known slice of the host budget."""
    arr = np.asarray(arr).astype(np.int64).T  # (n, 26)
    out = []
    for row in arr:
        v = 0
        for i in range(NLIMBS - 1, -1, -1):
            v = (v << 10) + int(row[i])
        out.append(v % P_INT)
    return out


def recover_kernel(lanes, digits):
    """lanes: (96, 2n) interleaved [R_0, G, R_1, G, ...]; digits: (64, 2n)
    interleaved [u1_0, u2_0, u1_1, u2_1, ...]. Returns one fused
    (97, n) buffer: per-signature Q = u1*R + u2*G (row 96 = infinity
    flags)."""
    table = build_table(lanes)
    acc, fl = _msm_scan(table, digits[:, None, :])
    # sum adjacent lane pairs (u1*R_i, u2*G) -> Q_i
    a, b = acc[:, 0::2], acc[:, 1::2]
    fa, fb = fl[0::2], fl[1::2]
    r = pl_add(a, b)
    out = jnp.where(fb[None, :], a, jnp.where(fa[None, :], b, r))
    ofl = fa & fb
    return jnp.concatenate(
        [out, ofl.astype(jnp.int32)[None, :]], axis=0
    )


recover_kernel_jit = jax.jit(recover_kernel)


# -- host marshal -----------------------------------------------------------


_W10 = (1 << np.arange(10)).astype(np.int32)


def limbs_from_ints(vals: Sequence[int]) -> np.ndarray:
    """(n, 26) limb rows, vectorized: bytes -> unpacked bits -> 10-bit
    windows (a Python per-limb loop costs ~1 s at pool-ingest batch
    sizes)."""
    raw = np.frombuffer(
        b"".join(v.to_bytes(32, "big") for v in vals), np.uint8
    ).reshape(-1, 32)
    bits = np.unpackbits(raw[:, ::-1], axis=1, bitorder="little")
    bits = np.concatenate(
        [bits, np.zeros((len(vals), 4), np.uint8)], axis=1
    )  # 260 bits
    return (
        bits.reshape(-1, NLIMBS, 10).astype(np.int32) * _W10
    ).sum(axis=2)


def pt_pack(points: Sequence[Optional[Tuple[int, int]]]) -> np.ndarray:
    """Affine (x, y) tuples (None = infinity) -> (96, n) Jacobian limbs."""
    n = len(points)
    out = np.zeros((POINT_ROWS, n), dtype=np.int32)
    xs = [p[0] if p else 0 for p in points]
    ys = [p[1] if p else 1 for p in points]
    zs = [0 if p is None else 1 for p in points]
    out[0:NLIMBS] = limbs_from_ints(xs).T
    out[COMP_ROWS : COMP_ROWS + NLIMBS] = limbs_from_ints(ys).T
    out[2 * COMP_ROWS, :] = np.asarray(zs, np.int32)
    return out


def _limbs_int(a) -> int:
    return sum(int(a[i]) << (BASE * i) for i in range(NLIMBS)) % P_INT


def pt_unpack(arr, flags=None) -> List[Optional[Tuple[int, int, int]]]:
    """(96, n) limbs -> Jacobian int tuples (None = infinity)."""
    arr = np.asarray(arr)
    xs = ints_from_limbs(arr[0:NLIMBS])
    ys = ints_from_limbs(arr[COMP_ROWS : COMP_ROWS + NLIMBS])
    zs = ints_from_limbs(arr[2 * COMP_ROWS : 2 * COMP_ROWS + NLIMBS])
    fl = (
        np.asarray(flags)
        if flags is not None
        else np.zeros(arr.shape[-1], bool)
    )
    return [
        None if (fl[i] or zs[i] == 0) else (xs[i], ys[i], zs[i])
        for i in range(arr.shape[-1])
    ]


def digits_col(scalars: Sequence[int]) -> np.ndarray:
    """MSB-first 4-bit digit planes (64, n), vectorized via nibble split."""
    raw = np.frombuffer(
        b"".join(s.to_bytes(32, "big") for s in scalars), np.uint8
    ).reshape(-1, 32)
    dig = np.empty((len(scalars), 64), np.int32)
    dig[:, 0::2] = raw >> 4
    dig[:, 1::2] = raw & 0xF
    return dig.T.copy()


class TpuEcdsaRecover:
    """Batched public-key recovery on the chip (pool-ingest scale).

    recover_batch(hashes, sigs) -> list of compressed pubkeys/None with
    semantics identical to ecdsa.recover_hash (differential-tested).
    Host does the cheap bigint work (validation, R decompress, u1/u2,
    batch affine); the chip runs the two 256-bit scalar multiplications
    per signature — ~99.9% of the serial cost."""

    # signatures per kernel launch: 4096 sigs = 8192 lanes bounds both
    # the set of compiled shapes and the power-of-two padding waste
    CHUNK = 4096

    def recover_batch(self, hashes, sigs) -> list:
        n = len(hashes)
        out: list = [None] * n
        vals = []  # (index, x, r, s, z, parity)
        for i in range(n):
            v = self._validate(hashes[i], sigs[i])
            if v is not None:
                vals.append((i, *v))
        if not vals:
            return out
        P, N = ecdsa.P, ecdsa.N
        # square roots for ALL candidate x on the chip, one launch
        m = len(vals)
        m_pad = 1 << max(0, m - 1).bit_length() if m > 1 else 1
        xs = [v[1] for v in vals] + [1] * (m_pad - m)
        y_lanes = np.asarray(
            sqrt_kernel_jit(
                jnp.asarray(limbs_from_ints(xs).T.copy()),
                jnp.asarray(_SQRT_BITS),
            )
        )
        ys = ints_from_limbs(y_lanes)[:m]
        # r^-1 for all signatures: ONE modular inversion via Montgomery's
        # trick (pow(r, -1, N) per signature was ~30% of the pipeline)
        rs = [v[2] for v in vals]
        pref = [1] * (m + 1)
        for i, r in enumerate(rs):
            pref[i + 1] = pref[i] * r % N
        inv_all = pow(pref[m], -1, N)
        rinvs = [0] * m
        for i in range(m - 1, -1, -1):
            rinvs[i] = pref[i] * inv_all % N
            inv_all = inv_all * rs[i] % N
        jobs = []  # (index, hash, sig, R_point, u1, u2)
        for k, (idx, x, r, s_, z, parity) in enumerate(vals):
            y = ys[k]
            if y * y % P != (pow(x, 3, P) + 7) % P:
                continue  # x^3+7 is a non-residue: invalid signature
            if (y & 1) != parity:
                y = P - y
            rinv = rinvs[k]
            u1 = s_ * rinv % N
            u2 = (N - z) * rinv % N if z else 0
            jobs.append((idx, hashes[idx], sigs[idx], (x, y), u1, u2))
        for lo in range(0, len(jobs), self.CHUNK):
            self._run_chunk(jobs[lo : lo + self.CHUNK], out)
        return out

    def _run_chunk(self, jobs, out) -> None:
        if not jobs:
            return
        m = len(jobs)
        m_pad = 1 << max(0, m - 1).bit_length() if m > 1 else 1
        g_aff = (ecdsa.GX, ecdsa.GY)
        pts: list = []
        u_digits: list = []
        for _idx, _h, _sig, r_pt, u1, u2 in jobs:
            pts.extend([r_pt, g_aff])
            u_digits.extend([u1, u2])
        for _ in range(m_pad - m):
            pts.extend([g_aff, g_aff])
            u_digits.extend([0, 0])
        fused = np.asarray(
            recover_kernel_jit(
                jnp.asarray(pt_pack(pts)),
                jnp.asarray(digits_col(u_digits)),
            )
        )
        qs = pt_unpack(fused[:POINT_ROWS], fused[POINT_ROWS] != 0)
        # batch affine: one modular inversion via Montgomery's trick
        zs = [q[2] if q else 1 for q in qs[:m]]
        prefix = [1] * (m + 1)
        for i, z in enumerate(zs):
            prefix[i + 1] = prefix[i] * z % P_INT
        inv_all = pow(prefix[m], -1, P_INT)
        zinvs = [0] * m
        for i in range(m - 1, -1, -1):
            zinvs[i] = prefix[i] * inv_all % P_INT
            inv_all = inv_all * zs[i] % P_INT
        for k, (idx, h, sig, _r_pt, _u1, _u2) in enumerate(jobs):
            q = qs[k]
            if q is None:
                # u1*R == +-u2*G degenerates the incomplete pairwise add
                # (Z=0); adversarially constructible, so the oracle scalar
                # path answers for this signature — identical result,
                # attacker gains nothing
                out[idx] = ecdsa.recover_hash(h, sig)
                continue
            zi = zinvs[k]
            zi2 = zi * zi % P_INT
            ax = q[0] * zi2 % P_INT
            ay = q[1] * zi2 % P_INT * zi % P_INT
            out[idx] = bytes([0x02 | (ay & 1)]) + ax.to_bytes(32, "big")

    @staticmethod
    def _validate(h: bytes, sig: bytes):
        """Cheap per-signature validation mirroring ecdsa._recover_hash_py;
        returns (x, r, s, z, parity) or None. The expensive parts — the
        square root (chip) and r^-1 (batched Montgomery inversion) — are
        hoisted out of the per-signature path."""
        if len(sig) != 65 or len(h) != 32:
            return None
        r = int.from_bytes(sig[:32], "big")
        s = int.from_bytes(sig[32:64], "big")
        v = sig[64]
        N, P = ecdsa.N, ecdsa.P
        if not (1 <= r < N and 1 <= s < N) or v > 3:
            return None
        x = r + (N if v & 2 else 0)
        if x >= P:
            return None
        z = int.from_bytes(h, "big") % N
        return (x, r, s, z, v & 1)
