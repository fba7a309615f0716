"""Pallas G1 kernel (ops/pg1.py) vs the host oracle.

Mirror of tests/test_msm.py for the round-3 VMEM-resident kernel: field-mul
fuzz (plain representation, fold-matrix reduction), group-law fuzz, windowed
MSM, tree reduce, and the full era kernel on tiny shapes. On CPU the kernels
run in pallas interpret mode (pg1.INTERPRET), so the same tests validate the
exact kernel bodies that compile on the chip.

Conformance anchor: the reference executes these aggregates as serial MCL
pairings/Lagrange loops (TPKE/PublicKey.cs:55-92 via HoneyBadger.cs:205-247).
"""
from __future__ import annotations

import random

import numpy as np
import pytest

import jax.numpy as jnp

from lachain_tpu.crypto import bls12381 as bls
from lachain_tpu.ops import msm, pg1


@pytest.fixture(scope="module")
def rng():
    return random.Random(0xFA11A5)


def _pack_fp(vals):
    return jnp.asarray(msm._ints_to_limbs_np(vals).T.copy())


def test_fp_mul_fuzz(rng):
    n = 128
    xs = [rng.randrange(bls.P) for _ in range(n)]
    ys = [rng.randrange(bls.P) for _ in range(n)]
    out = np.asarray(pg1.pl_fp_mul(_pack_fp(xs), _pack_fp(ys)))
    for i in range(n):
        assert pg1._limbs_int(out[:, i]) == xs[i] * ys[i] % bls.P
    # magnitude invariant: crush(3) must land limbs within the loose bound
    assert np.abs(out).max() < 1 << 12


def test_fp_mul_edge_values():
    edge = [0, 1, 2, bls.P - 1, bls.P - 2, (1 << 440) % bls.P, 3]
    n = len(edge)
    xs, ys = edge, list(reversed(edge))
    out = np.asarray(pg1.pl_fp_mul(_pack_fp(xs), _pack_fp(ys)))
    for i in range(n):
        assert pg1._limbs_int(out[:, i]) == xs[i] * ys[i] % bls.P


def test_dbl_add_vs_oracle(rng):
    n = 16
    pts = [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]
    qts = [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]
    pd, qd = jnp.asarray(pg1.g1_pack(pts)), jnp.asarray(pg1.g1_pack(qts))
    d_out = pg1.g1_unpack(np.asarray(pg1.pl_dbl(pd)))
    a_out = pg1.g1_unpack(np.asarray(pg1.pl_add(pd, qd)))
    for i in range(n):
        assert bls.g1_eq(d_out[i], bls.g1_dbl(pts[i]))
        assert bls.g1_eq(a_out[i], bls.g1_add(pts[i], qts[i]))


def test_msm_windowed_vs_oracle(rng):
    """Short (16-bit) scalars keep interpret mode fast on CPU while driving
    the identical kernel body the chip compiles."""
    n = 16
    pts = [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]
    scalars = [rng.randrange(1, 1 << 16) for _ in range(n)]
    scalars[3] = 0  # a zero lane must come back flagged infinity
    dig = jnp.asarray(pg1.digits_col(scalars, 4))
    acc, flags = pg1.msm_windowed(jnp.asarray(pg1.g1_pack(pts)), dig)
    got = pg1.g1_unpack(np.asarray(acc), np.asarray(flags))
    for i in range(n):
        want = bls.g1_mul(pts[i], scalars[i])
        assert bls.g1_eq(got[i], want), i
    assert bool(np.asarray(flags)[3])


def test_tree_reduce_flags(rng):
    n = 16
    pts = [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]
    flags = np.zeros(n, bool)
    flags[5] = flags[6] = True  # infinity lanes must drop out of the sum
    acc, fl = pg1.tree_reduce_k(
        jnp.asarray(pg1.g1_pack(pts)), jnp.asarray(flags), n
    )
    want = bls.G1_INF
    for i, p in enumerate(pts):
        if not flags[i]:
            want = bls.g1_add(want, p)
    got = pg1.g1_unpack(np.asarray(acc), np.asarray(fl))[0]
    assert bls.g1_eq(got, want)
    # all-infinity group
    acc2, fl2 = pg1.tree_reduce_k(
        jnp.asarray(pg1.g1_pack(pts)), jnp.asarray(np.ones(n, bool)), n
    )
    assert bool(np.asarray(fl2)[0])


def test_era_kernel_tiny(rng):
    """Full era semantics at S=2, K=4 with short scalars (interpret-mode
    budget): per-slot u/y RLC aggregates + split GLV combine halves."""
    s, k = 2, 4
    n = s * k
    u_pts = [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]
    y_pts = [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]
    rlc = [rng.randrange(1, 1 << 16) for _ in range(n)]
    lag = [rng.randrange(1, 1 << 16) if i % k != 1 else 0 for i in range(n)]
    out = pg1.era_kernel(
        jnp.asarray(pg1.g1_pack(u_pts)),
        jnp.asarray(pg1.g1_pack(y_pts)),
        jnp.asarray(pg1.digits_col(rlc, 4)),
        jnp.asarray(pg1.digits_col(lag, 4)),
        jnp.asarray(pg1.digits_col([0] * n, 4)),  # second GLV half zero
        k,
    )
    out_r, ofl_r, out_l, ofl_l = [np.asarray(o) for o in out]
    pts_r = pg1.g1_unpack(out_r, ofl_r)
    pts_l = pg1.g1_unpack(out_l, ofl_l)
    for si in range(s):
        u_agg = y_agg = comb = bls.G1_INF
        for i in range(si * k, (si + 1) * k):
            u_agg = bls.g1_add(u_agg, bls.g1_mul(u_pts[i], rlc[i]))
            y_agg = bls.g1_add(y_agg, bls.g1_mul(y_pts[i], rlc[i]))
            comb = bls.g1_add(comb, bls.g1_mul(u_pts[i], lag[i]))
        assert bls.g1_eq(pts_r[si], u_agg)
        assert bls.g1_eq(pts_r[s + si], y_agg)
        # comb half 2 is all-zero digits -> flagged; comb = half 1
        assert bool(ofl_l[s + si])
        assert bls.g1_eq(pts_l[si], comb)


def test_era_pack_roundtrip(rng):
    """era_pack_inputs + the device-side parse must reproduce the raw
    arrays bit-exactly (checked on host; the parse itself is plain jnp)."""
    n = 8
    pts = [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]
    u_np = pg1.g1_pack(pts)
    r16 = pg1.digits_col([rng.randrange(1, 1 << 64) for _ in range(n)], pg1.W64)
    l1 = pg1.digits_col([rng.randrange(1, 1 << 128) for _ in range(n)], pg1.W128)
    l2 = pg1.digits_col([rng.randrange(1, 1 << 128) for _ in range(n)], pg1.W128)
    buf = jnp.asarray(pg1.era_pack_inputs(u_np, r16, l1, l2))
    o = pg1.POINT_ROWS * n * 2
    u8 = buf[:o].reshape(pg1.POINT_ROWS, n, 2).astype(jnp.int32)
    u = u8[..., 0] + (u8[..., 1] << 8)
    assert (np.asarray(u) == u_np).all()
    r16_back = buf[o : o + pg1.W64 * n].reshape(pg1.W64, n)
    assert (np.asarray(r16_back) == r16).all()
    rest = buf[o + pg1.W64 * n :].reshape(2, pg1.W128, n)
    assert (np.asarray(rest[0]) == l1).all()
    assert (np.asarray(rest[1]) == l2).all()



def test_pallas_era_pipeline_end_to_end():
    """The bench path in miniature on the Pallas pipeline — including a
    NON-power-of-two validator count, which exercises run_era's per-slot
    lane padding (K=5 -> K_pad=8)."""
    from lachain_tpu.crypto import tpke
    from lachain_tpu.crypto.provider import get_backend
    from lachain_tpu.ops.verify import PallasEraPipeline

    class Rng:
        def __init__(self, seed):
            self._r = random.Random(seed)

        def randbelow(self, n):
            return self._r.randrange(n)

    n, f = 5, 1
    dealer = tpke.TpkeTrustedKeyGen(n, f, rng=Rng(3))
    y_points = [vk.y_i for vk in dealer.verification_keys]
    slots_raw = []
    for s in range(2):
        msg = bytes([s + 1]) * 32
        ct = dealer.pub.encrypt(msg, share_id=s, rng=Rng(s))
        h = tpke._hash_uv_to_g2(ct.u, ct.v)
        decs = [
            dealer.private_key(i).decrypt_share(ct, check=False)
            for i in range(n)
        ]
        slots_raw.append((ct, h, decs, msg))
    pipeline = PallasEraPipeline()
    kernel_slots = []
    for ct, h, decs, _ in slots_raw:
        chosen = decs[: f + 1]
        xs = [d.decryptor_id + 1 for d in chosen]
        cs = bls.fr_lagrange_coeffs(xs, at=0)
        row = [0] * n
        for d, c in zip(chosen, cs):
            row[d.decryptor_id] = c
        kernel_slots.append(([d.ui for d in decs], row))
    aggs, _ = pipeline.run_era(kernel_slots, y_points, Rng(9))
    backend = get_backend()
    pairs = []
    for s, (ct, h, _, _) in enumerate(slots_raw):
        pairs.append((aggs[s][0], h))
        pairs.append((bls.g1_neg(aggs[s][1]), ct.w))
    assert backend.pairing_check(pairs)
    for s, (ct, _, _, msg) in enumerate(slots_raw):
        pad = tpke._pad(aggs[s][2], len(ct.v))
        assert bytes(a ^ b for a, b in zip(ct.v, pad)) == msg
    # ragged input must raise, not mis-align lanes
    bad = [kernel_slots[0], (kernel_slots[1][0][:-1], kernel_slots[1][1])]
    with pytest.raises(ValueError):
        pipeline.run_era(bad, y_points, Rng(10))
