"""JAX kernel conformance vs the Python oracle (CPU, small batches).

Validates the device-side limb field arithmetic and batched curve ops that the
TPU hot path is built on. Mirrors the role of MclTests for the native binding.
"""
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lachain_tpu.crypto import bls12381 as bls  # noqa: E402
from lachain_tpu.ops import curve, fp  # noqa: E402

# jitted wrappers: tests drive the kernels the way production does (traced
# once, compiled), which is also orders of magnitude faster than eager.
j_mont_mul = jax.jit(fp.mont_mul)
j_add = jax.jit(fp.add)
j_sub = jax.jit(fp.sub)
j_neg = jax.jit(fp.neg)
j_g1_add = jax.jit(curve.g1_add)
j_g1_dbl = jax.jit(curve.g1_dbl)
j_g1_smul = jax.jit(curve.g1_scalar_mul_bits)
j_g1_msm = jax.jit(curve.g1_msm)
j_g1_reduce_sum = jax.jit(curve.g1_reduce_sum)
j_g2_add = jax.jit(curve.g2_add)
j_g2_smul = jax.jit(curve.g2_scalar_mul_bits)


def test_fp_mont_mul_matches_oracle():
    rng = random.Random(1)
    xs = [rng.randrange(bls.P) for _ in range(8)]
    ys = [rng.randrange(bls.P) for _ in range(8)]
    xm = jnp.asarray(np.stack([fp.to_mont_host(v) for v in xs]))
    ym = jnp.asarray(np.stack([fp.to_mont_host(v) for v in ys]))
    zm = j_mont_mul(xm, ym)
    for i in range(8):
        got = fp.from_mont_host(np.asarray(zm[i]))
        assert got == xs[i] * ys[i] % bls.P, i


def test_fp_add_sub_neg():
    rng = random.Random(2)
    xs = [rng.randrange(bls.P) for _ in range(4)] + [0]
    ys = [rng.randrange(bls.P) for _ in range(4)] + [0]
    xm = jnp.asarray(np.stack([fp.to_mont_host(v) for v in xs]))
    ym = jnp.asarray(np.stack([fp.to_mont_host(v) for v in ys]))
    s = j_add(xm, ym)
    d = j_sub(xm, ym)
    n = j_neg(xm)
    for i in range(5):
        assert fp.from_mont_host(np.asarray(s[i])) == (xs[i] + ys[i]) % bls.P
        assert fp.from_mont_host(np.asarray(d[i])) == (xs[i] - ys[i]) % bls.P
        assert fp.from_mont_host(np.asarray(n[i])) == (-xs[i]) % bls.P


def test_fp_carry_chain_regression():
    """sub(x, x) must be exactly zero (33-limb carry ripple), and values
    adjacent to p must reduce canonically — the fixed-round propagation bug."""
    rng = random.Random(99)
    vals = [rng.randrange(bls.P) for _ in range(3)] + [0, 1, bls.P - 1]
    xm = jnp.asarray(np.stack([fp.to_mont_host(v) for v in vals]))
    z = j_sub(xm, xm)
    assert bool(jnp.all(fp.is_zero(z)))
    # (p-1) + 1 == 0 mod p in plain (non-Montgomery) limb domain too
    a = jnp.asarray(np.stack([fp.to_mont_host(bls.P - 1)]))
    b = jnp.asarray(np.stack([fp.to_mont_host(1)]))
    s = j_add(a, b)
    assert bool(jnp.all(fp.is_zero(s)))


def _random_g1(rng, n):
    return [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]


def test_g1_add_dbl_matches_oracle():
    rng = random.Random(3)
    pts = _random_g1(rng, 4)
    qts = _random_g1(rng, 4)
    # include the special cases: equal points, negation, infinity
    pts += [pts[0], pts[1], bls.G1_INF, pts[2]]
    qts += [pts[0], bls.g1_neg(pts[1]), qts[0], bls.G1_INF]
    pd = jnp.asarray(curve.g1_to_device(pts))
    qd = jnp.asarray(curve.g1_to_device(qts))
    sums = curve.g1_from_device(j_g1_add(pd, qd))
    dbls = curve.g1_from_device(j_g1_dbl(pd))
    for i in range(len(pts)):
        assert bls.g1_eq(sums[i], bls.g1_add(pts[i], qts[i])), f"add {i}"
        assert bls.g1_eq(dbls[i], bls.g1_dbl(pts[i])), f"dbl {i}"


def test_g1_scalar_mul_matches_oracle():
    rng = random.Random(4)
    pts = _random_g1(rng, 4)
    scalars = [rng.randrange(bls.R) for _ in range(3)] + [0]
    pd = jnp.asarray(curve.g1_to_device(pts))
    bits = jnp.asarray(curve.scalars_to_bits(scalars))
    res = curve.g1_from_device(j_g1_smul(pd, bits))
    for i in range(4):
        assert bls.g1_eq(res[i], bls.g1_mul(pts[i], scalars[i])), i


def test_g1_msm_matches_oracle():
    rng = random.Random(5)
    n = 8
    pts = _random_g1(rng, n)
    scalars = [rng.randrange(bls.R) for _ in range(n)]
    pd = jnp.asarray(curve.g1_to_device(pts))
    bits = jnp.asarray(curve.scalars_to_bits(scalars))
    got = curve.g1_from_device(j_g1_msm(pd, bits)[None])[0]
    expect = bls.G1_INF
    for p, s in zip(pts, scalars):
        expect = bls.g1_add(expect, bls.g1_mul(p, s))
    assert bls.g1_eq(got, expect)


def _random_g2(rng, n):
    return [bls.g2_mul(bls.G2_GEN, rng.randrange(1, bls.R)) for _ in range(n)]


def test_g2_ops_match_oracle():
    rng = random.Random(6)
    pts = _random_g2(rng, 2) + [bls.G2_INF]
    qts = _random_g2(rng, 2) + [bls.G2_GEN]
    pd = jnp.asarray(curve.g2_to_device(pts))
    qd = jnp.asarray(curve.g2_to_device(qts))
    sums = curve.g2_from_device(j_g2_add(pd, qd))
    for i in range(len(pts)):
        assert bls.g2_eq(sums[i], bls.g2_add(pts[i], qts[i])), i
    scalars = [rng.randrange(bls.R) for _ in range(len(pts))]
    bits = jnp.asarray(curve.scalars_to_bits(scalars))
    muls = curve.g2_from_device(j_g2_smul(pd, bits))
    for i in range(len(pts)):
        assert bls.g2_eq(muls[i], bls.g2_mul(pts[i], scalars[i])), i


def test_g1_reduce_sum_odd_counts():
    """Regression: non-power-of-two batches must not silently drop points."""
    rng = random.Random(8)
    for n in (1, 3, 5, 7):
        pts = _random_g1(rng, n)
        pd = jnp.asarray(curve.g1_to_device(pts))
        got = curve.g1_from_device(j_g1_reduce_sum(pd)[None])[0]
        expect = bls.G1_INF
        for p in pts:
            expect = bls.g1_add(expect, p)
        assert bls.g1_eq(got, expect), n


def test_g1_msm_jits():
    rng = random.Random(7)
    n = 4
    pts = _random_g1(rng, n)
    scalars = [rng.randrange(bls.R) for _ in range(n)]
    pd = jnp.asarray(curve.g1_to_device(pts))
    bits = jnp.asarray(curve.scalars_to_bits(scalars, nbits=128))
    f = jax.jit(curve.g1_msm)
    out1 = f(pd, bits)
    out2 = f(pd, bits)  # cached call
    assert np.array_equal(np.asarray(out1), np.asarray(out2))

# slice marker: crypto/accelerator kernels ("make test-kernel")
pytestmark = pytest.mark.kernel
