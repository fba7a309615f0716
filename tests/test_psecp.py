"""Pallas secp256k1 recover kernel (ops/psecp.py) vs the ECDSA oracle.

CPU CI covers the field arithmetic, group law, marshal round-trips and the
host-side validation/scalar plumbing; the full windowed-scan recover path
(64 windows -> XLA-CPU compile explosion in emulation) is exercised on the
chip: chip_smoke.py holds a 2048-signature batch to the native library
there. The pool wires in through
ecdsa.recover_hash_batch's size-gated TPU routing.
"""
import random

import numpy as np
import pytest

import jax.numpy as jnp

from lachain_tpu.crypto import ecdsa
from lachain_tpu.ops import psecp


@pytest.fixture(scope="module")
def rng():
    return random.Random(0x5EC9)


def _pack_fp(vals):
    return jnp.asarray(psecp.limbs_from_ints(vals).T.copy())


def test_secp_field_mul_fuzz(rng):
    n = 64
    xs = [rng.randrange(ecdsa.P) for _ in range(n)]
    ys = [rng.randrange(ecdsa.P) for _ in range(n)]
    out = psecp._mul(_pack_fp(xs), _pack_fp(ys), psecp._const_args())
    got = psecp.ints_from_limbs(np.asarray(out))
    for i in range(n):
        assert got[i] == xs[i] * ys[i] % ecdsa.P
    assert np.abs(np.asarray(out)).max() < 1 << 13  # loose-limb bound


def test_secp_group_law_vs_oracle(rng):
    n = 4
    pts = [ecdsa._mul(ecdsa.G, rng.randrange(1, ecdsa.N)) for _ in range(n)]
    qts = [ecdsa._mul(ecdsa.G, rng.randrange(1, ecdsa.N)) for _ in range(n)]
    pd = jnp.asarray(psecp.pt_pack(pts))
    qd = jnp.asarray(psecp.pt_pack(qts))
    d = psecp.pt_unpack(np.asarray(psecp.pl_dbl(pd)))
    a = psecp.pt_unpack(np.asarray(psecp.pl_add(pd, qd)))

    def to_aff(j):
        x, y, z = j
        zi = pow(z, -1, ecdsa.P)
        zi2 = zi * zi % ecdsa.P
        return (x * zi2 % ecdsa.P, y * zi2 * zi % ecdsa.P)

    for i in range(n):
        assert to_aff(d[i]) == ecdsa._add(pts[i], pts[i])
        assert to_aff(a[i]) == ecdsa._add(pts[i], qts[i])


def test_pack_digit_roundtrips(rng):
    vals = [rng.randrange(ecdsa.P) for _ in range(9)] + [0, 1, ecdsa.P - 1]
    limbs = psecp.limbs_from_ints(vals)
    assert psecp.ints_from_limbs(limbs.T.copy()) == vals
    scalars = [rng.randrange(1 << 256) for _ in range(5)]
    dig = psecp.digits_col(scalars)
    for i, s in enumerate(scalars):
        back = 0
        for w in range(64):
            back = (back << 4) | int(dig[w, i])
        assert back == s


def test_validate_matches_oracle_edges(rng):
    priv = ecdsa.generate_private_key()
    h = bytes(range(32))
    sig = ecdsa.sign_hash(priv, h)
    v = psecp.TpuEcdsaRecover._validate(h, sig)
    assert v is not None
    x, r, s, z, parity = v
    assert r == int.from_bytes(sig[:32], "big")
    # malformed cases the oracle rejects must be rejected here too
    assert psecp.TpuEcdsaRecover._validate(h, sig[:40]) is None
    bad = bytearray(sig)
    bad[64] = 9  # v out of range
    assert psecp.TpuEcdsaRecover._validate(h, bytes(bad)) is None
    zero_r = b"\x00" * 32 + sig[32:]
    assert psecp.TpuEcdsaRecover._validate(h, zero_r) is None


def _degenerate_sig():
    """Adversarial signature with u1*R == u2*G: R = kG, s = (N-z)/k, so
    the kernel's incomplete pairwise add degenerates (Z=0) and the host
    must answer through the oracle path."""
    k = 0x1234567
    R = ecdsa._mul(ecdsa.G, k)
    r = R[0]
    assert r < ecdsa.N
    z = 0x55AA
    s = (ecdsa.N - z) * pow(k, -1, ecdsa.N) % ecdsa.N
    v = R[1] & 1
    sig = r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])
    h = z.to_bytes(32, "big")
    return h, sig


def test_degenerate_validation_path():
    h, sig = _degenerate_sig()
    # the oracle recovers SOME key for this signature
    want = ecdsa.recover_hash(h, sig)
    assert want is not None
    # host-side validation accepts it (the kernel-vs-oracle equivalence on
    # this input is chip_smoke.py's check (d))
    assert psecp.TpuEcdsaRecover._validate(h, sig) is not None


# slice marker: crypto/accelerator kernels ("make test-kernel")
pytestmark = pytest.mark.kernel
