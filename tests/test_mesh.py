"""Multi-device mesh tests on the virtual 8-CPU platform (conftest.py).

VERDICT r3 item #2: the mesh path must be builder-owned — shard-vs-single
bit-equality for the era step, non-power-of-two batch padding, uneven slot
counts, and the TPU backend actually selecting the mesh pipeline when >1
device is visible. The driver's dryrun_multichip covers compile+run; these
cover CORRECTNESS against the host oracle.
"""
import random

import numpy as np
import pytest

import jax

from lachain_tpu.parallel import mesh_unsupported_reason

# The guard must run BEFORE the mesh import: on jax builds without the
# top-level shard_map export the import itself raises, which a pytestmark
# skipif cannot intercept (it fires after collection imports the module).
_reason = mesh_unsupported_reason()
if _reason is not None:
    pytest.skip(_reason, allow_module_level=True)

from lachain_tpu.crypto import bls12381 as bls
from lachain_tpu.crypto import tpke
from lachain_tpu.parallel.mesh import (
    MeshEraPipeline,
    make_era_mesh,
    sharded_glv_era_step,
)

# slice marker: multi-device mesh crypto ("make test-mesh" / the CI mesh
# job). Kernel-compiling tests are additionally marked slow so the tier-1
# 'not slow' sweep never pays shard_map compiles; the mesh job runs -m mesh
# INCLUDING slow, so they can never silently skip everywhere.
pytestmark = pytest.mark.mesh


def _rand_points(rng, n):
    return [bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R)) for _ in range(n)]


def _oracle_msm(points, scalars):
    acc = bls.G1_INF
    for p, c in zip(points, scalars):
        acc = bls.g1_add(acc, bls.g1_mul(p, c))
    return acc


@pytest.mark.slow
def test_sharded_era_step_matches_single_device():
    """Bit-equality: the shard_mapped era kernel on the 8-device mesh equals
    the same kernel run unsharded on one device."""
    from lachain_tpu.ops import msm

    rng = random.Random(3)
    mesh = make_era_mesh(len(jax.devices()))
    n_slot, n_share = mesh.shape["slot"], mesh.shape["share"]
    s, k = n_slot, 2 * n_share
    pts = _rand_points(rng, s * k)
    u = msm.g1_to_device_loose(pts).reshape(s, k, 3, -1)
    y = msm.g1_to_device_loose(list(reversed(pts))).reshape(s, k, 3, -1)
    rlc = msm.scalars_to_digits(
        [rng.randrange(1, 1 << 64) for _ in range(s * k)], msm.W128
    ).reshape(s, k, msm.W128)
    halves = [msm.glv_split(rng.randrange(bls.R)) for _ in range(s * k)]
    lag1 = msm.scalars_to_digits([h[0] for h in halves], msm.W128).reshape(
        s, k, msm.W128
    )
    lag2 = msm.scalars_to_digits([h[1] for h in halves], msm.W128).reshape(
        s, k, msm.W128
    )

    single_pts, single_flags = jax.jit(msm.tpke_era_glv_kernel)(
        u, y, rlc, lag1, lag2
    )

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    step = sharded_glv_era_step(mesh)
    with mesh:
        args = []
        for arr, spec in (
            (u, P("slot", "share", None, None)),
            (y, P("slot", "share", None, None)),
            (rlc, P("slot", "share", None)),
            (lag1, P("slot", "share", None)),
            (lag2, P("slot", "share", None)),
        ):
            args.append(
                jax.device_put(jnp.asarray(arr), NamedSharding(mesh, spec))
            )
        mesh_pts, mesh_flags = step(*args)
    # decode both to canonical oracle points — limb layouts may differ in
    # Montgomery looseness, the POINTS must be identical
    from lachain_tpu.ops import msm as M

    for i in range(s):
        a = M.g1_from_device_loose(np.asarray(single_pts)[i], np.asarray(single_flags)[i])
        b = M.g1_from_device_loose(np.asarray(mesh_pts)[i], np.asarray(mesh_flags)[i])
        for pa, pb in zip(a, b):
            assert bls.g1_eq(pa, pb)


@pytest.mark.slow
@pytest.mark.parametrize("s,k", [(3, 5), (1, 9), (6, 22)])
def test_mesh_pipeline_nonpow2_padding(s, k):
    """MeshEraPipeline pads non-pow2 share counts and non-mesh-multiple slot
    counts; per-slot aggregates must equal the host oracle MSMs."""
    rng = random.Random(100 + s * k)
    pipe = MeshEraPipeline()
    y_points = _rand_points(rng, k)
    slots = []
    for _ in range(s):
        us = _rand_points(rng, k)
        lag = [rng.randrange(1, bls.R) if i < (k + 1) // 2 else 0 for i in range(k)]
        slots.append((us, lag))

    class R:
        def randbelow(self, n):
            return rng.randrange(n)

    out, rlc = pipe.run_era(slots, y_points, R())
    assert len(out) == s
    for (us, lag), (u_agg, y_agg, comb), rlc_row in zip(slots, out, rlc):
        assert bls.g1_eq(u_agg, _oracle_msm(us, rlc_row))
        assert bls.g1_eq(y_agg, _oracle_msm(y_points, rlc_row))
        assert bls.g1_eq(comb, _oracle_msm(us, lag))


@pytest.mark.slow
def test_mesh_pipeline_masked_absent_lanes():
    """Uneven slots: masked (absent-share) lanes contribute to neither
    aggregate — parity with the oracle over the live lanes only."""
    rng = random.Random(77)
    pipe = MeshEraPipeline()
    k = 7
    y_points = _rand_points(rng, k)
    us = _rand_points(rng, k)
    masks = [[True, False, True, True, False, True, True]]
    lag = [rng.randrange(1, bls.R) if m else 0 for m in masks[0]]
    slots = [(
        [u if m else bls.G1_INF for u, m in zip(us, masks[0])],
        lag,
    )]

    class R:
        def randbelow(self, n):
            return rng.randrange(n)

    out, rlc = pipe.run_era(slots, y_points, R(), masks=masks)
    (u_agg, y_agg, comb) = out[0]
    live = [i for i, m in enumerate(masks[0]) if m]
    assert all(rlc[0][i] == 0 for i in range(k) if i not in live)
    assert bls.g1_eq(u_agg, _oracle_msm([us[i] for i in live], [rlc[0][i] for i in live]))
    assert bls.g1_eq(y_agg, _oracle_msm([y_points[i] for i in live], [rlc[0][i] for i in live]))
    assert bls.g1_eq(comb, _oracle_msm([us[i] for i in live], [lag[i] for i in live]))


@pytest.mark.slow
def test_tpu_backend_selects_mesh_and_verifies():
    """End-to-end: with >1 device visible the TPU backend routes
    tpke_era_verify_combine through the mesh pipeline, and the results match
    a full TPKE fixture (verify+combine correct, bad share rejected)."""
    from lachain_tpu.crypto.tpu_backend import EraSlotJob, TpuBackend
    from lachain_tpu.parallel.mesh import MeshEraPipeline as MEP

    rng = random.Random(5)

    class R:
        def randbelow(self, n):
            return rng.randrange(n)

    n, f = 7, 2
    kg = tpke.TpkeTrustedKeyGen(n, f, rng=R())
    backend = TpuBackend(min_device_lanes=1)
    assert isinstance(backend._get_pipeline(), MEP)
    assert len(backend._get_pipeline().mesh.devices.flatten()) > 1

    jobs = []
    for s in range(3):
        ct = kg.pub.encrypt(b"mesh-%d" % s, share_id=s)
        decs = [kg.private_key(i).decrypt_share(ct, check=False) for i in range(f + 1)]
        cs = bls.fr_lagrange_coeffs([i + 1 for i in range(f + 1)], at=0)
        lag = [0] * n
        u = [None] * n
        for i, c in zip(range(f + 1), cs):
            lag[i] = c
            u[i] = decs[i].ui
        if s == 2:  # corrupt one chosen share: slot must report invalid
            u[0] = bls.g1_mul(u[0], 1337)
        jobs.append(
            EraSlotJob(
                u_by_validator=u,
                lagrange_row=lag,
                h=tpke.ciphertext_h(ct),
                w=ct.w,
            )
        )
    res = backend.tpke_era_verify_combine(jobs, kg.verification_keys)
    assert res[0][0] and res[1][0] and not res[2][0]
    assert backend.era_calls == 1


def test_mesh_padding_and_staging_unit():
    """Host-only invariants (no kernel compiles, runs in tier-1): padded
    shape math, staging-buffer re-clean after a shrinking live region, and
    the Lagrange digit-plane cache."""
    pipe = MeshEraPipeline(n_devices=8)
    assert pipe.mesh.shape["slot"] == 4 and pipe.mesh.shape["share"] == 2
    assert pipe.padded_shape(3, 5) == (4, 8)
    assert pipe.padded_shape(1, 9) == (4, 16)
    assert pipe.padded_shape(4, 4) == (4, 4)
    assert pipe.padded_shape(5, 4) == (8, 4)

    st = pipe._get_staging(4, 8)
    st.clean(4, 8)
    st.u[:] = 1
    st.rlc[:] = 7
    st._filled = (4, 8)
    st.clean(2, 2)  # stale tail from the (4,8) fill must be re-cleaned
    inf = np.broadcast_to(pipe._inf_row, (2, 8) + pipe._inf_row.shape)
    assert np.array_equal(st.u[2:, :8], inf)
    assert not st.rlc[2:, :8].any() and not st.rlc[:2, 2:8].any()
    assert st.rlc[:2, :2].all()  # live region untouched

    row = (123, 456, 789)
    planes = pipe._lag_cache.get(row)
    assert pipe._lag_cache.get(list(row)) is planes


# -- satellite: randomized mesh-vs-single-device differential -----------------
# One Glv (single-device oracle) run per N, reused across the three mesh
# shapes; both pipelines derive RLC coefficients through the shared era_rlc,
# so an identically seeded rng must yield identical coefficient rows and
# (by g1_eq, i.e. affine identity) identical per-slot aggregates.

_DIFF_CASES: dict = {}


def _diff_fixture(n):
    cached = _DIFF_CASES.get(n)
    if cached is not None:
        return cached
    from lachain_tpu.ops.verify import GlvEraPipeline

    rng = random.Random(9000 + n)
    k, s = n, 3  # s=3 divides none of the slot axes (1x1 aside): real padding
    y_points = _rand_points(rng, k)
    slots, masks = [], []
    for si in range(s):
        mask = [True] * k
        if si == 1:  # absent shares on the middle slot
            mask[0] = False
            mask[k - 1] = False
        lag = [rng.randrange(1, bls.R) if m else 0 for m in mask]
        us = [
            p if m else bls.G1_INF
            for p, m in zip(_rand_points(rng, k), mask)
        ]
        slots.append((us, lag))
        masks.append(mask)

    glv_rng = random.Random(31337 + n)

    class R:
        def randbelow(self, m):
            return glv_rng.randrange(m)

    out, rlc = GlvEraPipeline().run_era(slots, y_points, R(), masks=masks)
    _DIFF_CASES[n] = (slots, y_points, masks, out, rlc)
    return _DIFF_CASES[n]


@pytest.mark.slow
@pytest.mark.parametrize("n_devices", [1, 2, 8])  # meshes 1x1, 2x1, 4x2
@pytest.mark.parametrize("n", [4, 7, 16])
def test_mesh_vs_glv_differential(n, n_devices):
    """MeshEraPipeline.run_era must be point-identical (g1_eq — affine
    identity; Jacobian Z may differ) to the single-device GlvEraPipeline
    for the same inputs and rng seed, including masked lanes and slot
    counts that do not divide the mesh's slot axis."""
    from lachain_tpu.utils import metrics

    slots, y_points, masks, exp_out, exp_rlc = _diff_fixture(n)
    mesh_rng = random.Random(31337 + n)

    class R:
        def randbelow(self, m):
            return mesh_rng.randrange(m)

    pipe = MeshEraPipeline(n_devices=n_devices)
    assert pipe.n_devices == n_devices
    out, rlc = pipe.run_era(slots, y_points, R(), masks=masks)

    assert [list(r) for r in rlc] == [list(r) for r in exp_rlc]
    assert len(out) == len(exp_out)
    for (ua, ya, ca), (ub, yb, cb) in zip(out, exp_out):
        assert bls.g1_eq(ua, ub)
        assert bls.g1_eq(ya, yb)
        assert bls.g1_eq(ca, cb)

    # satellite gauges: published on every dispatch, once-per-shape logged
    s_pad, k_pad = pipe.padded_shape(len(slots), len(y_points))
    waste = 1.0 - (len(slots) * len(y_points)) / (s_pad * k_pad)
    assert metrics.gauge_value("mesh_devices") == n_devices
    # the gauge is published rounded to four places
    assert abs(metrics.gauge_value("mesh_pad_waste_fraction") - waste) < 1e-4
