"""The flagship TPU kernel: batched TPKE share verification + combination.

This is the era hot path of BASELINE.md re-designed batch-first. Per era a
validator receives up to N x N partially-decrypted shares; the reference
verifies each with 2 pairings and combines each slot's F+1 shares with a
Lagrange loop, serially (reference: HoneyBadger.cs:205-217 + TPKE/
PublicKey.cs:55-92). Here the whole batch collapses into:

  verify : e(sum_j c_j U_j, H) == e(sum_j c_j Y_j, W)  (random c_j)
  combine: U^x = sum_i lambda_i U_i                    (per slot)

i.e. three MSMs on device + 2 pairings on host. The MSMs are this module;
pairings ride the native C++ backend (lachain_tpu.crypto.native_backend) —
the host<->TPU split named in SURVEY.md §5 (the "sidecar" boundary).

`tpke_era_slots_step(u, y, rlc_bits, lagrange_bits)` is the jittable
"forward step" the mesh slice shards (parallel/mesh.py); the served path
runs the era pipelines below.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import curve, msm
from ..crypto import bls12381 as bls


def tpke_era_slots_step(u_pts, y_pts, rlc_bits, lagrange_bits):
    """Full-era kernel: S ACS slots x K shares each, all at once.

    Args:
      u_pts:         (S, K, 3, L) decryption shares per slot
      y_pts:         (S, K, 3, L) verification keys per slot
      rlc_bits:      (S, K, nbits) per-slot RLC coefficients
      lagrange_bits: (S, K, nbits) per-slot Lagrange coefficients (zero rows
                     for shares outside the combination subset)

    Returns (u_agg, y_agg, combined), each (S, 3, L): per-slot aggregates.
    The host finishes with one 2-pairing check per slot (shared final exp via
    the native backend's multi-pairing) — versus the reference's 2 pairings
    per SHARE (2*S*K total).

    This is the portable-XLA form of the era step (parallel/mesh.py
    shards it); the one-chip path is PallasEraPipeline below.
    """
    mul_rlc = curve.g1_scalar_mul_bits(u_pts, rlc_bits)      # (S, K, 3, L)
    mul_y = curve.g1_scalar_mul_bits(y_pts, rlc_bits)
    mul_lag = curve.g1_scalar_mul_bits(u_pts, lagrange_bits)

    def reduce_axis1(pts):
        # tree-reduce the share axis; g1_add broadcasts over the slot axis
        return curve.g1_reduce_sum(jnp.moveaxis(pts, 1, 0))  # (K, S, 3, L)

    return reduce_axis1(mul_rlc), reduce_axis1(mul_y), reduce_axis1(mul_lag)


tpke_era_slots_step_jit = jax.jit(tpke_era_slots_step)


def era_rlc(slots, k: int, rng, masks=None):
    """Shared S x K validation + RLC-coefficient generation for every era
    pipeline (device and host): per-lane 64-bit coefficients, zeroed on
    masked (absent-share) lanes. One definition so coefficient width and
    mask semantics cannot diverge between pipelines."""
    s = len(slots)
    for a_list, b_list in slots:
        if len(a_list) != k or len(b_list) != k:
            raise ValueError(
                f"every slot must carry exactly {k} shares/coefficients"
            )
    if masks is not None and (
        len(masks) != s or any(len(m) != k for m in masks)
    ):
        raise ValueError("masks must be S x K")
    rlc = [
        [rng.randbelow((1 << 64) - 1) + 1 for _ in range(k)]
        for _ in range(s)
    ]
    if masks is not None:
        rlc = [
            [c if m else 0 for c, m in zip(row, mrow)]
            for row, mrow in zip(rlc, masks)
        ]
    return rlc


class _TiledYCache:
    """Device-side marshal cache for era-invariant verification keys: one
    (rows, S*K_pad) tiled lane block per (key list, S, K_pad), keyed by
    id() with a strong reference so a collected list can never alias a new
    validator set (shared by the G1 and G2 Pallas pipelines)."""

    def __init__(self, limit: int = 4):
        self._cache = {}
        self._limit = limit

    def get(self, y_points, s: int, k_pad: int):
        import jax.numpy as jnp

        from . import pg1

        key = (id(y_points), s, k_pad)
        hit = self._cache.get(key)
        if hit is not None and hit[0] is y_points:
            return hit[1]
        padded = list(y_points) + [bls.G1_INF] * (k_pad - len(y_points))
        y_dev = jnp.asarray(np.tile(pg1.g1_pack(padded), (1, s)))
        if len(self._cache) >= self._limit:
            self._cache.pop(next(iter(self._cache)))
        self._cache[key] = (y_points, y_dev)
        return y_dev


def _pow2_at_least(k: int) -> int:
    return 1 << max(0, k - 1).bit_length() if k > 1 else 1


class GlvEraPipeline:
    """Round-2 era pipeline on the GLV/windowed kernel (ops/msm.py).

    Host side of the flagship path: vectorized marshal (batch inversion +
    numpy limb packing, no per-bit Python loops), one fused kernel launch
    for the whole era (verify RLC aggregates + GLV Lagrange combine), then
    ONE grand multi-pairing over 2S pairs and plaintext recovery.

    The reference executes the same work as 2*S*K serial pairings plus S
    serial Lagrange loops (TPKE/PublicKey.cs:55-92 via HoneyBadger.cs:
    205-247)."""

    def __init__(self, backend=None):
        import jax

        from ..crypto.provider import get_backend

        self._backend = backend or get_backend()
        self._kernel = jax.jit(msm.tpke_era_glv_kernel3)
        self._y_kernel = jax.jit(msm.y_agg_fixed_base)
        self._y_cache = {}

    def y_device(self, y_points) -> "object":
        """Build the fixed-base tables for the (per-validator-set,
        era-invariant) verification keys once and cache them.

        Keyed by id() BUT holding a strong reference to the key list and
        re-checking identity with `is` — so a garbage-collected list can
        never alias a new validator set's id. Up to 4 sets stay cached."""
        import jax
        import jax.numpy as jnp

        key = id(y_points)
        hit = self._y_cache.get(key)
        if hit is not None and hit[0] is y_points:
            return hit[1]
        y_dev = jnp.asarray(msm.g1_to_device_loose(list(y_points)))
        tables = jax.jit(msm.y_fixed_base_tables)(y_dev)
        if len(self._y_cache) >= 4:
            self._y_cache.pop(next(iter(self._y_cache)))
        self._y_cache[key] = (y_points, tables)
        return tables

    def run_era(self, slots, y_points, rng, masks=None) -> Tuple[list, list]:
        """slots: list of (u_list, lagrange_list) per ACS slot, where u_list
        holds the K decryption-share points and lagrange_list the combine
        coefficients (0 for shares outside the subset). y_points: the K
        verification keys. masks: optional S x K booleans zeroing the RLC
        coefficient of absent-share lanes (era_rlc semantics, shared with
        the host/Pallas/mesh pipelines). Returns (per-slot (u_agg, y_agg,
        combined) oracle points, rlc coefficients used) — the caller
        finishes with the grand pairing check against its H/W points.
        """
        import jax.numpy as jnp

        s = len(slots)
        k = len(y_points)
        u_np = np.stack(
            [msm.g1_to_device_loose(u_list) for u_list, _ in slots]
        )
        y_tables = self.y_device(y_points)
        rlc = era_rlc(slots, k, rng, masks)
        rlc64, rlc_d, lag1, lag2 = msm.era_digits(
            rlc, [lag_list for _, lag_list in slots]
        )
        pts, flags = self._kernel(
            jnp.asarray(u_np),
            jnp.asarray(rlc_d),
            jnp.asarray(lag1),
            jnp.asarray(lag2),
        )
        y_pts, y_flags = self._y_kernel(y_tables, jnp.asarray(rlc64))
        pts = np.asarray(pts)
        flags = np.asarray(flags)
        y_pts = np.asarray(y_pts)
        y_flags = np.asarray(y_flags)
        y_aggs = msm.g1_from_device_loose(y_pts, y_flags)
        out = []
        for i in range(s):
            three = msm.g1_from_device_loose(pts[i], flags[i])
            comb = msm.combine_or_host_msm(
                bls.g1_add(three[1], three[2]),
                slots[i][0],
                slots[i][1],
                self._backend,
            )
            out.append((three[0], y_aggs[i], comb))
        return out, rlc


class PallasEraPipeline:
    """Round-3 era pipeline on the VMEM-resident Pallas kernel (ops/pg1.py).

    Same contract as GlvEraPipeline.run_era, ~12x faster on the chip: the
    windowed MSM runs as one pallas_call per pass with the accumulator and
    the 16-entry tables resident in VMEM, the marshal uploads raw Jacobian
    limbs (no batch inversion, no Montgomery scale), and all per-era device
    outputs come back in a single buffer (one transfer, one fixed cost).

    Reference semantics unchanged: TPKE/PublicKey.cs:55-92 via
    HoneyBadger.cs:205-247."""

    def __init__(self, backend=None):
        from ..crypto.provider import get_backend

        self._backend = backend or get_backend()
        self._y_cache = _TiledYCache()

    def y_device(self, y_points, s: int):
        """Pack + upload the verification keys once per validator set and
        cache the (132, S*K_pad) duplicated lane block on device
        (_TiledYCache). K pads to the next power of two to match
        run_era's lane layout."""
        return self._y_cache.get(
            y_points, s, _pow2_at_least(len(y_points))
        )

    def run_era(self, slots, y_points, rng, masks=None):
        """slots: list of (u_list, lagrange_list) per ACS slot; y_points:
        the K verification keys. Returns (per-slot (u_agg, y_agg, combined)
        oracle points, rlc coefficients used).

        masks (optional): per-slot list of K bools; False lanes get a ZERO
        RLC coefficient so absent shares (the live-node case, where a slot
        holds only the F+1..K shares that have arrived) contribute to
        neither aggregate — the u_list entry for such a lane is ignored
        (pass G1_INF)."""
        import jax.numpy as jnp

        from . import pg1
        from .msm import glv_split

        s = len(slots)
        k = len(y_points)
        rlc = era_rlc(slots, k, rng, masks)
        # the in-kernel tree reduce sums power-of-two groups of adjacent
        # lanes: pad each slot to the next power of two with flagged-out
        # filler lanes (zero digits -> infinity flags)
        k_pad = _pow2_at_least(k)
        pad = k_pad - k
        u_flat = [u for u_list, _ in slots for u in u_list + [bls.G1_INF] * pad]
        u_np = pg1.g1_pack(u_flat)
        y_dev = self.y_device(y_points, s)
        rlc_flat = [c for row in rlc for c in row + [0] * pad]
        lag_flat = [
            c for _, lag_list in slots for c in lag_list + [0] * pad
        ]
        halves = [glv_split(v) for v in lag_flat]
        rlc16 = pg1.digits_col(rlc_flat, pg1.W64)
        lag1 = pg1.digits_col([h[0] for h in halves], pg1.W128)
        lag2 = pg1.digits_col([h[1] for h in halves], pg1.W128)
        buf = jnp.asarray(pg1.era_pack_inputs(u_np, rlc16, lag1, lag2))
        fused = np.asarray(  # ONE device->host transfer
            pg1.era_kernel_packed_jit(buf, y_dev, k=k_pad, n=s * k_pad)
        )
        pts, flags = fused[:132], fused[132] != 0
        cols = pg1.g1_unpack(pts, flags)  # 4S points: u_agg|y_agg|c1|c2
        out = []
        for i in range(s):
            u_agg = cols[i]
            y_agg = cols[s + i]
            comb = bls.g1_add(cols[2 * s + i], cols[3 * s + i])
            if comb[2] == 0 and any(c for c in slots[i][1]):
                # incomplete-add collision in the combine tree: no random-
                # coefficient soundness on this lane group, so fall back to
                # the host oracle MSM for the slot (same escape hatch as
                # GlvEraPipeline.run_era)
                u_list, lag_list = slots[i]
                comb = self._backend.g1_msm(
                    [u for u, c in zip(u_list, lag_list) if c],
                    [c for c in lag_list if c],
                )
            out.append((u_agg, y_agg, comb))
        return out, rlc


class TsPallasPipeline:
    """Coin-era pipeline on the Pallas G2 kernel (ops/pg2.py).

    run_era(coins, y_points, rng, masks) where coins = [(sig_list, lag_row)]
    per coin (K G2 signature shares + K Lagrange-at-0 coefficients) and
    y_points = the K per-validator TS public keys (G1). Returns
    (per-coin (sig_rlc_agg G2, y_rlc_agg G1, combined_sig G2), rlc).

    The host finishes with e(g1, sig_agg) == e(y_agg, H(msg)) per coin —
    ONE grand multi-pairing for all coins, versus the reference's 2
    pairings per share (ThresholdSigner.cs:92-95) and serial G2 Lagrange
    combine (PublicKeySet.cs:35-44)."""

    def __init__(self, backend=None):
        from ..crypto.provider import get_backend

        self._backend = backend or get_backend()
        self._y_cache = _TiledYCache()

    def run_era(self, coins, y_points, rng, masks=None):
        import jax.numpy as jnp

        from . import pg1, pg2

        s = len(coins)
        k = len(y_points)
        rlc = era_rlc(coins, k, rng, masks)
        k_pad = _pow2_at_least(k)
        pad = k_pad - k
        sig_flat = [
            p for sig_list, _ in coins for p in sig_list + [bls.G2_INF] * pad
        ]
        rlc_flat = [c for row in rlc for c in row + [0] * pad]
        lag_flat = [c for _, lag in coins for c in lag + [0] * pad]
        fused = np.asarray(  # ONE device->host transfer
            pg2.ts_era_kernel_jit(
                jnp.asarray(pg2.g2_pack(sig_flat)),
                self._y_cache.get(y_points, s, k_pad),
                jnp.asarray(pg1.digits_col(rlc_flat, pg1.W64)),
                jnp.asarray(pg1.digits_col(lag_flat, pg2.W256)),
                k=k_pad,
            )
        )
        pr = pg2.POINT2_ROWS
        pts, flags = fused[:pr], fused[pr] != 0
        sig_cols = pg2.g2_unpack(pts[:, : 2 * s], flags[: 2 * s])
        y_cols = pg1.g1_unpack(
            pts[: pg1.POINT_ROWS, 2 * s :], flags[2 * s :]
        )
        out = []
        for i in range(s):
            comb = sig_cols[s + i]
            if bls.g2_is_inf(comb) and any(c for c in coins[i][1]):
                # incomplete-add collision in the combine lanes: no RLC
                # soundness there, host-oracle fallback for this coin (same
                # escape hatch as PallasEraPipeline.run_era)
                sig_list, lag_list = coins[i]
                comb = self._backend.g2_msm(
                    [p for p, c in zip(sig_list, lag_list) if c],
                    [c for c in lag_list if c],
                )
            out.append((sig_cols[i], y_cols[i], comb))
        return out, rlc


class _HostEraPipelineBase:
    """Host-backend emulation of the device era-pipeline contract.

    Same `run_era(slots, y_points, rng, masks)` signature and semantics as
    the Pallas pipelines, computed with the host backend's MSMs; the share
    group differs per subclass (`_share_msm`). Two jobs:
      * CPU CI (JAX_PLATFORMS=cpu): XLA-CPU compilation of the emulated
        kernels costs minutes per static shape, so everything above the
        kernel boundary (aggregation, masking, soundness decisions) runs —
        and stays covered — on this path.
      * the plain reference chip_smoke.py holds the device pipelines to.
    Selection happens in crypto/tpu_backend.py: Pallas on a chip, this
    emulation when the tests name the CPU."""

    _share_msm = "g1_msm"

    def __init__(self, backend=None):
        from ..crypto.provider import get_backend

        self._backend = backend or get_backend()

    def run_era(self, slots, y_points, rng, masks=None):
        k = len(y_points)
        rlc = era_rlc(slots, k, rng, masks)
        share_msm = getattr(self._backend, self._share_msm)
        out = []
        for i, (pts_list, lag_list) in enumerate(slots):
            live = [j for j, c in enumerate(rlc[i]) if c]
            share_agg = share_msm(
                [pts_list[j] for j in live], [rlc[i][j] for j in live]
            )
            y_agg = self._backend.g1_msm(
                [y_points[j] for j in live], [rlc[i][j] for j in live]
            )
            comb_live = [j for j, c in enumerate(lag_list) if c]
            comb = share_msm(
                [pts_list[j] for j in comb_live],
                [lag_list[j] for j in comb_live],
            )
            out.append((share_agg, y_agg, comb))
        return out, rlc


class HostEraPipeline(_HostEraPipelineBase):
    """TPKE slots: shares are G1 points (see _HostEraPipelineBase)."""

    _share_msm = "g1_msm"


class TsHostEraPipeline(_HostEraPipelineBase):
    """Coin slots: shares are G2 signatures (see _HostEraPipelineBase)."""

    _share_msm = "g2_msm"
