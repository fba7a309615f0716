"""Device-mesh sharding for the batched crypto kernels.

The reference scales consensus crypto by protocol fan-out across OS threads
(SURVEY.md §2c "parallelism inventory"); the TPU-native equivalent is SPMD
over a jax.sharding.Mesh: the share axis (N validators x N slots per era) is
the data axis, sharded across devices with shard_map. Each device computes a
local MSM over its shard; the partial sums are combined with an all_gather
followed by a replicated log-tree of point additions (point addition is not
an elementwise psum-reduction, so the combine rides an explicit collective).

Multi-host scaling: the same mesh spans hosts; XLA routes the all_gather over
ICI within a pod slice and DCN across slices — this is the framework's
distributed communication backend for the crypto data plane (SURVEY.md §5
"Distributed communication backend"). Control-plane consensus messages stay
on the host network (lachain_tpu/network).
"""
from __future__ import annotations

import logging
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import curve
from ..utils import metrics, tracing

logger = logging.getLogger("lachain.mesh")


def make_mesh(n_devices: Optional[int] = None, axis: str = "shares") -> Mesh:
    """1-D mesh over the share/batch axis."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def sharded_g1_msm(mesh: Mesh, axis: str = "shares"):
    """Build a jitted MSM over the mesh: points (n,3,L), bits (n,nbits).

    n must be divisible by mesh size and the per-device shard a power of two.
    Output is replicated on every device.
    """

    def local_msm(points, bits):
        partial_sum = curve.g1_msm(points, bits)  # (3, L) local
        gathered = jax.lax.all_gather(partial_sum, axis)  # (ndev, 3, L)
        return curve.g1_reduce_sum(gathered)

    fn = shard_map(
        local_msm,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=P(),  # replicated
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_g2_msm(mesh: Mesh, axis: str = "shares"):
    def local_msm(points, bits):
        partial_sum = curve.g2_msm(points, bits)  # (3, 2, L)
        gathered = jax.lax.all_gather(partial_sum, axis)
        return curve.g2_reduce_sum(gathered)

    fn = shard_map(
        local_msm,
        mesh=mesh,
        in_specs=(P(axis, None, None, None), P(axis, None)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


def make_era_mesh(n_devices: int) -> Mesh:
    """2-D mesh for the era kernel: 'slot' = data-parallel over ACS slots,
    'share' = sequence-parallel over the within-slot share axis (the
    framework's dp x sp analog — SURVEY.md §5 maps the reference's
    protocol-thread fan-out onto exactly these two axes)."""
    devs = jax.devices()[:n_devices]
    if n_devices >= 4 and n_devices % 2 == 0:
        shape = (n_devices // 2, 2)
    else:
        shape = (n_devices, 1)
    return Mesh(np.array(devs).reshape(shape), ("slot", "share"))


def sharded_era_step(mesh: Mesh):
    """shard_map the full era kernel over a ('slot', 'share') mesh.

    Slots shard data-parallel (no cross-device traffic); the share axis
    shards within each slot, so per-device partial point-sums are combined
    with an all_gather over 'share' followed by a replicated point-add — the
    explicit-collective pattern for non-arithmetic reductions (point addition
    is not a psum).
    """
    from ..ops import verify as V
    from ..ops import curve as C

    def local_step(u, y, rlc, lag):
        u_agg, y_agg, comb = V.tpke_era_slots_step(u, y, rlc, lag)
        # (S_local, 3, L) partial sums over the local share shard
        def combine(pts):
            gathered = jax.lax.all_gather(pts, "share")  # (nshare, S_l, 3, L)
            return C.g1_reduce_sum(gathered)

        return combine(u_agg), combine(y_agg), combine(comb)

    fn = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            P("slot", "share", None, None),
            P("slot", "share", None, None),
            P("slot", "share", None),
            P("slot", "share", None),
        ),
        out_specs=(
            P("slot", None, None),
            P("slot", None, None),
            P("slot", None, None),
        ),
        # outputs ARE replicated over 'share' (all_gather + identical local
        # reduce on every device) but the static varying-axes checker cannot
        # infer that through the point-add tree
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_glv_era_step(mesh: Mesh):
    """shard_map the round-2 GLV/windowed era kernel (ops/msm.py) over a
    ('slot', 'share') mesh.

    Same layout as sharded_era_step: slots are data-parallel; the share axis
    shards within each slot. Each device runs the full windowed MSM over its
    local share shard (tables, window scan, local flagged tree-reduce), then
    the per-device partial sums are combined with an all_gather over 'share'
    plus a replicated flagged point-add tree — point addition is not a psum,
    so the combine is an explicit collective + local tree.
    """
    from ..ops import msm as M

    def local_step(u, y, rlc, lag1, lag2):
        pts, flags = M.tpke_era_glv_kernel(u, y, rlc, lag1, lag2)
        # (S_local, 4, 3, L) local partials + (S_local, 4) flags
        gp = jax.lax.all_gather(pts, "share")  # (nshare, S_l, 4, 3, L)
        gf = jax.lax.all_gather(flags, "share")
        return M.g1_tree_reduce_flagged(gp, gf, axis=0)

    fn = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            P("slot", "share", None, None),
            P("slot", "share", None, None),
            P("slot", "share", None),
            P("slot", "share", None),
            P("slot", "share", None),
        ),
        out_specs=(
            P("slot", None, None, None),
            P("slot", None),
        ),
        check_vma=False,
    )
    return jax.jit(fn)


def pad_pow2(n: int, multiple: int) -> int:
    """Smallest power of two >= n that is divisible by `multiple`."""
    size = max(multiple, 1)
    while size < n or size % multiple:
        size *= 2
    return size


class _EraStaging:
    """Preallocated host marshal buffers for one padded (s_pad, k_pad) grid.

    Filler lanes carry the device encoding of infinity in `u` and zero
    digits in the coefficient planes; `fill()` writes only the live
    [:s, :k] region and re-cleans whatever a PREVIOUS era with a larger
    live region left behind, so per-era work is proportional to live lanes
    instead of the padded grid."""

    __slots__ = ("u", "rlc", "lag1", "lag2", "_inf_row", "_filled")

    def __init__(self, s_pad: int, k_pad: int, inf_row: np.ndarray, w128: int):
        self._inf_row = inf_row  # (3, L) loose-Montgomery infinity
        self.u = np.broadcast_to(
            inf_row, (s_pad, k_pad) + inf_row.shape
        ).copy()
        self.rlc = np.zeros((s_pad, k_pad, w128), dtype=np.int32)
        self.lag1 = np.zeros((s_pad, k_pad, w128), dtype=np.int32)
        self.lag2 = np.zeros((s_pad, k_pad, w128), dtype=np.int32)
        self._filled = (0, 0)

    def clean(self, s: int, k: int) -> None:
        fs, fk = self._filled
        if fs > s:
            self.u[s:fs, :fk] = self._inf_row
            self.rlc[s:fs, :fk] = 0
            self.lag1[s:fs, :fk] = 0
            self.lag2[s:fs, :fk] = 0
        if fk > k:
            top = min(fs, s)
            self.u[:top, k:fk] = self._inf_row
            self.rlc[:top, k:fk] = 0
            self.lag1[:top, k:fk] = 0
            self.lag2[:top, k:fk] = 0
        self._filled = (s, k)


class _LagDigitCache:
    """Digit planes for Lagrange coefficient rows, keyed by the row values.

    A fixed signer set reuses the same Lagrange row across every slot of
    every era, so the glv_split + digit decomposition (the one remaining
    per-value Python loop in the era marshal) amortizes to a dict lookup."""

    def __init__(self, limit: int = 128):
        self._cache: dict = {}
        self._limit = limit

    def get(self, row) -> tuple:
        from ..ops import msm

        key = tuple(row)
        hit = self._cache.get(key)
        if hit is not None:
            return hit
        halves = [msm.glv_split(v) for v in row]
        planes = (
            msm.scalars_to_digits([h[0] for h in halves], msm.W128),
            msm.scalars_to_digits([h[1] for h in halves], msm.W128),
        )
        if len(self._cache) >= self._limit:
            self._cache.pop(next(iter(self._cache)))
        self._cache[key] = planes
        return planes


class MeshEraPipeline:
    """Multi-device era pipeline: the GLV/windowed era kernel shard_mapped
    over a ('slot', 'share') device mesh.

    Same `run_era(slots, y_points, rng, masks)` contract as the single-chip
    pipelines (ops/verify.py: GlvEraPipeline / PallasEraPipeline), selected
    by the TPU backend whenever more than one device is visible — this is
    how a pod slice (or the CI's 8 virtual CPU devices) runs the BASELINE
    N=128-class era batches: ACS slots data-parallel across the 'slot' axis,
    the within-slot share axis sequence-parallel across 'share' with an
    explicit all_gather + flagged point-add combine.

    `dispatch_era` is the async half of the same contract: it does the host
    marshal + device_put + kernel dispatch and returns a `finish()` closure
    that blocks on the result — callers (crypto_batcher) overlap chunk
    e+1's host marshal with chunk e's sharded kernel. At most TWO dispatches
    may be in flight per pipeline: the host staging is double-buffered, and
    a third dispatch would overwrite the buffer a still-running kernel's
    device_put may alias on single-device meshes.
    """

    MAX_INFLIGHT = 2

    def __init__(self, backend=None, n_devices: Optional[int] = None):
        from ..crypto.provider import get_backend
        from ..crypto import bls12381 as bls
        from ..ops import msm

        self._backend = backend or get_backend()
        ndev = n_devices if n_devices is not None else len(jax.devices())
        self.mesh = make_era_mesh(ndev)
        self.n_devices = int(self.mesh.devices.size)
        self._step = sharded_glv_era_step(self.mesh)
        # era-invariant verification keys: marshal + device_put once per
        # (key set, s_pad, k_pad) — id-keyed with a strong reference, same
        # pattern as ops/verify's _TiledYCache
        self._y_cache: dict = {}
        self._lag_cache = _LagDigitCache()
        # double-buffered staging per padded shape (see class docstring)
        self._staging: dict = {}
        self._inf_row = np.ascontiguousarray(
            msm.g1_to_device_loose([bls.G1_INF])[0]
        )
        self._seen_shapes: set = set()
        self.calls = 0
        # device-busy accounting for utilization reporting: seconds between
        # kernel dispatch and result-ready, summed over calls
        self.device_busy_s = 0.0
        self.allgather_mb = 0.0

    def padded_shape(self, s: int, k: int) -> tuple:
        """(s_pad, k_pad) the mesh will run for a live (s, k) era grid —
        the warmup uses this to dedupe tiers that collapse onto one padded
        kernel shape."""
        n_slot = self.mesh.shape["slot"]
        n_share = self.mesh.shape["share"]
        k_pad = pad_pow2(k, n_share)
        s_pad = ((s + n_slot - 1) // n_slot) * n_slot
        return s_pad, k_pad

    def _get_staging(self, s_pad: int, k_pad: int) -> _EraStaging:
        from ..ops import msm

        bufs = self._staging.get((s_pad, k_pad))
        if bufs is None:
            bufs = [
                [
                    _EraStaging(s_pad, k_pad, self._inf_row, msm.W128)
                    for _ in range(2)
                ],
                0,
            ]
            if len(self._staging) >= 8:
                self._staging.pop(next(iter(self._staging)))
            self._staging[(s_pad, k_pad)] = bufs
        pair, flip = bufs
        bufs[1] = flip + 1
        return pair[flip % 2]

    def _y_device(self, y_points, s_pad: int, k_pad: int):
        """Sharded device array for the verification-key grid: era-invariant
        for a fixed validator set, so both the host marshal AND the
        device_put are cached (the old path re-uploaded every era)."""
        from jax.sharding import NamedSharding

        from ..crypto import bls12381 as bls
        from ..ops import msm

        key = (id(y_points), s_pad, k_pad)
        hit = self._y_cache.get(key)
        if hit is not None and hit[0] is y_points:
            return hit[1]
        k = len(y_points)
        y_np = msm.g1_to_device_loose(
            (list(y_points) + [bls.G1_INF] * (k_pad - k)) * s_pad
        ).reshape(s_pad, k_pad, 3, -1)
        y_dev = jax.device_put(
            jnp.asarray(y_np),
            NamedSharding(self.mesh, P("slot", "share", None, None)),
        )
        if len(self._y_cache) >= 8:
            self._y_cache.pop(next(iter(self._y_cache)))
        self._y_cache[key] = (y_points, y_dev)
        return y_dev

    def _allgather_mb(self, s_pad: int) -> float:
        """Bytes the 'share' all_gather moves across the mesh for one call
        (statically computable from the padded shape): every device receives
        the other share-shards' (S_local, 4, 3, L) partials + flags."""
        from ..ops import fpl

        n_slot = self.mesh.shape["slot"]
        n_share = self.mesh.shape["share"]
        s_local = s_pad // n_slot
        shard_bytes = s_local * 4 * (3 * fpl.NLIMBS * 4 + 4)
        return self.n_devices * (n_share - 1) * shard_bytes / 1e6

    def dispatch_era(self, slots, y_points, rng, masks=None):
        """Async half of run_era: marshal + device_put + kernel dispatch,
        returning a finish() closure that blocks and decodes. See the class
        docstring for the MAX_INFLIGHT=2 double-buffer contract."""
        from jax.sharding import NamedSharding

        from ..crypto import bls12381 as bls
        from ..ops import msm
        from ..ops.verify import era_rlc

        s = len(slots)
        k = len(y_points)
        rlc = era_rlc(slots, k, rng, masks)
        s_pad, k_pad = self.padded_shape(s, k)
        waste = 1.0 - (s * k) / float(s_pad * k_pad)
        metrics.set_gauge("mesh_devices", self.n_devices)
        metrics.set_gauge("mesh_pad_waste_fraction", round(waste, 4))
        if (s_pad, k_pad) not in self._seen_shapes:
            self._seen_shapes.add((s_pad, k_pad))
            logger.info(
                "mesh era shape (s=%d,k=%d) -> padded (%d,%d) on %s: "
                "pad waste %.1f%%",
                s, k, s_pad, k_pad, dict(self.mesh.shape), 100.0 * waste,
            )

        with tracing.span(
            "mesh.marshal", cat="crypto", s=s, k=k, s_pad=s_pad, k_pad=k_pad
        ):
            stage = self._get_staging(s_pad, k_pad)
            stage.clean(s, k)
            # live points in one vectorized batch-inversion conversion;
            # filler lanes keep the prefilled infinity encoding
            u_all = [u for u_list, _ in slots for u in u_list]
            stage.u[:s, :k] = msm.g1_to_device_loose(u_all).reshape(
                s, k, 3, -1
            )
            # RLC digits: one byte-decomposition over all S*K coefficients,
            # embedded in the top W64 of W128 windows (era_digits layout)
            rlc64 = msm.scalars_to_digits(
                [c for row in rlc for c in row], msm.W64
            ).reshape(s, k, msm.W64)
            stage.rlc[:s, :k, : msm.W128 - msm.W64] = 0
            stage.rlc[:s, :k, msm.W128 - msm.W64 :] = rlc64
            # Lagrange digit planes: cached per coefficient row (fixed
            # signer sets repeat the same row across slots and eras)
            for i, (_, lag_list) in enumerate(slots):
                l1, l2 = self._lag_cache.get(lag_list)
                stage.lag1[i, :k] = l1
                stage.lag2[i, :k] = l2
            y_dev = self._y_device(y_points, s_pad, k_pad)

        ag_mb = self._allgather_mb(s_pad)
        with self.mesh:
            spec_pts = P("slot", "share", None, None)
            spec_dig = P("slot", "share", None)
            args = [
                jax.device_put(
                    jnp.asarray(arr), NamedSharding(self.mesh, spec)
                )
                for arr, spec in (
                    (stage.u, spec_pts),
                    (stage.rlc, spec_dig),
                    (stage.lag1, spec_dig),
                    (stage.lag2, spec_dig),
                )
            ]
            sid = tracing.begin(
                "mesh.device",
                cat="crypto",
                devices=self.n_devices,
                s_pad=s_pad,
                k_pad=k_pad,
                allgather_mb=round(ag_mb, 3),
            )
            t_dispatch = metrics.monotonic()
            pts, flags = self._step(
                args[0], y_dev, args[1], args[2], args[3]
            )
        self.calls += 1

        def finish():
            with tracing.wait("device", devices=self.n_devices):
                jax.block_until_ready((pts, flags))
            busy = metrics.monotonic() - t_dispatch
            tracing.end(sid)
            self.device_busy_s += busy
            self.allgather_mb += ag_mb
            p = np.asarray(pts)
            f = np.asarray(flags)
            out = []
            for i in range(s):
                cols = msm.g1_from_device_loose(p[i], f[i])
                comb = msm.combine_or_host_msm(
                    bls.g1_add(cols[2], cols[3]),
                    slots[i][0],
                    slots[i][1],
                    self._backend,
                )
                out.append((cols[0], cols[1], comb))
            return out, rlc

        return finish

    def run_era(self, slots, y_points, rng, masks=None):
        return self.dispatch_era(slots, y_points, rng, masks=masks)()
