"""TPU crypto backend: the device data plane behind the provider seam.

This is the third backend promised by `lachain_tpu.crypto.provider`
(role of the MCL-native provider swap in the reference,
/root/reference/src/Lachain.Crypto/CryptoProvider.cs:3-11 + ICrypto.cs:5-117):
consensus code calls the same interface, and the MSM-heavy batch work —
TPKE decryption-share verification + Lagrange combination, the era hot path
(HoneyBadger.cs:205-247 via TPKE/PublicKey.cs:55-92) — runs on the chip
through the Pallas era kernel (ops/pg1.py), while scalar ops, hashing and
pairings delegate to the host backend (native C++ if built, else the
Python oracle).

Design notes (SURVEY.md §7 hard part #4 — host<->TPU latency):
  * Opportunistic micro-batching: `tpke_era_verify_combine` runs whatever
    slots are ready RIGHT NOW (S >= 1); it never waits to fill a batch.
  * The Pallas kernel has static shapes: the slot count pads to the next
    power of two with fully-masked dummy slots, so at most log2(N)+1
    distinct (S_pad, K_pad) shapes ever compile per validator-set size.
  * Soundness: per-lane 64-bit random-linear-combination coefficients make
    every slot's aggregate equality independently random; all live slots
    fold into ONE grand multi-pairing (2 pairs per slot, shared final
    exponentiation). On failure the slot set is bisected — O(log S) pairing
    checks per bad slot, no extra kernel launches — and bad slots are
    reported invalid so callers prune the bad share(s) on the per-share
    host path (counted: crypto_tpu_era_slots_rejected_total).
"""
from __future__ import annotations

import secrets
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from . import bls12381 as bls
from ..utils import metrics


@dataclass
class CoinJob:
    """One common coin's pending share verification+combination work.

    sigma_by_signer: length-K row of partial-signature points (G2); None
        where validator j's share has not arrived (lane masked out).
    lagrange_row:    length-K row of Lagrange-at-0 coefficients; nonzero
        exactly on the t+1 shares chosen for the combination.
    h:               H_G2(msg) — the hashed coin id being signed.
    """

    sigma_by_signer: List[Optional[tuple]]
    lagrange_row: List[int]
    h: tuple


@dataclass
class EraSlotJob:
    """One ACS slot's pending verification+combination work.

    u_by_validator: length-K row of decryption-share points; None where
        validator j's share has not arrived (that lane is masked out).
    lagrange_row:   length-K row of Lagrange-at-0 coefficients; nonzero
        exactly on the t+1 shares chosen for the combination.
    h:              H_G2(U, V) for the slot's ciphertext.
    w:              the ciphertext's W point (G2).
    """

    u_by_validator: List[Optional[tuple]]
    lagrange_row: List[int]
    h: tuple
    w: tuple


class TpuBackend:
    """Provider backend routing era-shaped batch crypto through the TPU.

    Everything not explicitly overridden delegates to the host backend
    (`native` C++ when available, else the Python oracle) — pairings,
    hash-to-curve, deserialization, and single scalar muls are host ops by
    design (BASELINE.md: the host<->device split is the "sidecar" seam).
    """

    name = "tpu"

    def __init__(
        self,
        host_backend=None,
        pipeline=None,
        ts_pipeline=None,
        min_device_lanes=None,
    ):
        import os

        # below this many kernel lanes (S_pad x K_pad) an era batch runs on
        # the host pipeline even when a chip is present: a launch has a
        # fixed cost (marshal, upload, one download) that tiny batches
        # cannot amortize.
        #
        # The default routes ALL era shapes to the host. Both sides are
        # on the ledger since PR 22 (era_batch_device_ms against
        # era_batch_host_ms, one kept batch a traced run, on a v5e): 64
        # slots x 64 shares (4096 lanes), device 170-176 ms against host
        # 185-195 ms (hb64.*, ledger, PR 28); 7 slots x 8, host 9.6-10.3
        # ms against device 11.8 ms (hb7.quiet, ledger, PR 28). So the
        # crossover lies between 56 and 4096 lanes and the default is on
        # the wrong side of it at N=64; moving it is a routing policy with
        # a claim to prove, ROADMAP D1's. chip_smoke.py and the benchmark's
        # hb64-sim configuration pass min_device_lanes explicitly.
        if min_device_lanes is None:
            min_device_lanes = int(
                os.environ.get("LTPU_TPU_MIN_LANES", "1000000")
            )
        self.min_device_lanes = min_device_lanes
        if host_backend is None:
            from .native_backend import NativeBackend

            host_backend = NativeBackend()
        self._host = host_backend
        self._pipeline = pipeline  # lazy PallasEraPipeline (G1/TPKE)
        self._ts_pipeline = ts_pipeline  # lazy TsPallasPipeline (G2/coins)
        self._host_pipeline = None
        self._ts_host_pipeline = None
        self._y_cache: dict = {}
        # observability: proves the device path executed (asserted by tests
        # and exported through /metrics)
        self.era_calls = 0
        self.era_slots_total = 0
        self.ts_era_calls = 0
        self.ts_era_coins_total = 0
        self.device_msm_calls = 0

    def __getattr__(self, item):
        # only consulted for attributes NOT defined on TpuBackend: pairings,
        # hashing, g1/g2 ops, deserialization all ride the host backend
        return getattr(self._host, item)

    # -- device pipeline -----------------------------------------------------
    def _get_pipeline(self):
        if self._pipeline is None:
            import os

            from .provider import open_device

            platform = open_device()
            import jax

            from ..ops.verify import HostEraPipeline, PallasEraPipeline

            # Pipeline selection, from what the process can observe:
            #   >1 device (a multi-chip host, or CI's virtual 8-CPU mesh) ->
            #     the shard_mapped mesh pipeline (parallel/mesh.py): slots
            #     data-parallel, shares sequence-parallel.
            #   one TPU chip -> the VMEM-resident Pallas kernel.
            #   one CPU device (JAX_PLATFORMS=cpu, tests) -> host-MSM
            #     emulation of the same contract: XLA-CPU compilation of
            #     the emulated kernel costs minutes per static shape.
            # LTPU_FORCE_PALLAS=1 / LTPU_DISABLE_MESH=1 override for debug.
            n_dev = len(jax.devices())
            if os.environ.get("LTPU_FORCE_PALLAS") == "1":
                self._pipeline = PallasEraPipeline(self._host)
            elif n_dev > 1 and os.environ.get("LTPU_DISABLE_MESH") != "1":
                from ..parallel.mesh import MeshEraPipeline

                self._pipeline = MeshEraPipeline(self._host)
            elif platform == "tpu":
                self._pipeline = PallasEraPipeline(self._host)
            else:
                self._pipeline = HostEraPipeline(self._host)
        return self._pipeline

    @property
    def era_dispatch_depth(self) -> int:
        """How many era-batch dispatches may be in flight at once: the mesh
        pipeline's host-staging double buffer admits MAX_INFLIGHT; every
        synchronous pipeline is 1 (dispatch == run)."""
        return int(getattr(self._get_pipeline(), "MAX_INFLIGHT", 1))

    def _on_chip(self) -> bool:
        import os

        from .provider import open_device

        return (
            open_device() == "tpu"
            or os.environ.get("LTPU_FORCE_PALLAS") == "1"
        )

    def _get_ts_pipeline(self):
        if self._ts_pipeline is None:
            from ..ops.verify import TsHostEraPipeline, TsPallasPipeline

            if self._on_chip():
                self._ts_pipeline = TsPallasPipeline(self._host)
            else:
                self._ts_pipeline = TsHostEraPipeline(self._host)
        return self._ts_pipeline

    def _device_ok(self, n: int) -> bool:
        return n >= self.min_device_lanes and self._on_chip()

    def g1_msm(self, points, scalars):
        """Large MSMs ride the Pallas G1 engine; small ones go host. This
        is how TPKE batch_verify_shares/full_decrypt and the TS key
        aggregates hit the chip without their callers changing — the same
        provider-seam trick the reference's MCL swap uses. A device
        failure propagates: a path chosen for the chip does not degrade."""
        if not self._device_ok(len(points)):
            return self._host.g1_msm(points, scalars)
        return self._device_msm(points, scalars, g2=False)

    def g2_msm(self, points, scalars):
        """Large G2 MSMs (ThresholdSigner prune paths, TS combine at big N)
        ride the Pallas G2 engine (ops/pg2.py); small ones go host."""
        if not self._device_ok(len(points)):
            return self._host.g2_msm(points, scalars)
        return self._device_msm(points, scalars, g2=True)

    def _device_msm(self, points, scalars, g2: bool):
        import jax.numpy as jnp
        import numpy as np

        from ..ops import pg1, pg2
        from ..ops.verify import _pow2_at_least

        t0 = metrics.monotonic()
        n = len(points)
        n_pad = _pow2_at_least(n)
        inf = bls.G2_INF if g2 else bls.G1_INF
        pts = list(points) + [inf] * (n_pad - n)
        ss = [s % bls.R for s in scalars] + [0] * (n_pad - n)
        dig = jnp.asarray(pg1.digits_col(ss, 64))  # 256-bit windows
        if g2:
            fused = np.asarray(
                pg2.msm2_reduce_jit(
                    jnp.asarray(pg2.g2_pack(pts)), dig, n_pad
                )
            )
            pr = pg2.POINT2_ROWS
            out = pg2.g2_unpack(fused[:pr], fused[pr] != 0)
        else:
            fused = np.asarray(
                pg1.msm_reduce_jit(
                    jnp.asarray(pg1.g1_pack(pts)), dig, n_pad
                )
            )
            out = pg1.g1_unpack(fused[:132], fused[132] != 0)
        metrics.inc("crypto_tpu_device_msm_calls_total")
        metrics.observe_hist(
            "crypto_tpu_device_msm_seconds",
            metrics.monotonic() - t0,
            labels={"group": "g2" if g2 else "g1"},
        )
        self.device_msm_calls += 1
        return out[0]

    def _get_host_pipeline(self):
        if self._host_pipeline is None:
            from ..ops.verify import HostEraPipeline

            self._host_pipeline = HostEraPipeline(self._host)
        return self._host_pipeline

    def _get_ts_host_pipeline(self):
        if self._ts_host_pipeline is None:
            from ..ops.verify import TsHostEraPipeline

            self._ts_host_pipeline = TsHostEraPipeline(self._host)
        return self._ts_host_pipeline

    def _stable_y_points(self, vks, attr: str = "y_i") -> list:
        """One stable y-point list per verification-key list so the
        pipeline's device-side key marshal caches across eras (keyed by
        identity with a strong reference, same scheme as the pipeline).
        attr: "y_i" for TPKE verification keys, "y" for TS public keys."""
        key = (id(vks), attr)
        hit = self._y_cache.get(key)
        if hit is not None and hit[0] is vks:
            return hit[1]
        y_points = [getattr(vk, attr) for vk in vks]
        if len(self._y_cache) >= 8:
            self._y_cache.pop(next(iter(self._y_cache)))
        self._y_cache[key] = (vks, y_points)
        return y_points

    # -- the era-tick batch op ----------------------------------------------
    @metrics.timed("crypto_tpu_era_verify_combine")
    def tpke_era_verify_combine(
        self,
        jobs: Sequence[EraSlotJob],
        verification_keys,
        rng=secrets,
    ) -> List[Tuple[bool, Optional[tuple]]]:
        """Verify + combine every pending slot in ONE kernel launch.

        Returns per-job (all_shares_valid, combined_point). When a job's
        shares all verify, `combined` is U^x for the slot (feed the XOF pad
        directly — no separate full_decrypt needed). When the grand pairing
        check fails, bisection isolates the offending slot(s); those report
        (False, None) and the caller falls back to per-share host
        verification to prune the bad share(s).

        Reference semantics being batched: TPKE/PublicKey.cs:88-92 (per-
        share verify) + :55-86 (per-slot Lagrange combine), executed there
        serially per message via HoneyBadger.cs:205-247.
        """
        if not jobs:
            return []
        results = self._run_era_batch(
            jobs=jobs,
            rows=[j.u_by_validator for j in jobs],
            lags=[j.lagrange_row for j in jobs],
            y_points=self._stable_y_points(verification_keys),
            inf_point=bls.G1_INF,
            pipeline_getter=self._get_pipeline,
            host_pipeline_getter=self._get_host_pipeline,
            pairs_for=lambda job, agg: [
                (agg[0], job.h),
                (bls.g1_neg(agg[1]), job.w),
            ],
            rng=rng,
        )
        self.era_calls += 1
        self.era_slots_total += len(jobs)
        metrics.inc("crypto_tpu_era_kernel_calls_total")
        return results

    def tpke_era_verify_combine_async(
        self,
        jobs: Sequence[EraSlotJob],
        verification_keys,
        rng=secrets,
    ):
        """Two-phase tpke_era_verify_combine: does the host marshal +
        kernel dispatch now and returns a `finish()` closure producing the
        same per-job results.

        With the mesh pipeline the kernel runs asynchronously between
        dispatch and finish, so a caller holding several era chunks
        (consensus/crypto_batcher.flush) overlaps chunk e+1's host marshal
        with chunk e's sharded kernel — the double-buffer contract bounds
        in-flight dispatches to MeshEraPipeline.MAX_INFLIGHT. On host/
        Pallas pipelines the work happens at dispatch and finish() just
        returns it."""
        if not jobs:
            return lambda: []
        with metrics.measure("crypto_tpu_era_verify_combine"):
            fin = self._dispatch_era_batch(
                jobs=jobs,
                rows=[j.u_by_validator for j in jobs],
                lags=[j.lagrange_row for j in jobs],
                y_points=self._stable_y_points(verification_keys),
                inf_point=bls.G1_INF,
                pipeline_getter=self._get_pipeline,
                host_pipeline_getter=self._get_host_pipeline,
                pairs_for=lambda job, agg: [
                    (agg[0], job.h),
                    (bls.g1_neg(agg[1]), job.w),
                ],
                rng=rng,
            )

        def finish():
            with metrics.measure("crypto_tpu_era_verify_combine"):
                results = fin()
            self.era_calls += 1
            self.era_slots_total += len(jobs)
            metrics.inc("crypto_tpu_era_kernel_calls_total")
            return results

        return finish

    def _run_era_batch(
        self, jobs, rows, lags, y_points, inf_point, pipeline_getter,
        host_pipeline_getter, pairs_for, rng,
    ) -> List[Tuple[bool, Optional[tuple]]]:
        return self._dispatch_era_batch(
            jobs=jobs, rows=rows, lags=lags, y_points=y_points,
            inf_point=inf_point, pipeline_getter=pipeline_getter,
            host_pipeline_getter=host_pipeline_getter, pairs_for=pairs_for,
            rng=rng,
        )()

    def _dispatch_era_batch(
        self, jobs, rows, lags, y_points, inf_point, pipeline_getter,
        host_pipeline_getter, pairs_for, rng,
    ):
        """Shared engine for both era ops: mask absent lanes, pad the slot
        axis to a power of two with fully-masked dummy slots (bounds the
        static kernel shapes to log2(N)+1 per K), run the pipeline, then
        grand-multi-pair + bisect. `pairs_for(job, agg)` yields the two
        pairing pairs encoding that slot's verification equality; each
        slot's equality is independently randomized by its own RLC
        coefficients, so a pairing product over any subset is a sound
        batch check for that subset.

        Returns a finish() closure: pipelines exposing `dispatch_era`
        (parallel/mesh.MeshEraPipeline) run their kernel asynchronously
        until finish() blocks; synchronous pipelines complete at dispatch
        and finish() just post-processes."""
        from ..ops.verify import _pow2_at_least

        s = len(jobs)
        if s == 0:
            return lambda: []
        k = len(y_points)
        for row, lag in zip(rows, lags):
            if len(row) != k or len(lag) != k:
                raise ValueError(f"era job rows must have length {k}")
        slots = []
        masks = []
        for row, lag in zip(rows, lags):
            masks.append([p is not None for p in row])
            slots.append(
                ([p if p is not None else inf_point for p in row], list(lag))
            )
        s_pad = _pow2_at_least(s)
        for _ in range(s_pad - s):
            slots.append(([inf_point] * k, [0] * k))
            masks.append([False] * k)
        lanes = s_pad * _pow2_at_least(k)
        if lanes >= self.min_device_lanes:
            pipeline = pipeline_getter()
            path = "device"
        else:
            pipeline = host_pipeline_getter()
            path = "host"
        # pad-waste: fraction of the padded slot axis burnt on fully-masked
        # dummy slots — the number that explains bench variance and tunes
        # the batcher's max_slots_per_call
        metrics.inc("crypto_tpu_era_route_total", labels={"path": path})
        metrics.inc("crypto_tpu_era_slots_padded_total", s_pad - s)
        metrics.observe_hist(  # lint-allow: metric-name dimensionless slot-count distribution
            "crypto_tpu_era_batch_slots",
            s,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024),
        )
        metrics.observe_hist(  # lint-allow: metric-name dimensionless waste-fraction distribution
            "crypto_tpu_era_pad_waste",
            1.0 - s / s_pad,
            buckets=(0.0, 0.1, 0.2, 0.3, 0.4, 0.5),
        )
        t0 = metrics.monotonic()
        dispatch = getattr(pipeline, "dispatch_era", None)
        if dispatch is not None:
            pipeline_fin = dispatch(slots, y_points, rng, masks=masks)
        else:
            ran = pipeline.run_era(slots, y_points, rng, masks=masks)
            pipeline_fin = lambda: ran  # noqa: E731

        def finish():
            aggs, _rlc = pipeline_fin()
            metrics.observe_hist(
                "crypto_tpu_era_pipeline_seconds",
                metrics.monotonic() - t0,
                labels={"path": path},
            )

            def group_ok(idx: List[int]) -> bool:
                pairs = []
                for i in idx:
                    pairs.extend(pairs_for(jobs[i], aggs[i]))
                return self._host.pairing_check(pairs)

            from .provider import batch_bisect_verify

            ok_flags = batch_bisect_verify(group_ok, s)
            # rejected slots are re-done per share on the host by the
            # caller (pruning a bad share); an honest run must count zero
            metrics.inc(
                "crypto_tpu_era_slots_rejected_total", ok_flags.count(False)
            )
            return [
                (ok, aggs[i][2] if ok else None)
                for i, ok in enumerate(ok_flags)
            ]

        return finish

    @metrics.timed("crypto_tpu_ts_era_verify_combine")
    def ts_era_verify_combine(
        self,
        jobs: Sequence[CoinJob],
        ts_public_keys,
        rng=secrets,
    ) -> List[Tuple[bool, Optional[tuple]]]:
        """Verify + combine every pending common coin in ONE kernel launch.

        `ts_public_keys` is the per-validator TS key list (TsPublicKey,
        G1). Returns per-coin (all_shares_valid, combined_sigma). Same
        grand-multi-pairing + slot-bisection structure as
        `tpke_era_verify_combine`; the verify equality per coin is
        e(g1, sum c sigma_j) == e(sum c Y_j, H(coin id)).

        Reference semantics being batched: ThresholdSigner.cs:45-95 (2
        pairings per share) + PublicKeySet.cs:35-44 (serial G2 Lagrange),
        via CommonCoin.cs:75-96.
        """
        if not jobs:
            return []
        results = self._run_era_batch(
            jobs=jobs,
            rows=[j.sigma_by_signer for j in jobs],
            lags=[j.lagrange_row for j in jobs],
            y_points=self._stable_y_points(ts_public_keys, attr="y"),
            inf_point=bls.G2_INF,
            pipeline_getter=self._get_ts_pipeline,
            host_pipeline_getter=self._get_ts_host_pipeline,
            pairs_for=lambda job, agg: [
                (bls.G1_GEN, agg[0]),
                (bls.g1_neg(agg[1]), job.h),
            ],
            rng=rng,
        )
        self.ts_era_calls += 1
        self.ts_era_coins_total += len(jobs)
        metrics.inc("crypto_tpu_ts_era_kernel_calls_total")
        return results
