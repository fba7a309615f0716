"""Background kernel warmup: precompile the era-kernel shapes a node will hit.

The first era at a new (S_pad, K_pad) shape stalls while its program is
traced and compiled (tens of seconds cold; jax's persistent compile cache,
placed by crypto/provider.open_device, removes the compile on later starts
but not the trace) — a validator joining a running chain would burn its
first eras on it.

The reachable shapes are known a priori: the slot axis pads to a power of two
bounded by N, the share axis is fixed at pow2(N) — log2(N)+1 shapes total
(tpu_backend._run_era_batch). This module compiles them on a background
thread at node start, LARGEST FIRST (a healthy chain's first flush carries
close to N slots), so by the time the node's first era tick reaches the
device the hot shape is already compiled. JAX serializes compilations
internally, so a real call racing the warmup simply waits for the same
compile instead of duplicating it. A shape that fails to compile is an
error: the thread dies with its traceback and join() re-raises.

Reference contrast: the reference has no analogous cost (MCL is AOT-compiled
C++) — this is TPU-specific operational machinery.
"""
from __future__ import annotations

import logging
import threading
from typing import List, Optional, Sequence

logger = logging.getLogger("lachain.warmup")


def _pow2_at_least(n: int) -> int:
    size = 1
    while size < n:
        size *= 2
    return size


def era_warmup_shapes(n_validators: int) -> List[int]:
    """Slot-axis sizes to precompile, largest first."""
    top = _pow2_at_least(max(n_validators, 1))
    shapes = []
    s = top
    while s >= 1:
        shapes.append(s)
        s //= 2
    return shapes


def warmup_era_kernels(
    n_validators: int,
    backend=None,
    shapes: Optional[Sequence[int]] = None,
    include_ts: bool = True,
) -> Optional[threading.Thread]:
    """Start a daemon thread precompiling the TPKE (and optionally the
    G2/coin) era-kernel shapes for an N-validator chain. Returns the thread,
    or None when the backend has no device pipeline to warm."""
    from .provider import get_backend

    backend = backend or get_backend()
    if not hasattr(backend, "tpke_era_verify_combine") or not hasattr(
        backend, "_get_pipeline"
    ):
        return None  # host backends have no compile cost to hide

    def run() -> None:
        from . import bls12381 as bls
        from .tpu_backend import CoinJob, EraSlotJob

        k = n_validators
        todo = list(shapes) if shapes is not None else era_warmup_shapes(k)
        # mesh pipelines pad the (pow2) slot tiers again to a multiple of
        # the 'slot' mesh axis, collapsing the small tiers onto one padded
        # kernel shape — dedupe so warmup compiles each (mesh shape, s_pad,
        # k_pad) entry exactly once
        pipe = backend._get_pipeline()
        if hasattr(pipe, "padded_shape"):
            seen: set = set()
            deduped = []
            for s in todo:
                ps = pipe.padded_shape(s, k)
                if ps in seen:
                    continue
                seen.add(ps)
                deduped.append(s)
            todo = deduped
        for s in todo:
            jobs = [
                EraSlotJob(
                    u_by_validator=[None] * k,
                    lagrange_row=[0] * k,
                    h=bls.G2_GEN,
                    w=bls.G2_GEN,
                )
                for _ in range(s)
            ]
            backend.tpke_era_verify_combine(jobs, _dummy_vks(k))
            logger.info("warmed TPKE era shape S=%d K=%d", s, k)
        if include_ts and hasattr(backend, "ts_era_verify_combine"):
            jobs = [
                CoinJob(
                    sigma_by_signer=[None] * k,
                    lagrange_row=[0] * k,
                    h=bls.G2_GEN,
                )
            ]
            backend.ts_era_verify_combine(jobs, _dummy_ts_keys(k))
            logger.info("warmed TS coin-era shape K=%d", k)

    t = _WarmupThread(target=run, name="ltpu-kernel-warmup", daemon=True)
    t.start()
    return t


class _WarmupThread(threading.Thread):
    """A thread whose failure is not lost: the exception kills the thread
    (threading's hook prints the traceback) and join() raises it again."""

    error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            super().run()
        except BaseException as exc:
            self.error = exc
            raise

    def join(self, timeout: Optional[float] = None) -> None:
        super().join(timeout)
        if self.error is not None:
            raise self.error


_DUMMY_VKS_CACHE: dict = {}
_DUMMY_TS_CACHE: dict = {}


def _dummy_vks(k: int):
    """Stable per-K dummy TPKE verification keys: the pipelines cache
    device marshals by identity, so warmup must reuse ONE list per K (and
    that list must not alias the real validator set's)."""
    from . import bls12381 as bls
    from .tpke import TpkeVerificationKey

    vks = _DUMMY_VKS_CACHE.get(k)
    if vks is None:
        vks = [TpkeVerificationKey(bls.G1_GEN) for _ in range(k)]
        _DUMMY_VKS_CACHE[k] = vks
    return vks


def _dummy_ts_keys(k: int):
    """Stable per-K dummy threshold-signature public keys (attribute .y —
    the coin pipeline reads TsPublicKey, not TpkeVerificationKey)."""
    from . import bls12381 as bls
    from .threshold_sig import TsPublicKey

    keys = _DUMMY_TS_CACHE.get(k)
    if keys is None:
        keys = [TsPublicKey(bls.G1_GEN) for _ in range(k)]
        _DUMMY_TS_CACHE[k] = keys
    return keys
