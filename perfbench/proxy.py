"""The benchmark's seat at the backend seam: a delegating object installed
with crypto.provider.set_backend around the configuration's backend.

In every run it keeps the last TPKE era batch (jobs, keys, answers) for the
comparison that decides `correct`. In a traced run it also times the four
batch entry points on the host clock and writes a jax.profiler
TraceAnnotation around each, so the device trace shows which call an
operation belongs to. Everything else passes through untouched.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

TIMED = ("tpke_era_verify_combine", "ts_era_verify_combine", "g1_msm", "g2_msm")


class BackendProxy:
    def __init__(self, inner, timed: bool):
        self._inner = inner
        self._timed = timed
        self.name = inner.name  # provider.device_platform() asks for it
        self.last_era_batch: Optional[Tuple[list, object, list]] = None
        # (method, start, end, size) on time.monotonic; traced runs only
        self.calls: List[Tuple[str, float, float, int]] = []
        # method -> {size: calls}, always: names the shape behind a compile
        self.sizes: Dict[str, Dict[int, int]] = {m: {} for m in TIMED}

    def __getattr__(self, item):
        return getattr(self._inner, item)

    def _call(self, method: str, size: int, *args, **kw):
        fn = getattr(self._inner, method)
        seen = self.sizes[method]
        seen[size] = seen.get(size, 0) + 1
        if not self._timed:
            return fn(*args, **kw)
        import jax

        with jax.profiler.TraceAnnotation(f"backend.{method}", size=size):
            t0 = time.monotonic()
            out = fn(*args, **kw)
            self.calls.append((method, t0, time.monotonic(), size))
        return out

    def tpke_era_verify_combine(self, jobs, verification_keys, *args, **kw):
        out = self._call(
            "tpke_era_verify_combine", len(jobs), jobs, verification_keys,
            *args, **kw,
        )
        if jobs:
            self.last_era_batch = (list(jobs), verification_keys, list(out))
        return out

    def ts_era_verify_combine(self, jobs, keys, *args, **kw):
        return self._call(
            "ts_era_verify_combine", len(jobs), jobs, keys, *args, **kw
        )

    def g1_msm(self, points, scalars):
        return self._call("g1_msm", len(points), points, scalars)

    def g2_msm(self, points, scalars):
        return self._call("g2_msm", len(points), points, scalars)
