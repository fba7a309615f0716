"""Device-mesh parallelism package.

:mod:`.mesh` shards the era kernels with ``jax.shard_map`` over a device
mesh. Probe with :func:`mesh_unsupported_reason` before driving it — tests
skip on the probe, and single-device hosts run the host/Pallas pipelines
(crypto/tpu_backend.py).
"""
from __future__ import annotations

from typing import Optional


def mesh_unsupported_reason() -> Optional[str]:
    """None when the mesh pipeline can actually run here; otherwise a
    human-readable skip reason (a single-device host)."""
    import jax

    if len(jax.devices()) < 2:
        return "needs a multi-device platform"
    return None


def mesh_supported() -> bool:
    return mesh_unsupported_reason() is None
