"""Kernel warmup (crypto/warmup.py): precompiles every reachable era shape."""
import jax
import pytest

from lachain_tpu.crypto.warmup import era_warmup_shapes, warmup_era_kernels
from lachain_tpu.parallel import mesh_unsupported_reason


def test_shapes_largest_first():
    assert era_warmup_shapes(16) == [16, 8, 4, 2, 1]
    assert era_warmup_shapes(5) == [8, 4, 2, 1]


# With >1 visible device the backend selects the shard_mapped mesh pipeline
# (tpu_backend._get_pipeline), so the warmup run needs the mesh stack; on a
# single device it warms the host/Pallas pipeline and needs no guard.
# mesh+slow: compiles a shard_mapped kernel under the conftest's 8 forced
# devices — runs in the CI mesh job, stays out of the 'not slow' sweep.
@pytest.mark.mesh
@pytest.mark.slow
@pytest.mark.skipif(
    len(jax.devices()) > 1 and mesh_unsupported_reason() is not None,
    reason=f"backend would select the mesh pipeline: {mesh_unsupported_reason()}",
)
def test_warmup_runs_every_shape_through_backend():
    from lachain_tpu.crypto.tpu_backend import TpuBackend

    backend = TpuBackend(min_device_lanes=1)
    t = warmup_era_kernels(4, backend=backend, include_ts=True)
    assert t is not None
    t.join(timeout=600)
    assert not t.is_alive()
    # mesh pipelines collapse slot tiers that pad onto the same kernel
    # shape (warmup dedupes via padded_shape); single-device pipelines
    # warm every tier
    pipe = backend._get_pipeline()
    tiers = era_warmup_shapes(4)
    if hasattr(pipe, "padded_shape"):
        expected = len({pipe.padded_shape(s, 4) for s in tiers})
    else:
        expected = len(tiers)
    assert backend.era_calls == expected
    # the coin/G2 kernel path warmed too (regression: passing TPKE
    # verification keys here raised AttributeError and silently skipped it)
    assert backend.ts_era_calls >= 1


def test_warmup_noop_on_host_backend():
    from lachain_tpu.crypto.provider import PythonBackend

    assert warmup_era_kernels(4, backend=PythonBackend()) is None


# slice marker: crypto/accelerator kernels ("make test-kernel")
pytestmark = pytest.mark.kernel
