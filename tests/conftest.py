"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual 8-device CPU platform. What the compiled kernels do
on a chip is chip_smoke.py's to show, not this suite's.

Must run before any `import jax` anywhere in the test session.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# the config API too: it wins over anything that set the platform list
# before this file ran, the env var alone does not.
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
