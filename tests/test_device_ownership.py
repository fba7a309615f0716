"""One chip owner per process, and no fallback that hides the device (PR 21).

  * a device exception raised inside a stub pipeline propagates out of
    TpkeEraBatcher.flush and threshold_sig.era_verify_combine instead of
    landing on host crypto (the RS half is tests/test_rs_batch.py::
    test_device_failure_propagates);
  * a process on the native backend runs a batched-RBC devnet era without
    ever importing jax;
  * the TPU backend on a process that landed on the CPU without
    JAX_PLATFORMS=cpu in the environment is an error;
  * the compile cache resolves to $JAX_COMPILATION_CACHE_DIR or
    <checkout>/.jax_cache;
  * chip_smoke.py runs to its first check under JAX_PLATFORMS=cpu and fails
    there with a clear message and no result line.
"""
import os
import subprocess
import sys

import pytest

from lachain_tpu.crypto import provider
from lachain_tpu.crypto import threshold_sig as ts
from lachain_tpu.crypto.tpu_backend import TpuBackend

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class DeviceDown(RuntimeError):
    pass


class BrokenPipeline:
    """Stands in for a device pipeline whose launch fails."""

    def run_era(self, slots, y_points, rng, masks=None):
        raise DeviceDown("device pipeline down")


@pytest.fixture
def broken_backend():
    prev = provider.get_backend()
    backend = TpuBackend(
        host_backend=prev,
        pipeline=BrokenPipeline(),
        ts_pipeline=BrokenPipeline(),
        min_device_lanes=1,
    )
    provider.set_backend(backend)
    try:
        yield backend
    finally:
        provider.set_backend(prev)


def test_device_exception_propagates_from_batcher_flush(broken_backend):
    from lachain_tpu.consensus.crypto_batcher import TpkeEraBatcher
    from tests.test_tpu_backend import _job_for, _make_era

    dealer, slots = _make_era(4, 1, n_slots=2)
    jobs = [
        _job_for(4, 1, ct, dict(enumerate(decs))) for ct, decs, _msg in slots
    ]
    delivered = []
    batcher = TpkeEraBatcher()
    batcher.submit(jobs, dealer.verification_keys, delivered.append)
    with pytest.raises(DeviceDown):
        batcher.flush()
    assert delivered == []  # nobody was told to redo the work on the host


def test_device_exception_propagates_from_coin_batch(broken_backend):
    kg = ts.TsTrustedKeyGen(4, 1)
    shares = {i: kg.private_key_share(i).sign(b"coin") for i in range(2)}
    with pytest.raises(DeviceDown):
        ts.era_verify_combine(kg.pub_key_set, [(b"coin", shares)])


def test_host_backend_process_never_imports_jax():
    """Four `cli run` processes share a host with one chip only if the
    host-backend ones never touch jax: run a batched-RBC era (the path
    whose flushes used to probe jax.default_backend) in a fresh process."""
    script = (
        "import sys\n"
        "from lachain_tpu.core.devnet import Devnet\n"
        "from lachain_tpu.crypto.provider import get_backend\n"
        "net = Devnet(n=4, f=1, engine='native', rbc_batch=True,\n"
        "             initial_balances={bytes([9]) * 20: 10**9})\n"
        "net.run_era(1)\n"
        "net.close()\n"
        "assert get_backend().name == 'native', get_backend().name\n"
        "assert 'jax' not in sys.modules, 'host-backend process imported jax'\n"
        "print('clean')\n"
    )
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("LACHAIN_TPU_BACKEND", "LACHAIN_RS_DEVICE")
    }
    env["PYTHONPATH"] = ROOT
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("clean")


def test_tpu_backend_on_unnamed_cpu_is_an_error(monkeypatch):
    """This process's jax is on the CPU (conftest). With JAX_PLATFORMS=cpu
    in the environment that was asked for; without it, a process that
    wanted the chip landed on the CPU, and get_backend() must say so."""
    monkeypatch.setattr(provider, "_BACKEND", None)
    monkeypatch.setattr(provider, "_DEVICE_PLATFORM", [])
    monkeypatch.setenv("LACHAIN_TPU_BACKEND", "tpu")
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="resolved to the CPU"):
        provider.get_backend()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # cpu as a spare: no
    with pytest.raises(RuntimeError, match="resolved to the CPU"):
        provider.get_backend()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert provider.get_backend().name == "tpu"
    assert provider.device_platform() == "cpu"


def test_host_backend_reports_no_device(monkeypatch):
    from lachain_tpu.ops import rs_batch

    monkeypatch.delenv("LACHAIN_RS_DEVICE", raising=False)
    assert provider.get_backend().name != "tpu"
    assert provider.device_platform() is None
    assert rs_batch.device_enabled() is False


def test_compile_cache_dir_resolution(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert provider.compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert provider.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")


def test_unbuildable_native_library_is_an_error(monkeypatch):
    """get_backend() does not land on the Python oracle when the native
    library cannot be built — unless the oracle was asked for."""
    from lachain_tpu.crypto import native_backend

    def no_compiler(*a, **k):
        raise RuntimeError("native build failed")

    monkeypatch.setattr(native_backend, "ensure_built", no_compiler)
    monkeypatch.delenv("LACHAIN_BLS_LIB", raising=False)
    monkeypatch.setattr(provider, "_BACKEND", None)
    monkeypatch.delenv("LACHAIN_TPU_BACKEND", raising=False)
    with pytest.raises(RuntimeError, match="native build failed"):
        provider.get_backend()
    monkeypatch.setenv("LACHAIN_TPU_BACKEND", "python")
    assert provider.get_backend().name == "python"


def test_chip_smoke_fails_at_first_check_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode != 0
    assert "platform == 'tpu' (got 'cpu')" in out.stderr
    last = out.stdout.strip().splitlines()[-1]
    assert "FAIL" in last and not last.startswith("{")
    assert '"ok"' not in out.stdout


# slice marker: crypto/accelerator kernels ("make test-kernel")
pytestmark = pytest.mark.kernel
