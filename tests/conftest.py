"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual 8-device CPU platform. What the compiled kernels do
on a chip is chip_smoke.py's to show, not this suite's.

Must run before any `import jax` anywhere in the test session.

It also holds every test to one time limit, TEST_LIMIT_S, for its setup,
call and teardown together. At the limit a SIGALRM raises in the main
thread: the test fails with every thread's stack in its report, and its
worker goes on with the rest. A main thread stuck in C (a ctypes call into
a native engine, an XLA compile), where no Python signal handler runs, is
ended a quarter limit later by faulthandler, which prints every thread's
stack and exits the worker: xdist reports "worker 'gwN' crashed while
running <nodeid>" and starts a new one, which never runs that test again.
"""
import faulthandler
import os
import signal
import sys
import threading
import traceback

import pytest

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


# 5x the slowest test under six workers on 8 cores (56 s), 1.7x the
# slowest with two cores for those six (173 s; PR 40's runs)
TEST_LIMIT_S = 300


class LimitReached(BaseException):
    """Not an Exception: an `except Exception` around the stuck wait must
    not swallow it."""


_stderr_fd = 2


def pytest_configure(config):
    # output capture is suspended here: this is the run's real stderr,
    # where faulthandler's stacks must go, not a test's capture file
    global _stderr_fd
    _stderr_fd = os.dup(2)


def _other_threads_stacks() -> str:
    names = {t.ident: t.name for t in threading.enumerate()}
    return "".join(
        f"\n--- thread {names.get(ident, ident)}:\n" + "".join(traceback.format_stack(frame))
        for ident, frame in sys._current_frames().items()
        if ident != threading.get_ident()
    )


def _on_limit(signum, frame):
    # the main thread's stack is this exception's own traceback
    raise LimitReached(f"over the {TEST_LIMIT_S} s limit; the other threads:" + _other_threads_stacks())


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol():
    faulthandler.dump_traceback_later(TEST_LIMIT_S * 1.25, exit=True, file=_stderr_fd)
    previous = signal.signal(signal.SIGALRM, _on_limit)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


try:
    from xdist.scheduler import LoadFileScheduling
except ImportError:  # no xdist: a worker that dies ends the run anyway
    pass
else:

    class _LoadFileOnce(LoadFileScheduling):
        """`--dist loadfile` puts a dead worker's file back in the queue with
        the test it died in still pending, and may hand it to another worker
        before any hook hears of the crash: marked done here, first, the test
        is reported once, as crashed, and never run again."""

        def remove_node(self, node):
            crashed = None
            for unit in self.assigned_work[node].values():
                crashed = next((nodeid for nodeid, done in unit.items() if not done), None)
                if crashed is not None:
                    unit[crashed] = True
                    break
            super().remove_node(node)
            return crashed

    @pytest.hookimpl(optionalhook=True, tryfirst=True)
    def pytest_xdist_make_scheduler(config, log):
        if config.getvalue("dist") == "loadfile":
            return _LoadFileOnce(config, log)
        return None
