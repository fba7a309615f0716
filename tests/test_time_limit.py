"""The one time limit every test runs under (tests/conftest.py), shown on a
pytest run of its own whose conftest is the repo's with the limit at 2 s:
a test asleep in Python and one whose main thread waits in C on a locked
mutex both fail by name with a stack, each runs once, and the test after
them in the same file still runs."""
import os
import subprocess
import sys
import time
import xml.etree.ElementTree as ET

import pytest

pytest.importorskip("xdist")

LIMIT_S = 2

HANGS = '''
import ctypes
import threading
import time


def test_sleeps_in_python():
    threading.Thread(target=threading.Event().wait, name="waiter", daemon=True).start()
    while True:
        time.sleep(60)


def test_waits_in_c():
    lock = ctypes.CDLL(None).pthread_mutex_lock
    lock.argtypes, lock.restype = [ctypes.c_void_p], ctypes.c_int
    mutex = ctypes.create_string_buffer(64)  # zeroed: an unlocked normal mutex
    lock(mutex)
    lock(mutex)  # locked twice by one thread: waits for ever


def test_after_them():
    pass
'''

CONFTEST = '''
import importlib.util
import sys

sys.modules["jax"] = None  # the limit needs no jax: three processes start quicker
spec = importlib.util.spec_from_file_location("repo_conftest", {path!r})
repo = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo)
repo.TEST_LIMIT_S = {limit}
pytest_configure = repo.pytest_configure
pytest_runtest_protocol = repo.pytest_runtest_protocol
pytest_xdist_make_scheduler = repo.pytest_xdist_make_scheduler
'''


def test_a_hang_in_python_or_in_c_fails_once_at_the_limit_and_the_file_goes_on(tmp_path):
    (tmp_path / "conftest.py").write_text(
        CONFTEST.format(path=os.path.join(os.path.dirname(__file__), "conftest.py"), limit=LIMIT_S)
    )
    (tmp_path / "test_hangs.py").write_text(HANGS)
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env["PYTEST_DISABLE_PLUGIN_AUTOLOAD"] = "1"  # xdist alone: its workers start sooner
    began = time.monotonic()
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "xdist", "-n", "1",
         "--dist", "loadfile", "--junitxml", str(tmp_path / "out.xml"), "test_hangs.py"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    took = time.monotonic() - began
    said = run.stdout + run.stderr
    assert took < 30, said

    cases = {}
    for case in ET.parse(tmp_path / "out.xml").iter("testcase"):
        cases.setdefault(case.get("name"), []).append(case)
    assert sorted(cases) == ["test_after_them", "test_sleeps_in_python", "test_waits_in_c"], said
    assert all(len(ran) == 1 for ran in cases.values()), "a test that hung ran again"

    python, = cases["test_sleeps_in_python"]
    failure = python.find("failure")
    assert failure is not None and f"LimitReached: over the {LIMIT_S} s limit" in failure.text
    assert "time.sleep(60)" in failure.text  # the main thread, where it stopped
    assert "--- thread waiter:" in failure.text and "in wait" in failure.text

    native, = cases["test_waits_in_c"]
    crash = native.find("error")
    assert crash is not None and "crashed while running 'test_hangs.py::test_waits_in_c'" in crash.get("message")
    assert "Timeout (0:00:02.5" in said and "in test_waits_in_c" in said, said

    after, = cases["test_after_them"]
    assert after.find("failure") is None and after.find("error") is None
    assert run.returncode == 1
