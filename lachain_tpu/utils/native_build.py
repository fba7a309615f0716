"""Build-on-demand for the native libraries (crypto, consensus, storage).

The Makefiles compile with -march=native, so a library is only valid on the
CPU it was built on: a tree copied to another machine with its .so files
(the chip tool copies the disk as it stands) would load code the host
cannot execute (SIGILL in libbls381's start-up self-check, first chip call
of PR 21). The rebuild is therefore keyed on a stamp over the sources, the
Makefile, the compiler environment and the CPU's feature flags — not on
mtimes, which survive a copy.
"""
from __future__ import annotations

import fcntl
import glob
import hashlib
import os
import platform
import subprocess


def _cpu_flags() -> str:
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    return ""


def _stamp(native_dir: str) -> str:
    h = hashlib.sha256()
    sources = glob.glob(os.path.join(native_dir, "*.cpp"))
    for path in sorted(sources) + [os.path.join(native_dir, "Makefile")]:
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as fh:
            h.update(fh.read())
    for var in ("CXX", "CXXFLAGS"):
        h.update(os.environ.get(var, "").encode())
    h.update(platform.machine().encode())
    h.update(_cpu_flags().encode())
    return h.hexdigest()


def ensure_built(native_dir: str, lib_name: str) -> str:
    """Return the path of an up-to-date `lib_name`, (re)building it with
    `make -B` when the stamp beside it does not match this machine and
    these sources. A failed build raises with the compiler's output."""
    lib_path = os.path.join(native_dir, lib_name)
    stamp_path = lib_path + ".stamp"
    want = _stamp(native_dir)

    def fresh() -> bool:
        try:
            with open(stamp_path) as fh:
                return fh.read() == want and os.path.exists(lib_path)
        except OSError:
            return False

    if fresh():
        return lib_path
    # several node processes may start at once on a fresh checkout: one
    # builds, the others wait and find the stamp
    with open(os.path.join(native_dir, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not fresh():
            proc = subprocess.run(
                ["make", "-s", "-B", "-C", native_dir],
                capture_output=True,
                text=True,
            )
            if proc.returncode != 0:
                raise RuntimeError(
                    f"native build failed in {native_dir}:\n{proc.stderr}"
                )
            with open(stamp_path, "w") as fh:
                fh.write(want)
    return lib_path
