"""Shared build-and-load helper for in-tree native (C++) components.

The reference ships prebuilt .so files loaded via ctypes (libps.so at
executor.py:100-137, libc_runtime_api.so in _base.py); here each native
component compiles from its committed source on first use so the repo
stays self-contained.  Used by hetu_tpu/ps (embedding store) and
hetu_tpu/galvatron (DP search core).

The library file is named after a hash of the source, the compiler flags
and this machine's CPU, and lives in ``<checkout>/.native_build/`` (git-
ignored).  A tree copied to another machine therefore never loads code
that ``-march=native`` specialised for the first one: the name it looks
for does not exist there, and it builds.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading

_BUILD_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".native_build")
_FLAGS = ["-O3", "-march=native", "-std=c++17", "-shared", "-fPIC"]


def _cpu_identity():
    """What ``-march=native`` keys its code generation on: the CPU model
    and its feature flags (``/proc/cpuinfo``; the bare architecture name
    where that file does not exist)."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
    except OSError:
        return platform.machine()
    keep = {}
    for line in lines:
        key = line.split(":", 1)[0].strip()
        if key in ("model name", "flags", "Features"):
            keep.setdefault(key, line)
    return platform.machine() + "\n" + "\n".join(keep.values())


class NativeLib:
    """Lazily compiled + loaded shared library.

    declare(lib) is called once after load to set restype/argtypes.
    """

    def __init__(self, src, name, declare=None, extra_flags=()):
        self.src = src
        self.name = name
        self.declare = declare
        self.flags = _FLAGS + list(extra_flags)
        self._lock = threading.Lock()
        self._lib = None

    @property
    def lib_path(self):
        """Where the library built from THIS source with THESE flags on
        THIS CPU lives."""
        h = hashlib.sha256()
        with open(self.src, "rb") as f:
            h.update(f.read())
        h.update(" ".join(self.flags).encode())
        h.update(_cpu_identity().encode())
        return os.path.join(_BUILD_DIR,
                            f"{self.name}-{h.hexdigest()[:16]}.so")

    def build(self):
        lib_path = self.lib_path
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # compile beside the target and rename: a concurrent process
        # (PS servers start in their own) never loads a half-written file
        tmp = f"{lib_path}.{os.getpid()}.tmp"
        proc = subprocess.run(["g++"] + self.flags + ["-o", tmp, self.src],
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"building {os.path.basename(lib_path)} failed:\n"
                f"{proc.stderr}")
        os.replace(tmp, lib_path)
        return lib_path

    def load(self):
        with self._lock:
            if self._lib is not None:
                return self._lib
            lib_path = self.lib_path
            if not os.path.exists(lib_path):
                self.build()
            lib = ctypes.CDLL(lib_path)
            if self.declare is not None:
                self.declare(lib)
            self._lib = lib
            return lib
