"""Native (C++) components: build + ctypes loading.

The reference keeps its hot paths in C++ (backend/cpp/llama); here the
TPU compute path is XLA, and the native pieces are the host-side hot
paths: the GBNF token-mask engine (per-decode-step work under grammar
constraints) and the vector store scan. Every native component has a
pure-Python fallback — `load_library` returns None when the .so is absent
and callers degrade gracefully.

What gets loaded is keyed by its source: the library file is named
``lib<name>-<tag>.so`` where ``tag`` hashes every tracked source file
plus the compiler and flags ``make`` will use. A ``build/`` directory
copied from another machine, left by an older Makefile, or built from
edited source therefore never matches, and the loader builds from the
tracked ``.cpp`` instead of running it.
"""

from __future__ import annotations

import ctypes
import fcntl
import functools
import glob
import hashlib
import os
import subprocess
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
BUILD_DIR = os.path.join(_DIR, "build")
_SOURCES = ("Makefile", "gbnf_mask.cpp", "vecstore.cpp")

_cache: dict[str, Optional[ctypes.CDLL]] = {}


@functools.cache  # one `make flags` per process, not one per load
def source_tag() -> str:
    """Hash of the tracked sources + the compile line ``make`` resolves
    (so a CXX/CXXFLAGS override in the environment is a different
    library, not a reuse of the default one)."""
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    h.update(subprocess.run(
        ["make", "-s", "-C", _DIR, "flags"], capture_output=True,
        check=True).stdout)
    return h.hexdigest()[:12]


def library_path(name: str) -> str:
    """Where the library built from the CURRENT source lives."""
    return os.path.join(BUILD_DIR, f"lib{name}-{source_tag()}.so")


def build(quiet: bool = True) -> bool:
    """Invoke make for the current source tag and drop every other
    library file from the build dir; returns True if the libraries are
    present after.

    One build at a time per build dir, by an exclusive ``flock`` on the
    directory itself (no extra file): several processes building at
    once — pytest-xdist workers each importing tests/test_native.py on
    a fresh tree — raced on the Makefile's ``$@.tmp`` -> ``mv``, and
    the loser's ``make`` failed though the library was there."""
    try:
        tag = source_tag()
        os.makedirs(BUILD_DIR, exist_ok=True)
        lock = os.open(BUILD_DIR, os.O_RDONLY)
    except (OSError, subprocess.CalledProcessError):
        return False
    try:
        fcntl.flock(lock, fcntl.LOCK_EX)
        subprocess.run(
            ["make", "-C", _DIR, f"BUILD={BUILD_DIR}", f"TAG={tag}"],
            capture_output=quiet, check=True,
        )
        for stale in glob.glob(os.path.join(BUILD_DIR, "lib*.so")):
            if not stale.endswith(f"-{tag}.so"):
                os.unlink(stale)
    except (OSError, subprocess.CalledProcessError):
        return False
    finally:
        os.close(lock)  # releases the flock
    return True


def load_library(name: str, auto_build: bool = False) -> Optional[ctypes.CDLL]:
    """Load the library built from the current source; optionally build
    it first. None if unavailable (callers fall back to Python)."""
    if name in _cache:
        return _cache[name]
    try:
        path = library_path(name)
    except (OSError, subprocess.CalledProcessError):
        path = ""  # no make: nothing provably ours to load
    if path and not os.path.exists(path) and auto_build:
        build()
    lib: Optional[ctypes.CDLL] = None
    if path and os.path.exists(path):
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            lib = None
    _cache[name] = lib
    return lib
