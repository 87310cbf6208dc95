"""Native host runtime — lazy build & load.

Compiles ``src/cyclone_host.cpp`` into a shared library on first use (g++ is
in the image; no pip deps). Every consumer goes through :mod:`host`, which
falls back to pure-Python implementations when the toolchain is unavailable,
so the framework never hard-depends on the .so.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "cyclone_host.cpp")
_LIB_DIR = os.path.join(_HERE, "_lib")
_LIB = os.path.join(_LIB_DIR, "libcyclone_host.so")
_KEY = _LIB + ".key"

# no -march=native: _lib/ is git-ignored but rides along when a tree is
# copied between machines, and a library tuned to the build host's ISA must
# not be loaded on another CPU. The parsers are I/O- and memory-bound.
_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC"]
_LIBS = ["-lzstd", "-lpthread", "-ldl"]

_lock = threading.Lock()
_lib_handle = None
_build_failed = False


def _build_key() -> str:
    """Identity of the library the current source and flags produce."""
    h = hashlib.sha256(" ".join(_FLAGS + _LIBS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return h.hexdigest()


def _needs_build() -> bool:
    """Rebuild unless the key stored beside the library names exactly this
    source and these flags — mtimes say nothing about a library that was
    copied in with the tree."""
    if not (os.path.exists(_LIB) and os.path.exists(_KEY)):
        return True
    with open(_KEY) as f:
        return f.read() != _build_key()


def build(force: bool = False) -> Optional[str]:
    """Compile the native library; returns its path or None on failure."""
    global _build_failed
    with _lock:
        if not force and not _needs_build():
            return _LIB
        os.makedirs(_LIB_DIR, exist_ok=True)
        cmd = ["g++", *_FLAGS, _SRC, "-o", _LIB, *_LIBS]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
        except (OSError, subprocess.SubprocessError):
            # no toolchain / compile error: host.py's pure-Python
            # fallbacks engage
            _build_failed = True
            return None
        with open(_KEY, "w") as f:
            f.write(_build_key())
        _build_failed = False
        return _LIB


def load():
    """ctypes handle to the built library, or None (fallbacks engage)."""
    global _lib_handle
    if _lib_handle is not None:
        return _lib_handle
    if _build_failed:
        return None
    path = build()
    if path is None:
        return None
    import ctypes
    with _lock:
        if _lib_handle is None:
            _lib_handle = ctypes.CDLL(path)
    return _lib_handle
