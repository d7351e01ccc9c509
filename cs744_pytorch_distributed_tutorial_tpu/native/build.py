"""Compile-on-first-use for the native components.

g++ is baked into the image but pip installs are not allowed, so the
shared library is built directly (``g++ -O3 -shared -fPIC``) into a
version-keyed cache next to this package the first time it's needed.
A failed build warns and consumers, which check ``native_available()``,
fall back to NumPy.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import threading
import warnings

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_CACHE: dict[str, object] = {}


def _source_path(name: str) -> str:
    return os.path.join(_DIR, f"{name}.cpp")


def _lib_path(name: str) -> str:
    # Key the artifact to the source hash so edits trigger rebuilds and
    # stale .so files are never loaded.
    with open(_source_path(name), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"_{name}_{digest}.so")


def _build(name: str) -> str | None:
    src, lib = _source_path(name), _lib_path(name)
    if os.path.exists(lib):
        return lib
    # Per-process scratch name: concurrent builders (multi-host shared
    # filesystems, pytest-xdist) must not write the same tmp file, or a
    # half-written .so could be os.replace()d into the digest-keyed path
    # and cached as corrupt forever. os.replace itself is atomic.
    tmp = f"{lib}.tmp.{os.getpid()}"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        src, "-o", tmp,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
        os.replace(tmp, lib)
        return lib
    except (subprocess.SubprocessError, OSError) as e:
        # Consumers fall back to NumPy; say why, once, so a slow input
        # pipeline is not a mystery (load_library caches the None).
        stderr = (getattr(e, "stderr", None) or "")[-2000:]
        warnings.warn(
            f"native {name!r} did not build, using the NumPy fallback: "
            f"{e}\n{stderr}",
            RuntimeWarning,
            stacklevel=3,
        )
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass


def load_library(name: str = "batcher"):
    """ctypes.CDLL for a native component, or None if unbuildable."""
    with _LOCK:
        if name in _CACHE:
            return _CACHE[name]
        lib_path = _build(name)
        lib = None
        if lib_path is not None:
            import ctypes

            try:
                lib = ctypes.CDLL(lib_path)
            except OSError:
                lib = None
        _CACHE[name] = lib
        return lib


def native_available(name: str = "batcher") -> bool:
    return load_library(name) is not None
