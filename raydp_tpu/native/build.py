"""Lazy build of the native data-plane library.

Compiles raydp_tpu/native/src/*.cpp with g++ the first time it's needed.
No pybind11 in this image — the library is plain ``extern "C"`` + ctypes.

The binary's file name carries a hash of the sources and the compiler
flags (``libraydp_native-<key>.so``), so a library is loaded only when it
was built from exactly this source with exactly these flags — a copied
tree, a fresh checkout with odd mtimes or an edited source can never load
a stale binary. The flags name no host CPU (no ``-march=native``), so the
same key means the same code on every machine of the image.
"""
from __future__ import annotations

import contextlib
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC_DIR = os.path.join(_HERE, "src")
_LIB_PREFIX = "libraydp_native-"
_FLAG_SETS = (
    ("-O3", "-fopenmp"),
    ("-O3",),  # openmp may be unsupported
)
_lock = threading.Lock()
# True once THIS process has compiled the library (False when it found
# one already built from the same sources and flags).
built_here = False


def _sources() -> list:
    if not os.path.isdir(_SRC_DIR):
        return []
    return sorted(
        os.path.join(_SRC_DIR, f)
        for f in os.listdir(_SRC_DIR)
        if f.endswith(".cpp")
    )


def _lib_path(srcs: Sequence[str], flags: Sequence[str]) -> str:
    digest = hashlib.sha256(" ".join(flags).encode())
    for src in srcs:
        digest.update(os.path.basename(src).encode())
        with open(src, "rb") as f:
            digest.update(f.read())
    return os.path.join(
        _HERE, f"{_LIB_PREFIX}{digest.hexdigest()[:16]}.so"
    )


def ensure_built(verbose: bool = False) -> Optional[str]:
    """Build if needed; returns the .so path, or None if no toolchain."""
    global built_here
    with _lock:
        srcs = _sources()
        if not srcs:  # sources not shipped (e.g. wheel install) → fallback
            return None
        targets = [(flags, _lib_path(srcs, flags)) for flags in _FLAG_SETS]
        for _, path in targets:
            if os.path.exists(path):
                return path
        for flags, path in targets:
            # Build to a process-private temp path, then atomically
            # rename: concurrent worker processes may race here, and a
            # peer must never dlopen a half-written .so.
            tmp = f"{path}.tmp.{os.getpid()}"
            cmd = ["g++", *flags, "-shared", "-fPIC", "-o", tmp, *srcs]
            try:
                # raydp: ignore[R1] — the lock intentionally covers
                # the compile so concurrent callers build exactly
                # once; callers tolerate the (bounded) wait.
                subprocess.run(
                    cmd,
                    check=True,
                    capture_output=not verbose,
                    timeout=120,
                )
                os.replace(tmp, path)
            except (subprocess.SubprocessError, FileNotFoundError):
                continue
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            built_here = True
            # Binaries of other sources or flags are dead weight.
            for old in glob.glob(os.path.join(_HERE, f"{_LIB_PREFIX}*.so")):
                if old != path:
                    with contextlib.suppress(FileNotFoundError):
                        os.unlink(old)  # a peer may have removed it
            return path
        return None
