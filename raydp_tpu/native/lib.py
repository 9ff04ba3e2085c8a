"""ctypes bindings for the native data-plane library, with numpy fallback.

``gather_matrix`` assembles a training minibatch — rows ``indices`` of the
given numeric columns — into a contiguous row-major array ready for
``jax.device_put``. The native path avoids numpy's per-column fancy-index +
stack (which materializes column-major intermediates) and parallelizes
across rows.
"""
from __future__ import annotations

import ctypes
import logging
import os
from typing import Dict, Optional, Sequence

import numpy as np

from raydp_tpu.native import build

_COL_TYPES = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.int64): 2,
    np.dtype(np.int32): 3,
    np.dtype(np.int16): 4,
    np.dtype(np.uint8): 5,
}

logger = logging.getLogger(__name__)

_lib = None
_lib_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("RAYDP_TPU_DISABLE_NATIVE") == "1":
        return None
    path = build.ensure_built()
    if path is None:
        logger.warning(
            "native data-plane library did not build (no g++ or no "
            "sources); gather and hash-partition run on the numpy twins"
        )
        return None
    lib = ctypes.CDLL(path)
    lib.rdp_gather.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_void_p,
    ]
    for name in ("rdp_gather_matrix_f32", "rdp_gather_matrix_i32"):
        fn = getattr(lib, name)
        fn.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_void_p,
        ]
    lib.rdp_hash_bucket.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_void_p,
    ]
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


def native_status() -> Dict[str, object]:
    """Which implementation this process uses: ``{"native", "path",
    "built_here"}`` — ``built_here`` says whether this process compiled
    the library itself or loaded one already built from the same
    sources and flags."""
    lib = _load()
    return {
        "native": lib is not None,
        "path": lib._name if lib is not None else None,
        "built_here": lib is not None and build.built_here,
    }


def gather_matrix(
    columns: Sequence[np.ndarray],
    indices: np.ndarray,
    out_dtype=np.float32,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """``out[i, c] = columns[c][indices[i]]`` cast to ``out_dtype``.

    Columns must be 1-D, contiguous, numeric. ``out_dtype`` must be
    float32 or int32 (the two infeed staging formats).
    """
    ncols = len(columns)
    if ncols == 0:
        raise ValueError("need at least one column")
    out_dtype = np.dtype(out_dtype)
    if out_dtype not in (np.dtype(np.float32), np.dtype(np.int32)):
        raise ValueError("out_dtype must be float32 or int32")
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    n = indices.shape[0]
    cols = [np.ascontiguousarray(c) for c in columns]
    n_src = min(c.shape[0] for c in cols)
    _check_indices(indices, n_src)
    if out is None:
        out = np.empty((n, ncols), dtype=out_dtype)
    else:
        if (
            out.shape != (n, ncols)
            or out.dtype != out_dtype
            or not out.flags.c_contiguous
        ):
            raise ValueError("out must be C-contiguous (n, ncols) of out_dtype")

    lib = _load()
    if lib is not None and all(c.dtype in _COL_TYPES for c in cols):
        col_ptrs = (ctypes.c_void_p * ncols)(
            *[c.ctypes.data_as(ctypes.c_void_p).value for c in cols]
        )
        col_types = np.array([_COL_TYPES[c.dtype] for c in cols], dtype=np.int32)
        fn = (
            lib.rdp_gather_matrix_f32
            if out_dtype == np.float32
            else lib.rdp_gather_matrix_i32
        )
        fn(
            col_ptrs,
            col_types.ctypes.data_as(ctypes.c_void_p),
            ncols,
            indices.ctypes.data_as(ctypes.c_void_p),
            n,
            out.ctypes.data_as(ctypes.c_void_p),
        )
        return out

    # numpy fallback
    for c_idx, col in enumerate(cols):
        out[:, c_idx] = col[indices].astype(out_dtype, copy=False)
    return out


def _check_indices(indices: np.ndarray, n_src: int) -> None:
    """Native kernels do raw pointer math — validate here (the numpy
    fallback would raise IndexError; match that contract)."""
    if indices.size and (indices.min() < 0 or indices.max() >= n_src):
        raise IndexError(
            f"gather indices out of range [0, {n_src}) "
            f"(min={indices.min()}, max={indices.max()})"
        )


def hash_bucket(
    columns: Sequence[np.ndarray], n_buckets: int
) -> Optional[np.ndarray]:
    """Stable per-row bucket ids from numeric key columns (the shuffle
    partitioner hot path). Returns None when a column dtype is
    unsupported — callers fall back to the pandas hash.

    CONSISTENCY CONTRACT: every partition of one exchange must assign
    equal keys to equal buckets, and partitions are hashed in different
    processes. Therefore the RESULT depends only on the values: when the
    native library is unavailable, an exact numpy twin of the splitmix64
    kernel computes the identical buckets (never a different algorithm).
    """
    if not columns:
        return None
    cols = []
    for c in columns:
        c = np.ascontiguousarray(c)
        if c.dtype not in _COL_TYPES or c.ndim != 1:
            return None
        cols.append(c)
    n = cols[0].shape[0]
    if any(c.shape[0] != n for c in cols):
        return None
    lib = _load()
    if lib is None:
        return _hash_bucket_numpy(cols, n_buckets)
    out = np.empty(n, dtype=np.int64)
    col_ptrs = (ctypes.c_void_p * len(cols))(
        *[c.ctypes.data_as(ctypes.c_void_p).value for c in cols]
    )
    col_types = np.array([_COL_TYPES[c.dtype] for c in cols], dtype=np.int32)
    lib.rdp_hash_bucket(
        col_ptrs,
        col_types.ctypes.data_as(ctypes.c_void_p),
        len(cols),
        n,
        int(n_buckets),
        out.ctypes.data_as(ctypes.c_void_p),
    )
    return out


def _splitmix64_np(x: np.ndarray) -> np.ndarray:
    """Vectorized twin of the C++ rdp_mix64 (bit-exact)."""
    x = x + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def _load_bits_np(c: np.ndarray) -> np.ndarray:
    """Twin of the C++ load_bits: the uint64 the kernel hashes."""
    if c.dtype == np.float32:
        c = np.where(c == 0.0, np.float32(0.0), c)  # -0.0 → +0.0
        return c.view(np.uint32).astype(np.uint64)
    if c.dtype == np.float64:
        c = np.where(c == 0.0, 0.0, c)
        return c.view(np.uint64)
    if c.dtype == np.uint8:
        return c.astype(np.uint64)
    # signed ints: sign-extend exactly like the C++ int64_t cast
    return c.astype(np.int64).view(np.uint64)


def _hash_bucket_numpy(cols, n_buckets: int) -> np.ndarray:
    with np.errstate(over="ignore"):
        h = np.full(cols[0].shape[0], 0x517CC1B727220A95, dtype=np.uint64)
        for i, c in enumerate(cols):
            v = _load_bits_np(c) + np.uint64(
                (0x100000001B3 * i) & 0xFFFFFFFFFFFFFFFF
            )
            h = _splitmix64_np(h ^ _splitmix64_np(v))
        return (h % np.uint64(n_buckets)).astype(np.int64)


def gather_rows(src: np.ndarray, indices: np.ndarray) -> np.ndarray:
    """Row gather on a 2-D contiguous array via the native kernel."""
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    if src.ndim != 2:
        raise ValueError("gather_rows expects a 2-D array")
    _check_indices(indices, src.shape[0])
    lib = _load()
    if lib is None or not src.flags.c_contiguous:
        return src[indices]
    n = indices.shape[0]
    out = np.empty((n, src.shape[1]), dtype=src.dtype)
    width = src.strides[0]
    lib.rdp_gather(
        src.ctypes.data_as(ctypes.c_void_p),
        indices.ctypes.data_as(ctypes.c_void_p),
        n,
        width,
        out.ctypes.data_as(ctypes.c_void_p),
    )
    return out
