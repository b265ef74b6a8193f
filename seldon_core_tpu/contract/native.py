"""ctypes binding for the native tensor codec (csrc/codec.cpp).

Loads ``seldon_core_tpu/_native/libsctcodec.so`` when present (``make
native``); every entry point has a pure-Python answer, so the package works
without the native build — the binding only changes speed, never behavior.
The binary is git-ignored and built without ``-march=native``, so a tree
copied to another host runs it; which codec a server runs is reported on
``/stats/warmup`` (``device.native_codec``).
"""

from __future__ import annotations

import ctypes
import os

import numpy as np

_LIB_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "_native",
    "libsctcodec.so",
)

_lib = None


def _load(path: str = _LIB_PATH) -> None:
    global _lib
    if not os.path.exists(path):
        _lib = None
        return
    try:
        lib = ctypes.CDLL(path)
        lib.sct_parse_dense.restype = ctypes.c_longlong
        lib.sct_parse_dense.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_double), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_longlong), ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.sct_format_dense.restype = ctypes.c_longlong
        lib.sct_format_dense.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_longlong, ctypes.c_longlong,
            ctypes.c_char_p, ctypes.c_size_t,
        ]
    except OSError:  # pragma: no cover - corrupt build
        _lib = None
        return
    _lib = lib


_load()


def available() -> bool:
    return _lib is not None


def reload(path: str = _LIB_PATH) -> bool:
    """Re-probe for the .so (e.g. after an on-demand ``make native``);
    ``path`` loads a build kept outside the package directory."""
    _load(path)
    return _lib is not None


def parse_dense(fragment: bytes) -> tuple[np.ndarray, int] | None:
    """Parse a JSON numeric array fragment starting at ``[``.

    -> (array, bytes_consumed), or None when the fragment is not dense
    numeric (caller falls back to the Python decoder).
    """
    if _lib is None:
        return None
    # worst-case doubles: every other byte a digit
    cap = max(16, len(fragment) // 2 + 8)
    out = np.empty(cap, dtype=np.float64)
    shape = (ctypes.c_longlong * 2)()
    ndim = ctypes.c_int()
    consumed = ctypes.c_size_t()
    n = _lib.sct_parse_dense(
        fragment,
        len(fragment),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        cap,
        shape,
        ctypes.byref(ndim),
        ctypes.byref(consumed),
    )
    if n < 0:
        return None
    arr = out[:n]
    if ndim.value == 2:
        # The C parser can report a 2-D shape whose product disagrees with
        # the value count for mixed-depth content like [1.0,[2.0],[3.0]]
        # (scalars at depth 1 counted into n but not into rows*cols).  Such
        # input is not a dense matrix — fall back to the Python decoder
        # instead of raising from reshape.
        if n != shape[0] * shape[1]:
            return None
        arr = arr.reshape(shape[0], shape[1])
    return arr.copy(), consumed.value


def format_dense(arr: np.ndarray) -> str | None:
    """-> JSON text for a 1-D or 2-D float array, or None (fallback)."""
    if _lib is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.float64)
    if arr.ndim == 1:
        rows, cols = -1, arr.shape[0]
    elif arr.ndim == 2:
        rows, cols = arr.shape
    else:
        return None
    cap = max(256, arr.size * 28 + rows * 2 + 16 if rows > 0 else arr.size * 28 + 16)
    buf = ctypes.create_string_buffer(cap)
    w = _lib.sct_format_dense(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        rows,
        cols,
        buf,
        cap,
    )
    if w < 0:
        return None
    return buf.raw[:w].decode("ascii")
