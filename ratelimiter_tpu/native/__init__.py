"""Native host runtime: C++ bulk string hashing behind a ctypes seam, and
the resolve's rebuild of a dispatch's reply columns in one call.

The decision hot path is JAX/XLA on device; the *host* hot path is turning
string keys into u64 hashes at ingest (SURVEY.md §7.4 hard part #4). The
reference pays a Redis round-trip per key so its host cost never shows; at
10M+ decisions/s ours does, so hashing is native:

* ``hasher.cpp``   — the C++ kernels, built into ``_hasher.so`` on first
                     use and again whenever its bytes change
                     (``build.py``: the binary carries a hash of its
                     source);
* ``fallback.py``  — bit-identical vectorized NumPy twin for hosts with no
                     compiler;
* this module      — packing (Python strings -> one contiguous byte buffer
                     + offsets/lengths) and dispatch.

The second per-row pass of the host is at the other end of a dispatch:
``column_unpacker`` binds ``hasher.cpp``'s ``unpack_columns`` to one of the
packed result formats (the NumPy twins, which serve where nothing can be
built, are the formats' own ``unpack_*`` functions in ``ops/``).

pybind11 is deliberately not used (not in the image); the ABI is a C array
call through ctypes — zero copies beyond the unavoidable UTF-8 encode.
"""

from __future__ import annotations

import ctypes
import functools
import os
import threading
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from ratelimiter_tpu.native.build import load_extension
from ratelimiter_tpu.native.fallback import hash_packed_numpy

DEFAULT_SEED = 0x52_4C_54_50_55_31  # "RLTPU1"

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "_hasher.so")
_SRC = os.path.join(_DIR, "hasher.cpp")
_ABI = 2


_load_lock = threading.Lock()


def _load() -> Optional[tuple]:
    """(ctypes handle, CPython extension module) of a ``_hasher.so``
    built from this checkout's hasher.cpp (native/build.py), or None on a
    host that cannot build it — the NumPy twin serves there. Compiler and
    loader errors propagate. Serialized: the native door's dispatcher
    threads reach their first hash together."""
    with _load_lock:
        return _load_once()


@functools.lru_cache(maxsize=None)
def _load_once() -> Optional[tuple]:
    loaded = load_extension(
        _SO, [_SRC], module="ratelimiter_tpu.native._hasher",
        abi_symbol="rl_hasher_abi_version", abi=_ABI, opt="-O3")
    if loaded is not None:
        # The ctypes face; the module face carries hash_keylist.
        loaded[0].rl_bulk_hash_u64.restype = None
        loaded[0].rl_bulk_hash_u64.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_int64,
        ]
    return loaded


def native_available() -> bool:
    """True when the C extension is loaded (built or buildable here)."""
    return _load() is not None


def pack_keys(keys: Sequence[str]) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack strings into (buf uint8[], offsets int64[], byte_lengths int64[]).

    Fast path: one ``str.join`` + one encode for the whole batch, with
    per-key byte lengths taken from ``len`` — valid exactly when every key
    is ASCII, which the total-bytes check proves after the fact. Non-ASCII
    batches fall back to per-key encoding (correct, slower).
    """
    n = len(keys)
    if n == 0:
        return (np.empty(0, np.uint8), np.empty(0, np.int64),
                np.empty(0, np.int64))
    lengths = np.fromiter((len(k) for k in keys), dtype=np.int64, count=n)
    blob = "".join(keys).encode("utf-8")
    if len(blob) != int(lengths.sum()):
        # Some key is non-ASCII: char count != byte count. Re-pack exactly.
        encoded = [k.encode("utf-8") for k in keys]
        lengths = np.fromiter((len(e) for e in encoded), dtype=np.int64,
                              count=n)
        blob = b"".join(encoded)
    buf = np.frombuffer(blob, dtype=np.uint8)
    offsets = np.cumsum(lengths) - lengths
    return buf, offsets, lengths


def hash_packed(buf: np.ndarray, offsets: np.ndarray, lengths: np.ndarray,
                seed: int = DEFAULT_SEED) -> np.ndarray:
    """Hash a packed batch; native kernel when available, NumPy twin else."""
    loaded = _load()
    if loaded is None:
        return hash_packed_numpy(buf, offsets, lengths, seed)
    lib = loaded[0]
    n = offsets.shape[0]
    out = np.empty(n, dtype=np.uint64)
    if n:
        buf = np.ascontiguousarray(buf)
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        lengths = np.ascontiguousarray(lengths, dtype=np.int64)
        lib.rl_bulk_hash_u64(
            buf.ctypes.data, offsets.ctypes.data, lengths.ctypes.data,
            ctypes.c_uint64(seed & 0xFFFFFFFFFFFFFFFF),
            out.ctypes.data, ctypes.c_int64(n))
    return out


def bulk_hash_u64(keys: Sequence[str], seed: int = DEFAULT_SEED) -> np.ndarray:
    """Hash a batch of string keys to uint64.

    Fast path: the CPython extension iterates the list directly (zero-copy
    UTF-8 views, no Python-level packing). Fallback: pack + NumPy twin.
    """
    loaded = _load()
    if loaded is not None:
        if not isinstance(keys, list):
            keys = list(keys)
        out = np.empty(len(keys), dtype=np.uint64)
        loaded[1].hash_keylist(keys, seed & 0xFFFFFFFFFFFFFFFF,
                               out.ctypes.data)
        return out
    return hash_packed(*pack_keys(keys), seed=seed)


#: The packed result formats hasher.cpp's unpack_columns rebuilds, by the
#: name of the NumPy twin a lane's ``_result_format()`` returns
#: (sketch_kernels.unpack_window, bucket_kernels.unpack_bucket,
#: dense_kernels.unpack_dense) -> the C++ ``Format``.
_WINDOW_FORMAT = 0
_UNPACK_FORMATS = {"unpack_window": _WINDOW_FORMAT, "unpack_bucket": 1,
                   "unpack_dense": 2}


def column_unpacker(twin: Callable) -> Optional[Callable]:
    """``unpack(words, shards, tail, b, now_us, window_us, ns) ->
    ((allowed, remaining, retry_after, reset_at), admitted)`` for the
    packed format whose NumPy twin is ``twin``: BatchResult's four
    columns, bit for bit the twin's over ``sketch_kernels.result_rows``
    of the same fetch, and ``int(ns[allowed].sum())`` (0 for ``ns`` None),
    built by ONE call that never lets go of the interpreter. None where
    the extension cannot be built or the format is not one it knows —
    the twin serves there. Loads (and on a checkout's first use builds)
    the extension: call it where the lane is built, not on a dispatch."""
    loaded = _load()
    fmt = _UNPACK_FORMATS.get(getattr(twin, "__name__", None))
    if loaded is None or fmt is None:
        return None
    unpack_columns = loaded[1].unpack_columns
    windowed = fmt == _WINDOW_FORMAT

    def unpack(words: np.ndarray, shards: int, tail: int, b: int,
               now_us: int, window_us: int, ns: Optional[np.ndarray]):
        # The scalars the windowed twins compute in Python, as they do.
        if windowed:
            reset_us = now_us // window_us * window_us + window_us
            retry_denied = (reset_us - now_us) / 1e6
        else:
            reset_us, retry_denied = now_us + window_us, 0.0
        cols = (np.empty(b, np.bool_), np.empty(b, np.int64),
                np.empty(b, np.float64), np.empty(b, np.float64))
        if ns is not None:
            ns = np.ascontiguousarray(ns, dtype=np.int64)
        admitted = unpack_columns(
            fmt, np.ascontiguousarray(words, dtype=np.int32), shards, tail,
            b, now_us, retry_denied, reset_us / 1e6, ns, *cols)
        return cols, admitted

    return unpack
