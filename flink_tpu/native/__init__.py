"""Native host-runtime library: build, load, and ctypes bindings.

See native.cpp for what lives here and why (the reference's FRocksDB /
lz4-JNI / Unsafe analog layer). The .so is compiled on first import with
g++ -O3 and loaded via ctypes; every function has a numpy/zlib fallback so
the package works without a toolchain.

The artifact is named by a hash of native.cpp plus the compile command
(``_native-<hash>.so``) and built whenever that exact file is absent: a
checkout copied to another machine keeps neither mtimes nor this machine's
CPU, so nothing is reused on the strength of a timestamp, nothing is
compiled ``-march=native``, and any other ``_native*.so`` lying in the
directory is ignored.

Public surface:
    NATIVE_AVAILABLE          -- True when the C++ library loaded
    murmur_mix_batch(codes)   -- int32 murmur of uint32 codes
    key_group_batch(codes, max_parallelism)
    compress(data) / decompress(data)  -- block codec (native LZ4-style or
                                          zlib fallback; self-describing tag)
    HostHashIndex             -- int64 -> dense slot index (native or dict)
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import zlib
from typing import Optional

import numpy as np

__all__ = [
    "NATIVE_AVAILABLE", "murmur_mix_batch", "key_group_batch",
    "compress", "decompress", "HostHashIndex",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native.cpp")
_BUILD_CMD = ("g++", "-O3", "-shared", "-fPIC")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _artifact_path() -> str:
    digest = hashlib.sha256(" ".join(_BUILD_CMD).encode())
    with open(_SRC, "rb") as f:
        digest.update(f.read())
    return os.path.join(_HERE, f"_native-{digest.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    # compile to a private name and rename: a concurrent importer (tests
    # and workers start several processes at once) never loads a
    # half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        r = subprocess.run([*_BUILD_CMD, "-o", tmp, _SRC],
                           capture_output=True, timeout=120)
        if r.returncode != 0:
            return False
        os.replace(tmp, so)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        try:
            so = _artifact_path()
            if not os.path.exists(so) and not _build(so):
                return None
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        # signatures
        i64, u8p, u32p, i32p, i64p = (
            ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64))
        lib.murmur_mix_batch.argtypes = [u32p, i64, i32p]
        lib.key_group_batch.argtypes = [u32p, i64, ctypes.c_int32, i32p]
        lib.block_compress_bound.argtypes = [i64]
        lib.block_compress_bound.restype = i64
        lib.block_compress.argtypes = [u8p, i64, u8p]
        lib.block_compress.restype = i64
        lib.block_decompress.argtypes = [u8p, i64, u8p, i64]
        lib.block_decompress.restype = i64
        lib.block_raw_len.argtypes = [u8p, i64]
        lib.block_raw_len.restype = i64
        lib.hi_create.argtypes = [i64]
        lib.hi_create.restype = ctypes.c_void_p
        lib.hi_free.argtypes = [ctypes.c_void_p]
        lib.hi_size.argtypes = [ctypes.c_void_p]
        lib.hi_size.restype = i64
        lib.hi_upsert_batch.argtypes = [ctypes.c_void_p, i64p, i64, i32p]
        lib.hi_lookup_batch.argtypes = [ctypes.c_void_p, i64p, i64, i32p]
        _lib = lib
        return _lib


_loaded = _load()
NATIVE_AVAILABLE = _loaded is not None


def _u32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))


def _i32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _u8p(b):
    return ctypes.cast(ctypes.c_char_p(bytes(b) if not isinstance(b, bytes)
                                       else b),
                       ctypes.POINTER(ctypes.c_uint8))


def murmur_mix_batch(codes: np.ndarray) -> np.ndarray:
    """Vectorized reference murmur (bit-exact with keygroups.murmur_mix)."""
    codes = np.ascontiguousarray(codes, dtype=np.uint32)
    if _loaded is not None:
        out = np.empty(len(codes), np.int32)
        _loaded.murmur_mix_batch(_u32p(codes), len(codes), _i32p(out))
        return out
    from ..core.keygroups import murmur_mix
    return murmur_mix(codes)


def key_group_batch(codes: np.ndarray, max_parallelism: int) -> np.ndarray:
    codes = np.ascontiguousarray(codes, dtype=np.uint32)
    if _loaded is not None:
        out = np.empty(len(codes), np.int32)
        _loaded.key_group_batch(_u32p(codes), len(codes),
                                np.int32(max_parallelism), _i32p(out))
        return out
    from ..core.keygroups import murmur_mix
    return (murmur_mix(codes) % max_parallelism).astype(np.int32)


# -- block codec ------------------------------------------------------------
# 1-byte tag so either side can decode frames from the other implementation
_TAG_NATIVE = b"\x01"
_TAG_ZLIB = b"\x02"


def compress(data: bytes) -> bytes:
    if _loaded is not None:
        n = len(data)
        bound = _loaded.block_compress_bound(n)
        out = np.empty(bound, np.uint8)
        written = _loaded.block_compress(
            _u8p(data), n, out.ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint8)))
        return _TAG_NATIVE + out[:written].tobytes()
    return _TAG_ZLIB + zlib.compress(data, 1)


def decompress(data: bytes) -> bytes:
    tag, payload = data[:1], data[1:]
    if tag == _TAG_ZLIB:
        return zlib.decompress(payload)
    if tag != _TAG_NATIVE:
        raise ValueError("unknown compression tag")
    if _loaded is None:
        # durable data must stay recoverable on hosts without a toolchain:
        # slow pure-Python decoder for the native frame format
        return _py_block_decompress(payload)
    raw = _loaded.block_raw_len(_u8p(payload), len(payload))
    if raw < 0:
        raise ValueError("corrupt compressed block")
    out = np.empty(max(raw, 1), np.uint8)
    got = _loaded.block_decompress(
        _u8p(payload), len(payload),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), raw)
    if got != raw:
        raise ValueError("corrupt compressed block")
    return out[:raw].tobytes()


def _py_block_decompress(src: bytes) -> bytes:
    """Pure-Python decoder for native.cpp's block format (see the frame
    spec there); correctness fallback only — native path is ~100x faster."""
    if len(src) < 8:
        raise ValueError("corrupt compressed block")
    raw = int.from_bytes(src[:8], "little", signed=True)
    if raw < 0:
        raise ValueError("corrupt compressed block")
    ip, iend = 8, len(src)
    out = bytearray()
    while len(out) < raw:
        if ip >= iend:
            raise ValueError("corrupt compressed block")
        tok = src[ip]
        ip += 1
        lit_len = tok >> 4
        if lit_len == 15:
            while True:
                if ip >= iend:
                    raise ValueError("corrupt compressed block")
                b = src[ip]
                ip += 1
                lit_len += b
                if b != 255:
                    break
        if ip + lit_len > iend or len(out) + lit_len > raw:
            raise ValueError("corrupt compressed block")
        out += src[ip:ip + lit_len]
        ip += lit_len
        if len(out) >= raw:
            break
        if ip + 2 > iend:
            raise ValueError("corrupt compressed block")
        off = int.from_bytes(src[ip:ip + 2], "little")
        ip += 2
        match_len = tok & 15
        if match_len == 15:
            while True:
                if ip >= iend:
                    raise ValueError("corrupt compressed block")
                b = src[ip]
                ip += 1
                match_len += b
                if b != 255:
                    break
        match_len += 4
        if off == 0 or off > len(out) or len(out) + match_len > raw:
            raise ValueError("corrupt compressed block")
        start = len(out) - off
        for i in range(match_len):   # overlap-safe forward copy
            out.append(out[start + i])
    return bytes(out)


class HostHashIndex:
    """int64 key -> dense slot index (insertion order). Native open
    addressing when available, dict fallback otherwise. The host-side twin
    of ops/hash_table.py's device table."""

    def __init__(self, capacity: int = 1024):
        self._native = None
        if _loaded is not None:
            self._native = _loaded.hi_create(int(capacity))
        else:
            self._dict: dict[int, int] = {}

    def upsert(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        out = np.empty(len(keys), np.int32)
        if self._native is not None:
            _loaded.hi_upsert_batch(self._native, _i64p(keys), len(keys),
                                    _i32p(out))
            return out
        d = self._dict
        for i, k in enumerate(keys):
            out[i] = d.setdefault(int(k), len(d))
        return out

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        out = np.empty(len(keys), np.int32)
        if self._native is not None:
            _loaded.hi_lookup_batch(self._native, _i64p(keys), len(keys),
                                    _i32p(out))
            return out
        d = self._dict
        for i, k in enumerate(keys):
            out[i] = d.get(int(k), -1)
        return out

    def __len__(self) -> int:
        if self._native is not None:
            return int(_loaded.hi_size(self._native))
        return len(self._dict)

    def __del__(self):
        native = getattr(self, "_native", None)
        if native is not None and _loaded is not None:
            _loaded.hi_free(native)
            self._native = None
