"""Native host tier: ctypes bindings for native/columnar.cpp.

Compiles the shared library on first import (g++ -O3 -shared -fPIC,
rebuilt when the source changes) and exposes numpy-friendly wrappers.
Every function has a pure-NumPy fallback so the engine works without a
toolchain (``NATIVE_AVAILABLE`` reports which path is active).
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
from typing import Optional, Sequence

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_CHECKOUT = os.path.dirname(os.path.dirname(_HERE))
_SRC = os.path.join(_CHECKOUT, "native", "columnar.cpp")
# fixed, git-ignored build directory inside the checkout: the library is
# built from native/columnar.cpp as committed and from nothing else
_BUILD_DIR = os.path.join(_CHECKOUT, ".cache", "native")
_LIB: Optional[ctypes.CDLL] = None
NATIVE_AVAILABLE = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    """None (NumPy fallbacks) when there is no source, no toolchain or the
    library will not load; ``chip_smoke.py`` and the test header report
    ``NATIVE_AVAILABLE`` so the fallback is never silent."""
    if not os.path.exists(_SRC):
        return None
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    os.makedirs(_BUILD_DIR, exist_ok=True)
    lib_path = os.path.join(_BUILD_DIR, f"columnar_{digest}.so")
    # concurrent first imports (xdist workers, cluster processes) take
    # turns on a lock file; the first builds, the rest find the library
    with open(lib_path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not os.path.exists(lib_path):
            partial = lib_path + ".partial"
            try:
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                     "-o", partial, _SRC],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                os.replace(partial, lib_path)
            except (OSError, subprocess.SubprocessError):
                return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError:
        return None
    i64, u8p, i64p, i32p, u64p = (
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint64),
    )
    lib.tt_dict_encode.restype = i64
    lib.tt_dict_encode.argtypes = [ctypes.c_char_p, i64p, i64, i32p, i64p]
    lib.tt_varint_encode.restype = i64
    lib.tt_varint_encode.argtypes = [i64p, i64, u8p]
    lib.tt_varint_decode.restype = i64
    lib.tt_varint_decode.argtypes = [u8p, i64, i64, i64p]
    lib.tt_rle_encode.restype = i64
    lib.tt_rle_encode.argtypes = [i64p, i64, u8p]
    lib.tt_rle_decode.restype = i64
    lib.tt_rle_decode.argtypes = [u8p, i64, i64, i64p]
    lib.tt_bitpack_encode.restype = i64
    lib.tt_bitpack_encode.argtypes = [u64p, i64, ctypes.c_int32, u8p]
    lib.tt_bitpack_decode.restype = None
    lib.tt_bitpack_decode.argtypes = [u8p, i64, ctypes.c_int32, u64p]
    lib.tt_lz_compress.restype = i64
    lib.tt_lz_compress.argtypes = [u8p, i64, u8p]
    lib.tt_lz_decompress.restype = i64
    lib.tt_lz_decompress.argtypes = [u8p, i64, u8p, i64]
    lib.tt_snappy_decompress.restype = i64
    lib.tt_snappy_decompress.argtypes = [u8p, i64, u8p, i64]
    lib.tt_tpch_textpool.restype = i64
    lib.tt_tpch_textpool.argtypes = [u8p, i64, u8p, i64, i64]
    lib.tt_orc_rle2.restype = i64
    lib.tt_orc_rle2.argtypes = [u8p, i64, i64, ctypes.c_int32, i64p]
    lib.tt_orc_rle1.restype = i64
    lib.tt_orc_rle1.argtypes = [u8p, i64, i64, ctypes.c_int32, i64p]
    lib.tt_orc_byte_rle.restype = i64
    lib.tt_orc_byte_rle.argtypes = [u8p, i64, i64, u8p]
    lib.tt_orc_decimal64.restype = i64
    lib.tt_orc_decimal64.argtypes = [u8p, i64, i64, i64p]
    lib.tt_orc_rle2_encode.restype = i64
    lib.tt_orc_rle2_encode.argtypes = [i64p, i64, ctypes.c_int32, u8p]
    lib.tt_orc_byte_rle_encode.restype = i64
    lib.tt_orc_byte_rle_encode.argtypes = [u8p, i64, u8p]
    lib.tt_orc_varint_encode.restype = i64
    lib.tt_orc_varint_encode.argtypes = [u64p, i64, u8p]
    lib.tt_snappy_compress.restype = i64
    lib.tt_snappy_compress.argtypes = [u8p, i64, u8p]
    lib.tt_parquet_rle_decode.restype = i64
    lib.tt_parquet_rle_decode.argtypes = [u8p, i64, ctypes.c_int32, i64, i32p]
    lib.tt_parquet_rle_encode.restype = i64
    lib.tt_parquet_rle_encode.argtypes = [i32p, i64, ctypes.c_int32, u8p]
    lib.tt_pack_arena.restype = i64
    lib.tt_pack_arena.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), i64p, i64, u8p, i64,
    ]
    return lib


_LIB = _build_and_load()
NATIVE_AVAILABLE = _LIB is not None


import contextlib


@contextlib.contextmanager
def python_fallback():
    """Force every wrapper through its pure-Python path for the duration
    (session prop ``native_decode=false``; the decode parity tests).
    Flips the module-level handle, so native calls on OTHER threads also
    fall back while held — safe (fallbacks are bit-identical), just
    slower."""
    global _LIB
    saved, _LIB = _LIB, None
    try:
        yield
    finally:
        _LIB = saved


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


# === dictionary encode ======================================================


def dict_encode(strings: Sequence[str]) -> tuple[np.ndarray, list[str]]:
    """codes (int32) + unique values in first-seen order."""
    n = len(strings)
    if n == 0:
        return np.zeros(0, dtype=np.int32), []
    if _LIB is not None:
        offsets = np.zeros(n + 1, dtype=np.int64)
        pos = 0
        enc = [s.encode("utf-8", "surrogatepass") for s in strings]
        blob = b"".join(enc)
        for i, e in enumerate(enc):
            offsets[i] = pos
            pos += len(e)
        offsets[n] = pos
        codes = np.empty(n, dtype=np.int32)
        first = np.empty(n, dtype=np.int64)
        n_unique = _LIB.tt_dict_encode(
            blob,
            _ptr(offsets, ctypes.c_int64),
            n,
            _ptr(codes, ctypes.c_int32),
            _ptr(first, ctypes.c_int64),
        )
        uniques = [strings[first[j]] for j in range(n_unique)]
        return codes, uniques
    # fallback
    index: dict[str, int] = {}
    codes = np.empty(n, dtype=np.int32)
    uniques: list[str] = []
    for i, s in enumerate(strings):
        c = index.get(s)
        if c is None:
            c = len(uniques)
            index[s] = c
            uniques.append(s)
        codes[i] = c
    return codes, uniques


# === integer codecs =========================================================


def varint_encode(values: np.ndarray) -> bytes:
    values = np.ascontiguousarray(values, dtype=np.int64)
    n = len(values)
    if n == 0:
        return b""
    if _LIB is not None:
        out = np.empty(10 * n, dtype=np.uint8)
        ln = _LIB.tt_varint_encode(
            _ptr(values, ctypes.c_int64), n, _ptr(out, ctypes.c_uint8)
        )
        return out[:ln].tobytes()
    # fallback: delta + zigzag varint in python
    out = bytearray()
    prev = 0
    for v in values.tolist():
        u = ((v - prev) << 1) ^ ((v - prev) >> 63) if (v - prev) < 0 else (v - prev) << 1
        u &= (1 << 64) - 1
        prev = v
        while u >= 0x80:
            out.append((u & 0x7F) | 0x80)
            u >>= 7
        out.append(u)
    return bytes(out)


def varint_decode(data: bytes, n: int) -> np.ndarray:
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    if _LIB is not None:
        buf = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(n, dtype=np.int64)
        rc = _LIB.tt_varint_decode(
            _ptr(buf, ctypes.c_uint8), len(buf), n, _ptr(out, ctypes.c_int64)
        )
        if rc < 0:
            raise ValueError("corrupt varint page")
        return out
    out = np.empty(n, dtype=np.int64)
    pos = 0
    prev = 0
    for i in range(n):
        u = 0
        shift = 0
        while True:
            b = data[pos]
            pos += 1
            u |= (b & 0x7F) << shift
            if not (b & 0x80):
                break
            shift += 7
        d = (u >> 1) ^ -(u & 1)
        prev += d
        out[i] = prev
    return out


def rle_encode(values: np.ndarray) -> bytes:
    values = np.ascontiguousarray(values, dtype=np.int64)
    n = len(values)
    if n == 0:
        return b""
    if _LIB is not None:
        out = np.empty(20 * n + 16, dtype=np.uint8)
        ln = _LIB.tt_rle_encode(
            _ptr(values, ctypes.c_int64), n, _ptr(out, ctypes.c_uint8)
        )
        return out[:ln].tobytes()
    out = bytearray()
    i = 0
    vals = values.tolist()
    while i < n:
        run = 1
        while i + run < n and vals[i + run] == vals[i]:
            run += 1
        for u in (run, (vals[i] << 1) ^ (vals[i] >> 63) if vals[i] < 0 else vals[i] << 1):
            u &= (1 << 64) - 1
            while u >= 0x80:
                out.append((u & 0x7F) | 0x80)
                u >>= 7
            out.append(u)
        i += run
    return bytes(out)


def rle_decode(data: bytes, n: int) -> np.ndarray:
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    if _LIB is not None:
        buf = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(n, dtype=np.int64)
        rc = _LIB.tt_rle_decode(
            _ptr(buf, ctypes.c_uint8), len(buf), n, _ptr(out, ctypes.c_int64)
        )
        if rc < 0:
            raise ValueError("corrupt RLE page")
        return out
    out = np.empty(n, dtype=np.int64)
    pos = 0
    i = 0
    while i < n:
        parts = []
        for _ in range(2):
            u = 0
            shift = 0
            while True:
                b = data[pos]
                pos += 1
                u |= (b & 0x7F) << shift
                if not (b & 0x80):
                    break
                shift += 7
            parts.append(u)
        run, u = parts
        v = (u >> 1) ^ -(u & 1)
        for _ in range(run):
            if i < n:
                out[i] = v
                i += 1
    return out


def bitpack_encode(values: np.ndarray, width: int) -> bytes:
    values = np.ascontiguousarray(values, dtype=np.uint64)
    n = len(values)
    if n == 0 or width == 0:
        return b""
    if _LIB is not None:
        out = np.zeros((n * width + 7) // 8, dtype=np.uint8)
        _LIB.tt_bitpack_encode(
            _ptr(values, ctypes.c_uint64), n, width, _ptr(out, ctypes.c_uint8)
        )
        return out.tobytes()
    bits = np.zeros(n * width, dtype=np.uint8)
    for b in range(width):
        bits[b::width] = (values >> np.uint64(b)) & np.uint64(1)
    return np.packbits(bits, bitorder="little").tobytes()


def bitpack_decode(data: bytes, n: int, width: int) -> np.ndarray:
    if n == 0 or width == 0:
        return np.zeros(n, dtype=np.uint64)
    if _LIB is not None:
        buf = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(n, dtype=np.uint64)
        _LIB.tt_bitpack_decode(
            _ptr(buf, ctypes.c_uint8), n, width, _ptr(out, ctypes.c_uint64)
        )
        return out
    bits = np.unpackbits(np.frombuffer(data, dtype=np.uint8), bitorder="little")
    bits = bits[: n * width].reshape(n, width).astype(np.uint64)
    out = np.zeros(n, dtype=np.uint64)
    for b in range(width):
        out |= bits[:, b] << np.uint64(b)
    return out


def snappy_decompress(data: bytes, expected_len: int) -> bytes:
    """Snappy block format (Parquet's default codec). Python fallback
    implements the same tagged literal/copy stream."""
    if not data:
        return b""
    if _LIB is not None:
        inp = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(max(expected_len, 1), dtype=np.uint8)
        ln = _LIB.tt_snappy_decompress(
            _ptr(inp, ctypes.c_uint8), len(data), _ptr(out, ctypes.c_uint8),
            max(expected_len, 1),
        )
        if ln < 0:
            raise ValueError("corrupt snappy page")
        return out[:ln].tobytes()
    # pure-python fallback
    ip = 0
    ulen = 0
    shift = 0
    while True:
        b = data[ip]
        ip += 1
        ulen |= (b & 0x7F) << shift
        if not (b & 0x80):
            break
        shift += 7
    out = bytearray()
    n = len(data)
    while ip < n:
        tag = data[ip]
        ip += 1
        kind = tag & 3
        if kind == 0:
            ln = (tag >> 2) + 1
            if (tag >> 2) >= 60:
                nb = (tag >> 2) - 59
                ln = int.from_bytes(data[ip : ip + nb], "little") + 1
                ip += nb
            out += data[ip : ip + ln]
            ip += ln
        else:
            if kind == 1:
                ln = ((tag >> 2) & 7) + 4
                off = ((tag >> 5) << 8) | data[ip]
                ip += 1
            elif kind == 2:
                ln = (tag >> 2) + 1
                off = int.from_bytes(data[ip : ip + 2], "little")
                ip += 2
            else:
                ln = (tag >> 2) + 1
                off = int.from_bytes(data[ip : ip + 4], "little")
                ip += 4
            for _ in range(ln):
                out.append(out[-off])
    return bytes(out)


def snappy_compress(data: bytes) -> bytes:
    """Literal-only snappy stream (valid for any decoder)."""
    if _LIB is not None and data:
        inp = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(len(data) + len(data) // 64 + 32, dtype=np.uint8)
        ln = _LIB.tt_snappy_compress(
            _ptr(inp, ctypes.c_uint8), len(data), _ptr(out, ctypes.c_uint8)
        )
        return out[:ln].tobytes()
    out = bytearray()
    ulen = len(data)
    while ulen >= 0x80:
        out.append((ulen & 0x7F) | 0x80)
        ulen >>= 7
    out.append(ulen)
    ip = 0
    while ip < len(data):
        chunk = min(len(data) - ip, 65536)
        ln = chunk - 1
        if ln < 60:
            out.append(ln << 2)
        else:
            out.append(61 << 2)  # 61 => two length bytes
            out += (ln).to_bytes(2, "little")
        out += data[ip : ip + chunk]
        ip += chunk
    return bytes(out)


def parquet_rle_decode(data: bytes, bit_width: int, n: int) -> np.ndarray:
    """Parquet RLE/bit-packed hybrid (def levels, dictionary indices)."""
    if n == 0:
        return np.zeros(0, dtype=np.int32)
    if bit_width == 0:
        return np.zeros(n, dtype=np.int32)
    if _LIB is not None:
        buf = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(n, dtype=np.int32)
        rc = _LIB.tt_parquet_rle_decode(
            _ptr(buf, ctypes.c_uint8), len(buf), bit_width, n,
            _ptr(out, ctypes.c_int32),
        )
        if rc < 0:
            raise ValueError("corrupt parquet RLE run")
        return out
    out = np.empty(n, dtype=np.int32)
    ip = 0
    op = 0
    byte_width = (bit_width + 7) // 8
    while op < n and ip < len(data):
        header = 0
        shift = 0
        while True:
            b = data[ip]
            ip += 1
            header |= (b & 0x7F) << shift
            if not (b & 0x80):
                break
            shift += 7
        if header & 1:
            count = (header >> 1) * 8
            acc = 0
            acc_bits = 0
            mask = (1 << bit_width) - 1
            for _ in range(count):
                while acc_bits < bit_width and ip < len(data):
                    acc |= data[ip] << acc_bits
                    ip += 1
                    acc_bits += 8
                if op < n:
                    out[op] = acc & mask
                    op += 1
                acc >>= bit_width
                acc_bits -= bit_width
        else:
            count = header >> 1
            v = int.from_bytes(data[ip : ip + byte_width], "little")
            ip += byte_width
            for _ in range(count):
                if op < n:
                    out[op] = v
                    op += 1
    return out


def parquet_rle_encode(values: np.ndarray, bit_width: int) -> bytes:
    values = np.ascontiguousarray(values, dtype=np.int32)
    n = len(values)
    if n == 0:
        return b""
    if _LIB is not None:
        out = np.empty(n * 8 + 16, dtype=np.uint8)
        ln = _LIB.tt_parquet_rle_encode(
            _ptr(values, ctypes.c_int32), n, bit_width, _ptr(out, ctypes.c_uint8)
        )
        return out[:ln].tobytes()
    out = bytearray()
    byte_width = (bit_width + 7) // 8
    i = 0
    vals = values.tolist()
    while i < n:
        j = i
        while j < n and vals[j] == vals[i]:
            j += 1
        header = (j - i) << 1
        while header >= 0x80:
            out.append((header & 0x7F) | 0x80)
            header >>= 7
        out.append(header)
        out += int(vals[i] & 0xFFFFFFFF).to_bytes(4, "little")[:byte_width]
        i = j
    return bytes(out)


def lz_compress(data: bytes) -> bytes:
    if not data:
        return b""
    if _LIB is not None:
        inp = np.frombuffer(data, dtype=np.uint8)
        # worst case: all literals -> n + n/128 + 1 token bytes
        out = np.empty(len(data) + len(data) // 128 + 16, dtype=np.uint8)
        ln = _LIB.tt_lz_compress(
            _ptr(inp, ctypes.c_uint8), len(data), _ptr(out, ctypes.c_uint8)
        )
        return out[:ln].tobytes()
    import zlib

    return zlib.compress(data, 1)


def lz_decompress(data: bytes, expected_len: int) -> bytes:
    if not data:
        return b""
    if _LIB is not None:
        inp = np.frombuffer(data, dtype=np.uint8)
        out = np.empty(expected_len, dtype=np.uint8)
        ln = _LIB.tt_lz_decompress(
            _ptr(inp, ctypes.c_uint8), len(data), _ptr(out, ctypes.c_uint8),
            expected_len,
        )
        if ln < 0:
            raise ValueError("corrupt compressed page")
        return out[:ln].tobytes()
    import zlib

    return zlib.decompress(data)


def tpch_textpool(size: int, dists_blob: bytes, seed: int) -> np.ndarray:
    """Generate the dbgen grammar text pool (uint8 array of `size`).

    Native path is ~1s for the spec's 300MB pool; the Python fallback is
    the same algorithm (slow — callers cache the pool on disk either way).
    """
    if _LIB is not None:
        out = np.empty(size, dtype=np.uint8)
        blob = np.frombuffer(dists_blob, dtype=np.uint8)
        ln = _LIB.tt_tpch_textpool(
            _ptr(out, ctypes.c_uint8), size,
            _ptr(blob, ctypes.c_uint8), len(dists_blob), seed,
        )
        if ln != size:
            raise ValueError("text pool generation failed")
        return out
    from trino_tpu.connectors.dbgen import textpool_python

    return textpool_python(size, dists_blob, seed)


def orc_rle2(data: bytes, count: int, signed: bool) -> Optional[np.ndarray]:
    """ORC RLEv2 integer decode (None -> caller uses the Python path)."""
    if _LIB is None or count == 0:
        return None if _LIB is None else np.zeros(0, dtype=np.int64)
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(count, dtype=np.int64)
    rc = _LIB.tt_orc_rle2(
        _ptr(buf, ctypes.c_uint8), len(buf), count, int(signed),
        _ptr(out, ctypes.c_int64),
    )
    if rc < 0:
        raise ValueError("corrupt ORC RLEv2 stream")
    return out


def orc_rle1(data: bytes, count: int, signed: bool) -> Optional[np.ndarray]:
    if _LIB is None or count == 0:
        return None if _LIB is None else np.zeros(0, dtype=np.int64)
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(count, dtype=np.int64)
    rc = _LIB.tt_orc_rle1(
        _ptr(buf, ctypes.c_uint8), len(buf), count, int(signed),
        _ptr(out, ctypes.c_int64),
    )
    if rc < 0:
        raise ValueError("corrupt ORC RLEv1 stream")
    return out


def orc_byte_rle(data: bytes, count: int) -> Optional[np.ndarray]:
    if _LIB is None or count == 0:
        return None if _LIB is None else np.zeros(0, dtype=np.uint8)
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(count, dtype=np.uint8)
    rc = _LIB.tt_orc_byte_rle(
        _ptr(buf, ctypes.c_uint8), len(buf), count, _ptr(out, ctypes.c_uint8)
    )
    if rc < 0:
        raise ValueError("corrupt ORC byte-RLE stream")
    return out


def orc_decimal64(data: bytes, count: int) -> Optional[np.ndarray]:
    if _LIB is None or count == 0:
        return None if _LIB is None else np.zeros(0, dtype=np.int64)
    buf = np.frombuffer(data, dtype=np.uint8)
    out = np.empty(count, dtype=np.int64)
    rc = _LIB.tt_orc_decimal64(
        _ptr(buf, ctypes.c_uint8), len(buf), count, _ptr(out, ctypes.c_int64)
    )
    if rc < 0:
        raise ValueError("corrupt ORC decimal stream")
    return out


def orc_rle2_encode(vals: np.ndarray, signed: bool) -> Optional[bytes]:
    """ORC RLEv2 integer encode (None -> caller uses the Python path)."""
    if _LIB is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    n = len(vals)
    if n == 0:
        return b""
    out = np.empty(9 * n + 64, dtype=np.uint8)
    ln = _LIB.tt_orc_rle2_encode(
        _ptr(vals, ctypes.c_int64), n, int(signed), _ptr(out, ctypes.c_uint8)
    )
    return out[:ln].tobytes()


def orc_byte_rle_encode(b: np.ndarray) -> Optional[bytes]:
    if _LIB is None:
        return None
    b = np.ascontiguousarray(b, dtype=np.uint8)
    n = len(b)
    if n == 0:
        return b""
    out = np.empty(2 * n + 64, dtype=np.uint8)
    ln = _LIB.tt_orc_byte_rle_encode(
        _ptr(b, ctypes.c_uint8), n, _ptr(out, ctypes.c_uint8)
    )
    return out[:ln].tobytes()


def arena_words(nbytes_list: Sequence[int]) -> int:
    """uint32 words a staging arena needs for these source byte sizes
    (each source lands word-aligned with zeroed tail padding)."""
    return sum((nb + 3) // 4 for nb in nbytes_list)


def pack_arena(
    arrays: Sequence[np.ndarray], use_native: bool = True
) -> np.ndarray:
    """Copy column buffers into ONE contiguous uint32 staging arena.

    The coalesced-H2D hot loop: every buffer of a split (data, validity,
    selection) is packed word-aligned so the engine issues a single
    host->device transfer per shard. Native and numpy paths are
    bit-identical (tail padding is zeroed in both).
    """
    srcs = [np.ascontiguousarray(a) for a in arrays]
    sizes = [s.nbytes for s in srcs]
    total = arena_words(sizes)
    out = np.empty(total, dtype=np.uint32)
    if total == 0:
        return out
    if _LIB is not None and use_native:
        n = len(srcs)
        ptrs = (ctypes.c_void_p * n)(
            *[s.ctypes.data_as(ctypes.c_void_p).value for s in srcs]
        )
        nbytes = np.asarray(sizes, dtype=np.int64)
        rc = _LIB.tt_pack_arena(
            ptrs,
            _ptr(nbytes, ctypes.c_int64),
            n,
            out.view(np.uint8).ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint8)
            ),
            total,
        )
        if rc != total:
            raise ValueError("arena pack overrun")
        return out
    dst = out.view(np.uint8)
    pos = 0
    for s, nb in zip(srcs, sizes):
        dst[pos : pos + nb] = s.reshape(-1).view(np.uint8)
        padded = (nb + 3) & ~3
        if padded != nb:
            dst[pos + nb : pos + padded] = 0
        pos += padded
    return out


def orc_varint_encode(u: np.ndarray) -> Optional[bytes]:
    """Plain LEB128 of a uint64 array (no delta, unlike varint_encode)."""
    if _LIB is None:
        return None
    u = np.ascontiguousarray(u, dtype=np.uint64)
    n = len(u)
    if n == 0:
        return b""
    out = np.empty(10 * n + 16, dtype=np.uint8)
    ln = _LIB.tt_orc_varint_encode(
        _ptr(u, ctypes.c_uint64), n, _ptr(out, ctypes.c_uint8)
    )
    return out[:ln].tobytes()
