"""Record codec: ctypes binding to the C++ packer (native/record_codec.cpp)
with a format-identical pure-Python fallback.

The shared format — per value, zigzag(delta vs previous row, same column)
as a varint, row-major — compresses the framework's int32 record streams
(device traces, replay schedules) ~4-8x, and the native path packs them at
memory bandwidth instead of Python speed.

Record-log file layout:
    magic b"DEMIRECS" | u32 version | u32 row_width | u64 n_rows
    | u64 payload_bytes | payload
"""

from __future__ import annotations

import ctypes
import struct
from typing import Optional, Tuple

import numpy as np

from .build import build_library, native_source

_MAGIC = b"DEMIRECS"
_VERSION = 1

_SRC = native_source("record_codec.cpp")

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def _load_native() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    so = build_library(_SRC, "libdemi_records")
    if so is None:
        return None
    try:
        lib = ctypes.CDLL(so)
        lib.demi_pack.restype = ctypes.c_int64
        lib.demi_pack.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.demi_unpack.restype = ctypes.c_int64
        lib.demi_unpack.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64,
        ]
        _lib = lib
    except (OSError, AttributeError):
        _lib = None
    return _lib


def native_available() -> bool:
    return _load_native() is not None


# -- pure-Python fallback (same format) -------------------------------------

def _py_pack(data: np.ndarray) -> bytes:
    out = bytearray()
    prev = np.zeros(data.shape[1], np.int64)
    for row in data.astype(np.int64):
        deltas = row - prev
        prev = row
        for d in deltas:
            # Wrap the delta to int32 (identical to the native codec), then
            # 32-bit zigzag.
            d32 = ((int(d) + 2**31) % 2**32) - 2**31
            z = ((d32 << 1) ^ (d32 >> 31)) & 0xFFFFFFFF
            while True:
                if z < 0x80:
                    out.append(z)
                    break
                out.append((z & 0x7F) | 0x80)
                z >>= 7
    return bytes(out)


def _py_unpack(buf: bytes, n_rows: int, row_width: int) -> np.ndarray:
    out = np.zeros((n_rows, row_width), np.int32)
    pos = 0
    prev = np.zeros(row_width, np.int64)
    for r in range(n_rows):
        for c in range(row_width):
            z = 0
            shift = 0
            while True:
                if pos >= len(buf):
                    raise ValueError("truncated record log")
                b = buf[pos]
                pos += 1
                z |= (b & 0x7F) << shift
                if not (b & 0x80):
                    break
                shift += 7
            d = (z >> 1) ^ -(z & 1)
            prev[c] += d
            # int32 wraparound semantics to match the native codec
            prev[c] = ((prev[c] + 2**31) % 2**32) - 2**31
            out[r, c] = prev[c]
    return out


# -- public API --------------------------------------------------------------

def pack_records(data: np.ndarray) -> bytes:
    data = np.ascontiguousarray(data, np.int32)
    assert data.ndim == 2
    lib = _load_native()
    if lib is None:
        return _py_pack(data)
    cap = data.size * 5 + 16
    out = np.empty(cap, np.uint8)
    written = lib.demi_pack(
        data.ctypes.data, data.shape[0], data.shape[1], out.ctypes.data, cap
    )
    if written < 0:
        raise ValueError("pack overflow")
    return out[:written].tobytes()


def unpack_records(buf: bytes, n_rows: int, row_width: int) -> np.ndarray:
    lib = _load_native()
    if lib is None:
        return _py_unpack(buf, n_rows, row_width)
    raw = np.frombuffer(buf, np.uint8)
    out = np.empty((n_rows, row_width), np.int32)
    decoded = lib.demi_unpack(
        raw.ctypes.data, len(raw), out.ctypes.data, n_rows, row_width
    )
    if decoded != n_rows:
        raise ValueError("malformed record log")
    return out


def write_record_log(path: str, data: np.ndarray) -> str:
    data = np.ascontiguousarray(data, np.int32)
    payload = pack_records(data)
    with open(path, "wb") as f:
        f.write(_MAGIC)
        f.write(struct.pack("<IIQQ", _VERSION, data.shape[1], data.shape[0], len(payload)))
        f.write(payload)
    return path


def read_record_log(path: str) -> np.ndarray:
    with open(path, "rb") as f:
        magic = f.read(8)
        if magic != _MAGIC:
            raise ValueError(f"{path!r} is not a record log")
        version, width, rows, nbytes = struct.unpack("<IIQQ", f.read(24))
        if version != _VERSION:
            raise ValueError(f"unsupported record-log version {version}")
        payload = f.read(nbytes)
    # Sanity-bound the header before allocating: every value costs at least
    # one payload byte, so a corrupted rows/width field can't trigger a
    # huge allocation.
    if len(payload) != nbytes or rows * width > len(payload):
        raise ValueError("malformed record log (header/payload mismatch)")
    return unpack_records(payload, rows, width)
