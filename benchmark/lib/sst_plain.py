"""A plain reader of the block-table SST format, for the reference only.

It imports nothing of the package under test: the layout below is the file
format as `toplingdb_tpu/table/format.py` and `block.py` document it
(LevelDB's framing), read with numpy and the system's libsnappy.

  file    = blocks... | footer(53 B)
  footer  = checksum_type(1) | metaindex handle | index handle | padding
            | version(4, LE) | magic(8, LE)
  handle  = varint64 offset | varint64 size        (size without trailer)
  block   = payload | compression_type(1) | crc(4)
  payload = entries... | restart offsets (4 B each, LE) | restart count (4)
  entry   = varint32 shared | varint32 non_shared | varint32 value_len
            | key[shared:] | value
  index   = one entry per data block: separator key -> handle

Rows here have one key width and one value width (the deployment's record
shape), which lets all blocks be decoded in step: entry k of every block at
once, about 130 numpy steps a file instead of a Python loop over millions
of rows. A file that breaks that assumption raises `Unreadable`.
"""

from __future__ import annotations

import ctypes
import ctypes.util

import numpy as np

MAGIC = 0x7470756C736D5354  # "tpulsmST"
FOOTER_LEN = 53
TRAILER = 5
NO_COMPRESSION, SNAPPY = 0, 1


class Unreadable(Exception):
    pass


_snappy = None


def _libsnappy():
    global _snappy
    if _snappy is None:
        name = ctypes.util.find_library("snappy") or "libsnappy.so.1"
        lib = ctypes.CDLL(name)
        lib.snappy_uncompressed_length.restype = ctypes.c_int
        lib.snappy_uncompressed_length.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t)]
        lib.snappy_uncompress.restype = ctypes.c_int
        lib.snappy_uncompress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_size_t)]
        _snappy = lib
    return _snappy


def _varint(buf: bytes, off: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[off]
        off += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, off
        shift += 7


def _payload(data: bytes, offset: int, size: int) -> bytes:
    ctype = data[offset + size]
    raw = data[offset:offset + size]
    if ctype == NO_COMPRESSION:
        return raw
    if ctype != SNAPPY:
        raise Unreadable(f"compression type {ctype}")
    lib = _libsnappy()
    n = ctypes.c_size_t()
    if lib.snappy_uncompressed_length(raw, len(raw), ctypes.byref(n)) != 0:
        raise Unreadable("snappy length")
    out = ctypes.create_string_buffer(n.value)
    if lib.snappy_uncompress(raw, len(raw), out, ctypes.byref(n)) != 0:
        raise Unreadable("snappy payload")
    return out.raw[:n.value]


def block_handles(data: bytes) -> list[tuple[int, int]]:
    """(offset, size) of every data block, in file order."""
    if len(data) < FOOTER_LEN:
        raise Unreadable("shorter than a footer")
    foot = data[-FOOTER_LEN:]
    if int.from_bytes(foot[-8:], "little") != MAGIC:
        raise Unreadable("not a block-table SST")
    _, off = _varint(foot, 1)
    _, off = _varint(foot, off)
    i_off, off = _varint(foot, off)
    i_size, _ = _varint(foot, off)
    index = _payload(data, i_off, i_size)
    n_restarts = int.from_bytes(index[-4:], "little")
    end = len(index) - 4 - 4 * n_restarts
    handles, off = [], 0
    while off < end:
        _shared, off = _varint(index, off)
        non_shared, off = _varint(index, off)
        vlen, off = _varint(index, off)
        off += non_shared
        h_off, p = _varint(index, off)
        h_size, _ = _varint(index, p)
        handles.append((h_off, h_size))
        off += vlen
    return handles


def read_rows(path: str) -> tuple[np.ndarray, np.ndarray]:
    """Every row of one SST in file order: ([m, K] uint8 internal keys,
    [m, V] uint8 values)."""
    with open(path, "rb") as f:
        data = f.read()
    handles = block_handles(data)
    if not handles:
        return np.zeros((0, 0), np.uint8), np.zeros((0, 0), np.uint8)
    parts = [_payload(data, o, s) for o, s in handles]
    lens = np.fromiter((len(p) for p in parts), np.int64, len(parts))
    base = np.concatenate([[0], np.cumsum(lens)[:-1]])
    buf = np.frombuffer(b"".join(parts) + b"\0" * 64, dtype=np.uint8)
    last4 = base + lens - 4
    n_restarts = (buf[last4].astype(np.int64)
                  | buf[last4 + 1].astype(np.int64) << 8
                  | buf[last4 + 2].astype(np.int64) << 16
                  | buf[last4 + 3].astype(np.int64) << 24)
    limit = base + lens - 4 - 4 * n_restarts  # first byte past the entries

    # The record shape, from the first entry (a restart point: shared 0).
    if buf[base[0]] != 0:
        raise Unreadable("first entry shares a prefix")
    klen, vlen = int(buf[base[0] + 1]), int(buf[base[0] + 2])
    kcol = np.arange(klen, dtype=np.int64)
    vcol = np.arange(vlen, dtype=np.int64)

    pos = base.copy()
    prev = np.zeros((len(parts), klen), np.uint8)
    keys, vals, alive = [], [], []
    while True:
        live = pos < limit
        if not live.any():
            break
        p = np.where(live, pos, base)  # dead blocks re-read entry 0, masked
        shared = buf[p].astype(np.int64)
        non_shared = buf[p + 1].astype(np.int64)
        if ((buf[p] | buf[p + 1] | buf[p + 2]) >= 0x80).any() \
                or ((shared + non_shared != klen) | (buf[p + 2] != vlen))[
                    live].any():
            raise Unreadable("rows of more than one shape")
        src = p[:, None] + 3 + kcol[None, :] - shared[:, None]
        key = np.where(kcol[None, :] < shared[:, None], prev,
                       buf[np.maximum(src, 0)])
        val = buf[(p + 3 + non_shared)[:, None] + vcol[None, :]]
        keys.append(key)
        vals.append(val)
        alive.append(live)
        prev = key
        pos = np.where(live, p + 3 + non_shared + vlen, pos)
    if (pos != limit).any():
        raise Unreadable("a block's entries overrun its restart array")
    alive = np.stack(alive, axis=1)                 # [blocks, steps]
    keys = np.stack(keys, axis=1)[alive]            # block-major, in order
    vals = np.stack(vals, axis=1)[alive]
    return keys, vals


def split_internal(ikeys: np.ndarray):
    """[m, K] internal keys -> (user key numbers u64 (8-byte big-endian
    keys), sequence u64, value type u8): the trailer is LE64(seq<<8|type)."""
    if ikeys.shape[1] != 16:
        raise Unreadable(f"internal keys of {ikeys.shape[1]} bytes, not 8+8")
    ukey = ikeys[:, :8].copy().view(">u8").reshape(-1).astype(np.uint64)
    trailer = ikeys[:, 8:].copy().view("<u8").reshape(-1)
    return ukey, trailer >> np.uint64(8), (trailer & np.uint64(0xFF)).astype(
        np.uint8)
