"""A plain reader of the SingleFastTable SST format, for the reference only.

It imports nothing of the package under test: the layout below is the file
format as `toplingdb_tpu/table/single_fast.py` documents it, read with
numpy alone.

  file      = region | blocks... | metaindex | index | footer(53 B)
  footer    = checksum_type(1) | metaindex handle | index handle | padding
              | version(4, LE) | magic(8, LE, "tpulsmFT")
  handle    = varint64 offset | varint64 size       (size without trailer)
  block     = payload | compression_type(1) = 0 | crc(4, masked CRC32C of
              payload and type)
  region    = the file's first `data_size` bytes, unframed: a record a row,
              varint32 klen | varint32 vlen | internal key | value, in key
              order (user key ascending, then sequence descending)
  index     = u32 LE a row: where the row's record starts in the region
  metaindex = a block of (name -> handle), one restart an entry

  tpulsm.sf.data_crc    u32 LE: the masked CRC32C of the whole region
  tpulsm.range_del      a block of (begin internal key -> end user key)
  tpulsm.properties     a block of (name -> value); `tpulsm.data_size`
                        (decimal digits) is the region's length
  tpulsm.filter, tpulsm.sf.hash_index   not read here: they answer point
                        lookups, the rows are the region's

An internal key is the user key and 8 bytes more, `sequence << 8 | type`
little-endian. Rows may have any widths; `read_rows` wants one.
"""

from __future__ import annotations

import numpy as np

MAGIC = 0x7470756C736D4654  # "tpulsmFT"
FOOTER_LEN = 53
_MASK_DELTA = 0xA282EAD8


class Unreadable(Exception):
    pass


def _varint(buf, off: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[off]
        off += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, off
        shift += 7


def _block_entries(block: bytes) -> list[tuple[bytes, bytes]]:
    """(key, value) of every entry of a prefix-coded block."""
    n_restarts = int.from_bytes(block[-4:], "little")
    end = len(block) - 4 - 4 * n_restarts
    out, off, prev = [], 0, b""
    while off < end:
        shared, off = _varint(block, off)
        non_shared, off = _varint(block, off)
        vlen, off = _varint(block, off)
        key = prev[:shared] + block[off:off + non_shared]
        off += non_shared
        out.append((key, block[off:off + vlen]))
        off += vlen
        prev = key
    return out


def _crc_table() -> np.ndarray:
    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(t & 1, np.uint32(0x82F63B78) ^ (t >> 1), t >> 1)
    return t


_T = _crc_table()


def crc32c(data: bytes) -> int:
    """CRC32C (Castagnoli), a byte a step; `zlib` has no such polynomial.
    One step a byte in Python is what a plain reader can afford on a
    control's file, not on every run's: `read_table(verify=...)`."""
    c = 0xFFFFFFFF
    t = _T.tolist()
    for b in data:
        c = t[(c ^ b) & 0xFF] ^ (c >> 8)
    return c ^ 0xFFFFFFFF


def _unmask(masked: int) -> int:
    rot = (masked - _MASK_DELTA) & 0xFFFFFFFF
    return ((rot >> 17) | (rot << 15)) & 0xFFFFFFFF


def sections(data: bytes) -> dict[bytes, bytes]:
    """The file's meta blocks by name; the offset array under b"index"."""
    if len(data) < FOOTER_LEN:
        raise Unreadable("shorter than a footer")
    foot = data[-FOOTER_LEN:]
    if int.from_bytes(foot[-8:], "little") != MAGIC:
        raise Unreadable("not a SingleFastTable SST")
    m_off, off = _varint(foot, 1)
    m_size, off = _varint(foot, off)
    i_off, off = _varint(foot, off)
    i_size, _ = _varint(foot, off)
    out = {b"index": data[i_off:i_off + i_size]}
    for name, handle in _block_entries(data[m_off:m_off + m_size]):
        h_off, p = _varint(handle, 0)
        h_size, _ = _varint(handle, p)
        if data[h_off + h_size] != 0:
            raise Unreadable(f"block {name!r} is compressed")
        out[name] = data[h_off:h_off + h_size]
    return out


def read_table(path: str, verify: bool = False) -> dict:
    """One SingleFastTable whole: `key_lens`, `val_lens` [n], `key_buf`,
    `val_buf` (the internal keys and the values row after row, flat),
    `tombstones` [(begin user key, sequence, end user key)] and
    `data_size`. With `verify` the region is held against its checksum."""
    with open(path, "rb") as f:
        data = f.read()
    sec = sections(data)
    offs = np.frombuffer(sec[b"index"], "<u4").astype(np.int64)
    n = len(offs)
    props = dict(_block_entries(sec[b"tpulsm.properties"]))
    data_size = int(props[b"tpulsm.data_size"])
    tombs = []
    for begin, end in _block_entries(sec.get(b"tpulsm.range_del", b"\0" * 4)):
        trailer = int.from_bytes(begin[-8:], "little")
        tombs.append((begin[:-8], trailer >> 8, end))
    if verify:
        stored = _unmask(int.from_bytes(sec[b"tpulsm.sf.data_crc"], "little"))
        if crc32c(data[:data_size]) != stored:
            raise Unreadable("the region does not match its checksum")
    img = np.frombuffer(data, np.uint8)
    if n and (offs[0] != 0 or (np.diff(offs) <= 0).any()
              or offs[-1] >= data_size):
        raise Unreadable("offsets out of order or out of the region")
    # The two varints of every record. Lengths under 128 (one byte each)
    # are read at once; a record with a longer one is read by itself.
    klen = img[offs].astype(np.int64) if n else np.zeros(0, np.int64)
    vlen = img[offs + 1].astype(np.int64) if n else np.zeros(0, np.int64)
    head = np.full(n, 2, np.int64)
    for i in np.flatnonzero((klen >= 0x80) | (vlen >= 0x80)):
        k, p = _varint(data, int(offs[i]))
        v, p = _varint(data, p)
        klen[i], vlen[i], head[i] = k, v, p - int(offs[i])
    ends = np.append(offs[1:], data_size) if n else offs
    if ((offs + head + klen + vlen) != ends).any() or (klen < 8).any():
        raise Unreadable("a record does not end where the next begins")
    kpos = np.repeat(offs + head, klen) + (
        np.arange(int(klen.sum())) - np.repeat(np.cumsum(klen) - klen, klen))
    vpos = np.repeat(offs + head + klen, vlen) + (
        np.arange(int(vlen.sum())) - np.repeat(np.cumsum(vlen) - vlen, vlen))
    return {"key_lens": klen, "val_lens": vlen, "key_buf": img[kpos],
            "val_buf": img[vpos], "tombstones": tombs,
            "data_size": data_size}


def read_rows(path: str) -> tuple[np.ndarray, np.ndarray]:
    """Every row of one SingleFastTable in file order, as
    `sst_plain.read_rows` gives a block table's: ([m, K] uint8 internal
    keys, [m, V] uint8 values). Rows of more than one key or value width
    are `Unreadable` here."""
    t = read_table(path)
    n = len(t["key_lens"])
    if n == 0:
        return np.zeros((0, 0), np.uint8), np.zeros((0, 0), np.uint8)
    K, V = int(t["key_lens"][0]), int(t["val_lens"][0])
    if (t["key_lens"] != K).any() or (t["val_lens"] != V).any():
        raise Unreadable("rows of more than one width")
    return t["key_buf"].reshape(n, K), t["val_buf"].reshape(n, V)


def is_single_fast_table(path: str) -> bool:
    with open(path, "rb") as f:
        f.seek(-8, 2)
        return int.from_bytes(f.read(8), "little") == MAGIC
