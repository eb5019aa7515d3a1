"""A plain reader of the ZipTable SST format, for the reference only.

It imports nothing of the package under test: the layout below is the file
format as `toplingdb_tpu/table/zip_table.py` documents it, read with numpy
and the `zstandard` module.

  file      = sections... | metaindex | index | footer(53 B)
  footer    = checksum_type(1) | metaindex handle | index handle | padding
              | version(4, LE) | magic(8, LE, "tpulsmZT")
  handle    = varint64 offset | varint64 size       (size without trailer)
  section   = payload | compression_type(1) = 0 | crc(4)
  metaindex = a block of (section name -> handle), one restart an entry
  index     = u32 LE a key group: where the group's head starts in k.sfx

  tpulsm.zt.params   5 x u32 LE: version 1, G (keys a group), VG (values
                     a group), n (rows), flags (1: value lengths are u32,
                     2: a dictionary is stored, 4: key meta is u16)
  tpulsm.zt.k.meta   (shared-prefix length, suffix length) a row, u8 or
                     u16 LE; row i of group g (i % G != 0) shares its
                     prefix with row i - 1, a group's head shares none
  tpulsm.zt.k.sfx    the suffixes, row after row
  tpulsm.zt.v.lens   the value length a row, u16 or u32 LE
  tpulsm.zt.v.go     u32 LE a value group and one more: where group j's
                     payload starts in v.blob
  tpulsm.zt.v.flags  one bit a value group (LSB first): payload is a zstd
                     frame (under v.dict when one is stored), else raw
  tpulsm.zt.v.dict   the file's one zstd dictionary
  tpulsm.zt.v.blob   the payloads; a group's raw bytes are its rows'
                     values, row after row
  tpulsm.range_del   a block of (begin internal key -> end user key)

Rows here have one key width (the deployment's record shape), which lets
the front coding be undone in G numpy steps a file. A file that breaks
that raises `Unreadable`.
"""

from __future__ import annotations

import numpy as np
import zstandard

MAGIC = 0x7470756C736D5A54  # "tpulsmZT"
FOOTER_LEN = 53
FLAG_LENS32, FLAG_HAS_DICT, FLAG_META16 = 1, 2, 4


class Unreadable(Exception):
    pass


def _varint(buf: bytes, off: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[off]
        off += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, off
        shift += 7


def _block_entries(block: bytes) -> list[tuple[bytes, bytes]]:
    """(key, value) of every entry of a block built with one restart an
    entry or not: prefixes are undone as they come."""
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


def sections(data: bytes) -> dict[bytes, bytes]:
    """The file's sections by name; the index (group head offsets) under
    b"index"."""
    if len(data) < FOOTER_LEN:
        raise Unreadable("shorter than a footer")
    foot = data[-FOOTER_LEN:]
    if int.from_bytes(foot[-8:], "little") != MAGIC:
        raise Unreadable("not a ZipTable SST")
    m_off, off = _varint(foot, 1)
    m_size, off = _varint(foot, off)
    i_off, off = _varint(foot, off)
    i_size, _ = _varint(foot, off)
    out = {b"index": data[i_off:i_off + i_size]}
    for name, handle in _block_entries(data[m_off:m_off + m_size]):
        h_off, p = _varint(handle, 0)
        h_size, _ = _varint(handle, p)
        if data[h_off + h_size] != 0:
            raise Unreadable(f"section {name!r} is compressed")
        out[name] = data[h_off:h_off + h_size]
    return out


def read_table(path: str) -> dict:
    """One ZipTable whole: `keys` [n, K] uint8 internal keys in file
    order, `val_lens` [n], `val_buf` (the values row after row, flat),
    `tombstones` [(begin user key, sequence, end user key)], and `dict_len`
    (bytes of the stored dictionary, 0 for none)."""
    with open(path, "rb") as f:
        sec = sections(f.read())
    params = np.frombuffer(sec[b"tpulsm.zt.params"], "<u4")
    if len(params) < 5 or params[0] != 1:
        raise Unreadable("params")
    G, VG, n, flags = (int(x) for x in params[1:5])
    tombs = []
    for begin, end in _block_entries(sec.get(b"tpulsm.range_del", b"\0" * 4)):
        trailer = int.from_bytes(begin[-8:], "little")
        tombs.append((begin[:-8], trailer >> 8, end))
    vdict = sec.get(b"tpulsm.zt.v.dict", b"") if flags & FLAG_HAS_DICT else b""
    if n == 0:
        return {"keys": np.zeros((0, 0), np.uint8),
                "val_lens": np.zeros(0, np.int64),
                "val_buf": np.zeros(0, np.uint8), "tombstones": tombs,
                "dict_len": len(vdict)}

    # ---- keys: undo the front coding, row j of every group at once -----
    meta = np.frombuffer(sec[b"tpulsm.zt.k.meta"],
                         "<u2" if flags & FLAG_META16 else np.uint8)
    plen = meta[0::2].astype(np.int64)
    slen = meta[1::2].astype(np.int64)
    if len(plen) != n:
        raise Unreadable("key meta of another row count")
    K = int(plen[0] + slen[0])
    if ((plen + slen) != K).any() or (plen[0::G] != 0).any():
        raise Unreadable("keys of more than one width")
    sfx = np.frombuffer(sec[b"tpulsm.zt.k.sfx"] + b"\0" * K, np.uint8)
    soff = np.cumsum(slen) - slen
    heads = np.frombuffer(sec[b"index"], "<u4").astype(np.int64)
    if len(heads) != -(-n // G) or (soff[0::G] != heads).any():
        raise Unreadable("group heads are not where the index says")
    col = np.arange(K, dtype=np.int64)
    keys = np.empty((n, K), np.uint8)
    prev = None
    for j in range(G):
        rows = np.arange(j, n, G)
        if not len(rows):
            break
        p = plen[rows][:, None]
        src = soff[rows][:, None] + col[None, :] - p
        got = sfx[np.maximum(src, 0)]
        if j:
            got = np.where(col[None, :] < p, prev[:len(rows)], got)
        keys[rows] = got
        prev = got

    # ---- values: group after group --------------------------------------
    vlens = np.frombuffer(sec[b"tpulsm.zt.v.lens"],
                          "<u4" if flags & FLAG_LENS32 else "<u2"
                          ).astype(np.int64)
    if len(vlens) != n:
        raise Unreadable("value lengths of another row count")
    go = np.frombuffer(sec[b"tpulsm.zt.v.go"], "<u4").astype(np.int64)
    vflags = np.frombuffer(sec[b"tpulsm.zt.v.flags"], np.uint8)
    blob = sec[b"tpulsm.zt.v.blob"]
    n_groups = -(-n // VG)
    if len(go) != n_groups + 1:
        raise Unreadable("value directory of another group count")
    raw_len = np.add.reduceat(vlens, np.arange(0, n, VG))
    dctx = zstandard.ZstdDecompressor(
        dict_data=zstandard.ZstdCompressionDict(vdict)) if vdict \
        else zstandard.ZstdDecompressor()
    parts = []
    for g in range(n_groups):
        payload = blob[go[g]:go[g + 1]]
        if vflags[g >> 3] >> (g & 7) & 1:
            payload = dctx.decompress(payload,
                                      max_output_size=int(raw_len[g]) or 1)
        if len(payload) != raw_len[g]:
            raise Unreadable(f"value group {g} of another size")
        parts.append(payload)
    return {"keys": keys, "val_lens": vlens,
            "val_buf": np.frombuffer(b"".join(parts), np.uint8),
            "tombstones": tombs, "dict_len": len(vdict)}


def read_rows(path: str) -> tuple[np.ndarray, np.ndarray]:
    """Every row of one ZipTable in file order, as `sst_plain.read_rows`
    gives a block table's: ([m, K] uint8 internal keys, [m, V] uint8
    values). Values of more than one width are `Unreadable` here."""
    t = read_table(path)
    n = len(t["keys"])
    if n == 0:
        return np.zeros((0, 0), np.uint8), np.zeros((0, 0), np.uint8)
    V = int(t["val_lens"][0])
    if (t["val_lens"] != V).any():
        raise Unreadable("values of more than one width")
    return t["keys"], t["val_buf"].reshape(n, V)


def is_zip_table(path: str) -> bool:
    with open(path, "rb") as f:
        f.seek(-8, 2)
        return int.from_bytes(f.read(8), "little") == MAGIC
