"""The plain reference of the YCSB deployment: its generators, its records,
what a reader must see, and what one compaction must leave. numpy, the
standard library, `sst_plain` and `reference`; nothing of the package.

(a) YCSB's own generators, from the constants its source states
(`site.ycsb.Utils.fnvhash64`, `ZipfianGenerator`,
`ScrambledZipfianGenerator`): record n's key is "user" + the decimal
fnvhash64(n) (`insertorder=hashed`, `zeropadding=1`); an operation's record
is a Zipfian draw over 10,000,000,000 items at theta 0.99, hashed again and
taken modulo the record count.

(b) The record: fields field0..field9 of 100 bytes, serialised as the
RocksDB binding does (`RocksDBClient.serializeValues`: a 4-byte big-endian
name length, the name, a 4-byte big-endian value length, the value), 1,140
bytes. A field's value is an 8-byte little-endian stamp, the number of the
write that left it (record n's load is write n + 1; operation j of the
stream, write recordcount + 1 + j), then 92 seeded bytes picked by the
stamp and the field, so that a record's bytes follow from its ten stamps.

(c) `Oracle`: the ten stamps of a record before any operation of the
stream, from the stream's applied updates alone (no 2.3 GB of records).

(d) `compare_job`: one compaction's output against `reference.py`'s
survivors (puts only, no snapshot, bytewise keys), read by a plain reader
of block tables whose keys differ in length (`read_rows`: `sst_plain`'s
framing, LZ4 blocks through the system's liblz4).
"""

from __future__ import annotations

import ctypes
import ctypes.util
import json
import os

import numpy as np

from . import sst_plain
from .reference import expected_output

# ---------------------------------------------------------------- (a) ----

FNV_OFFSET = 0xCBF29CE484222325
FNV_PRIME = 1099511628211
ZIPF_ITEMS = 10_000_000_000          # ScrambledZipfianGenerator.ITEM_COUNT
ZIPF_THETA = 0.99                    # ZipfianGenerator.ZIPFIAN_CONSTANT
ZIPF_ZETAN = 26.46902820178302       # ScrambledZipfianGenerator.ZETAN
READ, UPDATE = 0, 1


def fnvhash64(v):
    """Utils.fnvhash64 over a uint64 array (or one int): FNV-1a over the
    eight low-order bytes, wrapping at 64 bits, then the absolute value of
    the signed result."""
    one = np.isscalar(v)
    v = np.atleast_1d(np.asarray(v, dtype=np.uint64)).copy()
    h = np.full(v.shape, FNV_OFFSET, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= np.uint64(FNV_PRIME)
            v >>= np.uint64(8)
    s = h.view(np.int64)
    if np.any(s == np.iinfo(np.int64).min):  # Math.abs would keep it < 0
        raise ValueError("fnvhash64 met Long.MIN_VALUE")
    out = np.abs(s).astype(np.uint64)
    return int(out[0]) if one else out


def user_keys(n: int) -> list[bytes]:
    """The keys of records 0..n-1, in record order."""
    return [b"user%d" % h for h in fnvhash64(np.arange(n)).tolist()]


def zipf_items(u: np.ndarray) -> np.ndarray:
    """ZipfianGenerator(0, ITEM_COUNT - 1, 0.99, ZETAN).nextValue for the
    uniform draws `u` in [0, 1)."""
    alpha = 1.0 / (1.0 - ZIPF_THETA)
    zeta2 = 1.0 + 0.5 ** ZIPF_THETA
    eta = (1.0 - (2.0 / ZIPF_ITEMS) ** (1.0 - ZIPF_THETA)) / (
        1.0 - zeta2 / ZIPF_ZETAN)
    uz = u * ZIPF_ZETAN
    ret = np.floor(ZIPF_ITEMS * np.power(eta * u - eta + 1.0, alpha))
    ret = np.where(uz < 1.0 + 0.5 ** ZIPF_THETA, 1.0, ret)
    ret = np.where(uz < 1.0, 0.0, ret)
    return ret.astype(np.uint64)


def scrambled_zipf(u: np.ndarray, recordcount: int) -> np.ndarray:
    """ScrambledZipfianGenerator(0, recordcount - 1).nextValue: the record
    index of each draw."""
    return (fnvhash64(zipf_items(u)) % np.uint64(recordcount)).astype(
        np.int64)


def hottest_share_bounds(m: int, recordcount: int, sigmas: float = 5.0):
    """Where the share of the most drawn record lies in m draws: item 0
    alone is drawn with probability 1/zetan; the record it hashes to may
    also be met by other items, at most ~m/recordcount of them more."""
    p = 1.0 / ZIPF_ZETAN
    sd = (p * (1 - p) / m) ** 0.5
    return p - sigmas * sd, p + sigmas * sd + 20.0 / recordcount + 1e-4


class Stream:
    """The operations of one run, drawn from the seed: kind (READ or
    UPDATE, half each), record index (scrambled Zipfian), the field an
    update writes (uniform), and each update's 100-byte value."""

    def __init__(self, recordcount: int, n_ops: int, seed: int):
        rng = np.random.default_rng([seed, 42])
        self.n = recordcount
        self.kind = (rng.random(n_ops) < 0.5).astype(np.int8)  # 1: UPDATE
        self.record = scrambled_zipf(rng.random(n_ops), recordcount)
        self.field = rng.integers(0, FIELDS, n_ops).astype(np.int8)
        self.fill = filler_table(seed)

    def __len__(self):
        return len(self.kind)

    def stamp(self, op) -> np.ndarray:
        return np.asarray(op, dtype=np.int64) + self.n + 1

    def update_values(self, lo: int, hi: int) -> bytes:
        """The values of the updates among operations lo..hi-1, in order,
        100 bytes each."""
        ops = lo + np.flatnonzero(self.kind[lo:hi] == UPDATE)
        return field_values(self.stamp(ops), self.field[ops],
                            self.fill).tobytes()


# ---------------------------------------------------------------- (b) ----

FIELDS = 10
FIELD_BYTES = 100
STAMP_BYTES = 8
_NAME = b"field"
FIELD_HEAD = 4 + len(_NAME) + 1 + 4          # name length, name, value length
FIELD_SPAN = FIELD_HEAD + FIELD_BYTES
RECORD_BYTES = FIELDS * FIELD_SPAN            # 1,140
FILLERS = 64


def field_offset(f: int) -> int:
    """Where field f's value starts in a record."""
    return f * FIELD_SPAN + FIELD_HEAD


def filler_table(seed: int) -> np.ndarray:
    """[FILLERS, 92] seeded bytes; a value stamped s in field f carries row
    (s + f) % FILLERS."""
    return np.random.default_rng([seed, 17]).integers(
        0, 256, (FILLERS, FIELD_BYTES - STAMP_BYTES), dtype=np.uint8)


def field_values(stamps, fields, fill) -> np.ndarray:
    """[m, 100] uint8: each value's stamp, then its filler row."""
    stamps = np.asarray(stamps, dtype=np.int64)
    out = np.empty((len(stamps), FIELD_BYTES), dtype=np.uint8)
    out[:, :STAMP_BYTES] = stamps.astype("<u8").view(np.uint8).reshape(
        -1, STAMP_BYTES)
    out[:, STAMP_BYTES:] = fill[(stamps + np.asarray(fields)) % FILLERS]
    return out


def _head() -> np.ndarray:
    row = np.zeros(RECORD_BYTES, dtype=np.uint8)
    for f in range(FIELDS):
        name = _NAME + str(f).encode()
        h = (len(name).to_bytes(4, "big") + name
             + FIELD_BYTES.to_bytes(4, "big"))
        o = f * FIELD_SPAN
        row[o:o + FIELD_HEAD] = np.frombuffer(h, np.uint8)
    return row


HEAD = _head()


def record_bytes(stamps: np.ndarray, fill) -> np.ndarray:
    """[m, 1140] uint8: the records whose fields carry [m, 10] stamps."""
    stamps = np.asarray(stamps, dtype=np.int64)
    m = len(stamps)
    out = np.broadcast_to(HEAD, (m, RECORD_BYTES)).copy()
    for f in range(FIELDS):
        o = field_offset(f)
        out[:, o:o + FIELD_BYTES] = field_values(stamps[:, f],
                                                 np.full(m, f), fill)
    return out


def stamps_of(recs: np.ndarray) -> np.ndarray:
    """[m, 10] stamps read out of [m, 1140] records."""
    cols = [recs[:, field_offset(f):field_offset(f) + STAMP_BYTES]
            for f in range(FIELDS)]
    return np.stack([np.ascontiguousarray(c).view("<u8").reshape(-1)
                     for c in cols], axis=1).astype(np.int64)


# ---------------------------------------------------------------- (c) ----

class Oracle:
    """The stamps of every record before any operation of the stream,
    from the updates that were applied (`not_applied`: updates that found
    no record and wrote nothing)."""

    def __init__(self, stream: Stream, n_ops: int, not_applied=()):
        ops = np.flatnonzero(stream.kind[:n_ops] == UPDATE)
        if len(not_applied):
            ops = np.setdiff1d(ops, np.asarray(not_applied, np.int64))
        code = stream.record[ops] * FIELDS + stream.field[ops]
        enc = (code.astype(np.int64) << 32) | ops.astype(np.int64)
        self.enc = np.sort(enc)
        self.stream = stream
        self.n_ops = n_ops

    def stamps_before(self, records, ops) -> np.ndarray:
        """[m, 10]: the stamps record[i] holds when operation ops[i] runs
        (the load's stamp where no applied update came before it)."""
        records = np.asarray(records, dtype=np.int64)
        ops = np.asarray(ops, dtype=np.int64)
        out = np.empty((len(records), FIELDS), dtype=np.int64)
        for f in range(FIELDS):
            code = records * FIELDS + f
            q = (code << 32) | ops
            i = np.searchsorted(self.enc, q, side="left") - 1
            ok = i >= 0
            hit = ok & ((self.enc[np.maximum(i, 0)] >> 32) == code)
            last = self.enc[np.maximum(i, 0)] & 0xFFFFFFFF
            out[:, f] = np.where(hit, self.stream.stamp(last), records + 1)
        return out

    def final(self, records) -> np.ndarray:
        return self.stamps_before(records, np.full(len(records), self.n_ops))

    def expected(self, records, ops=None) -> np.ndarray:
        """[m, 1140] records as a reader must see them."""
        st = (self.final(records) if ops is None
              else self.stamps_before(records, ops))
        return record_bytes(st, self.stream.fill)


def mismatches(got: list, want: np.ndarray) -> int:
    """How many of the answers `got` (bytes or None) differ from the rows
    of `want` ([m, 1140]; a row of None where no record must be)."""
    bad = 0
    full = [i for i, g in enumerate(got) if g is not None
            and len(g) == RECORD_BYTES and want[i] is not None]
    bad += len(got) - len(full) - sum(
        1 for i, g in enumerate(got) if g is None and want[i] is None)
    if full:
        mat = np.frombuffer(b"".join(got[i] for i in full),
                            np.uint8).reshape(len(full), RECORD_BYTES)
        bad += int((mat != np.stack([want[i] for i in full])).any(
            axis=1).sum())
    return bad


# ---------------------------------------------------------------- (d) ----

_lz4 = None


def _liblz4():
    global _lz4
    if _lz4 is None:
        lib = ctypes.CDLL(ctypes.util.find_library("lz4") or "liblz4.so.1")
        lib.LZ4_decompress_safe.restype = ctypes.c_int
        lib.LZ4_decompress_safe.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int]
        _lz4 = lib
    return _lz4


def _payload(data: bytes, offset: int, size: int) -> bytes:
    """A block's payload: raw and snappy as `sst_plain` reads them; LZ4
    (types 4, 5) as the format frames it: varint32 raw length, then one
    LZ4 block."""
    ctype = data[offset + size]
    if ctype not in (4, 5):
        return sst_plain._payload(data, offset, size)
    raw_len, p = sst_plain._varint(data, offset)
    out = ctypes.create_string_buffer(max(1, raw_len))
    got = _liblz4().LZ4_decompress_safe(
        data[p:offset + size], out, offset + size - p, raw_len)
    if got != raw_len:
        raise sst_plain.Unreadable("LZ4 payload")
    return out.raw[:raw_len]


def block_handles(data: bytes) -> list[tuple[int, int]]:
    """`sst_plain.block_handles`, with the index block read by `_payload`
    (an LZ4 file compresses its index block too)."""
    foot = data[-sst_plain.FOOTER_LEN:]
    if len(data) < sst_plain.FOOTER_LEN or int.from_bytes(
            foot[-8:], "little") != sst_plain.MAGIC:
        raise sst_plain.Unreadable("not a block-table SST")
    _, off = sst_plain._varint(foot, 1)
    _, off = sst_plain._varint(foot, off)
    i_off, off = sst_plain._varint(foot, off)
    i_size, _ = sst_plain._varint(foot, off)
    index = _payload(data, i_off, i_size)
    end = len(index) - 4 - 4 * int.from_bytes(index[-4:], "little")
    handles, off = [], 0
    while off < end:
        _shared, off = sst_plain._varint(index, off)
        non_shared, off = sst_plain._varint(index, off)
        vlen, off = sst_plain._varint(index, off)
        h_off, p = sst_plain._varint(index, off + non_shared)
        h_size, _ = sst_plain._varint(index, p)
        handles.append((h_off, h_size))
        off += non_shared + vlen
    return handles


KEY_WIDTH = 40       # internal keys up to 32 bytes of user key


def read_rows(path: str):
    """Every row of one block-table SST whose keys differ in length and
    whose values have one length: ([m, KEY_WIDTH] internal keys zero-padded,
    [m] their lengths, [m, V] values), in file order. Entry k of every
    block is decoded at once (a block holds a few 1 KB records), so a file
    is a handful of numpy steps."""
    with open(path, "rb") as f:
        data = f.read()
    handles = block_handles(data)
    if not handles:
        z = np.zeros(0, np.int64)
        return np.zeros((0, KEY_WIDTH), np.uint8), z, np.zeros((0, 0),
                                                               np.uint8)
    parts = [_payload(data, o, s) for o, s in handles]
    lens = np.fromiter((len(p) for p in parts), np.int64, len(parts))
    base = np.concatenate([[0], np.cumsum(lens)[:-1]])
    buf = np.frombuffer(b"".join(parts) + b"\0" * 4096, dtype=np.uint8)
    last4 = base + lens - 4
    n_restarts = (buf[last4].astype(np.int64)
                  | buf[last4 + 1].astype(np.int64) << 8
                  | buf[last4 + 2].astype(np.int64) << 16
                  | buf[last4 + 3].astype(np.int64) << 24)
    limit = base + lens - 4 - 4 * n_restarts
    kcol = np.arange(KEY_WIDTH, dtype=np.int64)
    pos = base.copy()
    prev = np.zeros((len(parts), KEY_WIDTH), np.uint8)
    keys, klens, vals, alive = [], [], [], []
    vlen0 = None
    while True:
        live = pos < limit
        if not live.any():
            break
        p = np.where(live, pos, base)
        shared = buf[p].astype(np.int64)
        non_shared = buf[p + 1].astype(np.int64)
        b0, b1, b2 = (buf[p + 2].astype(np.int64), buf[p + 3].astype(
            np.int64), buf[p + 4].astype(np.int64))
        one, two = b0 < 0x80, (b0 >= 0x80) & (b1 < 0x80)
        three = (b0 >= 0x80) & (b1 >= 0x80) & (b2 < 0x80)
        vlen = np.where(one, b0, np.where(two, (b0 & 0x7F) | b1 << 7,
                                          (b0 & 0x7F) | (b1 & 0x7F) << 7
                                          | b2 << 14))
        hdr = 2 + np.where(one, 1, np.where(two, 2, 3))
        if ((shared >= 0x80) | (non_shared >= 0x80) | ~(one | two | three)
                | (shared + non_shared > KEY_WIDTH))[live].any():
            raise sst_plain.Unreadable("an entry this reader does not take")
        v = vlen[live]
        if vlen0 is None:
            vlen0 = int(v[0])
        if (v != vlen0).any():
            raise sst_plain.Unreadable("values of more than one length")
        # Rows of the buffer as windows: a gather of whole rows, not of an
        # index a byte.
        klen = shared + non_shared
        src = np.maximum(p + hdr - shared, 0)
        key = np.where(kcol[None, :] < shared[:, None], prev,
                       _windows(buf, KEY_WIDTH)[src])
        key = np.where(kcol[None, :] < klen[:, None], key, 0).astype(np.uint8)
        vstart = p + hdr + non_shared
        val = _windows(buf, vlen0)[vstart]
        keys.append(key)
        klens.append(klen)
        vals.append(val)
        alive.append(live)
        prev = key
        pos = np.where(live, vstart + vlen0, pos)
    if (pos != limit).any():
        raise sst_plain.Unreadable("a block's entries overrun its restarts")
    alive = np.stack(alive, axis=1)
    return (np.stack(keys, axis=1)[alive], np.stack(klens, axis=1)[alive],
            np.stack(vals, axis=1)[alive])


def _windows(buf: np.ndarray, width: int) -> np.ndarray:
    """[len - width + 1, width] read-only view: row i is buf[i:i + width]."""
    return np.lib.stride_tricks.sliding_window_view(buf, width)


def split_internal(ikeys: np.ndarray, klens: np.ndarray):
    """(user keys as an 'S32' array, sequence u64, type u8). A user key
    holds no zero byte here (YCSB's are "user" and digits), so the 'S'
    order is the bytewise one."""
    m = len(klens)
    idx = (klens - 8)[:, None] + np.arange(8)[None, :]
    trailer = np.take_along_axis(ikeys, idx, axis=1).copy().view(
        "<u8").reshape(m)
    uk = np.where(np.arange(KEY_WIDTH)[None, :] < (klens - 8)[:, None],
                  ikeys, 0).astype(np.uint8)
    ukeys = np.ascontiguousarray(uk[:, :32]).view("S32").reshape(m)
    return ukeys, trailer >> np.uint64(8), (trailer & np.uint64(0xFF)).astype(
        np.uint8)


def _read_all(paths):
    ks, ls, vs = [], [], []
    for path in paths:
        k, ln, v = read_rows(path)
        if len(k):
            ks.append(k)
            ls.append(ln)
            vs.append(v)
    if not ks:
        z = np.zeros(0, np.uint64)
        return (np.zeros(0, "S32"), z, np.zeros(0, np.uint8),
                np.zeros((0, RECORD_BYTES), np.uint8))
    return split_internal(np.concatenate(ks), np.concatenate(ls)) + (
        np.concatenate(vs),)


def compare_job(job_dir: str) -> dict:
    """Counts for one finished job dir (params.json, results.json, out/):
    output rows that differ from `reference.expected_output`'s survivors
    (missing and extra rows included), and what the reply said of its rows
    against what the files hold."""
    with open(os.path.join(job_dir, "params.json")) as f:
        params = json.load(f)
    with open(os.path.join(job_dir, "results.json")) as f:
        results = json.load(f)
    iu, iseq, ity, ival = _read_all(params["input_files"])
    eu, eseq, ety, eval_ = expected_output(iu, iseq, ity, ival,
                                           bool(params["bottommost"]))
    ou, oseq, oty, oval = _read_all(
        [os.path.join(job_dir, "out", d["path"])
         for d in results["output_files"]])
    n = min(len(eu), len(ou))
    wrong = abs(len(eu) - len(ou))
    if n:
        same = ((eu[:n] == ou[:n]) & (eseq[:n] == oseq[:n])
                & (ety[:n] == oty[:n]) & (eval_[:n] == oval[:n]).all(axis=1))
        wrong += int((~same).sum())
    stats = results["stats"]
    return {
        "rows_in": int(len(iu)), "rows_out": int(len(ou)),
        "rows_expected": int(len(eu)), "rows_wrong": wrong,
        "varlen_shards": int(stats.get("varlen_shards", 0)),
        "records_misreported": int(stats["input_records"] != len(iu))
        + int(stats["output_records"] != len(ou)),
    }
