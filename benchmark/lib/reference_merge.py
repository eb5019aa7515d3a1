"""The plain reference of the merge deployment: what a reader must see, and
what one compaction must leave. numpy, the standard library and `sst_plain`
only; nothing of the package.

(a) `Oracle`: after writes 0..w of `workload_merge.MergeWorkload`, the exact
8 bytes of every key: the counter its `Put` of the load left, plus every
operand merged since, mod 2^64 — counting only what was written after the
newest `DeleteRange` that covers the key. A key whose newest write is such
a `DeleteRange` is absent.

(b) `survivors`: the rows one compaction leaves, with uint64-add operands
and range tombstones. Rows of one user key, newest first, fall into
stripes (rows that no snapshot tells apart); a range tombstone kills the
older rows of its own stripe that it covers. Of each stripe only what its
newest row stands for survives: a `Put` or a `Delete` as it is (a `Delete`
with nothing beneath it, at the bottommost level and under every snapshot,
goes); a `Merge` as the sum of the live operands from it downwards, onto
the live `Put` that ends the run if one does. The sum is a `Put` when the
run ended on a row of the stripe, or at the end of the key at the
bottommost level; else it stays one `Merge` operand. It keeps the newest
operand's sequence; a `Put` under every snapshot at the bottommost level
has its sequence zeroed.
"""

from __future__ import annotations

import json
import os

import numpy as np

from . import sst_plain
from .workload_merge import KEY_BYTES, KEY_TAIL, key_bytes

DELETE, PUT, MERGE, RANGE_DELETE = 0, 1, 2, 15
RANGE_DEL_BLOCK = b"tpulsm.range_del"
_BIG = np.iinfo(np.int64).max


# ---------------------------------------------------------------- (a) ----

class Oracle:
    """The state after `n_writes` writes and every DeleteRange due by then.
    Time: write w happens at 2w + 1, tombstone i at 2 * tomb_at[i]."""

    def __init__(self, wl, n_writes: int):
        n = wl.n
        loaded = min(n, n_writes)
        put_time = np.zeros(n, dtype=np.int64)
        base = np.zeros(n, dtype=np.uint64)
        k = wl.key_of[:loaded].astype(np.int64)
        put_time[k] = 2 * np.arange(loaded, dtype=np.int64) + 1
        base[k] = wl.numbers(0, loaded)
        tomb_time = np.zeros(n, dtype=np.int64)
        issued = int(np.searchsorted(wl.tomb_at, n_writes, side="right"))
        for at, lo in zip(wl.tomb_at[:issued], wl.tomb_lo[:issued]):
            tomb_time[int(lo):int(lo) + wl.width] = 2 * int(at)
        reset = np.maximum(put_time, tomb_time)
        has_base = put_time > tomb_time
        ok = wl.key_of[n:n_writes].astype(np.int64)
        live = 2 * np.arange(n, n_writes, dtype=np.int64) + 1 > reset[ok]
        total = np.where(has_base, base, np.uint64(0))
        np.add.at(total, ok[live], wl.numbers(n, n_writes)[live])
        merged = np.zeros(n, dtype=bool)
        merged[ok[live]] = True
        self.n = n
        self.present = has_base | merged
        self.value = total
        self.tomb_lo = wl.tomb_lo[:issued]

    def expected(self, keys) -> list:
        """Oracle answers for key numbers: 8 value bytes, or None."""
        keys = np.asarray(keys, dtype=np.uint64)
        inside = keys < np.uint64(self.n)
        k = np.where(inside, keys, 0).astype(np.int64)
        there = inside & self.present[k]
        blob = self.value[k].astype("<u8").tobytes()
        return [blob[8 * i:8 * i + 8] if ok else None
                for i, ok in enumerate(there)]


# ---------------------------------------------------------------- (b) ----

def survivors(ukey, seq, vtype, val, tombs, snapshots, bottommost: bool):
    """The output rows of one compaction, in output order. `ukey`, `seq`,
    `val` uint64 and `vtype` uint8 a row; `tombs` = (seq, lo, hi) uint64
    arrays of the range tombstones [lo, hi) of the inputs."""
    order = np.lexsort((np.iinfo(np.uint64).max - seq, ukey))
    ukey, seq, vtype, val = ukey[order], seq[order], vtype[order], val[order]
    n = len(ukey)
    if n == 0:
        return ukey, seq, vtype, val
    snaps = np.sort(np.asarray(snapshots, dtype=np.uint64))
    stripe = np.searchsorted(snaps, seq, side="left")

    dead = np.zeros(n, dtype=bool)  # killed by a range tombstone
    t_stripe = np.searchsorted(snaps, tombs[0], side="left")
    a = np.searchsorted(ukey, tombs[1], side="left")
    b = np.searchsorted(ukey, tombs[2], side="left")
    for i in np.flatnonzero(b > a):
        sl = slice(int(a[i]), int(b[i]))
        dead[sl] |= (seq[sl] < tombs[0][i]) & (stripe[sl] == t_stripe[i])

    first = np.ones(n, dtype=bool)  # first row of a (key, stripe) run
    first[1:] = (ukey[1:] != ukey[:-1]) | (stripe[1:] != stripe[:-1])
    start = np.flatnonzero(first)
    end = np.append(start[1:], n)
    key_ends = np.ones(len(start), dtype=bool)  # the key's oldest stripe
    key_ends[:-1] = ukey[start[1:]] != ukey[start[:-1]]

    # The run of live operands from the stripe's newest row downwards.
    operand = (vtype == MERGE) & ~dead
    stop = np.minimum(np.minimum.reduceat(
        np.where(operand, _BIG, np.arange(n)), start), end)
    csum = np.concatenate([[np.uint64(0)], np.cumsum(val)])  # wraps
    total = csum[stop] - csum[start]
    ended = stop < end
    s = np.minimum(stop, n - 1)
    on_put = ended & (vtype[s] == PUT) & ~dead[s]
    total = total + np.where(on_put, val[s], np.uint64(0))

    t0 = vtype[start]
    under_all = bool(bottommost) & (stripe[start] == 0)
    merges = operand[start]
    out_type = np.where(merges & (ended | (key_ends & bool(bottommost))),
                        PUT, t0).astype(np.uint8)
    keep = ~dead[start] & ((t0 == PUT) | merges
                           | ((t0 == DELETE) & ~under_all))
    out_val = np.where(merges, total, val[start])
    out_seq = np.where((out_type == PUT) & under_all, np.uint64(0),
                       seq[start])
    return (ukey[start][keep], out_seq[keep], out_type[keep],
            out_val[keep])


# ------------------------------------------------- reading the SSTs ------

def split_rows(ikeys: np.ndarray, vals: np.ndarray):
    """[m, 24] internal keys and [m, 8] values -> (key number, sequence,
    type, value number, rows whose key is not number + '0' * 8)."""
    if ikeys.shape[1] != KEY_BYTES + 8 or vals.shape[1] != 8:
        raise sst_plain.Unreadable(
            f"rows of {ikeys.shape[1]}+{vals.shape[1]} bytes, not 24+8")
    ukey = ikeys[:, :8].copy().view(">u8").reshape(-1).astype(np.uint64)
    odd = int((ikeys[:, 8:KEY_BYTES] != np.frombuffer(
        KEY_TAIL, np.uint8)).any(axis=1).sum())
    trailer = ikeys[:, KEY_BYTES:].copy().view("<u8").reshape(-1)
    return (ukey, trailer >> np.uint64(8),
            (trailer & np.uint64(0xFF)).astype(np.uint8),
            vals.copy().view("<u8").reshape(-1), odd)


def _entries(block: bytes):
    """(key, value) of every entry of one plain block."""
    n_restarts = int.from_bytes(block[-4:], "little")
    end = len(block) - 4 - 4 * n_restarts
    off, key = 0, b""
    while off < end:
        shared, off = sst_plain._varint(block, off)
        non_shared, off = sst_plain._varint(block, off)
        vlen, off = sst_plain._varint(block, off)
        key = key[:shared] + block[off:off + non_shared]
        off += non_shared
        yield key, block[off:off + vlen]
        off += vlen


def range_tombstones(path: str) -> list:
    """(sequence, begin key, end key) of the range tombstones of one SST:
    the meta block `tpulsm.range_del` (begin internal key -> end key),
    found through the metaindex, the footer's first handle."""
    with open(path, "rb") as f:
        data = f.read()
    foot = data[-sst_plain.FOOTER_LEN:]
    m_off, off = sst_plain._varint(foot, 1)
    m_size, _ = sst_plain._varint(foot, off)
    for name, handle in _entries(sst_plain._payload(data, m_off, m_size)):
        if name == RANGE_DEL_BLOCK:
            r_off, p = sst_plain._varint(handle, 0)
            r_size, _ = sst_plain._varint(handle, p)
            out = []
            for ikey, end in _entries(
                    sst_plain._payload(data, r_off, r_size)):
                trailer = int.from_bytes(ikey[-8:], "little")
                if trailer & 0xFF != RANGE_DELETE:
                    raise sst_plain.Unreadable("range_del entry type")
                out.append((trailer >> 8, ikey[:-8], end))
            return out
    return []


def _number(key: bytes) -> int:
    if len(key) != KEY_BYTES or key[8:] != KEY_TAIL:
        raise sst_plain.Unreadable(f"a tombstone bound of another shape: "
                                   f"{key!r}")
    return int.from_bytes(key[:8], "big")


def read_job_side(paths):
    """All rows and range tombstones of a list of SSTs."""
    cols, odd, tombs = [], 0, []
    for p in paths:
        k, v = sst_plain.read_rows(p)
        if len(k):
            *c, o = split_rows(k, v)
            cols.append(c)
            odd += o
        tombs += range_tombstones(p)
    rows = tuple(np.concatenate([c[i] for c in cols]) if cols
                 else np.zeros(0, np.uint8 if i == 2 else np.uint64)
                 for i in range(4))
    t = (np.array([s for s, _, _ in tombs], dtype=np.uint64),
         np.array([_number(b) for _, b, _ in tombs], dtype=np.uint64),
         np.array([_number(e) for _, _, e in tombs], dtype=np.uint64))
    return rows, t, odd


def rows_wrong(expected, got) -> int:
    """Rows of `got` that differ from `expected` (both (key, seq, type,
    value) in output order), missing and extra rows included."""
    n = min(len(expected[0]), len(got[0]))
    wrong = abs(len(expected[0]) - len(got[0]))
    if n:
        same = np.ones(n, dtype=bool)
        for e, g in zip(expected, got):
            same &= e[:n] == g[:n]
        wrong += int((~same).sum())
    return wrong


def compare_job(job_dir: str) -> dict:
    """Counts for one finished job dir (params.json, results.json, out/):
    rows of the output that differ from the reference's survivors of the
    inputs, rows of another key shape, and records misreported."""
    with open(os.path.join(job_dir, "params.json")) as f:
        params = json.load(f)
    with open(os.path.join(job_dir, "results.json")) as f:
        results = json.load(f)
    rows, tombs, odd = read_job_side(params["input_files"])
    want = survivors(*rows, tombs, params["snapshots"],
                     bool(params["bottommost"]))
    outs = [os.path.join(job_dir, "out", d["path"])
            for d in results["output_files"]]
    got, _out_tombs, odd_out = read_job_side(outs)
    return {
        "rows_in": int(len(rows[0])), "rows_out": int(len(got[0])),
        "rows_expected": int(len(want[0])),
        "operand_rows_in": int((rows[2] == MERGE).sum()),
        "tombstones_in": int(len(tombs[0])),
        "rows_wrong": rows_wrong(want, got) + odd + odd_out,
        "records_misreported": int(
            results["stats"]["input_records"] != len(rows[0]))
        + int(results["stats"]["output_records"] != len(got[0])),
    }


__all__ = ["Oracle", "survivors", "compare_job", "read_job_side",
           "rows_wrong", "range_tombstones", "split_rows", "key_bytes"]
