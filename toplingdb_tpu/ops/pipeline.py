"""Pipelined compaction data plane: overlap scan, merge/GC, and encode.

The serial columnar path (ops/device_compaction.py) is a three-phase
chain — scan every input SST into columnar buffers, one fused sort+GC
over the whole job, then encode+write the outputs — so its wall clock is
the SUM of the phases. This module restructures the same work as a
bounded three-stage pipeline at user-key-range shard granularity:

  reader   per input file, decode the blocks of one key-range shard per
           native call (windowed preads through a FilePrefetchBuffer),
           writing into a properties-sized preallocated ColumnarKV —
           independent files scan on parallel threads; a ZipTable or a
           SingleFastTable decodes the shard's ENTRY RANGE (no blocks):
           any reader that offers `entry_plane`, `split_candidates`,
           `entry_lower_bound`, `scan_native_ready` and `scan_into` is
           planned so, under the span `pipeline.<entry_plane>_scan`
  compute  as soon as EVERY file has scanned past shard s, run the
           device (uniform-shard upload + fused kernel) or host-twin
           (native k-way merge + GC) sort+GC over just that shard's rows
  writer   stream each shard's survivor order into the native block
           builder (write_tables_columnar's chunked-order mode) while
           later shards are still being scanned/computed

Key-range shards are cut at user-key boundaries (every version of a user
key lands in exactly one shard), so per-shard GC decisions — snapshot
stripes, tombstone shadowing, bottommost seqno zeroing — equal the
global ones and the concatenated survivor stream is byte-identical to
the serial path's; tests/test_compaction_pipeline.py asserts whole-file
SST equality. A job reads block files, ZipTables and SingleFastTables,
mixed freely, and writes any of the three. Jobs the pipeline does not
cover (plain, cuckoo or dict-compressed block inputs, missing properties,
jobs of one shard) raise PipelineIneligible and the caller falls back to
the serial path, which computes the same bytes.

MERGE operands and single-deletes stay on this plane: the compute stage
returns the rows of such "complex" user-key groups unreduced and flagged,
and a fold step between it and the writer resolves them a shard at a time
(`pipeline.merge_fold`; ops/device_compaction.py::fold_complex) — one
segmented reduction for an operator that declares a columnar fold
(uint64add), the per-group state machine for the others. Folded values
overwrite the chain's newest row in place or land in the slack at the end
of the value buffer, so the writer's hoisted pointers stay good.
"""

from __future__ import annotations

import ctypes
import threading

from toplingdb_tpu.utils import concurrency as ccy
import time
from queue import Empty, Full, Queue

import numpy as np

from toplingdb_tpu import native
from toplingdb_tpu.db import dbformat
from toplingdb_tpu.utils import telemetry
from toplingdb_tpu.utils.status import Corruption, NotSupported
from toplingdb_tpu.utils import errors as _errors


class PipelineIneligible(Exception):
    """Job shapes the pipeline does not cover; run the serial path."""


class _Done:
    pass


class _Err:
    def __init__(self, exc):
        self.exc = exc


_DONE = _Done()

# Reader-stage readahead: shard windows are MBs, so the prefetch buffer
# runs with a much larger ceiling than the per-iterator default.
_PF_READAHEAD = 8 << 20

_PU8 = ctypes.POINTER(ctypes.c_uint8)
_PI32 = ctypes.POINTER(ctypes.c_int32)


def pipeline_enabled(table_options=None) -> bool:
    """Whether the plane WRITES the job's output format: block tables and
    SingleFastTables (both stream a chunk at a time), and zip tables when
    the native zip data plane is on (write_tables_zip_columnar drains the
    chunk feed, then encodes); other formats consume whole arrays serially.
    What a job may READ is `_build_plan`'s to say: block files, ZipTables
    and SingleFastTables, mixed freely in one job; plain, cuckoo and
    dict-compressed block inputs leave the plane there."""
    f = getattr(table_options, "format", "block")
    if f == "zip":
        from toplingdb_tpu.table.zip_table import zip_plane_enabled

        return zip_plane_enabled()
    return f in ("block", "single_fast")


class _FilePlan:
    """Per-input-file scan plan: block handles grouped by shard, the
    file's slice of the preallocated global buffers, and the row bounds
    of each shard (filled in by the reader as decode progresses). A
    ZipTable or a SingleFastTable (`ranged`: the reader's `entry_plane`)
    has no blocks: it is planned by entry ranges, so its `groups` are
    entry ordinals and its row bounds are known with the plan."""

    __slots__ = ("reader", "pf", "block_offs", "block_lens", "groups",
                 "ne", "rk", "rv", "n_base", "k_base", "v_base",
                 "row_bounds", "verify", "ranged")


class _Progress:
    """Reader→compute coordination: per-file shard watermarks plus the
    first error; any failure stops every stage."""

    def __init__(self, n_files: int):
        self._done = [-1] * n_files
        self._cv = ccy.Condition("pipeline._Progress._cv")
        self.err: BaseException | None = None
        self.stop = False
        self.scan_end = 0.0

    def mark(self, fi: int, s: int) -> None:
        with self._cv:
            self._done[fi] = s
            self._cv.notify_all()

    def finish_file(self, fi: int) -> None:
        with self._cv:
            self.scan_end = max(self.scan_end, time.time())

    def fail(self, exc: BaseException) -> None:
        with self._cv:
            if self.err is None:
                self.err = exc
            self.stop = True
            self._cv.notify_all()

    def abort(self) -> None:
        with self._cv:
            self.stop = True
            self._cv.notify_all()

    def poll_shard(self, s: int) -> bool:
        with self._cv:
            if self.err is not None:
                raise self.err
            return min(self._done) >= s

    def wait_shard(self, s: int) -> None:
        with self._cv:
            while True:
                if self.err is not None:
                    raise self.err
                if self.stop:
                    raise PipelineIneligible("pipeline aborted")
                if min(self._done) >= s:
                    return
                self._cv.wait()


def _uk_at(kv, r: int) -> bytes:
    o = int(kv.key_offs[r])
    return kv.key_buf[o: o + int(kv.key_lens[r]) - 8].tobytes()


def _lower_bound(kv, lo: int, hi: int, key: bytes) -> int:
    """First row in [lo, hi) (internal-key sorted) with user key >= key."""
    while lo < hi:
        mid = (lo + hi) // 2
        if _uk_at(kv, mid) < key:
            lo = mid + 1
        else:
            hi = mid
    return lo


def _uniform_key_matrix(kv, lo: int, hi: int):
    """Rows [lo, hi) as an [n, key_len] view of the key buffer when they
    have one key length and lie densely (a file's rows do); else None."""
    if hi <= lo:
        return None
    lens = kv.key_lens[lo:hi]
    klen = int(lens[0])
    b0 = int(kv.key_offs[lo])
    if (int(lens.min()) != klen or int(lens.max()) != klen
            or int(kv.key_offs[hi - 1]) != b0 + (hi - 1 - lo) * klen):
        return None
    return kv.key_buf[b0:b0 + (hi - lo) * klen].reshape(hi - lo, klen)


def _lower_bounds(kv, lo: int, hi: int, keys: list[bytes],
                  packed: dict | None = None) -> np.ndarray:
    """_lower_bound of every user key of `keys` among rows [lo, hi), as
    offsets from lo. Rows of one key length are searched at once: their
    user keys, zero-padded to whole big-endian 8-byte words, sort as the
    keys do, so one searchsorted places every boundary; only a boundary
    whose padded words tie with a row's is bisected key by key. `packed`
    keeps the padded form of `keys` from one range to the next."""
    mat = _uniform_key_matrix(kv, lo, hi)
    if mat is None or not keys:
        return np.array([_lower_bound(kv, lo, hi, k) - lo for k in keys],
                        dtype=np.int64)
    n, ukl = hi - lo, mat.shape[1] - 8
    nw = max(1, (ukl + 7) // 8)
    dt = np.dtype([(f"w{i}", ">u8") for i in range(nw)])
    rows = np.zeros((n, nw * 8), dtype=np.uint8)
    rows[:, :ukl] = mat[:, :ukl]
    rows = rows.view(dt).reshape(n)
    kb = None if packed is None else packed.get(nw)
    if kb is None:
        kb = np.zeros((len(keys), nw * 8), dtype=np.uint8)
        for i, k in enumerate(keys):
            kb[i, :min(len(k), nw * 8)] = np.frombuffer(
                k[:nw * 8], dtype=np.uint8)
        kb = kb.view(dt).reshape(len(keys))
        if packed is not None:
            packed[nw] = kb
    left = np.searchsorted(rows, kb, side="left").astype(np.int64)
    right = np.searchsorted(rows, kb, side="right")
    for i in np.flatnonzero(right > left):
        if len(keys[i]) != ukl:  # equal words, equal length: equal keys
            left[i] = _lower_bound(kv, lo + int(left[i]), lo + int(right[i]),
                                   keys[i]) - lo
    return left


def _range_seq_vtype(kv, lo: int, hi: int):
    """(seq u64, vtype i32) for global rows [lo, hi) — generic trailer
    gather (the rows need not be a dense byte span)."""
    import sys

    mat = _uniform_key_matrix(kv, lo, hi)
    if mat is not None:
        trailer = np.ascontiguousarray(mat[:, -8:])  # a strided view
    else:
        offs = kv.key_offs[lo:hi].astype(np.int64)
        lens = kv.key_lens[lo:hi].astype(np.int64)
        tr_idx = (offs + lens - 8)[:, None] + np.arange(8)[None, :]
        trailer = np.ascontiguousarray(kv.key_buf[tr_idx])
    packed = trailer.view(np.uint64).reshape(hi - lo)
    if sys.byteorder == "big":
        packed = packed.byteswap()
    return packed >> np.uint64(8), \
        (packed & np.uint64(0xFF)).astype(np.int32)


def _build_plan(readers, value_slack: bool = False):
    """Validate prealloc eligibility, size the global buffers, pick the
    key-range splitters and each file's per-shard block groups. Returns
    (kv, files, splitters, (slack_lo, slack_hi)) or raises
    PipelineIneligible. With value_slack the value buffer is allocated
    with room behind the inputs' values for merge results (untouched pages
    cost nothing): as much again plus 16 B a row, within the int32
    budget.

    Inputs may be block files, ZipTables and SingleFastTables, in any mix.
    A block file is planned by block handles (its index separators are its
    splitter candidates); a reader that bounds and scans ENTRY RANGES (its
    `entry_plane` names it) by `[entry_lower_bound(splitter_i),
    entry_lower_bound(splitter_i+1))` a shard, `split_candidates` its
    candidates. All fill a slice of the preallocated buffers sized from
    their TableProperties. plain and cuckoo files, and block files
    compressed under a dictionary, are not planned: the job leaves.
    Each candidate stands for its file's rows a candidate (PR 32), so the
    shards stay even in ROWS whatever the formats' mix in a key range."""
    import bisect

    from toplingdb_tpu.ops import compaction_kernels as ck
    from toplingdb_tpu.ops.columnar_io import ColumnarKV
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.prefetch import FilePrefetchBuffer

    lib = native.lib()
    if lib is None or not hasattr(lib, "tpulsm_scan_blocks"):
        raise PipelineIneligible("native fused scan unavailable")
    infos = []
    tk = tv = tn = 0
    for r in readers:
        ranged = getattr(r, "entry_plane", None)
        if not ranged and not hasattr(r, "new_index_iterator"):
            raise PipelineIneligible("non-block input format")
        if getattr(r, "_compression_dict", b""):
            raise PipelineIneligible("dict-compressed input")
        p = getattr(r, "properties", None)
        if p is None:
            raise PipelineIneligible("input properties missing")
        ne, rk, rv = int(p.num_entries), int(p.raw_key_size), int(
            p.raw_value_size)
        if ne < 0 or rk < 0 or rv < 0 or (ne > 0 and rk == 0):
            raise PipelineIneligible("implausible input properties")
        if ranged:
            if ne != r.n:
                raise PipelineIneligible(ranged + " entries disagree with props")
            if ne and not r.scan_native_ready():
                raise PipelineIneligible(ranged + " scan plane unavailable")
            handles = None
            sep_uks = r.split_candidates(r.opts.block_size)
        else:
            idx = r.new_index_iterator()
            idx.seek_to_first()
            handles = []
            sep_uks = []
            for k, enc in idx.entries():
                handles.append(fmt.BlockHandle.decode_exact(enc))
                sep_uks.append(dbformat.extract_user_key(k))
            if ne and not handles:
                raise PipelineIneligible(
                    "entries claimed but no data blocks")
        infos.append((ne, rk, rv, handles, sep_uks))
        tk += rk
        tv += rv
        tn += ne
    if tk > 0x7FFFFF00 or tv > 0x7FFFFF00:
        raise PipelineIneligible("inputs exceed the int32 columnar budget")

    # Splitters: the per-file candidates (a block file's index separator
    # user keys, one a data block; an entry-ranged file's own), merged, each
    # standing for its file's rows a candidate, cut where the rows below
    # reach a quantile: shards even in ROWS, whatever the formats' mix in
    # a key range, because a shard past ROW_BUCKET rows meets a second
    # program. A job of one shard has nothing to overlap and is left to
    # the serial path.
    n_shards = ck.shard_count(tn)
    if n_shards < 2:
        raise PipelineIneligible("single-shard job")
    all_seps = sorted((uk, ne / len(uks))
                      for ne, _, _, _, uks in infos for uk in uks)
    # below[i]: the rows that candidates 0..i-1 stand for.
    below = np.cumsum([0.0] + [w for _, w in all_seps])
    splitters: list[bytes] = []
    for t in range(1, n_shards if all_seps else 1):
        at = int(np.searchsorted(below[:-1], below[-1] * t / n_shards,
                                 side="right")) - 1
        cand = all_seps[at][0]
        if not splitters or cand > splitters[-1]:
            splitters.append(cand)
    if not splitters:
        raise PipelineIneligible("inputs too uniform to shard")
    n_shards = len(splitters) + 1

    slack = min(tv + 16 * tn, 0x7FFFFF00 - tv) if value_slack else 0
    kv = ColumnarKV(
        np.empty(tk, dtype=np.uint8), np.empty(tn, dtype=np.int32),
        np.empty(tn, dtype=np.int32), np.empty(tv + slack, dtype=np.uint8),
        np.empty(tn, dtype=np.int32), np.empty(tn, dtype=np.int32),
    )

    files = []
    nb = kb = vb = 0
    for r, (ne, rk, rv, handles, sep_uks) in zip(readers, infos):
        if ne == 0:
            continue
        fp = _FilePlan()
        fp.reader = r
        fp.ranged = getattr(r, "entry_plane", None)
        fp.ne, fp.rk, fp.rv = ne, rk, rv
        fp.n_base, fp.k_base, fp.v_base = nb, kb, vb
        if fp.ranged:
            # Shard s is entries [groups[s], groups[s+1]): every version
            # of a user key sorts behind its seek key, so the bound of a
            # splitter is the first entry of that user key or a later one.
            fp.pf = fp.block_offs = fp.block_lens = None
            fp.groups = [0] + [
                r.entry_lower_bound(dbformat.make_internal_key(
                    spl, dbformat.MAX_SEQUENCE_NUMBER,
                    dbformat.VALUE_TYPE_FOR_SEEK))
                for spl in splitters] + [ne]
            fp.row_bounds = [nb + e for e in fp.groups]
            fp.verify = False  # the file was verified at open
        else:
            fp.pf = FilePrefetchBuffer(r._f, max_readahead=_PF_READAHEAD,
                                       initial_readahead=_PF_READAHEAD,
                                       arm_immediately=True)
            fp.block_offs = np.array([h.offset for h in handles],
                                     dtype=np.int64)
            fp.block_lens = np.array([h.size for h in handles],
                                     dtype=np.int64)
            # Shard s decodes blocks [groups[s], groups[s+1]); the group
            # ends at (inclusive) the first block whose separator user key
            # reaches the splitter — that block may straddle it, and its
            # tail rows belong to the next shard via the row-bound binary
            # search.
            g = [0]
            for spl in splitters:
                g.append(max(g[-1],
                             min(bisect.bisect_left(sep_uks, spl) + 1,
                                 len(handles))))
            g.append(len(handles))
            fp.groups = g
            fp.row_bounds = [nb] * n_shards + [nb + ne]
            fp.verify = bool(r.opts.verify_checksums)
        files.append(fp)
        nb += ne
        kb += rk
        vb += rv
    if not files:
        raise PipelineIneligible("no non-empty inputs")
    return kv, files, splitters, (tv, tv + slack)


def _scan_ranged_file(fi, fp, kv, prog, stats, stats_mu, trace_handle):
    """Reader worker of a file planned by entry ranges (a ZipTable, a
    SingleFastTable): decode one range a shard into the file's slice of
    the global buffers (the reader's `scan_into`: its native decoders).
    Each range is the span `pipeline.zip_scan` / `pipeline.sft_scan`;
    their wall sums into `zip_scan_usec` / `sft_scan_usec` (readers run
    side by side, a thread a file, so it is a sum over threads). The row
    bounds of every shard were fixed by the plan, so `mark` follows each
    range at once; the totals are held against the properties that sized
    the file's slice."""
    k_used = v_used = 0
    usec = 0
    for s in range(len(fp.groups) - 1):
        if prog.stop:
            return
        e0, e1 = fp.groups[s], fp.groups[s + 1]
        if e1 > e0:
            t0 = time.time()
            with telemetry.span_under(
                    trace_handle, f"pipeline.{fp.ranged}_scan",
                    file=fi, shard=s, rows=e1 - e0) as sp:
                # NotSupported when the range outgrows what the properties
                # left of the file's slice: the serial path takes the job.
                nk, nv = fp.reader.scan_into(
                    e0, e1, kv, fp.n_base + e0, fp.k_base + k_used,
                    fp.v_base + v_used, fp.rk - k_used, fp.rv - v_used)
                k_used += nk
                v_used += nv
                sp.tag(nbytes=nk + nv)
            usec += int((time.time() - t0) * 1e6)
        prog.mark(fi, s)
    if k_used != fp.rk or v_used != fp.rv:
        raise PipelineIneligible("scan totals disagree with props")
    with stats_mu:
        stats.count_input(fp.reader)
        stats.count_ranged_scan(fp.ranged, usec)
    prog.finish_file(fi)


def _scan_file(fi, fp, kv, prog, splitters, stats, stats_mu,
               trace_handle=None):
    """Reader worker: decode one file shard-by-shard into its slice of the
    global buffers, publishing row bounds + progress per shard."""
    lib = native.lib()
    n_shards = len(splitters) + 1
    try:
        if fp.ranged:
            _scan_ranged_file(fi, fp, kv, prog, stats, stats_mu, trace_handle)
            return
        rows = 0
        k_used = v_used = 0
        bound = 0  # file-local row bound of the current shard start
        for s in range(n_shards):
            if prog.stop:
                return
            blo, bhi = fp.groups[s], fp.groups[s + 1]
            # One span a file and shard that has blocks, entered here, on
            # the reader's own thread.
            with telemetry.span_under(
                    trace_handle if bhi > blo else None, "pipeline.scan",
                    file=fi, shard=s, blocks=bhi - blo) as sp:
                if bhi > blo:
                    w0 = int(fp.block_offs[blo])
                    w1 = int(fp.block_offs[bhi - 1]
                             + fp.block_lens[bhi - 1]) + 5
                    raw = fp.pf.read(w0, w1 - w0)
                    sp.tag(bytes=w1 - w0)
                    rawb = np.frombuffer(raw, dtype=np.uint8)
                    boffs = np.ascontiguousarray(fp.block_offs[blo:bhi] - w0)
                    blens = np.ascontiguousarray(fp.block_lens[blo:bhi])
                    rc = lib.tpulsm_scan_blocks(
                        native.np_u8p(rawb), len(rawb),
                        native.np_i64p(boffs), native.np_i64p(blens),
                        bhi - blo,
                        1 if fp.verify else 0,
                        ctypes.cast(kv.key_buf.ctypes.data + fp.k_base
                                    + k_used, _PU8), fp.rk - k_used,
                        ctypes.cast(kv.val_buf.ctypes.data + fp.v_base
                                    + v_used, _PU8), fp.rv - v_used,
                        ctypes.cast(kv.key_offs.ctypes.data
                                    + 4 * (fp.n_base + rows), _PI32),
                        ctypes.cast(kv.key_lens.ctypes.data
                                    + 4 * (fp.n_base + rows), _PI32),
                        ctypes.cast(kv.val_offs.ctypes.data
                                    + 4 * (fp.n_base + rows), _PI32),
                        ctypes.cast(kv.val_lens.ctypes.data
                                    + 4 * (fp.n_base + rows), _PI32),
                        fp.ne - rows, fp.k_base + k_used, fp.v_base + v_used,
                    )
                    if rc == -6:
                        raise Corruption(
                            "block checksum mismatch (pipeline)")
                    if rc == -8:
                        raise Corruption("block decode failed (pipeline)")
                    if rc < 0:
                        # -1 codec fallback, -2/-3/-4 capacity disagreements
                        # with the properties: the serial path covers these.
                        raise PipelineIneligible(f"native scan rc={rc}")
                    if rc > 0:
                        last = fp.n_base + rows + int(rc) - 1
                        k_used = int(kv.key_offs[last]) \
                            + int(kv.key_lens[last]) - fp.k_base
                        v_used = int(kv.val_offs[last]) \
                            + int(kv.val_lens[last]) - fp.v_base
                    rows += int(rc)
                    if rows > fp.ne:
                        raise PipelineIneligible(
                            "more entries than properties")
                if s < n_shards - 1:
                    nb = _lower_bound(kv, fp.n_base + bound, fp.n_base + rows,
                                      splitters[s]) - fp.n_base
                    fp.row_bounds[s + 1] = fp.n_base + nb
                    bound = nb
                if s == n_shards - 1 and (rows != fp.ne or k_used != fp.rk
                                          or v_used != fp.rv):
                    raise PipelineIneligible(
                        "scan totals disagree with props")
            prog.mark(fi, s)
        with stats_mu:
            stats.prefetch_hits += fp.pf.hits
            stats.prefetch_misses += fp.pf.misses
        prog.finish_file(fi)
    except BaseException as e:  # noqa: BLE001 — forwarded to the driver
        prog.fail(e)


def _cover_for_ranges(kv, ranges, frags, snaps):
    """Stripe-clamped max covering tombstone seqno per row of the shard's
    (sorted) per-file ranges, concatenated in range order (the serial
    columnar program calls it with its parts' spans). The fragments' boundaries
    are placed in each range by one search (_lower_bounds) and the rows
    they cover are judged together, so a job with thousands of fragments
    pays numpy calls a range, not Python steps a fragment."""
    if not frags:
        return None
    nf = len(frags)
    bounds = [f.begin for f in frags] + [f.end for f in frags]
    fseq = np.array([f.seq for f in frags], dtype=np.uint64)
    packed: dict = {}
    covs = []
    for lo, hi in ranges:
        n = hi - lo
        cov = np.zeros(n, dtype=np.uint64)
        if n:
            at = _lower_bounds(kv, lo, hi, bounds, packed)
            flo, fhi = at[:nf], at[nf:]
            hit = np.flatnonzero(fhi > flo)
            if len(hit):
                seqs, _vt = _range_seq_vtype(kv, lo, hi)
                if len(snaps):
                    idx = np.searchsorted(snaps, seqs, side="left")
                    upper = np.where(
                        idx < len(snaps),
                        snaps[np.minimum(idx, len(snaps) - 1)],
                        np.uint64(dbformat.MAX_SEQUENCE_NUMBER),
                    )
                else:
                    upper = np.full(n, dbformat.MAX_SEQUENCE_NUMBER,
                                    dtype=np.uint64)
                # Every (fragment, covered row) pair, flat.
                cnt = (fhi - flo)[hit]
                first = np.repeat(flo[hit] - (np.cumsum(cnt) - cnt), cnt)
                rows = first + np.arange(int(cnt.sum()))
                t = np.repeat(fseq[hit], cnt)
                elig = (t > seqs[rows]) & (t <= upper[rows])
                np.maximum.at(cov, rows[elig], t[elig])
        covs.append(cov)
    return np.concatenate(covs)


def _shard_ranges(files, s):
    return [(fp.row_bounds[s], fp.row_bounds[s + 1]) for fp in files
            if fp.row_bounds[s + 1] > fp.row_bounds[s]]


def _ranges_lmap(ranges) -> np.ndarray:
    if not ranges:
        return np.empty(0, np.int32)
    return np.concatenate([
        np.arange(lo, hi, dtype=np.int32) for lo, hi in ranges
    ])


def _put(outq, prog, item, shared) -> None:
    """Bounded put that gives up once any stage has failed or aborted.
    A full queue is back-pressure from the writer: that wait is the span
    `pipeline.wait_writer` and `stall_wait_writer_usec`."""
    if prog.stop:
        raise prog.err or PipelineIneligible("pipeline aborted")
    try:
        outq.put_nowait(item)
        return
    except Full:
        pass
    t0 = time.time()
    try:
        with telemetry.span_under(shared.trace, "pipeline.wait_writer"):
            while True:
                if prog.stop:
                    raise prog.err or PipelineIneligible("pipeline aborted")
                try:
                    outq.put(item, timeout=0.1)
                    return
                except Full:
                    continue
    finally:
        shared.stats.stall_wait_writer_usec += int((time.time() - t0) * 1e6)


def _wait_scan(prog, shared, s: int) -> None:
    """Block until every reader has scanned past shard s. When that takes
    a wait, the device's feeder is starved by the readers: the span
    `pipeline.wait_scan` and `stall_wait_scan_usec`."""
    if prog.poll_shard(s):
        prog.wait_shard(s)  # returns at once; raises if a stage stopped
        return
    t0 = time.time()
    try:
        with telemetry.span_under(shared.trace, "pipeline.wait_scan",
                                  shard=s):
            prog.wait_shard(s)
    finally:
        shared.stats.stall_wait_scan_usec += int((time.time() - t0) * 1e6)


def _host_compute(kv, files, splitters, prog, outq, shared, snapshots,
                  bottommost, frags, max_dev_key):
    """Compute worker, host-twin mode: native k-way merge + GC per shard;
    publishes global-row survivor chunks with zero-seq rows patched."""
    from toplingdb_tpu.ops import compaction_kernels as ck

    n_shards = len(splitters) + 1
    snaps = np.asarray(sorted(snapshots), dtype=np.uint64)
    for s in range(n_shards):
        _wait_scan(prog, shared, s)
        ranges = _shard_ranges(files, s)
        if not ranges:
            continue
        with telemetry.span_under(shared.trace, "pipeline.merge_gc",
                                  shard=s):
            og = _host_merge_gc_shard(ck, kv, ranges, shared, snapshots,
                                      snaps, bottommost, frags, max_dev_key,
                                      s)
        _put(outq, prog, og, shared)
    _put(outq, prog, _DONE, shared)


def _host_merge_gc_shard(ck, kv, ranges, shared, snapshots, snaps,
                         bottommost, frags, max_dev_key, s):
    """One shard through the native merge+GC host twin; returns the
    survivors' global rows in output order."""
    t0 = time.time()
    soffs = np.concatenate(
        [kv.key_offs[lo:hi] for lo, hi in ranges]).astype(np.int64)
    slens = np.concatenate(
        [kv.key_lens[lo:hi] for lo, hi in ranges]).astype(np.int64)
    mx = int(slens.max())
    if mx - 8 > max_dev_key:
        raise PipelineIneligible("keys exceed the device budget")
    rs = np.cumsum([0] + [hi - lo for lo, hi in ranges],
                   dtype=np.int64)
    cover = _timed_cover(kv, ranges, frags, snaps, shared)
    order, zero, cx, hc, seq_l, vt_l = ck.host_fused_full(
        kv.key_buf, soffs, slens, max(4, mx - 8), snapshots,
        bottommost, cover, run_starts=rs,
    )
    lmap = _ranges_lmap(ranges)
    og = lmap[order]
    shared.seqs[lmap] = seq_l
    shared.vtypes[lmap] = vt_l
    # A complex row's zero flag is provisional: the fold decides it again.
    zg = og[zero & ~cx] if hc else og[zero]
    shared.trailer_override[zg] = shared.vtypes[zg].astype(np.int64)
    shared.seqs[zg] = 0
    if hc:
        og = _fold_shard(kv, shared, s, og, cx,
                         None if cover is None else cover[order])
    shared.stats.host_compute_usec += int((time.time() - t0) * 1e6)
    return og


def _device_compute(kv, files, splitters, prog, outq, shared, snapshots,
                    bottommost, frags, max_dev_key):
    """Compute worker, device mode: upload each shard's uniform chunks as
    soon as its scan lands (async H2D + dispatch), finish in order —
    double-buffered so shard s+1 transfers while shard s computes. Every
    upload is committed to the job's chip (`shared.devices`, a dcompact
    service's grant; the default device without one). Under a grant of
    two chips or more (or TPULSM_MESH_COMPACT without a grant) shards
    round-robin over them instead (committed uploads pin each program,
    ops/mesh_compaction.py) and the lookahead widens to UPLOAD_DEPTH per
    chip; a chip that fails mid-job demotes the remaining shards to the
    default device.

    This thread feeds the device, so every step of it is a span: whatever
    the device's trace shows as idle inside a job has the name of what
    this thread was doing (PERF.md §3)."""
    from toplingdb_tpu.ops import compaction_kernels as ck
    from toplingdb_tpu.ops import device_runtime
    from toplingdb_tpu.ops import mesh_compaction as mc
    from toplingdb_tpu.parallel import mesh_plan as mp
    from toplingdb_tpu.utils.status import NotSupported

    n_shards = len(splitters) + 1
    snaps = np.asarray(sorted(snapshots), dtype=np.uint64)
    stats, trace = shared.stats, shared.trace
    mesh_devs = mc.pipeline_devices(n_shards, stats=stats, trace=trace,
                                    devices=shared.devices or None) or []
    # The one chip of a job that does not mesh (None: the default device).
    home = [shared.devices[0] if shared.devices else None]
    depth = [mp.UPLOAD_DEPTH * len(mesh_devs) if mesh_devs else 1]
    pendings = []  # (ranges, lmap, pending, s, dev, chunks, covers, cov)

    def _demote(exc) -> None:
        # Wedged chip: the rest of the job runs on the default device;
        # bytes are unchanged (same kernels), only placement degrades.
        mesh_devs.clear()
        home[0] = None
        depth[0] = 1
        stats.mesh_chips = 1
        stats.mesh_fallbacks = getattr(stats, "mesh_fallbacks", 0) + 1
        telemetry.span_event_under(trace, "compaction.mesh.fallback",
                                   0, reason="chip-wedged",
                                   error=type(exc).__name__)

    def start_one(s, chunks, covers, dev):
        """H2D enqueue, then jit dispatch (and the compile, when the
        shape is met for the first time)."""
        if mc._FAULT_HOOK is not None:
            mc._FAULT_HOOK(s, dev)
        with telemetry.span_under(trace, "pipeline.upload",
                                  shard=s) as sp:
            h = ck.upload_shard(chunks, covers, device=dev)
            nb = ck.shard_upload_nbytes(h)
            stats.h2d_bytes += nb
            stats.note_device(ck.shard_device(h).id)
            sp.tag(h2d_bytes=nb)
        with telemetry.span_under(trace, "pipeline.dispatch",
                                  shard=s) as sp:
            c0, h0 = device_runtime.compiles_now()
            pending = ck.fused_uniform_shard_start(h, snapshots, bottommost)
            c1, h1 = device_runtime.compiles_now()
            sp.tag(compiled=c1 - c0, cache_hit=h1 - h0)
        return pending

    def finish_one(item):
        if item is None:
            return
        ranges, lmap, pending, s, dev, chunks, covers, cov = item
        t0 = time.time()
        nb = sum(int(a.nbytes) for a in pending)
        # Device compute + D2H, as this thread waits for them.
        with telemetry.span_under(
                trace, "pipeline.merge_gc", shard=s, device=True,
                d2h_bytes=nb, **({} if dev is None else {"chip": str(dev)})):
            try:
                o, z, cx, hc = ck.fused_uniform_shard_finish(pending)
            except Exception as e:
                if dev is None or isinstance(e, NotSupported):
                    raise
                _demote(e)  # re-run this shard on the default device
                pending = start_one(s, chunks, covers, None)
                o, z, cx, hc = ck.fused_uniform_shard_finish(pending)
        stats.d2h_bytes += nb
        stats.device_wait_usec += int((time.time() - t0) * 1e6)
        # Host work between the device and the writer.
        with telemetry.span_under(trace, "pipeline.unpack", shard=s):
            og = lmap[o]
            for lo, hi in ranges:
                seq_r, vt_r = _range_seq_vtype(kv, lo, hi)
                shared.seqs[lo:hi] = seq_r
                shared.vtypes[lo:hi] = vt_r
            # A complex row's zero flag is provisional: the fold decides.
            zg = og[z & ~cx] if hc else og[z]
            shared.trailer_override[zg] = shared.vtypes[zg].astype(np.int64)
            shared.seqs[zg] = 0
            if hc:
                og = _fold_shard(kv, shared, s, og, cx,
                                 None if cov is None else cov[o])
        _put(outq, prog, og, shared)

    for s in range(n_shards):
        _wait_scan(prog, shared, s)
        ranges = _shard_ranges(files, s)
        if not ranges:
            pendings.append(None)
        else:
            t0 = time.time()
            # Host numpy before the upload: trailers stripped, covers.
            with telemetry.span_under(trace, "pipeline.chunk_prepare",
                                      shard=s):
                chunks, covers, cov = _prepare_shard_chunks(
                    ck, kv, ranges, frags, snaps, max_dev_key, shared)
            dev = mesh_devs[s % len(mesh_devs)] if mesh_devs else home[0]
            try:
                pending = start_one(s, chunks, covers, dev)
            except Exception as e:
                if dev is None or isinstance(e, NotSupported):
                    raise
                _demote(e)
                dev = None
                pending = start_one(s, chunks, covers, None)
            stats.transfer_time_usec += int((time.time() - t0) * 1e6)
            pendings.append((ranges, _ranges_lmap(ranges), pending, s, dev,
                             chunks, covers, cov))
        # keep the lookahead window in flight (one upload serially,
        # UPLOAD_DEPTH per chip under the mesh); finish older shards now
        while len(pendings) > depth[0]:
            finish_one(pendings.pop(0))
    while pendings:
        finish_one(pendings.pop(0))
    _put(outq, prog, _DONE, shared)


def _timed_cover(kv, ranges, frags, snaps, shared):
    """_cover_for_ranges as the span `pipeline.tombstone_cover` and the
    counter `tombstone_cover_usec`; None for a job without tombstones."""
    if not frags:
        return None
    t0 = time.time()
    with telemetry.span_under(shared.trace, "pipeline.tombstone_cover",
                              fragments=len(frags)):
        cov = _cover_for_ranges(kv, ranges, frags, snaps)
    shared.stats.tombstone_cover_usec += int((time.time() - t0) * 1e6)
    return cov


def _fold_shard(kv, shared, s, og, cx, cover):
    """The fold step between the compute stage and the writer: resolve the
    complex groups flagged in shard s's survivor stream `og` (global rows;
    `cx` flags them, `cover` is the stream's covering-tombstone seqnos or
    None). Returns the stream without the rows that folded away; the
    survivors' values, types and trailers are patched where the writer
    will read them."""
    from toplingdb_tpu.ops import device_compaction as dc

    f = shared.fold
    t0 = time.time()
    with telemetry.span_under(shared.trace, "pipeline.merge_fold",
                              shard=s) as sp:
        keep, ctr = dc.fold_complex(
            kv, og, cx, cover, shared.trailer_override, shared.seqs,
            shared.vtypes, f.icmp, f.snapshots, f.bottommost,
            f.merge_operator, f.rd, f.blob_resolver, patch=f.patch)
        sp.tag(**ctr)
    dc.count_fold(shared.stats, ctr, int((time.time() - t0) * 1e6))
    return og[keep]


class _Fold:
    """What the fold step needs of the job, and the slack at the end of
    the value buffer where results of another width than the row they
    replace are put (the buffer itself must not move: the readers and the
    writer hold pointers into it)."""

    def __init__(self, icmp, snapshots, bottommost, merge_operator, rd,
                 blob_resolver, slack_lo: int, slack_hi: int):
        self.icmp = icmp
        self.snapshots = snapshots
        self.bottommost = bottommost
        self.merge_operator = merge_operator
        self.rd = rd
        self.blob_resolver = blob_resolver
        self._pos = slack_lo
        self._end = slack_hi

    def patch(self, kv, rows, vals) -> None:
        for r, v in zip(rows, vals):
            n = len(v)
            if n == int(kv.val_lens[r]):
                off = int(kv.val_offs[r])
            else:
                if self._pos + n > self._end:
                    raise PipelineIneligible(
                        "merge results outgrew the value buffer's slack")
                off = self._pos
                self._pos += n
                kv.val_offs[r] = off
                kv.val_lens[r] = n
            kv.val_buf[off:off + n] = np.frombuffer(v, dtype=np.uint8)


def _prepare_shard_chunks(ck, kv, ranges, frags, snaps, max_dev_key, shared):
    """(chunks, covers, cov) of one shard for ck.upload_shard: a chunk a
    file range (`shard_chunks`: one key length, or keys of mixed length at
    a width bucket), and the covering-tombstone seqnos (by chunk, and
    whole) when the job has range tombstones. A range of a file is dense:
    its internal keys lie back to back from its first one on, which is all
    either kind of chunk needs. Keys past the device budget leave the
    pipeline (the serial branch then sends the job per entry, ARCHITECTURE.md
    §2.2.1.1); below it a shard of any mix of lengths stays on the plane.
    Which program a shard meets is a property of its own rows: a shard of
    one key length takes the uniform program with the shapes and static
    arguments it always had, so a deployment of one key length compiles
    nothing new, and a job's shards may take either."""
    pieces = []
    for lo, hi in ranges:
        lens = kv.key_lens[lo:hi]
        if int(lens.max()) - 8 > max_dev_key:
            raise PipelineIneligible("keys exceed the device budget")
        pieces.append((kv.key_buf[int(kv.key_offs[lo]):], lens))
    chunks = shard_chunks(pieces, shared.stats, shared.trace)
    cov = _timed_cover(kv, ranges, frags, snaps, shared)
    if cov is None:
        return chunks, None, None
    covers = []
    pos = 0
    for lo, hi in ranges:
        covers.append(cov[pos:pos + (hi - lo)])
        pos += hi - lo
    return chunks, covers, cov


class _Shared:
    """Arrays shared between compute and the writer (aliased per the
    chunked-order contract of write_tables_columnar) plus the stats and
    the telemetry handle stage workers parent their spans under."""

    __slots__ = ("trailer_override", "seqs", "vtypes", "stats", "trace",
                 "fold", "devices")


def run_pipelined(env, dbname, icmp, compaction, table_cache, table_options,
                  snapshots, new_file_number, creation_time, stats,
                  max_dev_key, column_family=(0, "default"),
                  merge_operator=None, blob_resolver=None, devices=None):
    """Run one compaction through the three-stage pipeline. Returns the
    write_tables_columnar file tuples plus the shared arrays used to
    build output metadata: (files, kv, vtypes, tombs). `devices`: the
    chips a dcompact service granted the job (None: the default device).

    Raises PipelineIneligible for shapes the serial path must take and
    propagates hard errors (Corruption, IO) after partial outputs are
    cleaned up by the writer."""
    from toplingdb_tpu.compaction.compaction_job import (
        surviving_tombstone_fragments,
    )
    from toplingdb_tpu.db.range_del import (
        RangeDelAggregator, RangeTombstone, fragment_tombstones,
    )
    from toplingdb_tpu.ops.columnar_io import write_tables_columnar
    from toplingdb_tpu.ops.compaction_kernels import MAX_SNAPSHOTS

    if not pipeline_enabled(table_options):
        raise PipelineIneligible("pipeline disabled")
    if len(snapshots) > MAX_SNAPSHOTS:
        raise PipelineIneligible("snapshot count exceeds the device cap")
    # The compaction root span lives on the ORCHESTRATING thread; stage
    # workers parent their per-shard spans under this exported handle.
    trace = telemetry.current_handle()
    # Everything before a thread starts: the plan (index walk of every
    # input, splitters, the preallocated buffers), tombstone fragments,
    # the arrays compute and writer share.
    with telemetry.span("pipeline.plan"):
        readers = [
            table_cache.get_reader(f.number)
            for _, f in compaction.all_inputs()
        ]
        # A job that may fold operands gets slack behind its values.
        kv, files, splitters, slack = _build_plan(
            readers, value_slack=merge_operator is not None)
        stats.input_records = kv.n

        rd = RangeDelAggregator(icmp.user_comparator)
        for r in readers:
            for b, e in r.range_del_entries():
                rd.add(RangeTombstone.from_table_entry(b, e))
        frags = (list(fragment_tombstones(rd.tombstones(),
                                          icmp.user_comparator))
                 if not rd.empty() else [])
        tombs = surviving_tombstone_fragments(
            rd, snapshots, compaction.bottommost, icmp.user_comparator,
        )

        shared = _Shared()
        shared.trailer_override = np.full(kv.n, -1, dtype=np.int64)
        shared.seqs = np.zeros(kv.n, dtype=np.uint64)
        shared.vtypes = np.zeros(kv.n, dtype=np.int32)
        shared.stats = stats
        shared.trace = trace
        shared.devices = list(devices) if devices else []
        shared.fold = _Fold(icmp, snapshots, compaction.bottommost,
                            merge_operator, None if rd.empty() else rd,
                            blob_resolver, *slack)
        stats.tombstone_fragments = len(frags)
    stats.pipelined = True

    prog = _Progress(len(files))
    outq: Queue = Queue(maxsize=4)
    stats_mu = ccy.Lock("pipeline.run_pipelined.stats_mu")

    t_scan0 = time.time()
    with telemetry.span("pipeline.spawn", readers=len(files)):
        rthreads = [
            ccy.spawn(f"pipeline-scan-{fi}", _scan_file, start=False,
                      args=(fi, fp, kv, prog, splitters, stats,
                            stats_mu, shared.trace))
            for fi, fp in enumerate(files)
        ]
        from toplingdb_tpu.ops.device_compaction import _host_sort

        compute_fn = _host_compute if _host_sort() else _device_compute
        cthread = ccy.spawn(
            "pipeline-compute", _compute_guard, start=False,
            args=(compute_fn, kv, files, splitters, prog, outq, shared,
                  snapshots, compaction.bottommost, frags, max_dev_key),
        )
        for t in rthreads:
            t.start()
        cthread.start()

    def chunk_stream():
        # The writer (write_tables_columnar, on this thread) asks for the
        # next chunk once it has consumed the last: each consumed chunk is
        # its span `pipeline.encode_write`, each wait here `pipeline.stall`.
        while True:
            try:
                item = outq.get_nowait()
            except Empty:
                t0 = time.time()
                with telemetry.span_under(trace, "pipeline.stall"):
                    item = outq.get()
                stats.pipeline_stall_usec += int((time.time() - t0) * 1e6)
            if item is _DONE:
                return
            if isinstance(item, _Err):
                raise item.exc
            yield item

    writer, counted = write_tables_columnar, {"stats": stats}
    if getattr(table_options, "format", "block") == "zip":
        from toplingdb_tpu.table.zip_table import write_tables_zip_columnar

        writer, counted = write_tables_zip_columnar, {"stats": stats}
    t_wr = time.time()
    try:
        out_files = writer(
            env, dbname, new_file_number, icmp, table_options, kv,
            chunk_stream(), shared.trailer_override, shared.vtypes,
            shared.seqs, tombs,
            creation_time if creation_time is not None else int(time.time()),
            max_output_file_size=compaction.max_output_file_size,
            column_family=column_family, **counted,
        )
    except BaseException:
        prog.abort()
        _drain_join(outq, [cthread] + rthreads)
        raise
    stats.encode_write_usec = max(0, int(
        (time.time() - t_wr) * 1e6) - stats.pipeline_stall_usec)
    with telemetry.span("pipeline.join"):
        for t in rthreads:
            t.join()
        cthread.join()
    if prog.err is not None:
        raise prog.err
    stats.input_scan_usec = int(
        ((prog.scan_end or time.time()) - t_scan0) * 1e6)
    return out_files, kv, shared.vtypes, tombs


def _compute_guard(fn, kv, files, splitters, prog, outq, shared, snapshots,
                   bottommost, frags, max_dev_key):
    try:
        fn(kv, files, splitters, prog, outq, shared, snapshots, bottommost,
           frags, max_dev_key)
    except BaseException as e:  # noqa: BLE001 — forwarded via the queue
        prog.fail(e)
        try:
            outq.put_nowait(_Err(e))
        except Exception as e2:
            # Queue full: the writer will observe prog.err after draining.
            _errors.swallow(reason="producer-error-queue-full", exc=e2)
            try:
                outq.get_nowait()
                outq.put_nowait(_Err(e))
            except Exception as e3:
                _errors.swallow(reason="producer-error-queue-race", exc=e3)


def _drain_join(outq: Queue, threads) -> None:
    """Unblock producers stuck on the bounded queue, then join."""
    deadline = time.time() + 10.0
    while any(t.is_alive() for t in threads) and time.time() < deadline:
        try:
            outq.get(timeout=0.05)
        except Empty:
            pass
    for t in threads:
        t.join(timeout=1.0)


def shard_chunks(pieces, stats=None, trace=None) -> list:
    """One shard's chunks for ck.upload_shard from its pieces, a (key bytes
    from the range's first internal key on, its key lengths) pair a file
    range: prepare_uniform_chunk's where the shard's keys have one length,
    else VarlenChunks at the width bucket of its longest user key
    (ck.varlen_width), packed under the span `pipeline.varlen_pack` and
    counted in the job's `varlen_*` stats. The pipeline and the serial
    branch (ops/device_compaction.py::_prepare_uniform_shards) both cut
    here, so a job that leaves the pipeline meets the same program."""
    from toplingdb_tpu.ops import compaction_kernels as ck

    kmin = min(int(lens.min()) for _, lens in pieces)
    kmax = max(int(lens.max()) for _, lens in pieces)
    if kmin == kmax:
        return [ck.prepare_uniform_chunk(buf, len(lens), kmax)
                for buf, lens in pieces]
    width = ck.varlen_width(kmax - 8)
    with telemetry.span_under(trace, "pipeline.varlen_pack",
                              width=width) as sp:
        chunks = [ck.prepare_varlen_chunk(buf, lens, width)
                  for buf, lens in pieces]
        sp.tag(rows=sum(c.n for c in chunks))
    if stats is not None:
        stats.count_varlen_shard(chunks)
    return chunks
