"""Device (TPU) compaction data plane: host orchestration.

Replaces the CPU heap-merge + CompactionIterator with:
  1. raw sequential reads of every input file (no host merge),
  2. one device sort realizing internal-key order (ops.compaction_kernels),
  3. device GC masking (stripes, visibility, tombstone shadowing),
  4. host resolution of "complex" groups (merge operands / single-delete),
  5. the SAME build_outputs() as the CPU path → byte-identical SSTs.

This is the kernel surface called out in SURVEY.md §3.4/§7 step 5; the
serializable executor boundary (compaction/executor.py) selects it with
device="tpu" (or "cpu-jax", XLA:CPU, in tests) — a label that
run_device_compaction checks against what JAX reports.
"""

from __future__ import annotations

import bisect
import os
import time
import types

import numpy as np

from toplingdb_tpu.compaction.compaction_iterator import CompactionIterator
from toplingdb_tpu.compaction.compaction_job import (
    CompactionStats,
    build_outputs,
    surviving_tombstone_fragments,
)
from toplingdb_tpu.db import dbformat
from toplingdb_tpu.db.range_del import RangeDelAggregator, RangeTombstone, fragment_tombstones
from toplingdb_tpu.ops import compaction_kernels as ck
from toplingdb_tpu.ops import device_runtime
from toplingdb_tpu.ops.columnar import ColumnarEntries
from toplingdb_tpu.utils import telemetry as _tele
from toplingdb_tpu.utils.status import NotSupported


def collect_raw_entries(compaction, table_cache, icmp, stats=None):
    """Sequentially read every input file's entries (NO host merge — the
    device sort is the merge). Returns (entries list, RangeDelAggregator);
    `stats` (CompactionStats) accumulates the scan's readahead counters."""
    entries: list[tuple[bytes, bytes]] = []
    rd = RangeDelAggregator(icmp.user_comparator)
    for _, f in compaction.all_inputs():
        r = table_cache.get_reader(f.number)
        it = r.new_iterator()
        it.seek_to_first()
        for k, v in it.entries():
            entries.append((k, v))
        if stats is not None:
            stats.count_input(r)
            if hasattr(it, "prefetch_counts"):  # a ZipTable reads no blocks
                h, m = it.prefetch_counts()
                stats.prefetch_hits += h
                stats.prefetch_misses += m
        for b, e in r.range_del_entries():
            rd.add(RangeTombstone.from_table_entry(b, e))
    return entries, rd


def _tombstone_cover(sorted_user_keys: list[bytes], rd: RangeDelAggregator,
                     ucmp, sorted_seqs, snapshots) -> np.ndarray | None:
    """Per-sorted-entry max covering tombstone seqno (uint64), CLAMPED TO
    EACH ENTRY'S SNAPSHOT STRIPE — a tombstone above the next snapshot must
    not mask an in-stripe one (it can't delete the entry, but the in-stripe
    one does). Interval mapping on host (fragments are few; entries many)."""
    if rd.empty():
        return None
    n = len(sorted_user_keys)
    cover = np.zeros(n, dtype=np.uint64)
    seqs = np.asarray(sorted_seqs, dtype=np.uint64)
    snaps = np.asarray(sorted(snapshots), dtype=np.uint64)
    if len(snaps):
        idx = np.searchsorted(snaps, seqs, side="left")
        upper = np.where(
            idx < len(snaps), snaps[np.minimum(idx, len(snaps) - 1)],
            np.uint64(dbformat.MAX_SEQUENCE_NUMBER),
        )
    else:
        upper = np.full(n, dbformat.MAX_SEQUENCE_NUMBER, dtype=np.uint64)
    for frag in fragment_tombstones(rd.tombstones(), ucmp):
        lo = bisect.bisect_left(sorted_user_keys, frag.begin)
        hi = bisect.bisect_left(sorted_user_keys, frag.end)
        if lo < hi:
            t = np.uint64(frag.seq)
            sl = slice(lo, hi)
            elig = (t > seqs[sl]) & (t <= upper[sl]) & (t > cover[sl])
            cover[sl] = np.where(elig, t, cover[sl])
    return cover


# Longest user key the device paths accept: the sort uses one operand per
# 4 key bytes, and XLA compile time grows with operand count. Longer keys
# route to the host CompactionIterator (scheduler fallback-to-local).
MAX_DEVICE_KEY_BYTES = 128


def _host_sort() -> bool:
    """TPULSM_HOST_SORT=1: a chipless deployment asks for the native host
    twins in place of the jax programs. Only a user (or a test) sets it."""
    return os.environ.get("TPULSM_HOST_SORT") == "1"


def device_gc_entries(entries, icmp, snapshots, bottommost,
                      merge_operator=None, compaction_filter=None,
                      compaction_filter_level=0, rd=None,
                      max_key_bytes=None, blob_resolver=None):
    """Runs the device data plane over raw (unsorted) entries; yields the
    surviving (internal_key, value) stream — semantically identical to
    CompactionIterator.entries() over the merged sorted input."""
    if not entries:
        return
    if max_key_bytes is None:
        longest = max(len(k) for k, _ in entries) - 8
        if longest > MAX_DEVICE_KEY_BYTES:
            raise NotSupported(
                f"user keys up to {longest}B exceed the device key budget "
                f"({MAX_DEVICE_KEY_BYTES}B); use the CPU path"
            )
    if icmp.user_comparator.name() != dbformat.BYTEWISE.name():
        # The device sort realizes bytewise-ascending user-key order; other
        # comparators must use the host path (scheduler falls back).
        raise NotSupported(
            f"device compaction requires the bytewise comparator, "
            f"got {icmp.user_comparator.name()!r}"
        )
    col = ColumnarEntries.from_entries(entries, max_key_bytes)
    padded = ck.pad_columns(col)
    sorted_cols, perm = ck.device_sort(padded)
    cover = None
    sorted_uks = None
    if rd is not None:
        sorted_uks = [col.user_key(i) for i in perm]
        cover = _tombstone_cover(sorted_uks, rd, icmp.user_comparator,
                                 col.seq[perm], snapshots)
    keep, zero_seq, host_resolve, group_id = ck.gc_mask(
        sorted_cols, snapshots, cover, bottommost
    )

    # Host-side finishing: complex groups through the reference state
    # machine; simple survivors filtered/zeroed to match it exactly.
    helper = CompactionIterator(
        _EmptyIter(), icmp, snapshots, bottommost_level=bottommost,
        merge_operator=merge_operator, compaction_filter=compaction_filter,
        compaction_filter_level=compaction_filter_level, range_del_agg=rd,
        blob_resolver=blob_resolver,
    )
    earliest = min(snapshots) if snapshots else dbformat.MAX_SEQUENCE_NUMBER
    from toplingdb_tpu.utils.compaction_filter import Decision

    n = col.n
    values = col.values
    ikeys = col.ikeys
    fast = compaction_filter is None  # fast path: emit original ikey bytes
    i = 0
    while i < n:
        if host_resolve[i]:
            g = group_id[i]
            j = i
            group = []
            while j < n and group_id[j] == g:
                oi = perm[j]
                group.append((int(col.seq[oi]), int(col.vtype[oi]), values[oi]))
                j += 1
            yield from helper._process_group(col.user_key(perm[i]), group)
            i = j
            continue
        if keep[i]:
            oi = perm[i]
            if fast:
                if zero_seq[i]:
                    yield dbformat.make_internal_key(
                        ikeys[oi][:-8], 0, int(col.vtype[oi])
                    ), values[oi]
                else:
                    yield ikeys[oi], values[oi]
                i += 1
                continue
            seq, t = int(col.seq[oi]), int(col.vtype[oi])
            val = values[oi]
            uk = col.user_key(oi)
            if t == dbformat.ValueType.VALUE and seq <= earliest:
                d, newv = compaction_filter.filter(
                    compaction_filter_level, uk, val
                )
                if d == Decision.REMOVE:
                    i += 1
                    continue
                if d == Decision.CHANGE_VALUE:
                    val = newv if newv is not None else b""
            if zero_seq[i]:
                seq = 0
            yield dbformat.make_internal_key(uk, seq, t), val
        i += 1


class _EmptyIter:
    def valid(self):
        return False


class _FallbackToEntries(Exception):
    """Raised inside the columnar fast path when the job needs per-entry
    semantics (complex groups present)."""


def _part_user_key(part, i: int) -> bytes:
    o = int(part.key_offs[i])
    return part.key_buf[o: o + int(part.key_lens[i]) - 8].tobytes()


def _shard_splitters(part, n_shards: int) -> list[bytes]:
    """Evenly spaced user keys from one sorted part (deduped, ascending)."""
    spl = []
    for s in range(1, n_shards):
        spl.append(_part_user_key(part, part.n * s // n_shards))
    return sorted(set(spl))


def _part_bounds(part, splitters: list[bytes]) -> list[int]:
    """Row bounds [0, b1, ..., n] for one sorted part: b_s = first row whose
    user key >= splitters[s-1] (all copies of a user key land in ONE shard)."""
    b = [0]
    for spl in splitters:
        lo, hi = b[-1], part.n
        while lo < hi:
            mid = (lo + hi) // 2
            if _part_user_key(part, mid) < spl:
                lo = mid + 1
            else:
                hi = mid
        b.append(lo)
    b.append(part.n)
    return b


def _collect_raw_columnar(compaction, table_cache, icmp, want_uploads=False,
                          stats=None):
    """Scan every input file into columnar buffers — in parallel threads
    (the native decoders run GIL-free under ctypes). Block files, ZipTables
    and SingleFastTables, in any mix: a whole-file scan of the latter two is
    `pipeline.zip_scan` / `pipeline.sft_scan`, counted in `stats`. With
    want_uploads, ALSO split the sorted parts into user-key-range shards
    and prepare (host-side, no device traffic yet) each shard's uniform
    chunk columns. Returns (kv, rd, shards, parts) where shards is None
    when the sharded uniform device path does not apply (sparse layout,
    non-uniform key lengths, oversized shards); otherwise shards[s] =
    (chunks, row_ranges): prepare_uniform_chunk outputs plus the
    (global_lo, global_hi) row spans into the concatenated kv that each
    chunk covers, in chunk order."""
    from concurrent.futures import ThreadPoolExecutor

    from toplingdb_tpu.ops.columnar_io import (
        ColumnarKV,
        scan_table_columnar,
        scan_tables_columnar_prealloc,
    )

    readers = [
        table_cache.get_reader(f.number) for _, f in compaction.all_inputs()
    ]
    trace = _tele.current_handle()
    walls = []  # (entry_plane, usec) of each entry-ranged input's scan

    def scan_one(r, scan=scan_table_columnar):
        """One input whole; an entry-ranged one under its span and wall."""
        plane = getattr(r, "entry_plane", None)
        if not plane:
            return scan(r)
        t0 = time.time()
        with _tele.span_under(trace, f"pipeline.{plane}_scan",
                              rows=r.n) as sp:
            part = scan(r)
            sp.tag(nbytes=int(r.properties.raw_key_size
                              + r.properties.raw_value_size))
        walls.append((plane, int((time.time() - t0) * 1e6)))
        return part

    pre = scan_tables_columnar_prealloc(readers, scan_one)
    if pre is None:  # a ZipTable among the inputs, or props that disagree
        with ThreadPoolExecutor(max(1, min(8, len(readers)))) as ex:
            parts = list(ex.map(scan_one, readers))
        pre = ColumnarKV.concat(parts), parts
    kv, parts = pre
    # Booked here, on one thread: the scans ran side by side.
    for r in readers if stats is not None else ():
        stats.count_input(r)
    for plane, usec in walls if stats is not None else ():
        stats.count_ranged_scan(plane, usec)
    rd = RangeDelAggregator(icmp.user_comparator)
    for r in readers:
        for b, e in r.range_del_entries():
            rd.add(RangeTombstone.from_table_entry(b, e))

    shards = None
    if want_uploads:
        shards = _prepare_uniform_shards(parts)
    return kv, rd, shards, parts


def _prepare_uniform_shards(parts):
    """Host half of the sharded uniform device path: validate density +
    uniform key length, pick range splitters (as many shards as the
    pipeline would cut: ck.shard_count), slice every part into per-shard
    chunks. Returns shards list or None when ineligible."""
    uniform_len = 0
    total_rows = 0
    for part in parts:
        if not part.n:
            continue
        L = int(part.key_lens[0])
        dense_uniform = (
            part.key_lens.min() == part.key_lens.max()
            and len(part.key_buf) == part.n * L
            and int(part.key_offs[0]) == 0
            and np.array_equal(
                part.key_offs[1:],
                (np.cumsum(part.key_lens) - part.key_lens)[1:],
            )
        )
        if not dense_uniform:
            return None
        if uniform_len and L != uniform_len:
            return None
        uniform_len = L
        total_rows += part.n
    if not total_rows:
        return None

    splitters = None
    for part in parts:
        if part.n:
            splitters = _shard_splitters(part, ck.shard_count(total_rows))
            break
    shards = [([], []) for _ in range(len(splitters) + 1)]
    row_base = 0
    for part in parts:
        if not part.n:
            continue
        bounds = _part_bounds(part, splitters)
        for s in range(len(bounds) - 1):
            lo, hi = bounds[s], bounds[s + 1]
            if lo == hi:
                continue
            blo = int(part.key_offs[lo])
            bhi = int(part.key_offs[hi - 1]) + int(part.key_lens[hi - 1])
            shards[s][0].append(ck.prepare_uniform_chunk(
                part.key_buf[blo:bhi], hi - lo, uniform_len,
            ))
            shards[s][1].append((row_base + lo, row_base + hi))
        row_base += part.n
    shards = [sh for sh in shards if sh[0]]
    for chunks, _ranges in shards:
        if sum(c[2] for c in chunks) > ck.MAX_SHARD_ROWS:
            return None  # skewed splitters blew the 24-bit row budget
    return shards or None


def _ranges_lmap(ranges) -> np.ndarray:
    """Local shard row -> global concat row map for a shard's chunk
    (global_lo, global_hi) spans."""
    if not ranges:
        return np.empty(0, np.int32)
    return np.concatenate([
        np.arange(lo, hi, dtype=np.int32) for lo, hi in ranges
    ])


def _kv_user_key(kv, r: int) -> bytes:
    o = int(kv.key_offs[r])
    return kv.key_buf[o: o + int(kv.key_lens[r]) - 8].tobytes()


def _patch_kv_values(kv, rows: list[int], vals: list[bytes]) -> None:
    """Append replacement values (folded merge results etc.) to kv's value
    buffer and repoint the rows at them — the columnar writer then emits
    them with zero further special-casing."""
    side = b"".join(vals)
    base = len(kv.val_buf)
    if base + len(side) > 2 ** 31 - 8:
        raise _FallbackToEntries()  # int32 offset budget
    kv.val_buf = np.concatenate([
        kv.val_buf, np.frombuffer(side, dtype=np.uint8)
    ])
    if not kv.val_offs.flags.writeable:
        kv.val_offs = kv.val_offs.copy()
    if not kv.val_lens.flags.writeable:
        kv.val_lens = kv.val_lens.copy()
    off = base
    for r, v in zip(rows, vals):
        kv.val_offs[r] = off
        kv.val_lens[r] = len(v)
        off += len(v)


def _resolve_complex_mask(kv, order, cx_flags, trailer_override, seqs,
                          vtypes, helper, patch=_patch_kv_values):
    """Fold the complex (MERGE / SINGLE_DELETE) user-key groups flagged in
    the survivor stream through the reference state machine
    (CompactionIterator._process_group, the MergeHelper::MergeUntil role,
    /root/reference/db/merge_helper.h:104) WITHOUT abandoning the columnar
    path: each group's emitted entries overwrite the group's leading rows
    (trailer/seq/vtype overrides + value replacements handed to `patch`);
    surplus rows drop out. Returns (the stream's keep mask, the number of
    groups); mutates trailer_override/seqs/vtypes and patches kv in
    place. One Python call a group: the path of operators without a
    columnar fold."""
    n_stream = len(order)
    n_groups = 0
    keep_mask = np.ones(n_stream, dtype=bool)
    repl_rows: list[int] = []
    repl_vals: list[bytes] = []
    pos_list = np.flatnonzero(cx_flags)
    i = 0
    P = len(pos_list)
    while i < P:
        p0 = int(pos_list[i])
        uk = _kv_user_key(kv, int(order[p0]))
        j = i + 1
        while (j < P and int(pos_list[j]) == int(pos_list[j - 1]) + 1
               and _kv_user_key(kv, int(order[int(pos_list[j])])) == uk):
            j += 1
        rows = [int(order[int(pos_list[t])]) for t in range(i, j)]
        group = [(int(seqs[r]), int(vtypes[r]), kv.value(r)) for r in rows]
        emitted = list(helper._process_group(uk, group))
        if len(emitted) > len(rows):
            raise _FallbackToEntries()  # cannot happen; belt and braces
        for t, (ik, v) in enumerate(emitted):
            r = rows[t]
            if ik[:-8] != uk:
                raise _FallbackToEntries()
            packed = int.from_bytes(ik[-8:], "little")
            if packed >= 2 ** 63:
                raise _FallbackToEntries()  # int64 trailer budget
            trailer_override[r] = packed
            seqs[r] = packed >> 8
            vtypes[r] = packed & 0xFF
            if v != kv.value(r):
                repl_rows.append(r)
                repl_vals.append(v)
        for t in range(len(emitted), len(rows)):
            keep_mask[int(pos_list[i + t])] = False
        n_groups += 1
        i = j
    if repl_rows:
        patch(kv, repl_rows, repl_vals)
    return keep_mask, n_groups


def _same_key_as_previous(kv, rows: np.ndarray):
    """bool[m]: rows[i] has the user key of rows[i - 1] ([0] is False), or
    None when the keys are not of one length in one dense buffer (the
    per-group resolver compares those). Keys of whole 8-byte words are
    compared a word at a time, others byte by byte."""
    lens = kv.key_lens[rows]
    klen = int(lens[0])
    r64 = rows.astype(np.int64)
    if (int(lens.min()) != klen or int(lens.max()) != klen
            or len(kv.key_buf) < kv.n * klen
            or not np.array_equal(kv.key_offs[rows], r64 * klen)):
        return None
    buf = kv.key_buf[:kv.n * klen]
    same = np.zeros(len(rows), dtype=bool)
    if klen % 8 == 0 and buf.ctypes.data % 8 == 0:
        words = buf.view(np.uint64).reshape(kv.n, klen // 8)
        eq = np.ones(len(rows) - 1, dtype=bool)
        for c in range(klen // 8 - 1):  # a strided gather a word
            w = words[:, c][r64]
            eq &= w[1:] == w[:-1]
        same[1:] = eq
    else:
        uk = buf.reshape(kv.n, klen)[r64, :klen - 8]
        same[1:] = (uk[1:] == uk[:-1]).all(axis=1)
    return same


def _gather_values(kv, rows: np.ndarray, fold):
    """The fold.width-byte values of `rows` as fold.dtype numbers."""
    W = fold.width
    vo = kv.val_offs[rows].astype(np.int64)
    if kv.val_buf.ctypes.data % W == 0 and not (vo % W).any():
        return kv.val_buf[:len(kv.val_buf) // W * W].view(fold.dtype)[vo // W]
    return np.ascontiguousarray(
        kv.val_buf[vo[:, None] + np.arange(W)[None, :]]
    ).view(fold.dtype).reshape(len(rows))


def _scatter_values(kv, rows: np.ndarray, vals: np.ndarray, fold) -> None:
    """Overwrite the fold.width-byte value slots of `rows` with `vals`."""
    W = fold.width
    if not kv.val_buf.flags.writeable:
        kv.val_buf = kv.val_buf.copy()
    vo = kv.val_offs[rows].astype(np.int64)
    if kv.val_buf.ctypes.data % W == 0 and not (vo % W).any():
        kv.val_buf[:len(kv.val_buf) // W * W].view(fold.dtype)[vo // W] = vals
    else:
        kv.val_buf[vo[:, None] + np.arange(W)[None, :]] = \
            np.ascontiguousarray(vals).view(np.uint8).reshape(len(rows), W)


def _fold_complex_columnar(kv, order, cx_flags, cover, trailer_override,
                           seqs, vtypes, snaps, bottommost, fold, helper,
                           patch=_patch_kv_values):
    """Vectorised twin of _resolve_complex_mask for an operator that
    declares a ColumnarFold: the flagged groups of one survivor stream
    fold in one segmented reduction, no Python a row or a group.

    `cover[i]` (or None): the stripe-clamped max covering range-tombstone
    seqno of stream position i, 0 = uncovered. `snaps`: sorted uint64
    snapshot seqnos. The rules are _process_group's, per (user key,
    snapshot stripe) segment of rows, newest first: a covered first row
    drops the segment; a first VALUE or DELETION survives as the device
    would have decided it; a first MERGE leads a chain of uncovered MERGE
    rows that folds with the VALUE that ends it (unless a range tombstone
    covers that base), into a VALUE when a DELETION, a covered row, or —
    bottommost — the end of the group ends it, else into one MERGE
    operand; nothing folds across a stripe. The result takes the chain's
    newest row: its value slot is overwritten in place (same width), its
    trailer overridden. Groups holding anything else (SINGLE_DELETION,
    blob or entity bases, a value of another width) go through the
    per-group resolver, them alone.
    Returns (keep_mask over the stream, counters dict)."""
    VT = dbformat.ValueType
    keep_mask = np.ones(len(order), dtype=bool)
    pos = np.flatnonzero(cx_flags)
    m = len(pos)
    counters = {"groups": 0, "operand_rows": 0, "rows_folded": 0}
    if m == 0:
        return keep_mask, counters
    rows = order[pos]
    same_key = _same_key_as_previous(kv, rows)
    vt = vtypes[rows]
    if same_key is None:
        counters["operand_rows"] = int((vt == int(VT.MERGE)).sum())
        keep, counters["groups"] = _resolve_complex_mask(
            kv, order, cx_flags, trailer_override, seqs, vtypes, helper,
            patch)
        counters["rows_folded"] = int(m - keep[pos].sum())
        return keep, counters
    W = fold.width
    new_group = ~same_key
    if m != len(order):  # flagged runs need not be neighbours
        new_group[1:] |= pos[1:] != pos[:-1] + 1
    new_seg = new_group
    stripe = None
    if len(snaps):
        stripe = np.searchsorted(snaps, seqs[rows], side="left")
        new_seg = new_group.copy()
        new_seg[1:] |= stripe[1:] != stripe[:-1]
    seg_start = np.flatnonzero(new_seg)
    n_seg = len(seg_start)
    covered = (cover[pos] != 0 if cover is not None
               else np.zeros(m, dtype=bool))
    is_merge = vt == int(VT.MERGE)
    is_value = vt == int(VT.VALUE)
    odd = ~(is_merge | is_value | (vt == int(VT.DELETION))) | (
        (is_merge | is_value) & (kv.val_lens[rows] != W))
    counters["groups"] = int(new_group.sum())
    counters["operand_rows"] = int(is_merge.sum())
    row_py = None
    if odd.any():
        grp_start = np.flatnonzero(new_group)
        row_py = np.logical_or.reduceat(odd, grp_start)[
            np.cumsum(new_group) - 1]

    # Chains: the leading uncovered MERGE rows of a segment, and the row
    # that ends them. A segment whose first row is no such operand has a
    # bad row at its head, so none of its rows counts as chain or end.
    bad = ~(is_merge & ~covered)
    cb = np.cumsum(bad, dtype=np.int32)
    head = (cb - bad)[seg_start]  # bad rows before each segment
    bad_in_seg = cb - np.repeat(
        head, np.diff(np.append(seg_start, m)))
    chain = bad_in_seg == 0
    term = bad & (bad_in_seg == 1) & ~new_seg
    seg_merges = ~bad[seg_start]
    seg_has_term = np.add.reduceat(term, seg_start, dtype=np.int32) > 0
    seg_last_of_group = np.ones(n_seg, dtype=bool)
    seg_last_of_group[:-1] = new_group[seg_start[1:]]

    # What each segment leaves, at its first row.
    r0 = rows[seg_start]
    r0_vt = vt[seg_start]
    r0_bottom0 = (np.full(n_seg, bool(bottommost)) if stripe is None
                  else bool(bottommost) & (stripe[seg_start] == 0))
    to_value = seg_merges & (seg_has_term | (seg_last_of_group
                                             & bool(bottommost)))
    emit = ~covered[seg_start] & (
        (r0_vt == int(VT.VALUE))
        | ((r0_vt == int(VT.DELETION)) & ~r0_bottom0)
        | seg_merges)
    out_vt = np.where(to_value, int(VT.VALUE), r0_vt)
    zero = emit & r0_bottom0 & (out_vt == int(VT.VALUE))
    out_seq = np.where(zero, np.uint64(0), seqs[r0])
    if row_py is not None:
        emit &= ~row_py[seg_start]
        seg_merges = seg_merges & ~row_py[seg_start]

    # The fold itself: one segmented reduction over the chains' values
    # (and the bases they end on), newest first. A chain of one operand
    # with nothing beneath it reduces to itself.
    if seg_merges.any():
        part = chain | (term & is_value & ~covered)
        if row_py is not None:
            part &= ~row_py
        sel = np.flatnonzero(part)
        starts = np.flatnonzero(new_seg[sel])  # a chain starts its segment
        sums = fold.reduce(_gather_values(kv, rows[sel], fold),
                           starts).astype(fold.dtype, copy=False)
        _scatter_values(kv, r0[seg_merges], sums, fold)

    er = r0[emit]
    vtypes[er] = out_vt[emit].astype(vtypes.dtype)
    seqs[er] = out_seq[emit]
    trailer_override[er] = (
        (out_seq[emit] << np.uint64(8)) | out_vt[emit].astype(np.uint64)
    ).astype(np.int64)

    keep_c = np.zeros(m, dtype=bool)
    keep_c[seg_start[emit]] = True
    keep_mask[pos] = keep_c
    if row_py is not None:
        py_pos = pos[row_py]
        py_flags = np.zeros(len(order), dtype=bool)
        py_flags[py_pos] = True
        keep_mask[py_pos] = _resolve_complex_mask(
            kv, order, py_flags, trailer_override, seqs, vtypes, helper,
            patch)[0][py_pos]
    counters["rows_folded"] = int(m - keep_mask[pos].sum())
    return keep_mask, counters


def fold_complex(kv, order, cx_flags, cover, trailer_override, seqs, vtypes,
                 icmp, snapshots, bottommost, merge_operator, rd,
                 blob_resolver, patch=_patch_kv_values):
    """Resolve the complex groups the device flagged in one survivor
    stream (a whole job's in the serial program, a shard's in the
    pipeline): the columnar fold when the operator declares one, else the
    per-group resolver. Returns (keep mask over the stream, counters)."""
    helper = CompactionIterator(
        _EmptyIter(), icmp, snapshots, bottommost_level=bottommost,
        merge_operator=merge_operator, range_del_agg=rd,
        blob_resolver=blob_resolver,
    )
    fold = (merge_operator.columnar_fold()
            if merge_operator is not None else None)
    if fold is not None:
        return _fold_complex_columnar(
            kv, order, cx_flags, cover, trailer_override, seqs, vtypes,
            np.asarray(sorted(snapshots), dtype=np.uint64), bottommost,
            fold, helper, patch)
    rows = order[cx_flags]
    operands = int((vtypes[rows] == int(dbformat.ValueType.MERGE)).sum())
    keep, groups = _resolve_complex_mask(
        kv, order, cx_flags, trailer_override, seqs, vtypes, helper, patch)
    return keep, {"groups": groups, "operand_rows": operands,
                  "rows_folded": int(len(rows) - keep[cx_flags].sum())}


def count_fold(stats, ctr: dict, usec: int) -> None:
    stats.merge_groups += ctr["groups"]
    stats.merge_operand_rows += ctr["operand_rows"]
    stats.merge_rows_folded += ctr["rows_folded"]
    stats.merge_fold_usec += usec


def _verify_columnar_output(env, icmp, table_options, path, kv, vtypes,
                            sel) -> None:
    """Protection check for ONE columnar-plane output file: the entries
    on disk must be exactly the surviving input rows `sel` (post
    merge-resolution value patching, seq zeroing exempt) — the
    per-entry-checksum form of paranoid_file_checks, shared by the serial
    columnar, sharded-device, and pipelined paths."""
    from toplingdb_tpu.compaction.compaction_job import verify_output_table
    from toplingdb_tpu.utils import protection as _p

    pb = table_options.protection_bytes_per_key
    expected: dict[int, int] = {}
    for r in sel.tolist():
        ik = kv.ikey(r)
        cs = _p.truncate(
            _p.protect_entry(int(vtypes[r]), ik[:-8], kv.value(r)), pb)
        expected[cs] = expected.get(cs, 0) + 1
    verify_output_table(env, path, icmp, table_options, expected, len(sel))


def _outputs_from_files(env, files, kv, vtypes, stats, icmp=None,
                        table_options=None):
    """Output FileMetaData list from write_tables_columnar tuples: empty
    outputs deleted, blob refs decoded from surviving BLOB_INDEX rows —
    shared by the serial columnar and pipelined paths. With icmp +
    table_options given and protection active, every output is re-read
    and verified against its surviving input rows before it is returned
    (_verify_columnar_output)."""
    from toplingdb_tpu.db.blob import decode_blob_index
    from toplingdb_tpu.db.version_edit import FileMetaData

    pb = (getattr(table_options, "protection_bytes_per_key", 0)
          if table_options is not None else 0)
    outputs = []
    for fnum, path, props, smallest, largest, sel in files:
        if props.num_entries == 0 and props.num_range_deletions == 0:
            env.delete_file(path)
            continue
        if pb:
            _verify_columnar_output(env, icmp, table_options, path, kv,
                                    vtypes, sel)
        blob_refs = set()
        bi_mask = vtypes[sel] == dbformat.ValueType.BLOB_INDEX
        if bi_mask.any():
            for oi in sel[bi_mask]:
                blob_refs.add(decode_blob_index(kv.value(oi))[0])
        meta = FileMetaData(
            number=fnum, file_size=env.get_file_size(path),
            smallest=smallest, largest=largest,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno,
            num_entries=props.num_entries,
            num_deletions=props.num_deletions,
            num_range_deletions=props.num_range_deletions,
            blob_refs=sorted(blob_refs),
        )
        outputs.append(meta)
        stats.output_bytes += meta.file_size
        stats.output_files += 1
        stats.output_records += props.num_entries
        stats.count_output(table_options, props, meta.file_size)
    return outputs


def _run_device_compaction_columnar(env, dbname, icmp, compaction, table_cache,
                                    table_options, snapshots, merge_operator,
                                    new_file_number, creation_time,
                                    device_name, column_family=(0, "default"),
                                    blob_resolver=None):
    from toplingdb_tpu.compaction.compaction_job import (
        surviving_tombstone_fragments,
    )
    from toplingdb_tpu.ops.columnar_io import write_tables_columnar

    t0 = time.time()
    stats = CompactionStats(device=device_name)
    stats.input_bytes = compaction.total_input_bytes()
    stats.input_files = len(compaction.all_inputs())

    # Pipelined data plane first: scan, sort+GC and encode overlap at
    # key-range-shard granularity (ops/pipeline.py), byte-identical
    # outputs. Shapes it does not cover fall through to the serial path
    # below with clean stats.
    from toplingdb_tpu.ops import pipeline as pl

    if pl.pipeline_enabled(table_options):
        pstats = CompactionStats(device=device_name)
        pstats.input_bytes = stats.input_bytes
        pstats.input_files = stats.input_files
        try:
            pfiles, pkv, pvt, _ptombs = pl.run_pipelined(
                env, dbname, icmp, compaction, table_cache, table_options,
                snapshots, new_file_number, creation_time, pstats,
                MAX_DEVICE_KEY_BYTES, column_family,
                merge_operator=merge_operator, blob_resolver=blob_resolver,
            )
        except (pl.PipelineIneligible, NotSupported) as e:
            # The serial path decides (and re-raises what it must); the
            # job's stats say why it left the pipeline.
            stats.pipeline_exit = f"{type(e).__name__}: {e}"
        else:
            with _tele.span("compaction.finish"):
                outputs = _outputs_from_files(env, pfiles, pkv, pvt, pstats,
                                              icmp=icmp,
                                              table_options=table_options)
                # The job's columnar buffers (hundreds of MB) go back to
                # the allocator here, inside the span, not at some return.
                del pfiles, pkv, pvt, _ptombs
            pstats.work_time_usec = int((time.time() - t0) * 1e6)
            return outputs, pstats
    try:
        with _tele.span("compaction.input_scan"):
            kv, rd, shards, parts = _collect_raw_columnar(
                compaction, table_cache, icmp,
                want_uploads=not _host_sort(), stats=stats,
            )
    except NotSupported:
        raise _FallbackToEntries()  # >2GiB columnar buffers etc.
    stats.input_scan_usec = int((time.time() - t0) * 1e6)
    stats.input_records = kv.n
    if kv.n == 0 and rd.empty():
        stats.work_time_usec = int((time.time() - t0) * 1e6)
        return [], stats
    if kv.n and int(kv.key_lens.max()) - 8 > MAX_DEVICE_KEY_BYTES:
        # Exceeds the sort-operand budget (and the 4096B native block-builder
        # key buffer); the entries path re-checks and routes to the CPU.
        raise _FallbackToEntries()
    t_fin = time.time()
    mkb = max(4, int(kv.key_lens.max()) - 8) if kv.n else 4
    col = any_complex = None
    prep = _tele.span("pipeline.chunk_prepare")  # host numpy before upload
    if not _host_sort():
        # Host-sort mode gets seq/vtype from the fused native merge+GC —
        # gathering trailers here would be pure waste at bench scale.
        seq_a, vt_a = pl._range_seq_vtype(kv, 0, kv.n)
        col = types.SimpleNamespace(seq=seq_a, vtype=vt_a, n=kv.n)
        _VT = dbformat.ValueType
        any_complex = bool(kv.n) and bool(np.any(
            (col.vtype == int(_VT.MERGE))
            | (col.vtype == int(_VT.SINGLE_DELETION))
        ))
    stats.finish_usec += int((time.time() - t_fin) * 1e6)
    streamed = False
    order = zero_flags = cx_flags = None
    has_complex = False
    try:
        # Range tombstones ride the fused kernels as a per-row max-covering
        # seqno side input (stripe-clamped on host; fragments are few).
        t_cov = time.time()
        cover = None
        if not rd.empty():
            with _tele.span("pipeline.tombstone_cover") as sp:
                frags = list(fragment_tombstones(rd.tombstones(),
                                                 icmp.user_comparator))
                # Per ORIGINAL row (concat order), stripe-clamped: the
                # pipeline's cover over the parts' row spans.
                bounds = np.cumsum([0] + [p_.n for p_ in parts])
                cover = pl._cover_for_ranges(
                    kv, list(zip(bounds[:-1].tolist(), bounds[1:].tolist())),
                    frags, np.asarray(sorted(snapshots), dtype=np.uint64))
                sp.tag(fragments=len(frags))
            stats.tombstone_fragments = len(frags)
            stats.tombstone_cover_usec += int((time.time() - t_cov) * 1e6)
        stats.host_compute_usec += int((time.time() - t_cov) * 1e6)
        prep.finish()
        if _host_sort():
            t_hc = time.time()
            rs = np.cumsum([0] + [p_.n for p_ in parts], dtype=np.int64)
            order, zero_flags, cx_flags, has_complex, seq_a, vt_a = \
                ck.host_fused_full(
                    kv.key_buf, kv.key_offs, kv.key_lens, mkb,
                    snapshots, compaction.bottommost, cover,
                    run_starts=rs,
                )
            stats.host_compute_usec += int((time.time() - t_hc) * 1e6)
            col = types.SimpleNamespace(seq=seq_a, vtype=vt_a, n=kv.n)
        elif shards is not None:
            # Upload + dispatch through the mesh seam: serial mode uploads
            # every shard up front to the default device (device_put and
            # jit dispatch are async; shard s+1's transfer streams while
            # shard s computes, and fused_uniform_shard_start enqueues
            # each D2H copy so results stream back); TPULSM_MESH_COMPACT
            # places shards round-robin over every chip instead, double-
            # buffered per chip (ops/mesh_compaction.py).
            from toplingdb_tpu.ops import mesh_compaction as mc

            t_up = time.time()
            with _tele.span("pipeline.upload", shards=len(shards)) as up:
                finish_shard, _mesh_on = mc.dispatch_shards(
                    shards, cover, snapshots, compaction.bottommost,
                    stats=stats, any_complex=bool(any_complex),
                    trace=_tele.current_handle(),
                )
                up.tag(h2d_bytes=stats.h2d_bytes)
            # Upload-enqueue span (device_put is async, so this is a lower
            # bound; the blocking download waits below add the rest).
            stats.transfer_time_usec += int((time.time() - t_up) * 1e6)
            if not any_complex and \
                    getattr(table_options, "format", "block") in (
                        "block", "zip", "single_fast"):
                # STREAM each shard's survivors straight into the SST
                # writer — block or single_fast building overlaps the
                # remaining shards' compute + download. (The zip writer
                # drains the feed, then encodes.)
                streamed = True
            else:
                # Complex groups must fold BEFORE the writer hoists its
                # value-buffer pointers, so collect every shard first;
                # the shard programs still overlap each other.
                orders, zfs, cxs = [], [], []
                for s_i, (_chunks, ranges) in enumerate(shards):
                    t_dn = time.time()
                    with _tele.span("pipeline.merge_gc", shard=s_i,
                                    device=True):
                        o, z, cx, hc = finish_shard(s_i)
                    stats.device_wait_usec += int(
                        (time.time() - t_dn) * 1e6)
                    lmap = _ranges_lmap(ranges)
                    orders.append(lmap[o])
                    zfs.append(z)
                    cxs.append(cx)
                    has_complex = has_complex or hc
                order = (np.concatenate(orders) if orders
                         else np.empty(0, np.int32))
                zero_flags = (np.concatenate(zfs) if zfs
                              else np.empty(0, bool))
                cx_flags = (np.concatenate(cxs) if cxs
                            else np.empty(0, bool))
        else:
            order, zero_flags, cx_flags, has_complex = \
                ck.fused_encode_sort_gc(
                    kv.key_buf, kv.key_offs, kv.key_lens, mkb, snapshots,
                    compaction.bottommost, cover,
                )
    except NotSupported:
        raise _FallbackToEntries()  # non-dense buffers, >cap snapshots etc.

    t_fin = time.time()
    trailer_override = np.full(kv.n, -1, dtype=np.int64)
    seqs = col.seq.copy()
    vtypes = col.vtype
    if not streamed:
        # packed trailer for seq 0 is just the type byte. Complex rows'
        # zero flags are provisional — _process_group re-decides them.
        zmask = zero_flags if not has_complex else (zero_flags & ~cx_flags)
        zero_orig = order[zmask]
        trailer_override[zero_orig] = col.vtype[zero_orig].astype(np.int64)
        seqs[zero_orig] = 0
        if has_complex:
            vtypes = vtypes.copy()
            t_rs = time.time()
            with _tele.span("pipeline.merge_fold") as sp:
                keep, ctr = fold_complex(
                    kv, order, cx_flags,
                    None if cover is None else cover[order],
                    trailer_override, seqs, vtypes, icmp, snapshots,
                    compaction.bottommost, merge_operator,
                    None if rd.empty() else rd, blob_resolver)
                order = order[keep]
                sp.tag(**ctr)
            stats.resolve_usec = int((time.time() - t_rs) * 1e6)
            count_fold(stats, ctr, stats.resolve_usec)
        order_feed = order
    else:
        # Shard streaming: each chunk's trailers/seqs land just before the
        # writer consumes it (the writer reads both arrays per native call).
        def _shard_order_chunks():
            for s_i, (_chunks, ranges) in enumerate(shards):
                t_dn = time.time()
                with _tele.span("pipeline.merge_gc", shard=s_i,
                                device=True):
                    o, z, _cx, hc = finish_shard(s_i)
                stats.device_wait_usec += int((time.time() - t_dn) * 1e6)
                if hc:
                    raise _FallbackToEntries()
                with _tele.span("pipeline.unpack", shard=s_i):
                    lmap = _ranges_lmap(ranges)
                    order_s = lmap[o]
                    zero_s = order_s[z]
                    trailer_override[zero_s] = \
                        col.vtype[zero_s].astype(np.int64)
                    seqs[zero_s] = 0
                yield order_s

        order_feed = _shard_order_chunks()

    tombs = surviving_tombstone_fragments(
        rd, snapshots, compaction.bottommost, icmp.user_comparator
    )
    # finish = zero-seq patch + tombstone finalize, MINUS the separately
    # reported complex-group resolve that ran inside this window.
    stats.finish_usec += max(
        0, int((time.time() - t_fin) * 1e6) - stats.resolve_usec)
    outputs = []
    t_wr = time.time()
    if order is None or len(order) or tombs:
        try:
            if getattr(table_options, "format", "block") == "zip":
                from toplingdb_tpu.table.zip_table import (
                    write_tables_zip_columnar,
                )

                files = write_tables_zip_columnar(
                    env, dbname, new_file_number, icmp, table_options, kv,
                    order_feed, trailer_override, vtypes, seqs, tombs,
                    creation_time if creation_time is not None
                    else int(time.time()),
                    max_output_file_size=compaction.max_output_file_size,
                    column_family=column_family, stats=stats,
                )
            else:
                files = write_tables_columnar(
                    env, dbname, new_file_number, icmp, table_options, kv,
                    order_feed, trailer_override, vtypes, seqs, tombs,
                    creation_time if creation_time is not None
                    else int(time.time()),
                    max_output_file_size=compaction.max_output_file_size,
                    column_family=column_family, stats=stats,
                )
        except NotSupported:
            # Native builder refused (oversized key / restart overflow):
            # the per-entry path handles these (partials already cleaned).
            raise _FallbackToEntries()
        with _tele.span("compaction.finish"):
            outputs = _outputs_from_files(env, files, kv, vtypes, stats,
                                          icmp=icmp,
                                          table_options=table_options)
    stats.encode_write_usec = int((time.time() - t_wr) * 1e6)
    stats.work_time_usec = int((time.time() - t0) * 1e6)
    return outputs, stats


def run_device_compaction(env, dbname, icmp, compaction, table_cache,
                          table_options, snapshots, merge_operator=None,
                          compaction_filter=None, new_file_number=None,
                          creation_time=None, device_name="tpu",
                          blob_resolver=None, blob_gc=None,
                          column_family=(0, "default")):
    """Device counterpart of run_compaction_to_tables — same signature shape,
    byte-identical outputs (including output cutting). Jobs without a
    compaction filter take the fully-columnar native fast path; the rest
    stream through the per-entry generator. Active blob GC rewrites values,
    so it routes through the per-entry path.

    `device_name` is checked, not trusted: the job raises NotSupported
    unless JAX runs on the platform it names (device_runtime), and it
    raises when the native library is missing — the device path has no
    quiet detour through the CPU or through per-entry Python. A kernel
    that fails to compile raises out of here as it is."""
    from toplingdb_tpu import native

    device_runtime.require_device(device_name)
    if native.lib() is None:
        raise NotSupported(
            "the device compaction path needs the native library "
            "(toplingdb_tpu/native: g++ missing or the build failed)")
    _tele.watch_gc()  # the process that holds the chip watches its heap
    gc_us0, gc_n0 = _tele.gc_totals()
    with device_runtime.count_compiles() as jit:
        outputs, stats = _run_device_compaction(
            env, dbname, icmp, compaction, table_cache, table_options,
            snapshots, merge_operator, compaction_filter, new_file_number,
            creation_time, device_name, blob_resolver, blob_gc,
            column_family)
    gc_us1, gc_n1 = _tele.gc_totals()
    stats.gc_pause_usec = gc_us1 - gc_us0
    stats.gc_collections = gc_n1 - gc_n0
    stats.jit_compiles = jit.compiled
    stats.jit_cache_hits = jit.cache_hits
    stats.jit_compile_usec = int(jit.seconds * 1e6)
    return outputs, stats


def _run_device_compaction(env, dbname, icmp, compaction, table_cache,
                           table_options, snapshots, merge_operator,
                           compaction_filter, new_file_number, creation_time,
                           device_name, blob_resolver, blob_gc,
                           column_family):
    if (compaction_filter is None
            and (blob_gc is None or not blob_gc.active)
            and not getattr(table_options, "properties_collector_factories", None)
            and getattr(table_options, "format", "block") in (
                "block", "zip", "single_fast")
            and getattr(table_options, "index_type", "binary") == "binary"
            and icmp.user_comparator.name() == dbformat.BYTEWISE.name()):
        try:
            return _run_device_compaction_columnar(
                env, dbname, icmp, compaction, table_cache, table_options,
                snapshots, merge_operator, new_file_number, creation_time,
                device_name, column_family, blob_resolver=blob_resolver,
            )
        except _FallbackToEntries:
            pass  # eligibility, not error handling: per-entry semantics
    t0 = time.time()
    stats = CompactionStats(device=device_name)
    stats.input_bytes = compaction.total_input_bytes()
    stats.input_files = len(compaction.all_inputs())
    entries, rd = collect_raw_entries(compaction, table_cache, icmp, stats)
    stats.input_records = len(entries)
    rd_or_none = None if rd.empty() else rd
    stream = device_gc_entries(
        entries, icmp, snapshots, compaction.bottommost,
        merge_operator=merge_operator, compaction_filter=compaction_filter,
        compaction_filter_level=compaction.output_level, rd=rd_or_none,
        blob_resolver=blob_resolver,
    )
    tombs = surviving_tombstone_fragments(
        rd, snapshots, compaction.bottommost, icmp.user_comparator
    )
    if blob_gc is not None and blob_gc.active:
        stream = blob_gc.rewrite(stream)
    try:
        outputs = build_outputs(
            env, dbname, icmp, compaction, stream, tombs, new_file_number,
            table_options, stats,
            creation_time if creation_time is not None else int(time.time()),
            column_family=column_family,
        )
    except BaseException:
        if blob_gc is not None:
            blob_gc.abort()
        raise
    if blob_gc is not None:
        blob_gc.finish()
    stats.work_time_usec = int((time.time() - t0) * 1e6)
    return outputs, stats
