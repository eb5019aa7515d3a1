"""MERGE operands and range tombstones on the pipelined data plane
(ops/pipeline.py + ops/device_compaction.py::fold_complex): every case row
by row against the benchmark's plain reference
(benchmark/lib/reference_merge.py) and byte-identical to the serial columnar
program and to the CPU compaction path; and the served deployment
(universal, uint64add, DeleteRange) against the reference's oracle."""

import os
import struct
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))

from lib import reference_merge as ref  # noqa: E402
from lib.workload_merge import MergeWorkload  # noqa: E402

from toplingdb_tpu.db.dbformat import (  # noqa: E402
    InternalKeyComparator, ValueType, make_internal_key,
)
from toplingdb_tpu.utils.merge_operator import UInt64AddOperator  # noqa: E402

ICMP = InternalKeyComparator()
TAIL = b"0" * 8
P, M, D = int(ValueType.VALUE), int(ValueType.MERGE), int(ValueType.DELETION)


class PerGroupAdd(UInt64AddOperator):
    """uint64add without the columnar fold: the per-group resolver."""

    def columnar_fold(self):
        return None


def ukey(k: int) -> bytes:
    return struct.pack(">Q", k) + TAIL


def make_rows(case: str, seed: int, n_keys=900, runs=4, per_run=1500):
    """Seeded input rows of one job: per run (key number, seq, type, value
    number) and the job's range tombstones (seq, lo, hi). Newer runs hold
    higher sequences, as the runs of an LSM do."""
    rng = np.random.default_rng(seed)
    p_put = {"operands_only": 0.0, "operands_on_base": 0.25}.get(case, 0.15)
    p_del = 0.05 if case in ("mixed", "snapshot_between") else 0.0
    out, tombs = [], []
    seq = 1
    for r in range(runs):
        keys = rng.integers(0, n_keys, per_run)
        kind = rng.random(per_run)
        types = np.where(kind < p_put, P, np.where(kind < p_put + p_del, D, M))
        if r == 0 and case != "operands_only":
            types[: per_run // 2] = P  # bases under the operands
        seqs = np.arange(seq, seq + per_run)
        seq += per_run
        vals = rng.integers(0, 1 << 63, per_run, dtype=np.uint64) * 2 + 1
        vals[types == D] = 0
        out.append((keys, seqs, types, vals))
        if case in ("under_range_tombstone", "mixed", "snapshot_between"):
            for _ in range(6):
                lo = int(rng.integers(0, n_keys - 40))
                # Among this run's rows in time, so that it cuts chains.
                tombs.append((int(seqs[int(rng.integers(0, per_run))]), lo,
                              lo + int(rng.integers(1, 40))))
    return out, tombs


def write_runs(env, dbdir, topts, runs, tombs, first_fnum=21):
    import toplingdb_tpu.db.filename as fn
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.table.builder import TableBuilder

    metas = []
    for r, (keys, seqs, types, vals) in enumerate(runs):
        fnum = first_fnum + r
        # A tombstone shares no sequence with a point row: drop the row.
        taken = {t[0] for t in tombs}
        rows = sorted(
            ((ukey(int(k)), int(s), int(t), int(v))
             for k, s, t, v in zip(keys, seqs, types, vals)
             if int(s) not in taken),
            key=lambda x: (x[0], -x[1]))
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, ICMP, topts)
        for uk, s, t, v in rows:
            b.add(make_internal_key(uk, s, t),
                  b"" if t == D else struct.pack("<Q", v))
        lo_s, hi_s = int(seqs[0]), int(seqs[-1])
        for ts, lo, hi in tombs:
            if lo_s <= ts <= hi_s:
                b.add_tombstone(make_internal_key(
                    ukey(lo), ts, ValueType.RANGE_DELETION), ukey(hi))
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum,
            file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno))
    return metas


def run_job(env, dbdir, metas, topts, alloc_base, snapshots, bottommost,
            op, device=True):
    from toplingdb_tpu.compaction.compaction_job import (
        run_compaction_to_tables,
    )
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.ops.device_compaction import run_device_compaction

    nums = iter(range(alloc_base, alloc_base + 100))
    tc = TableCache(env, dbdir, ICMP, topts)
    c = Compaction(level=0, output_level=0 if not bottommost else 2,
                   inputs=list(metas), bottommost=bottommost,
                   max_output_file_size=1 << 62)
    kw = dict(merge_operator=op, new_file_number=lambda: next(nums),
              creation_time=7)
    if device:
        return run_device_compaction(env, dbdir, ICMP, c, tc, topts,
                                     snapshots, device_name="cpu-jax", **kw)
    return run_compaction_to_tables(env, dbdir, ICMP, c, tc, topts,
                                    snapshots, **kw)


def read_outputs(env, dbdir, outs, topts):
    """(key number, seq, type, value number) rows of the output files, read
    with the package's reader (rows of a `Delete` have no value)."""
    from toplingdb_tpu.db.table_cache import TableCache

    tc = TableCache(env, dbdir, ICMP, topts)
    k, s, t, v = [], [], [], []
    for m in outs:
        it = tc.get_reader(m.number).new_iterator()
        it.seek_to_first()
        for ik, val in it.entries():
            assert ik[8:16] == TAIL
            k.append(int.from_bytes(ik[:8], "big"))
            tr = int.from_bytes(ik[-8:], "little")
            s.append(tr >> 8)
            t.append(tr & 0xFF)
            v.append(int.from_bytes(val, "little") if val else 0)
    return (np.array(k, np.uint64), np.array(s, np.uint64),
            np.array(t, np.uint8), np.array(v, np.uint64))


def sst_bytes(dbdir, outs):
    import toplingdb_tpu.db.filename as fn

    return [open(fn.table_file_name(dbdir, m.number), "rb").read()
            for m in outs]


CASES = [
    ("operands_only", False, [], "columnar"),
    ("operands_only", True, [], "columnar"),
    ("operands_on_base", False, [], "columnar"),
    ("operands_on_base", True, [], "columnar"),
    ("under_range_tombstone", False, [], "columnar"),
    ("under_range_tombstone", True, [], "columnar"),
    ("snapshot_between", False, [1700, 3100, 4400], "columnar"),
    ("snapshot_between", True, [1700, 3100, 4400], "columnar"),
    ("mixed", True, [2900], "columnar"),
    ("operands_on_base", True, [], "per_group"),
    ("under_range_tombstone", False, [2500], "per_group"),
]


@pytest.mark.parametrize("mode", ["device", "host"])
@pytest.mark.parametrize("case,bottommost,snapshots,fold", CASES)
def test_pipelined_merge_job(tmp_path, monkeypatch, case, bottommost,
                             snapshots, fold, mode):
    """A job with MERGE rows runs through run_pipelined to the end
    (`pipeline_exit` empty, fold counters set); its output equals the plain
    reference's survivors row by row and, byte for byte, the serial
    columnar program's and the CPU compaction path's."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import compaction_kernels as ck
    from toplingdb_tpu.ops import pipeline as pl
    from toplingdb_tpu.table.builder import TableOptions

    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 4)
    if mode == "host":
        monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    else:
        monkeypatch.delenv("TPULSM_HOST_SORT", raising=False)
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    op = UInt64AddOperator() if fold == "columnar" else PerGroupAdd()
    runs, tombs = make_rows(case, seed=len(case) * 7 + bottommost)
    metas = write_runs(env, dbdir, topts, runs, tombs)

    out_pipe, st = run_job(env, dbdir, metas, topts, 2000, snapshots,
                           bottommost, op)
    assert st.pipelined and st.pipeline_exit == ""
    assert st.merge_operand_rows > 0 and st.merge_groups > 0
    assert st.merge_rows_folded > 0 and st.merge_fold_usec > 0
    assert (st.tombstone_fragments > 0) == bool(tombs)
    if mode == "device":
        assert st.host_compute_usec == 0

    taken = {t[0] for t in tombs}
    cols = [np.concatenate([r[i] for r in runs]) for i in range(4)]
    live = ~np.isin(cols[1], list(taken))
    want = ref.survivors(
        cols[0][live].astype(np.uint64), cols[1][live].astype(np.uint64),
        cols[2][live].astype(np.uint8), cols[3][live].astype(np.uint64),
        tuple(np.array([t[i] for t in tombs], dtype=np.uint64)
              for i in range(3)),
        snapshots, bottommost)
    got = read_outputs(env, dbdir, out_pipe, topts)
    assert ref.rows_wrong(want, got) == 0
    assert st.output_records == len(want[0])

    monkeypatch.setattr(pl, "pipeline_enabled", lambda *_a: False)
    out_serial, st_s = run_job(env, dbdir, metas, topts, 3000, snapshots,
                               bottommost, op)
    assert not st_s.pipelined
    assert st_s.merge_operand_rows == st.merge_operand_rows
    assert st_s.merge_groups == st.merge_groups
    out_cpu, _ = run_job(env, dbdir, metas, topts, 4000, snapshots,
                         bottommost, op, device=False)
    assert sst_bytes(dbdir, out_pipe) == sst_bytes(dbdir, out_serial)
    assert sst_bytes(dbdir, out_pipe) == sst_bytes(dbdir, out_cpu)


def test_columnar_fold_sends_odd_groups_to_the_per_group_resolver(
        tmp_path, monkeypatch):
    """Values of another width than the operator's, and single-deletes,
    go through the per-group resolver, their groups alone; results of
    another length land in the value buffer's slack. Byte-identical to the
    CPU path."""
    import toplingdb_tpu.db.filename as fn
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import compaction_kernels as ck
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions

    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 4)
    monkeypatch.delenv("TPULSM_HOST_SORT", raising=False)
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    rng = np.random.default_rng(5)
    metas = []
    for r in range(3):
        rows = []
        for i, k in enumerate(sorted(set(rng.integers(0, 700, 500)))):
            seq = r * 1000 + i + 1
            roll = (int(k) + r) % 11
            if roll == 0:
                rows.append((ukey(int(k)), seq, M, b"\x05"))  # 1-byte operand
            elif roll == 1:
                rows.append((ukey(int(k)), seq,
                             int(ValueType.SINGLE_DELETION), b""))
            elif roll == 2 and r == 0:
                rows.append((ukey(int(k)), seq, P, b"\x01\x02\x03"))
            else:
                rows.append((ukey(int(k)), seq, M if r else P,
                             struct.pack("<Q", seq * 3 + 1)))
        w = env.new_writable_file(fn.table_file_name(dbdir, 21 + r))
        b = TableBuilder(w, ICMP, topts)
        for uk, s, t, v in rows:
            b.add(make_internal_key(uk, s, t), v)
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=21 + r,
            file_size=env.get_file_size(fn.table_file_name(dbdir, 21 + r)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno))
    op = UInt64AddOperator()
    out_pipe, st = run_job(env, dbdir, metas, topts, 2000, [1500], False, op)
    assert st.pipelined and st.pipeline_exit == ""
    out_cpu, _ = run_job(env, dbdir, metas, topts, 4000, [1500], False, op,
                         device=False)
    assert sst_bytes(dbdir, out_pipe) == sst_bytes(dbdir, out_cpu)


@pytest.mark.parametrize("shape", ["words", "bytes", "mixed_lengths",
                                   "read_only_values"])
def test_columnar_fold_equals_the_per_group_resolver(shape):
    """The columnar fold against the per-group resolver on one flagged
    stream, for each way it reads the keys and writes the values: user keys
    of whole 8-byte words, of another length (compared byte by byte), of
    several lengths (left to the per-group resolver), and a value buffer
    that came read-only."""
    from toplingdb_tpu.ops import device_compaction as dc
    from toplingdb_tpu.ops.columnar_io import ColumnarKV

    rng = np.random.default_rng(11)
    ukl = {"words": 16, "bytes": 10}.get(shape, 16)
    rows = []
    for k in sorted(set(rng.integers(0, 400, 300).tolist())):
        uk = struct.pack(">Q", k).rjust(ukl, b"k")
        if shape == "mixed_lengths" and k % 3 == 0:
            uk += b"x"
        n = int(rng.integers(1, 6))
        seqs = sorted(rng.choice(np.arange(1, 5000), n, replace=False),
                      reverse=True)
        for i, sq in enumerate(seqs):
            t = P if (i == n - 1 and k % 2) else (D if k % 7 == 0 and i == 1
                                                   else M)
            rows.append((uk, int(sq), t,
                         b"" if t == D else struct.pack("<Q", sq * 3 + 1)))
    rows.sort(key=lambda r: (r[0], -r[1]))

    def stream():
        keys = [make_internal_key(uk, sq, t) for uk, sq, t, _ in rows]
        kl = np.array([len(x) for x in keys], np.int32)
        vl = np.array([len(r[3]) for r in rows], np.int32)
        vb = np.frombuffer(b"".join(r[3] for r in rows), np.uint8)
        kv = ColumnarKV(
            np.frombuffer(b"".join(keys), np.uint8).copy(),
            (np.cumsum(kl) - kl).astype(np.int32), kl,
            vb if shape == "read_only_values" else vb.copy(),
            (np.cumsum(vl) - vl).astype(np.int32), vl)
        return (kv, np.array([r[1] for r in rows], np.uint64),
                np.array([r[2] for r in rows], np.int32),
                np.full(len(rows), -1, np.int64))

    got = {}
    for name, op in (("columnar", UInt64AddOperator()),
                     ("per_group", PerGroupAdd())):
        kv, seqs, vts, tro = stream()
        keep, ctr = dc.fold_complex(
            kv, np.arange(len(rows), dtype=np.int32),
            np.ones(len(rows), bool), None, tro, seqs, vts, ICMP, [2500],
            False, op, None, None)
        # What the writer would emit: which slot of a group carries a
        # result is the resolver's own business.
        got[name] = ([(kv.ikey(r)[:-8],
                       int(tro[r]) if tro[r] >= 0
                       else int.from_bytes(kv.ikey(r)[-8:], "little"),
                       kv.value(r)) for r in np.flatnonzero(keep).tolist()],
                     ctr)
    assert got["columnar"] == got["per_group"]
    assert got["columnar"][1]["rows_folded"] > 0


def test_covered_merge_base_folds_onto_nothing():
    """A range tombstone between a base and the operands above it deletes
    the base: the operands fold onto nothing (CompactionIterator, the
    reference of every plane)."""
    from toplingdb_tpu.compaction.compaction_iterator import (
        CompactionIterator,
    )
    from toplingdb_tpu.db.range_del import RangeDelAggregator, RangeTombstone

    rd = RangeDelAggregator(ICMP.user_comparator)
    rd.add(RangeTombstone(5, b"a", b"c"))
    it = CompactionIterator(None, ICMP, [], merge_operator=UInt64AddOperator(),
                            range_del_agg=rd)
    group = [(9, M, struct.pack("<Q", 100)), (2, P, struct.pack("<Q", 1))]
    (ik, v), = it._process_group(b"b", group)
    assert v == struct.pack("<Q", 100)
    assert ik == make_internal_key(b"b", 9, ValueType.VALUE)


@pytest.mark.parametrize("order", ["bytewise", "reverse"])
def test_fragment_sweep_matches_a_pairwise_fragmenter(order):
    """The one sweep against the definition, pair by pair: a fragment
    between two neighbouring boundary points carries every sequence whose
    tombstone spans both."""
    import random

    from toplingdb_tpu.db import dbformat, range_del
    from toplingdb_tpu.db.range_del import RangeTombstone

    ucmp = (dbformat.BYTEWISE if order == "bytewise"
            else dbformat.REVERSE_BYTEWISE)
    lt = (lambda a, b: a < b) if order == "bytewise" else (lambda a, b: a > b)

    def pairwise(ts):
        import functools

        points = sorted({t.begin for t in ts} | {t.end for t in ts},
                        key=functools.cmp_to_key(ucmp.compare))
        return [RangeTombstone(s, a, b) for a, b in zip(points, points[1:])
                for s in sorted({t.seq for t in ts
                                 if not lt(a, t.begin) and not lt(t.end, b)},
                                reverse=True)]

    random.seed(3)
    for _ in range(200):
        ts = [RangeTombstone(random.randint(1, 6),
                             bytes([random.randint(97, 105)]),
                             bytes([random.randint(97, 105)]))
              for _ in range(random.randint(1, 12))]
        assert range_del.fragment_tombstones(ts, ucmp) == pairwise(ts)


def test_lower_bounds_match_the_bisect(tmp_path):
    """The one-search placement of tombstone bounds equals the key-by-key
    bisect, for bounds shorter, longer and equal to the rows' keys."""
    from toplingdb_tpu.ops import pipeline as pl
    from toplingdb_tpu.ops.columnar_io import ColumnarKV

    rng = np.random.default_rng(2)
    nums = np.sort(rng.choice(5000, 800, replace=False))
    nums = np.repeat(nums, rng.integers(1, 3, len(nums)))
    n = len(nums)
    ik = np.zeros((n, 24), dtype=np.uint8)
    ik[:, :8] = nums.astype(">u8").view(np.uint8).reshape(n, 8)
    ik[:, 8:16] = rng.integers(0, 3, (n, 8))  # ties on the first word
    order = np.lexsort(tuple(ik[:, c] for c in range(15, -1, -1)))
    ik = ik[order]
    kv = ColumnarKV(ik.reshape(-1).copy(), np.arange(n, dtype=np.int32) * 24,
                    np.full(n, 24, np.int32), np.zeros(0, np.uint8),
                    np.zeros(n, np.int32), np.zeros(n, np.int32))
    keys = [bytes(ik[i, :16]) for i in rng.integers(0, n, 40)]
    keys += [k[:5] for k in keys[:10]] + [k + b"\x00" for k in keys[:10]]
    keys += [k[:8] for k in keys[:10]] + [b"", b"\xff" * 20]
    for lo, hi in ((0, n), (100, 500)):
        got = pl._lower_bounds(kv, lo, hi, keys)
        assert list(got) == [pl._lower_bound(kv, lo, hi, k) - lo
                             for k in keys]


@pytest.mark.parametrize("reopen", [False, True])
def test_served_universal_merge_matches_the_oracle(tmp_path, reopen):
    """The deployment at a tiny size: universal compaction, uint64add,
    DeleteRange in the fill and in the merge stream; gets, a multi_get and
    a scan equal the reference's oracle, before and after a reopen."""
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.table.builder import TableOptions

    n, ops = 6000, 30000
    wl = MergeWorkload(n, ops, seed=11 + reopen, every=1000, width=50)
    kb, vb = wl.encode(0, n + ops)
    tb, te, _ = wl.tombstones(n + ops)
    opts = Options(create_if_missing=True, compaction_style="universal",
                   merge_operator=UInt64AddOperator(),
                   write_buffer_size=128 << 10,
                   table_options=TableOptions(block_size=1024),
                   level0_file_num_compaction_trigger=4)
    db = DB.open(str(tmp_path / "db"), opts)
    w = t = 0
    while w < n + ops:
        wb = WriteBatch()
        for j in range(w, w + 500):
            if j < n:
                wb.put(kb[16 * j:16 * j + 16], vb[8 * j:8 * j + 8])
            else:
                wb.merge(kb[16 * j:16 * j + 16], vb[8 * j:8 * j + 8])
        db.write(wb)
        w += 500
        while t < len(wl.tomb_at) and wl.tomb_at[t] <= w:
            db.delete_range(tb[16 * t:16 * t + 16], te[16 * t:16 * t + 16])
            t += 1
    db.wait_for_compactions()
    if reopen:
        db.close()
        db = DB.open(str(tmp_path / "db"), opts)
    oracle = ref.Oracle(wl, n + ops)
    rng = np.random.default_rng(3)
    keys = np.concatenate([rng.integers(0, n + 200, 600).astype(np.uint64),
                           wl.tomb_lo[:20] + np.uint64(3)])
    want = oracle.expected(keys)
    assert sum(x is None for x in want) > 20
    kbytes = ref.key_bytes(keys).tobytes()
    klist = [kbytes[16 * i:16 * i + 16] for i in range(len(keys))]
    assert [db.get(k) for k in klist] == want
    assert db.multi_get(klist) == want
    lo = int(wl.tomb_lo[-1]) - 100
    span = np.arange(max(0, lo), max(0, lo) + 400, dtype=np.uint64)
    want_scan = [(k, v) for k, v in zip(
        (ref.key_bytes(span).tobytes()[16 * i:16 * i + 16]
         for i in range(len(span))), oracle.expected(span)) if v is not None]
    it = db.new_iterator()
    it.seek(want_scan[0][0])
    got = []
    while it.valid() and len(got) < len(want_scan):
        got.append((it.key(), it.value()))
        it.next()
    assert got == want_scan
    db.close()
