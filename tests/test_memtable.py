import struct

import numpy as np
import pytest

from toplingdb_tpu.db.dbformat import (
    InternalKeyComparator,
    ValueType,
    make_internal_key,
    split_internal_key,
)
from toplingdb_tpu.db.memtable import MemTable, create_memtable_rep
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.utils import statistics as st

ICMP = InternalKeyComparator()
MAXSEQ = 2**56 - 1


def test_versions_newest_first():
    m = MemTable(ICMP)
    m.add(1, ValueType.VALUE, b"k", b"v1")
    m.add(5, ValueType.VALUE, b"k", b"v5")
    m.add(3, ValueType.VALUE, b"k", b"v3")
    assert [s for s, _, _ in m.entries_for_key(b"k", MAXSEQ)] == [5, 3, 1]
    # Snapshot at 4 hides seq 5.
    assert [s for s, _, _ in m.entries_for_key(b"k", 4)] == [3, 1]


def test_iteration_order():
    m = MemTable(ICMP)
    m.add(2, ValueType.VALUE, b"b", b"vb")
    m.add(1, ValueType.VALUE, b"a", b"va")
    m.add(3, ValueType.DELETION, b"a", b"")
    keys = [split_internal_key(k)[:2] for k, _ in m.iter_entries()]
    assert keys == [(b"a", 3), (b"a", 1), (b"b", 2)]


def test_range_tombstone_coverage():
    m = MemTable(ICMP)
    m.add(10, ValueType.RANGE_DELETION, b"c", b"g")
    assert m.covering_tombstone_seq(b"c", MAXSEQ) == 10
    assert m.covering_tombstone_seq(b"f", MAXSEQ) == 10
    assert m.covering_tombstone_seq(b"g", MAXSEQ) == 0  # end exclusive
    assert m.covering_tombstone_seq(b"b", MAXSEQ) == 0
    assert m.covering_tombstone_seq(b"d", 9) == 0  # snapshot before tombstone


def test_memtable_iterator_protocol():
    m = MemTable(ICMP)
    for i in range(10):
        m.add(i + 1, ValueType.VALUE, b"k%02d" % i, b"v%d" % i)
    it = m.new_iterator()
    it.seek_to_first()
    assert it.valid()
    ks = []
    while it.valid():
        ks.append(split_internal_key(it.key())[0])
        it.next()
    assert ks == [b"k%02d" % i for i in range(10)]
    it.seek(make_internal_key(b"k05", MAXSEQ, 0x7F))
    assert split_internal_key(it.key())[0] == b"k05"
    it.prev()
    assert split_internal_key(it.key())[0] == b"k04"
    it.seek_to_last()
    assert split_internal_key(it.key())[0] == b"k09"


def test_iterator_stable_under_concurrent_insert():
    m = MemTable(ICMP)
    for i in range(0, 20, 2):
        m.add(i + 1, ValueType.VALUE, b"k%02d" % i, b"v")
    it = m.new_iterator()
    it.seek_to_first()
    seen = [split_internal_key(it.key())[0]]
    # Insert new keys while iterating; iterator must not skip/repeat.
    m.add(100, ValueType.VALUE, b"k01", b"new")
    it.next()
    seen.append(split_internal_key(it.key())[0])
    assert seen == [b"k00", b"k01"]


def test_hash_prefix_rep_matches_skiplist_semantics(tmp_path):
    """hash_skiplist rep (prefix-bucketed): same DB behavior as the default
    rep — ordered scans, reverse iteration, version visibility."""
    import random

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    rng = random.Random(5)
    dumps = {}
    for rep in ("skiplist", "hash_skiplist"):
        d = str(tmp_path / rep)
        db = DB.open(d, Options(write_buffer_size=1 << 22, memtable_rep=rep,
                                disable_auto_compactions=True))
        model = {}
        for i in range(3000):
            k = b"key%05d" % rng.randrange(2000)
            if rng.random() < 0.85:
                v = b"v%05d" % i
                db.put(k, v); model[k] = v
            else:
                db.delete(k); model.pop(k, None)
        rng = random.Random(5)  # same sequence for both reps
        for k in (b"key00000", b"key01000", b"key01999", b"zzz"):
            assert db.get(k) == model.get(k)
        it = db.new_iterator()
        it.seek_to_first()
        fwd = list(it.entries())
        assert fwd == sorted(model.items())
        it2 = db.new_iterator()
        it2.seek_to_last()
        rev = []
        while it2.valid():
            rev.append((it2.key(), it2.value()))
            it2.prev()
        assert rev == fwd[::-1]
        it3 = db.new_iterator()
        it3.seek(b"key01000")
        assert it3.valid()
        dumps[rep] = fwd
        db.close()
    assert dumps["skiplist"] == dumps["hash_skiplist"]


def test_hash_prefix_rep_unit():
    from toplingdb_tpu.db.memtable import HashPrefixRep

    r = HashPrefixRep(prefix_len=3)
    import random

    rng = random.Random(1)
    keys = []
    for i in range(500):
        uk = b"%03d-%04d" % (rng.randrange(20), i)
        skey = (uk, rng.randrange(1 << 32))
        keys.append(skey)
        r.insert(skey, b"v%d" % i)
    assert len(r) == 500
    ordered = [k for k, _ in r.iter_all()]
    assert ordered == sorted(keys)
    # Cursor walk equals iter_all.
    walked = []
    pos = r.pos_first()
    while pos is not None:
        walked.append(r.entry_at(pos)[0])
        pos = r.pos_next(pos)
    assert walked == ordered
    # seek_ge / seek_lt on bucket boundaries.
    mid = sorted(keys)[250]
    assert r.entry_at(r.pos_seek_ge(mid))[0] == mid
    lt = r.pos_seek_lt(mid)
    assert r.entry_at(lt)[0] == sorted(keys)[249]
    assert r.pos_seek_lt(sorted(keys)[0]) is None
    assert r.pos_seek_ge((b"\xff\xff\xff\xff", 0)) is None


def test_columnar_flush_byte_parity(tmp_path):
    """The single-native-call columnar flush (MemTable.export_columnar +
    write_tables_columnar) must produce byte-identical SSTs to the
    per-entry iterator path (reference FlushJob::WriteLevel0Table,
    /root/reference/db/flush_job.cc:833) — including deletions, duplicate
    user keys across seqnos, and range tombstones."""
    import random

    from toplingdb_tpu.db import filename as fn
    from toplingdb_tpu.db.dbformat import InternalKeyComparator, ValueType
    from toplingdb_tpu.db.flush_job import flush_memtable_to_table
    from toplingdb_tpu.db.memtable import (
        MemTable,
        NativeSkipListRep,
        PyVectorRep,
    )
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableOptions

    try:
        native_rep = NativeSkipListRep()
    except RuntimeError:
        import pytest

        pytest.skip("native library unavailable")
    icmp = InternalKeyComparator()
    env = default_env()

    def fill(mem, n=20000):
        rng = random.Random(7)
        seq = 1
        for i in range(n):
            k = b"k%07d" % rng.randrange(n // 3)
            t = (ValueType.DELETION if rng.random() < 0.1
                 else ValueType.VALUE)
            v = b"" if t == ValueType.DELETION else b"val%d" % i
            mem.add(seq, t, k, v)
            seq += 1
        mem.add(seq, ValueType.RANGE_DELETION, b"k0000100", b"k0000300")

    m1 = MemTable(icmp, native_rep)
    fill(m1)
    m2 = MemTable(icmp, PyVectorRep())
    fill(m2)
    d = str(tmp_path)
    topts = TableOptions(block_size=4096)
    # The parity assertion is only meaningful if the fast path actually
    # engages for m1 — a silent fallback would compare slow vs slow.
    from toplingdb_tpu.db import flush_job as fj

    calls = []
    orig = fj._flush_columnar

    def spy(*a, **kw):
        r = orig(*a, **kw)
        calls.append(r)
        return r

    fj._flush_columnar = spy
    try:
        meta1 = flush_memtable_to_table(env, d, 11, icmp, [m1], topts,
                                        creation_time=5)
    finally:
        fj._flush_columnar = orig
    assert calls and calls[0] is not None, "columnar fast path did not run"
    meta2 = flush_memtable_to_table(env, d, 12, icmp, [m2], topts,
                                    creation_time=5)
    b1 = open(fn.table_file_name(d, 11), "rb").read()
    b2 = open(fn.table_file_name(d, 12), "rb").read()
    assert b1 == b2
    assert meta1.num_entries == meta2.num_entries == 20000
    assert meta1.num_range_deletions == 1
    assert meta1.smallest == meta2.smallest
    assert meta1.largest == meta2.largest


# ---------------------------------------------------------------------------
# The native skiplist takes a run of records at a time (SkipList::insert_run:
# sorted, searches interleaved under prefetch, key and value inline in the
# node). One property for every entry point: whatever the keys, the values
# and the length of the run, the list reads back as a plain sorted list.
# tests/test_write_plane.py drives the fused plane with the same helpers.
# ---------------------------------------------------------------------------

V, D, M, SD = (ValueType.VALUE, ValueType.DELETION, ValueType.MERGE,
               ValueType.SINGLE_DELETION)
RUN_LENGTHS = (1, 2, 15, 16, 17, 500, 5000)
MAXPACKED = 2**64 - 1
_PADDED = (b"", b"\0", b"ab", b"ab\0", b"ab\0\0", b"abcdefgh", b"abcdefgh\0",
           b"abcdefgh\0\0", b"abcdefg", b"abcdefg\0", b"\0\0\0\0\0\0\0\0",
           b"\0\0\0\0\0\0\0\0\0")


def _be8(x) -> bytes:
    return struct.pack(">Q", int(x))


def _shape_k8(rng, n):
    return [(V, _be8(x), b"v%020d" % i)
            for i, x in enumerate(rng.integers(0, 4 * n + 8, n))]


def _shape_k16_equal_prefix(rng, n):
    return [(V, b"prefix__" + _be8(x), b"%08d" % i)
            for i, x in enumerate(rng.integers(0, 4 * n + 8, n))]


def _shape_k0to7(rng, n):
    return [(V, rng.bytes(int(rng.integers(0, 8))), b"v%d" % i)
            for i in range(n)]


def _shape_zero_padded_prefix(rng, n):
    return [(V, _PADDED[int(rng.integers(0, len(_PADDED)))], b"v%d" % i)
            for i in range(n)]


def _shape_k200(rng, n):
    return [(V, b"p" * 190 + _be8(x) + b"t" * int(rng.integers(0, 3)),
             b"v%d" % i)
            for i, x in enumerate(rng.integers(0, 4 * n + 8, n))]


def _shape_empty_values(rng, n):
    return [(V, _be8(x), b"") for x in rng.integers(0, 4 * n + 8, n)]


def _shape_values_over_127(rng, n):
    return [(V, _be8(x), bytes([i % 251]) * int(rng.integers(128, 400)))
            for i, x in enumerate(rng.integers(0, 4 * n + 8, n))]


def _shape_same_key_3_times(rng, n):
    keys = rng.integers(0, 4 * n + 8, n // 3 + 1)
    return [(V, _be8(keys[i % len(keys)]), b"v%d" % i) for i in range(n)]


def _shape_deletes_and_merges(rng, n):
    # Merges stay under half of a batch: the fused plane leaves a
    # merge-heavy batch to insert_wb.
    kinds = (V, V, V, D, M, SD, V, D)
    out = []
    for i, x in enumerate(rng.integers(0, n + 8, n)):
        t = kinds[int(rng.integers(0, len(kinds)))]
        out.append((t, _be8(x), b"" if t in (D, SD) else b"m%d" % i))
    return out


KEY_SHAPES = {f.__name__[len("_shape_"):]: f for f in (
    _shape_k8, _shape_k16_equal_prefix, _shape_k0to7,
    _shape_zero_padded_prefix, _shape_k200, _shape_empty_values,
    _shape_values_over_127, _shape_same_key_3_times,
    _shape_deletes_and_merges)}


def make_ops(shape: str, n: int):
    return KEY_SHAPES[shape](np.random.default_rng([n, len(shape)]), n)


def make_batch(ops, pb: int = 0) -> WriteBatch:
    wb = WriteBatch(protection_bytes_per_key=pb)
    add = {V: wb.put, M: wb.merge}
    for t, k, v in ops:
        if t in add:
            add[t](k, v)
        else:
            (wb.delete if t == D else wb.single_delete)(k)
    return wb


def sorted_rows(ops, first_seq: int):
    """The plain list: ((user key, ~(seq<<8|type)), value), in the list's
    order; a record's sequence is first_seq + its place in the batch."""
    return sorted(((k, MAXPACKED - ((first_seq + i) << 8 | int(t))), v)
                  for i, (t, k, v) in enumerate(ops))


def check_reads_back(mem: MemTable, want) -> None:
    """Forward and backward iteration, seek_ge / seek_lt of every key and
    the flush's export against the plain sorted list."""
    rep = mem._rep
    assert len(rep) == len(want)
    assert list(rep.iter_all()) == want
    back, pos = [], rep.pos_last()
    while pos is not None:                      # seek_lt of every key
        back.append(rep.entry_at(pos))
        pos = rep.pos_seek_lt(back[-1][0])
    assert back == want[::-1]
    first_of = {}
    for row in want:
        first_of.setdefault(row[0][0], row)
    for row in want:
        (uk, inv), _ = row
        assert rep.entry_at(rep.pos_seek_ge((uk, inv))) == row
        assert rep.entry_at(rep.pos_seek_ge((uk, 0))) == first_of[uk]
    assert rep.pos_seek_lt(want[0][0]) is None
    kv, seqs, vtypes = mem.export_columnar()
    got = []
    for i in range(len(seqs)):
        ko, kl = int(kv.key_offs[i]), int(kv.key_lens[i])
        vo, vl = int(kv.val_offs[i]), int(kv.val_lens[i])
        ik = bytes(kv.key_buf[ko:ko + kl])
        packed = int(seqs[i]) << 8 | int(vtypes[i])
        assert ik[-8:] == struct.pack("<Q", packed)
        got.append(((ik[:-8], MAXPACKED - packed),
                    bytes(kv.val_buf[vo:vo + vl])))
    assert got == want


def _apply_insert(mem, ops, first_seq, pb):
    for i, (t, k, v) in enumerate(ops):       # runs of one
        mem.add(first_seq + i, t, k, v)


def _apply_insert_batch(mem, ops, first_seq, pb):
    """The rep's flat-column entry point by itself (MemTable.add_batch,
    its caller, leaves batches under four records to add())."""
    rows = [(k, MAXPACKED - ((first_seq + i) << 8 | int(t)), v)
            for i, (t, k, v) in enumerate(ops)]
    kl = np.array([len(r[0]) for r in rows], np.int32)
    vl = np.array([len(r[2]) for r in rows], np.int32)
    mem._rep.insert_batch(
        np.frombuffer(b"".join(r[0] for r in rows) + b"\0", np.uint8),
        np.cumsum(kl, dtype=np.int64) - kl, kl,
        np.array([r[1] for r in rows], np.uint64),
        np.frombuffer(b"".join(r[2] for r in rows) + b"\0", np.uint8),
        np.cumsum(vl, dtype=np.int64) - vl, vl, len(rows))


def _apply_insert_wb(mem, ops, first_seq, pb):
    assert make_batch(ops, pb).insert_into(mem, first_seq) == len(ops)


ENTRY_POINTS = {"insert": (_apply_insert, 0),
                "insert_batch": (_apply_insert_batch, 0),
                "insert_wb": (_apply_insert_wb, 0),
                "insert_wb_prot": (_apply_insert_wb, 8)}


def replayed(ops):
    """The same records with other values: what a WAL replay over a
    half-flushed memtable hands the list (an exact (key, seq) duplicate)."""
    return [(t, k, v if t in (D, SD) else b"again-" + v[:40])
            for t, k, v in ops]


@pytest.mark.parametrize("n", RUN_LENGTHS)
@pytest.mark.parametrize("shape", sorted(KEY_SHAPES))
@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_run_insert_reads_back_sorted(entry, shape, n):
    apply, pb = ENTRY_POINTS[entry]
    stats = st.Statistics()
    mem = MemTable(ICMP, create_memtable_rep("skiplist"),
                   protection_bytes=pb, stats=stats)
    ops = make_ops(shape, n)
    first_seq = 1000
    apply(mem, ops, first_seq, pb)
    check_reads_back(mem, sorted_rows(ops, first_seq))
    if entry != "insert_batch":         # booked by the MemTable
        assert mem.num_entries == n
        assert mem.num_deletes == sum(t in (D, SD) for t, _, _ in ops)
    if entry.startswith("insert_wb"):   # one wire image, one native call
        assert stats.get_ticker_count(st.MEMTABLE_INSERT_RECORDS) == n
        assert stats.get_ticker_count(st.MEMTABLE_INSERT_RUN_RECORDS) == (
            n if n >= 2 else 0)
    # An exact (key, seq) duplicate replaces the value; the count stands.
    again = replayed(ops)
    apply(mem, again, first_seq, pb)
    check_reads_back(mem, sorted_rows(again, first_seq))
    # And a later batch lands among the rows that are there.
    more = make_ops(shape, min(n, 40) + 1)
    apply(mem, more, first_seq + n, pb)
    check_reads_back(mem, sorted(sorted_rows(again, first_seq)
                                 + sorted_rows(more, first_seq + n)))
