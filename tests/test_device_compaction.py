"""Device data plane: parity with the CPU CompactionIterator, byte-identical
SST outputs, and the serialized worker boundary."""

import random
import struct

import pytest

from toplingdb_tpu.compaction.compaction_iterator import CompactionIterator
from toplingdb_tpu.db.dbformat import (
    InternalKeyComparator,
    ValueType,
    make_internal_key,
)
from toplingdb_tpu.db.range_del import RangeDelAggregator, RangeTombstone
from toplingdb_tpu.ops import compaction_kernels as ck
from toplingdb_tpu.ops.device_compaction import device_gc_entries
from toplingdb_tpu.utils.merge_operator import UInt64AddOperator

ICMP = InternalKeyComparator()


class ListIter:
    def __init__(self, items):
        self._items = items
        self._i = 0

    def valid(self):
        return self._i < len(self._items)

    def key(self):
        return self._items[self._i][0]

    def value(self):
        return self._items[self._i][1]

    def next(self):
        self._i += 1


def cpu_reference(entries, snaps, bottom, rd=None, op=None):
    srt = sorted(entries, key=lambda kv: ICMP.sort_key(kv[0]))
    ci = CompactionIterator(
        ListIter(srt), ICMP, snaps, bottommost_level=bottom,
        merge_operator=op, range_del_agg=rd,
    )
    return list(ci.entries())


def gen_workload(rng, n, key_space=200, with_merge=True):
    entries = []
    for seq in range(1, n + 1):
        k = b"key%04d" % rng.randrange(key_space)
        r = rng.random()
        if r < 0.6:
            entries.append((make_internal_key(k, seq, ValueType.VALUE),
                            b"v%06d" % seq))
        elif r < 0.75:
            entries.append((make_internal_key(k, seq, ValueType.DELETION), b""))
        elif r < 0.85 and with_merge:
            entries.append((make_internal_key(k, seq, ValueType.MERGE),
                            struct.pack("<Q", seq)))
        else:
            entries.append((make_internal_key(k, seq, ValueType.SINGLE_DELETION), b""))
    return entries


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_device_matches_cpu_state_machine(seed):
    rng = random.Random(seed)
    entries = gen_workload(rng, rng.randrange(50, 400))
    maxseq = len(entries)
    snaps = sorted(rng.sample(range(1, maxseq + 1), rng.randrange(0, 4)))
    bottom = rng.random() < 0.5
    rd = None
    if rng.random() < 0.6:
        rd = RangeDelAggregator(ICMP.user_comparator)
        for _ in range(rng.randrange(1, 4)):
            a = b"key%04d" % rng.randrange(200)
            b = b"key%04d" % rng.randrange(200)
            if a > b:
                a, b = b, a
            if a != b:
                rd.add(RangeTombstone(rng.randrange(1, maxseq), a, b))
        if rd.empty():
            rd = None
    op = UInt64AddOperator()
    want = cpu_reference(entries, snaps, bottom, rd, op)
    got = list(device_gc_entries(
        entries, ICMP, snaps, bottom, merge_operator=op, rd=rd
    ))
    assert got == want


def test_device_empty_and_single():
    assert list(device_gc_entries([], ICMP, [], True)) == []
    e = [(make_internal_key(b"k", 1, ValueType.VALUE), b"v")]
    assert list(device_gc_entries(e, ICMP, [], False)) == e


def test_device_unsorted_input_is_merged():
    # Entries arrive as concatenated runs, unsorted overall.
    run1 = [(make_internal_key(b"b", 2, ValueType.VALUE), b"v2"),
            (make_internal_key(b"d", 4, ValueType.VALUE), b"v4")]
    run2 = [(make_internal_key(b"a", 1, ValueType.VALUE), b"v1"),
            (make_internal_key(b"c", 3, ValueType.VALUE), b"v3")]
    got = list(device_gc_entries(run1 + run2, ICMP, [], False))
    assert [k[:-8] for k, _ in got] == [b"a", b"b", b"c", b"d"]


def test_full_sst_byte_parity(tmp_path):
    """run_compaction_to_tables vs run_device_compaction: identical bytes."""
    from toplingdb_tpu.compaction.compaction_job import run_compaction_to_tables
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops.device_compaction import run_device_compaction
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions

    env = default_env()
    dbdir = str(tmp_path)
    rng = random.Random(99)
    topts = TableOptions(block_size=512)

    # Build two input "runs" as real SSTs.
    metas = []
    seq = 1
    for fnum in (11, 12):
        entries = []
        for i in range(300):
            k = b"key%05d" % rng.randrange(400)
            entries.append((make_internal_key(k, seq, ValueType.VALUE),
                            b"val%08d" % seq))
            seq += 1
        entries.sort(key=lambda kv: ICMP.sort_key(kv[0]))
        dedup = [e for i, e in enumerate(entries)
                 if i == 0 or ICMP.compare(entries[i - 1][0], e[0]) != 0]
        import toplingdb_tpu.db.filename as fn
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, ICMP, topts)
        for k, v in dedup:
            b.add(k, v)
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum, file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno, largest_seqno=props.largest_seqno,
        ))

    tc = TableCache(env, dbdir, ICMP, topts)
    c = Compaction(level=0, output_level=1, inputs=metas, bottommost=True,
                   max_output_file_size=16 * 1024)

    def make_alloc(start):
        state = [start]

        def alloc():
            state[0] += 1
            return state[0]

        return alloc

    out_cpu, _ = run_compaction_to_tables(
        env, dbdir, ICMP, c, tc, topts, [], new_file_number=make_alloc(100),
        creation_time=12345,
    )
    out_dev, _ = run_device_compaction(
        env, dbdir, ICMP, c, tc, topts, [], new_file_number=make_alloc(200),
        creation_time=12345, device_name="cpu-jax",
    )
    assert len(out_cpu) == len(out_dev) >= 1
    import toplingdb_tpu.db.filename as fn
    for mc, md in zip(out_cpu, out_dev):
        bc = open(fn.table_file_name(dbdir, mc.number), "rb").read()
        bd = open(fn.table_file_name(dbdir, md.number), "rb").read()
        assert bc == bd  # bit-identical SSTs (BASELINE.json north-star check)
        assert mc.smallest == md.smallest and mc.largest == md.largest


def test_subprocess_worker_end_to_end(tmp_db_path):
    from toplingdb_tpu.compaction.executor import SubprocessCompactionExecutorFactory
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    opts = Options(
        write_buffer_size=8 * 1024,
        compaction_executor_factory=SubprocessCompactionExecutorFactory(device="cpu"),
    )
    with DB.open(tmp_db_path, opts) as db:
        for i in range(3000):
            db.put(b"key%05d" % (i % 1000), b"val%07d" % i)
        db.flush()
        db.compact_range()
        db.wait_for_compactions()
        for k in range(0, 1000, 83):
            last = max(i for i in range(k, 3000, 1000))
            assert db.get(b"key%05d" % k) == b"val%07d" % last
        v = db.versions.current
        assert sum(f.num_entries for _, f in v.all_files()) == 1000


def test_device_executor_in_db(tmp_db_path):
    from toplingdb_tpu.compaction.executor import DeviceCompactionExecutorFactory
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    opts = Options(
        write_buffer_size=8 * 1024,
        compaction_executor_factory=DeviceCompactionExecutorFactory(device="cpu-jax"),
    )
    with DB.open(tmp_db_path, opts) as db:
        for i in range(3000):
            db.put(b"key%05d" % (i % 1000), b"val%07d" % i)
        db.delete_range(b"key00100", b"key00200")
        db.flush()
        db.compact_range()
        assert db.get(b"key00150") is None
        assert db.get(b"key00250") is not None
        assert db._compaction_scheduler.last_error is None


def test_columnar_fast_path_byte_parity(tmp_path):
    """Single-output jobs take the native columnar path; bytes must equal the
    per-entry CPU path exactly."""
    from toplingdb_tpu.compaction.compaction_job import run_compaction_to_tables
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops.device_compaction import run_device_compaction
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions
    import toplingdb_tpu.db.filename as fn

    env = default_env()
    dbdir = str(tmp_path)
    rng = random.Random(5)
    topts = TableOptions(block_size=512)
    metas = []
    seq = 1
    for fnum in (21, 22, 23):
        entries = []
        for i in range(250):
            k = b"key%05d" % rng.randrange(300)
            t = ValueType.VALUE if rng.random() < 0.8 else ValueType.DELETION
            entries.append((make_internal_key(k, seq, t), b"val%06d" % seq))
            seq += 1
        entries.sort(key=lambda kv: ICMP.sort_key(kv[0]))
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, ICMP, topts)
        for k, v in entries:
            b.add(k, v)
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum, file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno, largest_seqno=props.largest_seqno,
        ))
    tc = TableCache(env, dbdir, ICMP, topts)
    # Single-output (huge max size) with snapshots: fast-path eligible.
    c = Compaction(level=0, output_level=2, inputs=metas, bottommost=True,
                   max_output_file_size=1 << 62)

    def mk(start):
        s = [start]

        def alloc():
            s[0] += 1
            return s[0]

        return alloc

    out_cpu, _ = run_compaction_to_tables(
        env, dbdir, ICMP, c, tc, topts, [200, 400], new_file_number=mk(500),
        creation_time=7,
    )
    out_dev, stats = run_device_compaction(
        env, dbdir, ICMP, c, tc, topts, [200, 400], new_file_number=mk(600),
        creation_time=7, device_name="cpu-jax",
    )
    assert len(out_cpu) == len(out_dev) == 1
    bc = open(fn.table_file_name(dbdir, out_cpu[0].number), "rb").read()
    bd = open(fn.table_file_name(dbdir, out_dev[0].number), "rb").read()
    assert bc == bd
    assert out_cpu[0].smallest == out_dev[0].smallest
    assert out_cpu[0].largest == out_dev[0].largest
    assert out_cpu[0].num_entries == out_dev[0].num_entries


def test_http_dcompact_service_end_to_end(tmp_db_path):
    """HTTP worker service: DB routes compactions over HTTP + shared dir
    (the curl+NFS transport shape of the reference's dcompact)."""
    from toplingdb_tpu.compaction.dcompact_service import (
        DcompactWorkerService, HttpCompactionExecutorFactory,
    )
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    svc = DcompactWorkerService(device="cpu")
    port = svc.start()
    try:
        opts = Options(
            write_buffer_size=8 * 1024,
            compaction_executor_factory=HttpCompactionExecutorFactory(
                [f"http://127.0.0.1:{port}"], device="cpu",
            ),
        )
        with DB.open(tmp_db_path, opts) as db:
            for i in range(3000):
                db.put(b"key%05d" % (i % 1000), b"val%07d" % i)
            db.flush()
            db.compact_range()
            db.wait_for_compactions()
            for k in range(0, 1000, 83):
                last = max(i for i in range(k, 3000, 1000))
                assert db.get(b"key%05d" % k) == b"val%07d" % last
        assert svc.jobs_done >= 1
    finally:
        svc.stop()


def test_http_dcompact_fallback_on_dead_worker(tmp_db_path):
    """Unreachable worker → fallback-to-local keeps the DB correct."""
    from toplingdb_tpu.compaction.dcompact_service import (
        HttpCompactionExecutorFactory,
    )
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    opts = Options(
        write_buffer_size=8 * 1024,
        compaction_executor_factory=HttpCompactionExecutorFactory(
            ["http://127.0.0.1:1"], device="cpu", timeout=0.5,
        ),
    )
    with DB.open(tmp_db_path, opts) as db:
        for i in range(2000):
            db.put(b"key%05d" % (i % 500), b"val%07d" % i)
        db.flush()
        db.compact_range()
        for k in range(0, 500, 41):
            last = max(i for i in range(k, 2000, 500))
            assert db.get(b"key%05d" % k) == b"val%07d" % last


def test_device_in_stripe_tombstone_not_masked_by_newer_stripe():
    """Regression (model-check seed 23): two range tombstones covering a key
    straddle a snapshot; the in-stripe (older) tombstone must still delete
    the value even though the max covering seq is above the snapshot —
    device and host must agree."""
    k = b"key084"
    entries = [(make_internal_key(k, 219, ValueType.VALUE), b"v000322")]
    rd = RangeDelAggregator(ICMP.user_comparator)
    rd.add(RangeTombstone(262, b"key031", b"key091"))  # below snapshot: kills
    rd.add(RangeTombstone(283, b"key063", b"key137"))  # above snapshot
    snaps = [276, 286]
    want = cpu_reference(entries, snaps, True, rd, None)
    got = list(device_gc_entries(entries, ICMP, snaps, True, rd=rd))
    assert got == want
    assert got == [], "value@219 must be deleted by tombstone@262 (stripe 0)"


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_host_sort_twin_matches_fused_kernel(seed):
    """fused_encode_sort_gc_host (the TPULSM_HOST_SORT numpy twin used when
    no accelerator is reachable) must produce IDENTICAL outputs to the jax
    fused kernel."""
    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = random.Random(seed)
    entries = gen_workload(rng, rng.randrange(30, 300))
    entries.sort(key=lambda kv: ICMP.sort_key(kv[0]))  # any order works; vary
    if seed % 2:
        rng.shuffle(entries)
    key_buf = bytearray()
    offs, lens = [], []
    for ik, _ in entries:
        offs.append(len(key_buf))
        lens.append(len(ik))
        key_buf += ik
    kb = np.frombuffer(bytes(key_buf), dtype=np.uint8)
    ko = np.array(offs, np.int64)
    kl = np.array(lens, np.int64)
    mkb = max(4, int(kl.max()) - 8)
    snaps = sorted(rng.sample(range(1, len(entries) + 2),
                              rng.randrange(0, 4)))
    bottom = rng.random() < 0.5
    a = ck.fused_encode_sort_gc(kb, ko, kl, mkb, snaps, bottom)
    b = ck.fused_encode_sort_gc_host(kb, ko, kl, mkb, snaps, bottom)
    assert np.array_equal(a[0], b[0]), "survivor order differs"
    assert np.array_equal(a[1], b[1]), "zero flags differ"
    assert np.array_equal(a[2], b[2]), "complex flags differ"
    assert a[3] == b[3], "has_complex differs"


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_host_sort_twin_varlen_keys_and_big_seqnos(seed):
    """Host-twin parity where it's riskiest: variable-length keys (length
    tie-break, prefix ordering) and seqnos crossing the 2^24/2^32 word
    boundaries of the device's split-word sort."""
    import numpy as np

    from toplingdb_tpu.db.dbformat import ValueType, make_internal_key
    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = random.Random(seed)
    entries = []
    for i in range(rng.randrange(50, 250)):
        klen = rng.randrange(1, 24)
        uk = bytes(rng.randrange(97, 100) for _ in range(klen))  # a-c: dups
        seq = rng.choice([rng.randrange(1, 1 << 10),
                          rng.randrange(1 << 23, 1 << 25),
                          rng.randrange(1 << 31, 1 << 40)])
        t = ValueType.VALUE if rng.random() < 0.8 else ValueType.DELETION
        entries.append((make_internal_key(uk, seq, t), b"v%d" % i))
    key_buf = bytearray()
    offs, lens = [], []
    for ik, _ in entries:
        offs.append(len(key_buf)); lens.append(len(ik)); key_buf += ik
    kb = np.frombuffer(bytes(key_buf), dtype=np.uint8)
    ko = np.array(offs, np.int64); kl = np.array(lens, np.int64)
    mkb = max(4, int(kl.max()) - 8)
    snaps = sorted(rng.sample(range(1, 1 << 40), rng.randrange(0, 5)))
    bottom = rng.random() < 0.5
    a = ck.fused_encode_sort_gc(kb, ko, kl, mkb, snaps, bottom)
    b = ck.fused_encode_sort_gc_host(kb, ko, kl, mkb, snaps, bottom)
    assert np.array_equal(a[0], b[0])
    assert np.array_equal(a[1], b[1])
    assert np.array_equal(a[2], b[2])
    assert a[3] == b[3]


def test_host_sort_tombstone_path_byte_parity(tmp_path, monkeypatch):
    """TPULSM_HOST_SORT=1 covers the tombstone-bearing columnar branch too:
    same SST bytes as the jax path."""
    import os

    from toplingdb_tpu.compaction.executor import DeviceCompactionExecutorFactory
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    outs = {}
    for host in (0, 1):
        if host:
            monkeypatch.setenv("TPULSM_HOST_SORT", "1")
        else:
            monkeypatch.delenv("TPULSM_HOST_SORT", raising=False)
        d = str(tmp_path / f"db{host}")
        o = Options(write_buffer_size=1 << 20, disable_auto_compactions=True,
                    compaction_executor_factory=DeviceCompactionExecutorFactory(
                        device="cpu-jax"))
        with DB.open(d, o) as db:
            for i in range(3000):
                db.put(b"key%05d" % (i % 2000), b"v%05d" % i)
            snap = db.get_snapshot()  # pins the tombstone through compaction
            db.delete_range(b"key00500", b"key01500")
            db.flush()
            from unittest import mock

            with mock.patch("time.time", lambda: 1753750123.0):
                db.compact_range()
            snap.release()
            ssts = sorted(f for f in os.listdir(d) if f.endswith(".sst"))
            outs[host] = [open(os.path.join(d, f), "rb").read()
                          for f in ssts]
    assert len(outs[0]) == len(outs[1]) and outs[0], "no outputs"
    for x, y in zip(outs[0], outs[1]):
        assert x == y, "host-sort tombstone path bytes differ from jax path"


def test_multi_shard_parity(tmp_path, monkeypatch):
    """The serial branch cuts a job of several shards into user-key-range
    shards (per-shard device programs, stitched survivor orders); bytes
    must equal
    the single-shard device path and the CPU path — both uniform-length and
    variable-length keys."""
    from toplingdb_tpu.compaction.compaction_job import run_compaction_to_tables
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import pipeline as pl
    from toplingdb_tpu.ops.device_compaction import run_device_compaction
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions
    import os
    import toplingdb_tpu.db.filename as fn

    env = default_env()
    topts = TableOptions(block_size=512)
    # The serial branch, whatever the shard count.
    monkeypatch.setattr(pl, "pipeline_enabled", lambda *_a: False)
    for mode, keyfmt in (
        ("uniform", lambda r: b"key%05d" % r.randrange(400)),
        ("varlen", lambda r: b"k%0*d" % (r.randrange(3, 9), r.randrange(400))),
    ):
        dbdir = str(tmp_path / mode)
        os.makedirs(dbdir)
        rng = random.Random(17)
        metas = []
        seq = 1
        for fnum in (41, 42, 43):
            entries = []
            for _ in range(300):
                t = (ValueType.VALUE if rng.random() < 0.8
                     else ValueType.DELETION)
                entries.append(
                    (make_internal_key(keyfmt(rng), seq, t), b"val%06d" % seq)
                )
                seq += 1
            entries.sort(key=lambda kv: ICMP.sort_key(kv[0]))
            w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
            b = TableBuilder(w, ICMP, topts)
            last = None
            for k, v in entries:
                if last == k:
                    continue
                b.add(k, v)
                last = k
            props = b.finish()
            w.close()
            metas.append(FileMetaData(
                number=fnum,
                file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
                smallest=b.smallest_key, largest=b.largest_key,
                smallest_seqno=props.smallest_seqno,
                largest_seqno=props.largest_seqno,
            ))
        tc = TableCache(env, dbdir, ICMP, topts)

        def mk(base):
            s = [base]

            def alloc():
                s[0] += 1
                return s[0]

            return alloc

        outs = {}
        for shards in (0, 1, 4, 7):
            c = Compaction(level=0, output_level=2, inputs=list(metas),
                           bottommost=True, max_output_file_size=1 << 62)
            if shards:
                # Shard even the small test inputs.
                monkeypatch.setattr(ck, "shard_count",
                                    lambda total_rows, n=shards: n)
                outs[shards], _ = run_device_compaction(
                    env, dbdir, ICMP, c, tc, topts, [250, 600],
                    new_file_number=mk(500 + shards * 20), creation_time=7,
                    device_name="cpu-jax",
                )
            else:
                outs[0], _ = run_compaction_to_tables(
                    env, dbdir, ICMP, c, tc, topts, [250, 600],
                    new_file_number=mk(490), creation_time=7,
                )
        ref = [open(fn.table_file_name(dbdir, m.number), "rb").read()
               for m in outs[0]]
        assert ref, f"{mode}: no outputs"
        for shards in (1, 4, 7):
            got = [open(fn.table_file_name(dbdir, m.number), "rb").read()
                   for m in outs[shards]]
            assert got == ref, f"{mode}: shards={shards} bytes differ"


@pytest.mark.parametrize("shards", [0, 4])
def test_device_columnar_complex_tombstones_snapshots(tmp_path, monkeypatch,
                                                      shards):
    """The columnar device path (NOT the per-entry fallback) must handle a
    job with DeleteRange fragments + MERGE/SINGLE_DELETE groups + 200 live
    snapshots, byte-identical to the CPU path (VERDICT r2 task 2: cover
    rides the fused kernels, complex groups fold host-side in-stream, the
    snapshot cap is bucketed past 64)."""
    import os
    import struct

    from toplingdb_tpu.compaction.compaction_job import run_compaction_to_tables
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.dbformat import MAX_SEQUENCE_NUMBER
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import device_compaction as dc
    from toplingdb_tpu.ops import pipeline as pl
    from toplingdb_tpu.ops.device_compaction import run_device_compaction
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions
    import toplingdb_tpu.db.filename as fn

    env = default_env()
    topts = TableOptions(block_size=512)
    dbdir = str(tmp_path / f"s{shards}")
    os.makedirs(dbdir)
    rng = random.Random(77 + shards)
    if shards:  # several shards, on the serial branch
        monkeypatch.setattr(pl, "pipeline_enabled", lambda *_a: False)
        monkeypatch.setattr(ck, "shard_count", lambda total_rows: shards)

    metas = []
    seq = 1
    for fnum in (61, 62, 63):
        entries = []
        for _ in range(400):
            k = b"key%05d" % rng.randrange(500)
            r = rng.random()
            if r < 0.6:
                entries.append((make_internal_key(k, seq, ValueType.VALUE),
                                b"val%06d" % seq))
            elif r < 0.8:
                entries.append((make_internal_key(k, seq, ValueType.MERGE),
                                struct.pack("<Q", seq % 97)))
            elif r < 0.9:
                entries.append((make_internal_key(k, seq, ValueType.DELETION),
                                b""))
            else:
                entries.append((make_internal_key(
                    k, seq, ValueType.SINGLE_DELETION), b""))
            seq += 1
        entries.sort(key=lambda kv: ICMP.sort_key(kv[0]))
        dedup = [e for i, e in enumerate(entries)
                 if i == 0 or entries[i - 1][0] != e[0]]
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, ICMP, topts)
        for k, v in dedup:
            b.add(k, v)
        # Two range tombstones per file, written into the range-del block.
        for _ in range(2):
            lo = rng.randrange(450)
            begin = b"key%05d" % lo
            end = b"key%05d" % (lo + rng.randrange(10, 60))
            b.add_tombstone(
                make_internal_key(begin, seq, ValueType.RANGE_DELETION), end)
            seq += 1
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum,
            file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno,
        ))
    tc = TableCache(env, dbdir, ICMP, topts)
    snapshots = sorted(rng.sample(range(1, seq), 200))  # > old 64 cap
    op = UInt64AddOperator()

    def mk(base):
        s = [base]

        def alloc():
            s[0] += 1
            return s[0]

        return alloc

    c1 = Compaction(level=0, output_level=2, inputs=list(metas),
                    bottommost=True, max_output_file_size=1 << 62)
    out_cpu, _ = run_compaction_to_tables(
        env, dbdir, ICMP, c1, tc, topts, snapshots, merge_operator=op,
        new_file_number=mk(700), creation_time=7,
    )

    # The per-entry fallback must NOT run: this job must stay columnar.
    def no_fallback(*a, **k):
        raise AssertionError("columnar path fell back to per-entry scan")

    monkeypatch.setattr(dc, "collect_raw_entries", no_fallback)
    c2 = Compaction(level=0, output_level=2, inputs=list(metas),
                    bottommost=True, max_output_file_size=1 << 62)
    out_dev, _ = run_device_compaction(
        env, dbdir, ICMP, c2, tc, topts, snapshots, merge_operator=op,
        new_file_number=mk(800), creation_time=7, device_name="cpu-jax",
    )
    assert len(out_cpu) == len(out_dev) >= 1
    for mc, md in zip(out_cpu, out_dev):
        bc = open(fn.table_file_name(dbdir, mc.number), "rb").read()
        bd = open(fn.table_file_name(dbdir, md.number), "rb").read()
        assert bc == bd, "complex/tombstone columnar path bytes differ"
        assert mc.smallest == md.smallest and mc.largest == md.largest
        assert mc.num_entries == md.num_entries


def test_device_columnar_complex_host_twin_parity(tmp_path, monkeypatch):
    """TPULSM_HOST_SORT=1 twin of the complex/tombstone columnar path."""
    monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    test_device_columnar_complex_tombstones_snapshots(
        tmp_path, monkeypatch, 0)


def _uniform_chunks(rng, L, n_chunks, key_of, vtypes=(ValueType.VALUE,),
                    chunk_rows=None):
    """Presorted runs of internal keys of one length L, as the pipeline
    hands them to the upload: (prepared chunks, raw key bytes, rows)."""
    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    raw, chunks = bytearray(), []
    seq = 1
    for _ in range(n_chunks):
        n = chunk_rows or rng.randrange(5, 200)
        buf = bytearray()
        for k in sorted(key_of(rng) for _ in range(n)):
            buf += make_internal_key(k, seq, rng.choice(vtypes))
            seq += 1
        chunks.append(ck.prepare_uniform_chunk(
            np.frombuffer(bytes(buf), np.uint8), n, L))
        raw += buf
    return chunks, np.frombuffer(bytes(raw), np.uint8), seq - 1


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_plain_upload_matches_host_oracle(seed):
    """The shard's keys go up as they are; the fused program's survivors,
    zero-seq flags and complex-group flags equal the host twin's."""
    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = random.Random(seed)
    L = rng.choice([12, 16, 24])  # internal key len (uk_len = L - 8)
    chunks, raw, rows = _uniform_chunks(
        rng, L, rng.randrange(1, 4),
        lambda r: b"k%0*d" % (L - 9, r.randrange(100)),
        vtypes=(ValueType.VALUE, ValueType.VALUE, ValueType.DELETION,
                ValueType.MERGE))
    snaps = sorted(rng.sample(range(1, rows + 2), rng.randrange(0, 3)))
    h = ck.upload_uniform_shard(chunks)
    assert h["ukb"].shape == (h["packed_lo"].shape[0] * (L - 8),)
    got = ck.fused_uniform_shard_finish(
        ck.fused_uniform_shard_start(h, snaps, True))
    want = ck.host_fused_full(
        raw, np.arange(rows, dtype=np.int64) * L,
        np.full(rows, L, dtype=np.int64), L - 8, snaps, True)
    assert np.array_equal(got[0], want[0]), "survivor order differs"
    assert np.array_equal(got[1], want[1]), "zero-seq flags differ"
    assert np.array_equal(got[2], want[2]), "complex flags differ"
    assert got[3] == want[3] and bool(got[2].any()) == got[3]


def test_one_fused_program_a_row_bucket():
    """The program's shapes come from the row bucket and the key length,
    never from the keys: a shard of long shared prefixes and a shard of
    none, same bucket, compile once (the front-coded upload's suffix
    buffer made a program a power of two of its data-dependent length)."""
    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = random.Random(7)
    L = 29  # uk_len 21: a shape no other test brings
    shared, _, _ = _uniform_chunks(
        rng, L, 2, lambda r: b"p" * 18 + b"%03d" % r.randrange(1000),
        chunk_rows=100)
    distinct, _, _ = _uniform_chunks(
        rng, L, 2, lambda r: bytes(r.randrange(256) for _ in range(21)),
        chunk_rows=100)
    before = ck._fused_uniform_shard_impl._cache_size()
    sizes = []
    for chunks in (shared, distinct):
        h = ck.upload_uniform_shard(chunks)
        assert h["packed_hi"].shape == h["packed_lo"].shape == (256,)
        ck.fused_uniform_shard_finish(
            ck.fused_uniform_shard_start(h, [], False))
        sizes.append(ck._fused_uniform_shard_impl._cache_size())
    assert sizes == [before + 1, before + 1]


@pytest.mark.parametrize("with_covers", [False, True])
def test_shard_upload_bytes_are_rows_times_key_and_word(with_covers):
    """What goes up: p x (uk_len + 8) bytes of keys and trailer words,
    nothing a chunk, and two u32 planes when tombstones cover rows."""
    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = random.Random(11)
    L = 16
    chunks, _, rows = _uniform_chunks(
        rng, L, 3, lambda r: b"k%07d" % r.randrange(10 ** 6))
    covers = None
    if with_covers:
        covers = [np.zeros(c[2], dtype=np.uint64) for c in chunks]
        covers[1][0] = 5
    h = ck.upload_uniform_shard(chunks, covers)
    p = ck._next_pow2(rows)
    want = p * (L - 8 + 8) + (2 * p * 4 if with_covers else 0)
    assert ck.shard_upload_nbytes(h) == want
    assert (h["tomb_hi"] is not None) == with_covers
    assert sum(hasattr(v, "nbytes") for v in h.values()) == (
        5 if with_covers else 3)


@pytest.mark.parametrize("with_tombs", [False, True],
                         ids=["plain", "tombstones"])
@pytest.mark.parametrize("seed", [41, 42, 43])
def test_fused_shard_takes_trailers_over_the_whole_56_bits(seed, with_tombs):
    """The trailer words go up as the keys hold them: rows of ONE chunk
    whose sequence numbers lie anywhere in 0..2^56-1 (zeroed rows beside
    rows past 2^24, 2^32 and 2^55), snapshots on both sides of every gap,
    with and without the tombstone planes, against the host twin."""
    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = random.Random(seed)
    L, n_chunks = 16, 3
    edges = [0, 1, (1 << 24) - 1, 1 << 24, (1 << 32) - 1, 1 << 32,
             (1 << 32) + 5, 1 << 55, (1 << 56) - 1]
    raw, chunks, all_seqs = bytearray(), [], []
    for _ in range(n_chunks):
        n = rng.randrange(40, 120)
        seqs = set(rng.sample(edges, 5))
        while len(seqs) < n:
            seqs.add(rng.randrange(1 << rng.choice([8, 24, 25, 33, 56])))
        rows = sorted(
            ((b"k%07d" % rng.randrange(12), s) for s in seqs),
            key=lambda ks: (ks[0], -ks[1]))  # internal-key order
        buf = b"".join(
            make_internal_key(k, s, rng.choice(
                (ValueType.VALUE, ValueType.VALUE, ValueType.DELETION,
                 ValueType.MERGE)))
            for k, s in rows)
        chunks.append(ck.prepare_uniform_chunk(
            np.frombuffer(buf, np.uint8), n, L))
        raw += buf
        all_seqs += [s for _, s in rows]
    rows = len(all_seqs)
    raw = np.frombuffer(bytes(raw), np.uint8)
    snaps = sorted({(1 << 24) - 2, (1 << 24) + 1, (1 << 32) + 1,
                    (1 << 55) + 1, rng.choice(all_seqs)})
    covers = cover = None
    if with_tombs:
        cover = np.zeros(rows, dtype=np.uint64)
        for r in rng.sample(range(rows), rows // 4):
            # A cover is clamped to its row's snapshot stripe by the host
            # (host_gc_mask): newer than the row, by one or by as much as
            # the stripe has room for, or older than it.
            sq = all_seqs[r]
            top = min([x for x in snaps if x >= sq] + [(1 << 56) - 1])
            cover[r] = rng.choice([min(sq + 1, top), top, sq // 2])
        covers, pos = [], 0
        for c in chunks:
            covers.append(cover[pos:pos + c[2]])
            pos += c[2]
    bottommost = bool(seed % 2)
    got = ck.fused_uniform_shard_finish(ck.fused_uniform_shard_start(
        ck.upload_uniform_shard(chunks, covers), snaps, bottommost))
    want = ck.host_fused_full(
        raw, np.arange(rows, dtype=np.int64) * L,
        np.full(rows, L, dtype=np.int64), L - 8, snaps, bottommost, cover)
    assert np.array_equal(got[0], want[0]), "survivor order differs"
    assert np.array_equal(got[1], want[1]), "zero-seq flags differ"
    assert np.array_equal(got[2], want[2]), "complex flags differ"
    assert got[3] == want[3]


def test_host_merge_runs_matches_full_sort():
    """tpulsm_merge_runs (multi-threaded k-way merge of presorted runs)
    must reproduce
    tpulsm_sort_entries' exact order/new_key/packed outputs."""
    import numpy as np

    from toplingdb_tpu.ops import compaction_kernels as ck

    rng = np.random.default_rng(9)
    # (n_runs, rows_per_run, mixed_lens): the 60k-per-run case crosses the
    # 1<<16 threshold that enables the SPLITTER-PARTITIONED multithread
    # merge; mixed key lengths exercise the len tiebreak + kw padding.
    for n_runs, rows, mixed in ((1, 2000, False), (3, 1500, True),
                                (4, 60_000, False), (5, 1200, True)):
        parts = []
        for _ in range(n_runs):
            n = int(rng.integers(rows // 2, rows + 1))
            uk = np.sort(rng.integers(0, max(10, n // 2), n))
            seqs = rng.integers(1, 1 << 40, n).astype(np.uint64)
            if mixed:
                ks = np.array([(b"%08d" % k)[: 4 + (k % 5)] for k in uk])
                ks = np.array(sorted(ks))
            else:
                ks = np.array([b"%08d" % k for k in uk])
            order = np.lexsort(
                (np.iinfo(np.int64).max - seqs.view(np.int64), ks))
            recs = []
            for oi in order:
                packed = (int(seqs[oi]) << 8) | 1
                recs.append(bytes(ks[oi])
                            + packed.to_bytes(8, "little"))
            parts.append(recs)
        recs = [r for p_ in parts for r in p_]
        buf = np.frombuffer(b"".join(recs), np.uint8)
        lens = np.array([len(r) for r in recs], np.int64)
        offs = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
        ns = [len(p_) for p_ in parts]
        rs = np.cumsum([0] + ns, dtype=np.int64)
        a = ck.host_sort_order(buf, offs, lens)
        b = ck.host_sort_order(buf, offs, lens, run_starts=rs)
        if a is None or b is None:
            import pytest

            pytest.skip("native lib unavailable")
        assert np.array_equal(a[0], b[0]), (n_runs, mixed)
        assert np.array_equal(a[1], b[1])
        assert np.array_equal(a[2], b[2])
        # malformed boundaries must fall back, not corrupt
        bad = rs.copy()
        bad[-1] -= 1
        c = ck.host_sort_order(buf, offs, lens, run_starts=bad)
        assert np.array_equal(a[0], c[0])
