import struct

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.options import FlushOptions, Options, ReadOptions, WriteOptions
from toplingdb_tpu.utils.merge_operator import StringAppendOperator, UInt64AddOperator
from toplingdb_tpu.utils.status import InvalidArgument


def opts(**kw):
    kw.setdefault("write_buffer_size", 32 * 1024)
    return Options(**kw)


def test_open_put_get_close_reopen(tmp_db_path):
    with DB.open(tmp_db_path, opts()) as db:
        db.put(b"a", b"1")
        db.put(b"b", b"2")
        assert db.get(b"a") == b"1"
        assert db.get(b"missing") is None
    with DB.open(tmp_db_path, opts()) as db:
        assert db.get(b"a") == b"1"
        assert db.get(b"b") == b"2"


def test_create_if_missing_false(tmp_db_path):
    with pytest.raises(InvalidArgument):
        DB.open(tmp_db_path, opts(create_if_missing=False))


def test_error_if_exists(tmp_db_path):
    DB.open(tmp_db_path, opts()).close()
    with pytest.raises(InvalidArgument):
        DB.open(tmp_db_path, opts(error_if_exists=True))


def test_overwrite_and_delete(tmp_db_path):
    with DB.open(tmp_db_path, opts()) as db:
        db.put(b"k", b"v1")
        db.put(b"k", b"v2")
        assert db.get(b"k") == b"v2"
        db.delete(b"k")
        assert db.get(b"k") is None
        db.put(b"k", b"v3")
        assert db.get(b"k") == b"v3"


def test_flush_and_read_from_sst(tmp_db_path):
    with DB.open(tmp_db_path, opts()) as db:
        for i in range(100):
            db.put(b"key%04d" % i, b"val%04d" % i)
        db.flush()
        assert db.mem.empty()
        assert len(db.versions.current.files[0]) >= 1
        assert db.get(b"key0050") == b"val0050"
        db.delete(b"key0050")
        db.flush()
        assert db.get(b"key0050") is None  # tombstone in newer L0 file


def test_recovery_replays_wal(tmp_db_path):
    db = DB.open(tmp_db_path, opts())
    db.put(b"durable", b"yes", WriteOptions(sync=True))
    # Simulate crash: drop the handle without close() (no flush).
    db._closed = True
    db2 = DB.open(tmp_db_path, opts())
    assert db2.get(b"durable") == b"yes"
    db2.close()


def test_auto_flush_on_write_buffer_full(tmp_db_path):
    with DB.open(tmp_db_path, opts(write_buffer_size=8 * 1024)) as db:
        for i in range(2000):
            db.put(b"key%06d" % i, b"x" * 30)
        db.wait_for_compactions()
        assert db.versions.current.num_files() > 0
        assert db.get(b"key000000") == b"x" * 30
        assert db.get(b"key001999") == b"x" * 30


def test_snapshot_isolation(tmp_db_path):
    with DB.open(tmp_db_path, opts()) as db:
        db.put(b"k", b"old")
        snap = db.get_snapshot()
        db.put(b"k", b"new")
        db.delete(b"k2")
        assert db.get(b"k", ReadOptions(snapshot=snap)) == b"old"
        assert db.get(b"k") == b"new"
        # Snapshot survives flush.
        db.flush()
        assert db.get(b"k", ReadOptions(snapshot=snap)) == b"old"
        snap.release()


def test_merge_operator(tmp_db_path):
    with DB.open(tmp_db_path, opts(merge_operator=UInt64AddOperator())) as db:
        db.merge(b"c", struct.pack("<Q", 1))
        db.merge(b"c", struct.pack("<Q", 2))
        assert struct.unpack("<Q", db.get(b"c"))[0] == 3
        db.flush()
        db.merge(b"c", struct.pack("<Q", 10))  # operand in mem, base in SST
        assert struct.unpack("<Q", db.get(b"c"))[0] == 13
        db.put(b"c", struct.pack("<Q", 100))   # put resets the chain
        db.merge(b"c", struct.pack("<Q", 1))
        assert struct.unpack("<Q", db.get(b"c"))[0] == 101


def test_merge_across_flush_with_delete(tmp_db_path):
    with DB.open(tmp_db_path, opts(merge_operator=StringAppendOperator())) as db:
        db.put(b"s", b"base")
        db.flush()
        db.delete(b"s")
        db.merge(b"s", b"x")
        db.merge(b"s", b"y")
        assert db.get(b"s") == b"x,y"  # delete cuts the chain from base


def test_delete_range(tmp_db_path):
    with DB.open(tmp_db_path, opts()) as db:
        for i in range(100):
            db.put(b"key%03d" % i, b"v")
        db.delete_range(b"key020", b"key040")
        assert db.get(b"key019") == b"v"
        assert db.get(b"key020") is None
        assert db.get(b"key039") is None
        assert db.get(b"key040") == b"v"
        # Writes after the tombstone are visible.
        db.put(b"key025", b"back")
        assert db.get(b"key025") == b"back"
        # Survives flush and reopen.
        db.flush()
        assert db.get(b"key030") is None
    with DB.open(tmp_db_path, opts()) as db:
        assert db.get(b"key030") is None
        assert db.get(b"key025") == b"back"


def test_write_batch_atomic(tmp_db_path):
    from toplingdb_tpu.db.write_batch import WriteBatch

    with DB.open(tmp_db_path, opts()) as db:
        b = WriteBatch()
        b.put(b"a", b"1")
        b.put(b"b", b"2")
        b.delete(b"a")
        db.write(b)
        assert db.get(b"a") is None
        assert db.get(b"b") == b"2"


def test_reopen_after_many_flushes(tmp_db_path):
    expected = {}
    for round_ in range(3):
        with DB.open(tmp_db_path, opts()) as db:
            for i in range(50):
                k = b"key%03d" % (round_ * 50 + i)
                v = b"r%d" % round_
                db.put(k, v)
                expected[k] = v
            db.flush()
    with DB.open(tmp_db_path, opts()) as db:
        for k, v in expected.items():
            assert db.get(k) == v, k


def test_get_property(tmp_db_path):
    with DB.open(tmp_db_path, opts()) as db:
        db.put(b"a", b"1")
        db.flush()
        assert "L0: 1 files" in db.get_property("tpulsm.stats")
        assert db.get_property("tpulsm.num-files-at-level0") == "1"


def test_blob_files(tmp_db_path):
    """Key-value separation: big values go to .blob files; reads resolve
    transparently through get, iterators, compaction, and reopen."""
    import os

    with DB.open(tmp_db_path, opts(enable_blob_files=True, min_blob_size=100)) as db:
        small = b"s" * 10
        big = b"B" * 5000
        for i in range(200):
            db.put(b"key%03d" % i, big if i % 2 else small)
        db.flush()
        assert any(f.endswith(".blob") for f in os.listdir(tmp_db_path))
        assert db.get(b"key001") == big
        assert db.get(b"key002") == small
        it = db.new_iterator()
        it.seek_to_first()
        vals = [v for _, v in it.entries()]
        assert vals[1] == big and vals[2] == small
        # SSTs must be small (values separated).
        sst_bytes = sum(
            os.path.getsize(f"{tmp_db_path}/{f}")
            for f in os.listdir(tmp_db_path) if f.endswith(".sst")
        )
        assert sst_bytes < 100 * 5000 / 4
        db.compact_range()  # blob indexes pass through compaction
        assert db.get(b"key199") == big
    with DB.open(tmp_db_path, opts(enable_blob_files=True, min_blob_size=100)) as db:
        assert db.get(b"key001") == b"B" * 5000
        assert db.get(b"key002") == b"s" * 10


def test_blob_merge_resolves_base(tmp_db_path):
    """Review regression: merge over a blob-separated base must fold the
    REAL value, not the raw blob index bytes."""
    with DB.open(tmp_db_path, opts(enable_blob_files=True, min_blob_size=100,
                                   merge_operator=StringAppendOperator())) as db:
        big = b"B" * 500
        db.put(b"k", big)
        db.flush()                     # value becomes BLOB_INDEX
        db.merge(b"k", b"tail")
        db.flush()
        db.compact_range()
        assert db.get(b"k") == big + b",tail"
    with DB.open(tmp_db_path, opts(enable_blob_files=True, min_blob_size=100,
                                   merge_operator=StringAppendOperator())) as db:
        assert db.get(b"k") == b"B" * 500 + b",tail"


def test_checkpoint_includes_blob_files(tmp_db_path, tmp_path):
    """Review regression: checkpoints of blob-enabled DBs must be openable."""
    from toplingdb_tpu.utilities.checkpoint import create_checkpoint

    dst = str(tmp_path / "ckpt")
    with DB.open(tmp_db_path, opts(enable_blob_files=True, min_blob_size=100)) as db:
        db.put(b"k", b"B" * 500)
        db.flush()
        create_checkpoint(db, dst)
    with DB.open(dst, opts(enable_blob_files=True, min_blob_size=100)) as db2:
        assert db2.get(b"k") == b"B" * 500


def test_blob_min_size_zero_separates_everything(tmp_db_path):
    import os

    with DB.open(tmp_db_path, opts(enable_blob_files=True, min_blob_size=0)) as db:
        db.put(b"k", b"tiny")
        db.flush()
        assert any(f.endswith(".blob") for f in os.listdir(tmp_db_path))
        assert db.get(b"k") == b"tiny"


def test_wide_column_magic_collision(tmp_db_path):
    from toplingdb_tpu.db.wide_columns import DEFAULT_COLUMN, get_entity

    with DB.open(tmp_db_path, opts()) as db:
        tricky = b"\x00WCE1" + b"\xff\xfe arbitrary binary"
        db.put(b"k", tricky)
        e = get_entity(db, b"k")
        # Must fall back to the default-column view, not raise.
        assert e == {DEFAULT_COLUMN: tricky} or DEFAULT_COLUMN not in e


def test_multi_get_batched(tmp_db_path):
    with DB.open(tmp_db_path, opts(write_buffer_size=8 * 1024)) as db:
        for i in range(2000):
            db.put(b"key%05d" % (i % 600), b"v%07d" % i)
        db.flush()
        db.delete(b"key00005")
        db.delete_range(b"key00100", b"key00110")
        keys = [b"key%05d" % k for k in range(0, 600, 7)] + [b"missing", b"key00005", b"key00105"]
        got = db.multi_get(keys)
        want = [db.get(k) for k in keys]
        assert got == want
        assert db.multi_get([]) == []


def test_multi_get_newest_version_across_levels(tmp_db_path):
    """A key with its newest version in L0 and older versions deeper must not
    be resolved from the deeper file first."""
    with DB.open(tmp_db_path, opts(disable_auto_compactions=True)) as db:
        db.put(b"k", b"old")
        db.put(b"other", b"x")
        db.flush()
        db.compact_range()          # old version now at the bottom level
        db.put(b"k", b"new")
        db.flush()                  # new version in L0
        assert db.multi_get([b"k", b"other"]) == [b"new", b"x"]


def test_write_stall_on_l0_pileup(tmp_db_path):
    with DB.open(tmp_db_path, opts(
        write_buffer_size=4 * 1024, disable_auto_compactions=True,
    )) as db:
        import time

        for r in range(5):
            for i in range(100):
                db.put(b"k%05d" % (r * 100 + i), b"x" * 30)
            db.flush()
        assert len(db.versions.current.files[0]) >= 5
        # Stalls are a no-op while compaction is disabled (bulk-load mode).
        t0 = time.monotonic()
        db._maybe_stall_writes(timeout=1.0)
        assert time.monotonic() - t0 < 0.2
        # Enable compaction and lower the triggers: the stall must hold until
        # L0 drains below the stop trigger (or the timeout).
        db.options.level0_slowdown_writes_trigger = 2
        db.options.level0_stop_writes_trigger = 4
        db.options.disable_auto_compactions = False
        t0 = time.monotonic()
        db._maybe_stall_writes(timeout=3.0)
        dt = time.monotonic() - t0
        assert db._max_l0_files() < 4 or dt >= 3.0
        db.wait_for_compactions()


def test_repair_db(tmp_db_path):
    from toplingdb_tpu.db.repair import repair_db

    with DB.open(tmp_db_path, opts(write_buffer_size=8 * 1024)) as db:
        for i in range(1500):
            db.put(b"key%05d" % i, b"v%05d" % i)
        db.flush()
        db.put(b"wal-only", b"yes")
        db._wal.sync()
        db._closed = True  # crash
    import os

    # Destroy the MANIFEST entirely.
    for f in os.listdir(tmp_db_path):
        if f.startswith("MANIFEST") or f == "CURRENT":
            os.remove(f"{tmp_db_path}/{f}")
    report = repair_db(tmp_db_path, opts())
    assert report["tables_kept"] >= 1
    with DB.open(tmp_db_path, opts()) as db:
        assert db.get(b"key00750") == b"v00750"
        assert db.get(b"wal-only") == b"yes"


def test_group_commit_concurrent_writers(tmp_db_path):
    """Many threads write concurrently; the leader/follower protocol must
    apply every batch exactly once with distinct sequences (reference
    WriteThread::JoinBatchGroup semantics)."""
    import threading

    n_threads, per_thread = 8, 50
    with DB.open(tmp_db_path, opts(write_buffer_size=1 << 20)) as db:
        errs = []

        def writer(tid):
            try:
                for i in range(per_thread):
                    db.put(f"t{tid:02d}-{i:04d}".encode(), f"v{tid}.{i}".encode())
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert db.versions.last_sequence == n_threads * per_thread
        for tid in range(n_threads):
            for i in range(per_thread):
                assert db.get(f"t{tid:02d}-{i:04d}".encode()) == \
                    f"v{tid}.{i}".encode()
    # Durability: every write must be replayable from the merged WAL records.
    with DB.open(tmp_db_path, opts()) as db:
        assert db.get(b"t00-0000") == b"v0.0"
        assert db.get(b"t07-0049") == b"v7.49"


def test_group_commit_merges_queued_followers(tmp_db_path):
    """While the leader is stuck inside the WAL append, followers queue up;
    the next leader must commit them as ONE merged WAL record."""
    import threading
    import time

    with DB.open(tmp_db_path, opts()) as db:
        wal = db._wal
        real_add = wal.add_record
        records = []
        gate = threading.Event()

        def slow_add(data):
            records.append(data)
            if len(records) == 1:
                gate.wait(5.0)  # hold the leader so followers pile up
            real_add(data)

        wal.add_record = slow_add
        t0 = threading.Thread(target=db.put, args=(b"lead", b"0"))
        t0.start()
        while not records:
            time.sleep(0.001)
        followers = [
            threading.Thread(target=db.put, args=(f"f{i}".encode(), b"x"))
            for i in range(4)
        ]
        for t in followers:
            t.start()
        time.sleep(0.05)  # let followers enqueue behind the stuck leader
        gate.set()
        t0.join()
        for t in followers:
            t.join()
        # Leader's record + one merged record for the queued followers.
        assert len(records) == 2
        from toplingdb_tpu.db.write_batch import WriteBatch

        merged = WriteBatch(records[1])
        assert merged.count() == 4
        for i in range(4):
            assert db.get(f"f{i}".encode()) == b"x"


def _blob_files(db):
    from toplingdb_tpu.db import filename as fn

    return sorted(
        num for child in db.env.get_children(db.dbname)
        for t, num in [fn.parse_file_name(child)] if t == fn.FileType.BLOB
    )


def test_blob_refs_tracked_and_unreferenced_blob_deleted(tmp_db_path):
    """FileMetaData.blob_refs keeps referenced blob files alive; once every
    referencing SST is compacted away, the blob file is GC'd."""
    o = opts(enable_blob_files=True, min_blob_size=10,
             disable_auto_compactions=True)
    with DB.open(tmp_db_path, o) as db:
        db.put(b"k1", b"B" * 100)
        db.flush()
        assert db.versions.current.files[0][0].blob_refs, \
            "flush must record the blob ref"
        assert len(_blob_files(db)) == 1
        # Overwrite with a small value, then compact to the bottom: the old
        # blob entry is superseded, no SST references the blob file anymore.
        db.put(b"k1", b"small")
        db.flush()
        db.compact_range()
        assert db.get(b"k1") == b"small"
        assert _blob_files(db) == [], "unreferenced blob file must be deleted"
    with DB.open(tmp_db_path, o) as db:
        assert db.get(b"k1") == b"small"


def test_blob_refs_survive_reopen_and_passthrough_compaction(tmp_db_path):
    o = opts(enable_blob_files=True, min_blob_size=10,
             disable_auto_compactions=True)
    with DB.open(tmp_db_path, o) as db:
        for i in range(5):
            db.put(f"k{i}".encode(), f"V{i}".encode() * 20)
        db.flush()
        refs0 = db.versions.current.files[0][0].blob_refs
        assert refs0
    with DB.open(tmp_db_path, o) as db:  # MANIFEST round-trip
        assert db.versions.current.files[0][0].blob_refs == refs0
        db.compact_range()  # passthrough: output SST must carry the refs
        files = [f for lvl in db.versions.current.files for f in lvl]
        assert len(files) == 1
        assert files[0].blob_refs == refs0
        assert len(_blob_files(db)) == 1
        for i in range(5):
            assert db.get(f"k{i}".encode()) == f"V{i}".encode() * 20


def test_blob_garbage_collection_rewrites_old_files(tmp_db_path):
    """With GC enabled at cutoff 1.0, compaction rewrites every surviving
    blob out of the aged files, which are then deleted."""
    o = opts(enable_blob_files=True, min_blob_size=10,
             enable_blob_garbage_collection=True,
             blob_garbage_collection_age_cutoff=1.0,
             disable_auto_compactions=True)
    with DB.open(tmp_db_path, o) as db:
        for i in range(4):
            db.put(f"a{i}".encode(), f"X{i}".encode() * 30)
        db.flush()
        for i in range(4):
            db.put(f"b{i}".encode(), f"Y{i}".encode() * 30)
        db.flush()
        old = _blob_files(db)
        assert len(old) == 2
        db.compact_range()
        new = _blob_files(db)
        assert len(new) == 1 and new[0] not in old, \
            "survivors must move to ONE fresh blob file; aged files deleted"
        for i in range(4):
            assert db.get(f"a{i}".encode()) == f"X{i}".encode() * 30
            assert db.get(f"b{i}".encode()) == f"Y{i}".encode() * 30
    with DB.open(tmp_db_path, o) as db:
        assert db.get(b"a0") == b"X0" * 30


def test_blob_gc_inlines_small_survivors(tmp_db_path):
    """A GC'd blob whose value now sits under min_blob_size is inlined back
    into the SST (type flips BLOB_INDEX → VALUE)."""
    o = opts(enable_blob_files=True, min_blob_size=10,
             disable_auto_compactions=True)
    with DB.open(tmp_db_path, o) as db:
        db.put(b"k", b"Z" * 50)
        db.flush()
    # Reopen with a bigger min_blob_size: at GC time the 50B value is below
    # the new 100B threshold, so it must be inlined.
    o2 = opts(enable_blob_files=True, min_blob_size=100,
              enable_blob_garbage_collection=True,
              blob_garbage_collection_age_cutoff=1.0,
              disable_auto_compactions=True)
    with DB.open(tmp_db_path, o2) as db:
        db.compact_range()
        assert db.get(b"k") == b"Z" * 50
        assert _blob_files(db) == []
        files = [f for lvl in db.versions.current.files for f in lvl]
        assert all(not f.blob_refs for f in files)


def test_repair_db_multi_cf(tmp_db_path):
    """Repair reconstructs column families from table properties and WAL
    CF-prefixed records (reference db/repair.cc keeps CFs too)."""
    import os

    from toplingdb_tpu.db.repair import repair_db

    with DB.open(tmp_db_path, opts()) as db:
        cf = db.create_column_family("meta")
        db.put(b"dk", b"dv")
        db.put(b"mk", b"mv", cf=cf)
        db.flush()
        db.put(b"wal-d", b"1")
        db.put(b"wal-m", b"2", cf=cf)
        db._wal.sync()
        db._closed = True  # crash
    for f in os.listdir(tmp_db_path):
        if f.startswith("MANIFEST") or f == "CURRENT":
            os.remove(f"{tmp_db_path}/{f}")
    report = repair_db(tmp_db_path, opts())
    assert "meta" in report["column_families"].values()
    with DB.open(tmp_db_path, opts()) as db:
        cf = db.get_column_family("meta")
        assert cf is not None
        assert db.get(b"dk") == b"dv"
        assert db.get(b"mk", cf=cf) == b"mv"
        assert db.get(b"wal-d") == b"1"
        assert db.get(b"wal-m", cf=cf) == b"2"
        assert db.get(b"mk") is None, "CF data must not leak into default"


def test_write_buffer_manager_across_dbs(tmp_path):
    """A shared WriteBufferManager budget forces early flushes across DB
    instances and tracks usage (reference write_buffer_manager.h:37)."""
    from toplingdb_tpu.utils.rate_limiter import WriteBufferManager

    wbm = WriteBufferManager(24 * 1024)
    o1 = opts(write_buffer_size=1 << 26, write_buffer_manager=wbm)
    o2 = opts(write_buffer_size=1 << 26, write_buffer_manager=wbm)
    with DB.open(str(tmp_path / "db1"), o1) as db1, \
            DB.open(str(tmp_path / "db2"), o2) as db2:
        for i in range(400):
            db1.put(b"a%04d" % i, b"x" * 40)
            db2.put(b"b%04d" % i, b"y" * 40)
        # Per-DB write_buffer_size (64MiB) would never flush; the shared
        # 24KiB budget must have. (A sealed memtable becomes a file on the
        # flush thread: wait for it.)
        db1.wait_for_compactions()
        db2.wait_for_compactions()
        flushed = (db1.versions.current.num_files()
                   + db2.versions.current.num_files())
        assert flushed > 0, "shared budget never triggered a flush"
        assert wbm.memory_usage() <= 64 * 1024
        assert db1.get(b"a0000") == b"x" * 40
        assert db2.get(b"b0399") == b"y" * 40
        # Manual flush must release the charge too (not only close). A
        # small residual is the fresh empty memtables' head allocations —
        # physical accounting charges those (reference WBM counts arena
        # blocks of empty memtables too).
        db1.flush()
        db2.flush()
        assert wbm.memory_usage() < 4096, \
            "flush must release the DB's data charge"
    assert wbm.memory_usage() == 0, "close must release the DB's charge"


def test_verify_checksum_detects_corruption(tmp_db_path):
    import os

    from toplingdb_tpu.utils.status import Corruption

    with DB.open(tmp_db_path, opts(disable_auto_compactions=True)) as db:
        for i in range(500):
            db.put(b"k%04d" % i, b"v" * 40)
        db.flush()
        db.verify_checksum()  # clean pass
        f = db.versions.current.files[0][0]
        path = f"{tmp_db_path}/{f.number:06d}.sst"
        db.table_cache.evict(f.number)
        data = bytearray(open(path, "rb").read())
        data[len(data) // 3] ^= 0xFF  # flip a data-block byte
        open(path, "wb").write(bytes(data))
        with pytest.raises(Corruption):
            db.verify_checksum()
        db._closed = True  # skip close-flush against the corrupt file


def test_get_approximate_sizes(tmp_db_path):
    with DB.open(tmp_db_path, opts(disable_auto_compactions=True)) as db:
        for i in range(3000):
            db.put(b"key%05d" % i, b"v" * 64)
        db.flush()
        sizes = db.get_approximate_sizes(
            [(b"key00000", b"key03000"), (b"key01000", b"key01100"),
             (b"zz", b"zzz")]
        )
        assert sizes[0] > sizes[1] > 0
        assert sizes[2] == 0
        total = sum(f.file_size for _, f in db.versions.current.all_files())
        assert sizes[0] <= total * 1.2


def test_delete_files_in_range(tmp_db_path):
    with DB.open(tmp_db_path, opts(write_buffer_size=8 * 1024,
                                   target_file_size_base=16 * 1024,
                                   disable_auto_compactions=True)) as db:
        for i in range(4000):
            db.put(b"key%05d" % i, b"x" * 40)
        db.flush()
        db.compact_range()  # push everything to L1+ (multiple files)
        v = db.versions.current
        n_before = v.num_files()
        assert n_before > 2
        dropped = db.delete_files_in_range(b"key00500", b"key03500")
        assert dropped > 0
        # Fully-contained ranges are gone; boundary data survives.
        assert db.get(b"key00000") is not None
        assert db.get(b"key03999") is not None
        assert db.versions.current.num_files() == n_before - dropped
    with DB.open(tmp_db_path, opts()) as db:
        assert db.get(b"key00000") is not None


def test_pause_continue_background_work(tmp_db_path):
    with DB.open(tmp_db_path, opts(write_buffer_size=4 * 1024,
                                   level0_file_num_compaction_trigger=2)) as db:
        db.pause_background_work()
        for i in range(600):
            db.put(b"key%05d" % i, b"x" * 30)
        db.flush()  # the sealed memtables are files once this returns
        n_l0 = len(db.versions.current.files[0])
        assert n_l0 >= 2, "L0 should pile up while paused"
        db.continue_background_work()
        db.wait_for_compactions()
        assert db.get(b"key00001") == b"x" * 30


def test_block_cache_tracer(tmp_db_path, tmp_path):
    from toplingdb_tpu.utils.cache import (
        BlockCacheTracer, LRUCache, analyze_block_cache_trace,
    )

    trace = str(tmp_path / "bc.trace")
    tracer = BlockCacheTracer(trace)
    o = opts(disable_auto_compactions=True,
             block_cache=LRUCache(1 << 20, tracer=tracer))
    with DB.open(tmp_db_path, o) as db:
        for i in range(1000):
            db.put(b"k%04d" % i, b"v" * 30)
        db.flush()
        for _ in range(3):
            assert db.get(b"k0500") == b"v" * 30
    tracer.close()
    agg = analyze_block_cache_trace(trace)
    assert agg["hits"] + agg["misses"] > 0
    assert agg["hits"] > 0, "repeat reads must hit the cache"


def test_extended_properties(tmp_db_path):
    with DB.open(tmp_db_path, opts(disable_auto_compactions=True)) as db:
        for i in range(200):
            db.put(b"k%04d" % i, b"v")
        db.flush()
        for i in range(100, 300):
            db.put(b"k%04d" % i, b"v")
        snap = db.get_snapshot()
        assert int(db.get_property("tpulsm.estimate-num-keys")) >= 200
        assert int(db.get_property("tpulsm.cur-size-all-mem-tables")) > 0
        assert db.get_property("tpulsm.num-snapshots") == "1"
        assert int(db.get_property("tpulsm.estimate-live-data-size")) > 0
        assert db.get_property("tpulsm.background-errors") == "0"
        assert db.get_property("tpulsm.num-running-compactions") == "0"
        snap.release()


def test_get_merge_operands(tmp_db_path):
    with DB.open(tmp_db_path, opts(merge_operator=StringAppendOperator())) as db:
        db.put(b"k", b"base")
        db.merge(b"k", b"a")
        db.flush()
        db.merge(b"k", b"b")
        assert db.get_merge_operands(b"k") == [b"base", b"a", b"b"]
        assert db.get(b"k") == b"base,a,b"
        db.put(b"plain", b"v")
        assert db.get_merge_operands(b"plain") == [b"v"]
        assert db.get_merge_operands(b"missing") == []
        db.delete(b"k")
        db.merge(b"k", b"after")
        assert db.get_merge_operands(b"k") == [b"after"]


def test_get_merge_operands_snapshot_and_zeroed(tmp_db_path):
    """Review regressions: a post-snapshot range tombstone must not hide the
    chain under the snapshot, and seqno-zeroed survivors stay visible."""
    with DB.open(tmp_db_path, opts(merge_operator=StringAppendOperator(),
                                   disable_auto_compactions=True)) as db:
        db.put(b"k", b"base")
        db.merge(b"k", b"a")
        snap = db.get_snapshot()
        db.delete_range(b"a", b"z")
        db.flush()
        assert db.get_merge_operands(b"k") == []  # covered now
        assert db.get_merge_operands(
            b"k", ReadOptions(snapshot=snap)) == [b"base", b"a"]
        snap.release()
        # Seqno-zeroed value after bottommost compaction stays visible.
        db.put(b"z2", b"zv")
        db.compact_range()
        assert db.get_merge_operands(b"z2") == [b"zv"]


def test_put_get_entity_api(tmp_db_path):
    with DB.open(tmp_db_path, opts()) as db:
        db.put_entity(b"user1", {b"name": b"alice", b"age": b"30"})
        e = db.get_entity(b"user1")
        assert e == {b"name": b"alice", b"age": b"30"}
        db.put(b"plain", b"v")
        assert db.get_entity(b"plain") == {b"": b"v"}
        assert db.get_entity(b"missing") is None
        db.flush()
        db.compact_range()
        assert db.get_entity(b"user1")[b"name"] == b"alice"


def test_set_options_dynamic(tmp_db_path):
    from toplingdb_tpu.utils.config import load_latest_options

    with DB.open(tmp_db_path, opts()) as db:
        db.set_options({"write_buffer_size": 999_999,
                        "disable_auto_compactions": True})
        assert db.options.write_buffer_size == 999_999
        with pytest.raises(InvalidArgument):
            db.set_options({"num_levels": 3})  # immutable
        with pytest.raises(InvalidArgument):
            db.set_options({"no_such_option": 1})
        loaded = load_latest_options(tmp_db_path)
        assert loaded.write_buffer_size == 999_999
        assert loaded.disable_auto_compactions is True
        import os

        n_opts = sum(1 for f in os.listdir(tmp_db_path)
                     if f.startswith("OPTIONS-"))
        assert n_opts == 1, "old OPTIONS file not rolled"


def test_async_multi_get_matches_sync(tmp_db_path):
    """ReadOptions.async_io (fiber-MultiGet analogue): identical results to
    the synchronous batched path across memtable/L0/deep-level sources,
    snapshots, and misses."""
    import random

    o = opts(write_buffer_size=8 * 1024, disable_auto_compactions=True)
    with DB.open(tmp_db_path, o) as db:
        rng = random.Random(6)
        for i in range(3000):
            db.put(b"key%05d" % (i % 2000), b"v%05d" % i)
            if i % 700 == 699:
                db.flush()
        db.compact_range()
        for i in range(0, 2000, 3):
            db.put(b"key%05d" % i, b"mem%05d" % i)  # memtable layer on top
        snap = db.get_snapshot()
        db.delete_range(b"key00100", b"key00300")
        keys = [b"key%05d" % rng.randrange(2500) for _ in range(300)]
        sync = db.multi_get(keys)
        a = db.multi_get(keys, ReadOptions(async_io=True))
        assert a == sync
        ssnap = db.multi_get(keys, ReadOptions(snapshot=snap))
        asnap = db.multi_get(keys, ReadOptions(snapshot=snap, async_io=True))
        assert asnap == ssnap
        snap.release()


def test_persistent_stats_history(tmp_db_path):
    """persist_stats(to_db=True) stores samples in the hidden stats CF;
    they survive reopen (reference persist_stats_to_disk)."""
    from toplingdb_tpu.utils import statistics as st
    from toplingdb_tpu.utils.statistics import Statistics

    o = opts(statistics=Statistics())
    with DB.open(tmp_db_path, o) as db:
        db.put(b"a", b"1")
        db.persist_stats(to_db=True)
        hist = db.get_stats_history(include_persisted=True)
        assert hist and any(
            d.get(st.NUMBER_KEYS_WRITTEN) for _, d in hist
        )
    with DB.open(tmp_db_path, opts(statistics=Statistics())) as db:
        hist = db.get_stats_history(include_persisted=True)
        assert hist, "persisted samples lost on reopen"
        # Hidden CF stays out of the default keyspace.
        it = db.new_iterator()
        it.seek_to_first()
        assert [k for k, _ in it.entries()] == [b"a"]


def test_disable_enable_file_deletions(tmp_db_path):
    import os

    with DB.open(tmp_db_path, opts(disable_auto_compactions=True)) as db:
        for i in range(500):
            db.put(b"k%03d" % i, b"v")
        db.flush()
        old = {f for f in os.listdir(tmp_db_path) if f.endswith(".sst")}
        db.disable_file_deletions()
        db.disable_file_deletions()  # counted
        db.compact_range()
        now = {f for f in os.listdir(tmp_db_path) if f.endswith(".sst")}
        assert old <= now, "obsolete inputs deleted while pinned"
        db.enable_file_deletions()
        db.compact_range()
        still = {f for f in os.listdir(tmp_db_path) if f.endswith(".sst")}
        assert old <= still, "second disable ignored"
        db.enable_file_deletions()
        after = {f for f in os.listdir(tmp_db_path) if f.endswith(".sst")}
        assert not (old & after), "obsolete files kept after enable"
        assert db.get(b"k250") == b"v"
        db.flush_wal(sync=True)


def test_empty_range_delete_is_noop(tmp_db_path):
    """Soak regression: delete_range(begin == end) deletes nothing and must
    not flush a boundless empty table into the MANIFEST."""
    with DB.open(tmp_db_path, opts()) as db:
        db.delete_range(b"k", b"k")       # empty range, empty memtable
        db.flush()                        # must not crash / write junk
        assert db.versions.current.num_files() == 0
        db.put(b"a", b"1")
        db.delete_range(b"z", b"a")       # inverted = empty too
        db.flush()
        assert db.get(b"a") == b"1"
        db.delete_range(b"a", b"a\x00")   # minimal REAL range
        assert db.get(b"a") is None
    with DB.open(tmp_db_path, opts()) as db:
        assert db.get(b"a") is None


def test_get_live_files_and_wal_files(tmp_db_path):
    """GetLiveFiles/GetSortedWalFiles: copying exactly those files yields an
    openable DB (the external-backup contract)."""
    import os
    import shutil

    with DB.open(tmp_db_path, opts(enable_blob_files=True,
                                   min_blob_size=64,
                                   disable_auto_compactions=True)) as db:
        for i in range(300):
            db.put(b"k%04d" % i, b"V" * (100 if i % 3 else 10))
        db.disable_file_deletions()
        try:
            files, manifest_size = db.get_live_files()
            wals = db.get_sorted_wal_files()
            assert any(f.endswith(".sst") for f in files)
            assert any(f.endswith(".blob") for f in files)
            assert "CURRENT" in files
            assert manifest_size > 0
            dst = tmp_db_path + "_copy"
            os.makedirs(dst)
            for f in files + wals:
                shutil.copy2(os.path.join(tmp_db_path, f),
                             os.path.join(dst, f))
                if f.startswith("MANIFEST-"):
                    # Truncate at the snapshot point (the live manifest may
                    # have grown since).
                    with open(os.path.join(dst, f), "r+b") as mf:
                        mf.truncate(manifest_size)
        finally:
            db.enable_file_deletions()
    with DB.open(dst, opts(enable_blob_files=True, min_blob_size=64)) as db2:
        assert db2.get(b"k0100") == b"V" * 100
        assert db2.get(b"k0000") == b"V" * 10


def test_error_handler_severity_classes(tmp_path):
    """Reference ErrorHandler severity mapping (db/error_handler.h:28):
    SOFT keeps foreground writes alive, HARD blocks writes until resume(),
    FATAL/UNRECOVERABLE (corruption / MANIFEST) refuse resume()."""
    from toplingdb_tpu.utils.status import (
        Corruption, IOError_, Severity,
    )

    db = DB.open(str(tmp_path / "db"), Options())
    # SOFT: retryable flush IO error — writes continue, severity visible.
    db._set_background_error(IOError_("enospc", retryable=True), "flush")
    assert db._bg_error_severity == Severity.SOFT_ERROR
    db.put(b"k", b"v")  # foreground writes stay up under SOFT
    assert db.get(b"k") == b"v"
    db.resume()
    assert db.get_property("tpulsm.background-errors") == "0"

    # HARD: non-retryable WAL-adjacent error — writes raise until resume.
    db._set_background_error(IOError_("disk gone"), "wal")
    assert db._bg_error_severity == Severity.HARD_ERROR
    with pytest.raises(IOError_):
        db.put(b"k2", b"v2")
    db.resume()
    db.put(b"k2", b"v2")

    # Escalation: a later worse error replaces a milder one.
    db._set_background_error(IOError_("enospc", retryable=True), "flush")
    db._set_background_error(Corruption("bad block"), "flush")
    assert db._bg_error_severity == Severity.FATAL_ERROR
    with pytest.raises(IOError_):
        db.resume()
    assert db.get_property("tpulsm.bg-error-severity") == "FATAL_ERROR"
    # Reads still work at FATAL; reopen is the way out.
    assert db.get(b"k2") == b"v2"
    db._bg_error = None  # simulate reopen for close()
    db._bg_error_severity = Severity.NO_ERROR
    db.close()

    # UNRECOVERABLE: corruption discovered BY compaction.
    db = DB.open(str(tmp_path / "db2"), Options())
    db._set_background_error(Corruption("merge saw garbage"), "compaction")
    assert db._bg_error_severity == Severity.UNRECOVERABLE
    with pytest.raises(IOError_):
        db.resume()
    db._bg_error = None
    db._bg_error_severity = Severity.NO_ERROR
    db.close()


def test_blob_gc_shrinks_storage_on_overwrite(tmp_db_path):
    """Compaction-time blob GC (reference blob_garbage_collection_age_cutoff
    + BlobFileBuilder rewrite): after overwriting every blob-backed value
    and compacting, dead blob data must be reclaimed — storage shrinks and
    the old blob files are gone (VERDICT r03 item 7 'Done' criterion)."""
    import glob
    import os

    o = opts(enable_blob_files=True, min_blob_size=50,
             enable_blob_garbage_collection=True,
             blob_garbage_collection_age_cutoff=1.0,
             write_buffer_size=1 << 20)
    with DB.open(tmp_db_path, o) as db:
        for i in range(2000):
            db.put(b"k%05d" % i, b"B" * 500)
        db.flush()
        for i in range(2000):
            db.put(b"k%05d" % i, b"C" * 500)
        db.flush()

        def blob_bytes():
            return sum(os.path.getsize(p)
                       for p in glob.glob(tmp_db_path + "/*.blob"))

        before = blob_bytes()
        db.compact_range(None, None)
        db.wait_for_compactions()
        after = blob_bytes()
        assert after < before * 0.6, (before, after)
        for i in range(0, 2000, 97):
            assert db.get(b"k%05d" % i) == b"C" * 500


def test_wide_column_entity_semantics(tmp_db_path):
    """Reference db/wide semantics: PutEntity stores columns; a plain Get
    (and iterator value()) over the entity returns the anonymous default
    column; GetEntity / Iterator.columns() return the full set — across
    flush + compaction."""
    with DB.open(tmp_db_path, opts()) as db:
        db.put_entity(b"e1", {b"": b"defv", b"city": b"paris",
                              b"age": b"30"})
        db.put_entity(b"e2", {b"city": b"rome"})  # no default column
        db.put(b"plain", b"pv")
        assert db.get(b"e1") == b"defv"
        assert db.get(b"e2") == b""
        assert db.get(b"plain") == b"pv"
        db.flush()
        db.compact_range(None, None)
        db.wait_for_compactions()
        assert db.get(b"e1") == b"defv"
        assert db.get_entity(b"e1") == {b"": b"defv", b"city": b"paris",
                                        b"age": b"30"}
        assert db.get_entity(b"plain") == {b"": b"pv"}
        it = db.new_iterator()
        it.seek(b"e1")
        assert it.value() == b"defv"
        assert it.columns()[b"city"] == b"paris"
        it.seek(b"plain")
        assert it.columns() == {b"": b"pv"}
