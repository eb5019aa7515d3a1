"""Sync-point interleaving tests for the two cross-thread seams the
concurrency plane pins down:

1. db.py — a staged write group's async-WAL durability barrier vs the
   memtable switch (which closes the WAL the group appended to). The
   `_mt_inflight` drain in `_switch_memtable` is the protocol; the
   dependency forces the switch to start only once a group has entered
   its barrier window, so the drain handshake (cv wait vs completion
   notify) actually runs under contention.
2. sharding — a writer parked at a closed write fence vs the migration
   cutover. The dependency holds the cutover until a writer is parked,
   so the parked writer MUST wake into the post-swap world and
   re-resolve onto the new primary (epoch bump).

3. db.py — the flush thread's install vs an unlocked reader and vs the
   writer's next seal. The install puts the table into the version
   BEFORE it drops the memtable from `imm` (a reader walks `[mem] + imm`
   and then the version, with no lock: in the other order it would find
   a row in neither), all in one hold of `_mutex`, so a seal that arrives
   meanwhile waits for the hold and queues behind the installed unit.

The first two tests drive the orders with
`get_sync_point_registry().load_dependency(...)` — no sleeps; the flush
tests hold the flush thread inside a sync-point callback.
"""

import threading
import time

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.options import FlushOptions, Options, WriteOptions
from toplingdb_tpu.sharding import ShardMigration, open_local_cluster
from toplingdb_tpu.utils.statistics import Statistics
from toplingdb_tpu.utils.sync_point import get_sync_point_registry


@pytest.fixture
def sync_points():
    reg = get_sync_point_registry()
    reg.clear_all()
    yield reg
    reg.clear_all()


def test_wal_barrier_vs_memtable_switch(tmp_path, sync_points):
    """Forced order: a pipelined group reaches its async-WAL barrier ->
    THEN the flush thread's memtable switch may start. The switch closes
    the group's WAL; every acknowledged write must survive reopen."""
    reg = sync_points
    opts = Options(create_if_missing=True, enable_pipelined_write=True,
                   enable_async_wal=True)
    db = DB.open(str(tmp_path / "db"), opts)
    at_barrier = threading.Event()
    reg.set_callback("DBImpl::GroupCommit:BeforeWALBarrier",
                     lambda _arg: at_barrier.set())
    reg.load_dependency([
        ("DBImpl::GroupCommit:BeforeWALBarrier",
         "DBImpl::SwitchMemtable:Start"),
    ])
    reg.enable_processing()

    err = []

    def writer():
        try:
            for i in range(50):
                db.put(b"k%04d" % i, b"v%d" % i,
                       WriteOptions(sync=(i % 7 == 0)))
        except BaseException as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=writer, name="interleave-writer")
    t.start()
    # The flush (and its switch) may only start once a write group is in
    # its barrier window; the event keeps the mutex free until then so
    # the dependency cannot deadlock the leader out of ever reaching it.
    assert at_barrier.wait(timeout=30.0), "no group reached the barrier"
    db.flush(FlushOptions())
    t.join(timeout=30.0)
    assert not t.is_alive()
    assert not err, err
    reg.clear_all()
    db.close()

    db2 = DB.open(str(tmp_path / "db"), Options())
    try:
        for i in range(50):
            assert db2.get(b"k%04d" % i) == b"v%d" % i
    finally:
        db2.close()


def test_fenced_writer_vs_migration_cutover(tmp_path, sync_points):
    """Forced order: the migration cutover waits until a writer is
    parked at the closed fence. The parked writer must wake AFTER the
    swap + epoch bump and land its write on the NEW primary."""
    reg = sync_points
    r = open_local_cluster(str(tmp_path),
                           [("a", None, b"m"), ("b", b"m", None)],
                           statistics=Statistics())
    old_primary = None
    try:
        for i in range(120):
            r.put(b"m%05d" % i, b"v%d" % i)
        old_primary = r._serving("b").primary
        old_epoch = r.map.get("b").epoch

        reg.load_dependency([
            ("ShardRouter::WriteGate:Parked",
             "ShardMigration::BeforeCutover"),
        ])
        reg.enable_processing()

        mig_out, mig_err = [], []

        def migrate():
            try:
                mig_out.append(
                    ShardMigration(r, "b", str(tmp_path / "b-new")).run())
            except BaseException as e:  # noqa: BLE001
                mig_err.append(e)

        mt = threading.Thread(target=migrate, name="interleave-migrate")
        mt.start()
        # Wait for the fence to close, then write: the writer parks at
        # the gate, which is what releases the cutover.
        for _ in range(3000):
            if r.map.get("b").state == "fenced":
                break
            time.sleep(0.01)
        assert r.map.get("b").state == "fenced"
        tok = r.put(b"m88888", b"post-cutover")
        mt.join(timeout=60.0)
        assert not mt.is_alive()
        assert not mig_err, mig_err
        reg.clear_all()

        # The parked write re-resolved onto the NEW primary/epoch.
        assert tok.epoch == r.map.get("b").epoch
        assert tok.epoch > old_epoch
        assert r._serving("b").primary is not old_primary
        assert r.get(b"m88888", token=tok) == b"post-cutover"
        # Cutover retires the replaced stack (the old primary is closed,
        # so no late write can ever land there); reopen its directory to
        # prove the parked write was never applied to it.
        assert old_primary._closed
        reopened = DB.open(old_primary.dbname,
                           Options(create_if_missing=False))
        try:
            assert reopened.get(b"m88888") is None
        finally:
            reopened.close()
        assert r.get(b"m00042") == b"v42"
    finally:
        reg.clear_all()
        r.close()


def test_flush_install_reaches_the_version_before_it_leaves_imm(
        tmp_path, sync_points):
    """Pinned order: log_and_apply, THEN the drop from imm. A reader runs
    between the two and must find every row (in both places); with the
    order reversed it would find them in neither."""
    reg = sync_points
    db = DB.open(str(tmp_path / "db"),
                 Options(create_if_missing=True,
                         disable_auto_compactions=True))
    between = threading.Event()
    reader_done = threading.Event()
    seen = {}

    def at_drop(_arg):
        seen["imm"] = len(db.imm)
        seen["l0"] = len(db.versions.current.files[0])
        between.set()
        assert reader_done.wait(timeout=30.0)

    reg.set_callback("FlushJob::BeforeImmDrop", at_drop)
    reg.enable_processing()
    got = {}

    def reader():
        assert between.wait(timeout=30.0)
        for i in range(200):                    # unlocked point reads
            got[i] = db.get(b"k%04d" % i)
        reader_done.set()

    t = threading.Thread(target=reader, name="interleave-reader")
    t.start()
    try:
        for i in range(200):
            db.put(b"k%04d" % i, b"v%d" % i)
        db.flush()
        t.join(timeout=30.0)
        assert not t.is_alive()
        assert seen == {"imm": 1, "l0": 1}
        assert got == {i: b"v%d" % i for i in range(200)}
        assert not db.imm
    finally:
        reader_done.set()
        reg.clear_all()
        db.close()


def test_seal_arrives_while_the_flush_thread_installs(tmp_path,
                                                      sync_points):
    """The flush thread is inside its install (holding _mutex) when the
    writer fills the next memtable: the seal waits for the hold, then
    queues its unit behind the installed one. Two L0 files, in seal
    order, every write readable."""
    reg = sync_points
    db = DB.open(str(tmp_path / "db"),
                 Options(create_if_missing=True, write_buffer_size=16 << 10,
                         max_write_buffer_number=3,
                         disable_auto_compactions=True,
                         statistics=Statistics()))
    installing = threading.Event()
    writer_started = threading.Event()
    first = [True]

    def at_drop(_arg):
        if first[0]:
            first[0] = False
            installing.set()
            assert writer_started.wait(timeout=30.0)
            time.sleep(0.05)    # the writer is at _mutex by now

    reg.set_callback("FlushJob::BeforeImmDrop", at_drop)
    reg.enable_processing()
    err = []

    def writer():
        try:
            assert installing.wait(timeout=30.0)
            writer_started.set()
            for i in range(400):                # > one write buffer
                db.put(b"w%04d" % i, b"x" * 60)
        except BaseException as e:  # noqa: BLE001
            err.append(e)

    t = threading.Thread(target=writer, name="interleave-sealer")
    t.start()
    try:
        for i in range(100):
            db.put(b"k%04d" % i, b"v%d" % i)
        db.flush(FlushOptions(wait=False))
        t.join(timeout=30.0)
        assert not t.is_alive()
        assert not err, err
        db.flush()
        files = sorted(db.versions.current.files[0], key=lambda f: f.number)
        assert len(files) >= 2
        assert files[0].largest_seqno == 100    # the first unit, alone
        assert all(a.largest_seqno < b.smallest_seqno
                   for a, b in zip(files, files[1:]))
        assert db.get(b"k0042") == b"v42"
        assert db.get(b"w0399") == b"x" * 60
    finally:
        writer_started.set()
        reg.clear_all()
        db.close()
