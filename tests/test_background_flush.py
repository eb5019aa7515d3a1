"""The flush thread (db/db.py): the writer seals a full memtable and hands
the sealed unit to the DB's one flush thread; `max_write_buffer_number` is
the only thing a put waits for.

The tests hold the thread at a sync point (`FlushJob::Start`: before a
table is built, no lock held; `FlushJob::BeforeImmDrop`: the table is in
the version, the memtable still in `imm`, `_mutex` held) and look at the
DB from other threads meanwhile.
"""

import shutil
import threading
import time

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.env.env import PosixEnv
from toplingdb_tpu.env.fault_injection import FaultInjectionEnv
from toplingdb_tpu.options import FlushOptions, Options, ReadOptions
from toplingdb_tpu.utils import statistics as st
from toplingdb_tpu.utils.status import IOError_
from toplingdb_tpu.utils.sync_point import get_sync_point_registry

NO_WAIT = FlushOptions(wait=False)


@pytest.fixture
def sync_points():
    reg = get_sync_point_registry()
    reg.clear_all()
    yield reg
    reg.clear_all()


class Hold:
    """Holds whichever thread reaches `point` (from its `skip`-th arrival
    on) until `release()`."""

    def __init__(self, reg, point: str, skip: int = 0):
        self.reached = threading.Event()
        self._open = threading.Event()
        self._skip = skip
        reg.set_callback(point, self._arrive)
        reg.enable_processing()

    def _arrive(self, _arg) -> None:
        if self._skip:
            self._skip -= 1
            return
        self.reached.set()
        assert self._open.wait(timeout=60.0), "never released"

    def release(self) -> None:
        self._open.set()


def opts(**kw) -> Options:
    kw.setdefault("write_buffer_size", 1 << 20)
    return Options(create_if_missing=True, disable_auto_compactions=True,
                   statistics=st.Statistics(), **kw)


def rows(n: int, tag: bytes = b"v", start: int = 0) -> dict:
    return {b"key%05d" % i: tag + b"%05d" % i
            for i in range(start, start + n)}


def put_all(db, kv: dict, cf=None) -> None:
    for k, v in kv.items():
        db.put(k, v, cf=cf)


def until(cond, timeout: float = 30.0) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(0.005)
    return cond()


def l0(db, cf_id: int = 0) -> list:
    return list(db.versions.cf_current(cf_id).files[0])


def ticker(db, name: str) -> int:
    return db.stats.get_ticker_count(name)


# -- reads of a sealed memtable: before, during and after the install -------


def _read_get(db, want, snap, _it):
    return {k: db.get(k) for k in want}


def _read_multi_get(db, want, snap, _it):
    keys = list(want)
    return dict(zip(keys, db.multi_get(keys)))


def _read_snapshot(db, want, snap, _it):
    ro = ReadOptions(snapshot=snap)
    return {k: db.get(k, ro) for k in want}


def _read_iterator(db, want, snap, it):
    """`it` was made before the seal: a new one would need _mutex, which
    the flush thread holds at FlushJob::BeforeImmDrop."""
    got = {}
    it.seek_to_first()
    while it.valid():
        got[it.key()] = it.value()
        it.next()
    return {k: got.get(k) for k in want}


@pytest.mark.parametrize("read", [_read_get, _read_multi_get,
                                  _read_snapshot, _read_iterator])
def test_reads_find_a_sealed_memtables_rows_through_its_install(
        tmp_path, sync_points, read):
    db = DB.open(str(tmp_path / "db"), opts())
    at_start = Hold(sync_points, "FlushJob::Start")
    at_drop = Hold(sync_points, "FlushJob::BeforeImmDrop")
    try:
        sealed = rows(300)
        put_all(db, sealed)
        snap = db.get_snapshot()
        it = db.new_iterator()
        db.flush(NO_WAIT)                       # seals, does not wait
        assert at_start.reached.wait(30.0)
        assert len(db.imm) == 1 and not l0(db)
        # Writes after the seal are acknowledged beside the held flush.
        later = rows(50, tag=b"w")              # overwrites 50 sealed keys
        put_all(db, later)
        now = {**sealed, **later}
        want = sealed if read in (_read_snapshot, _read_iterator) else now
        assert read(db, want, snap, it) == want         # before
        at_start.release()
        assert at_drop.reached.wait(30.0)
        assert len(db.imm) == 1 and len(l0(db)) == 1    # in both
        assert read(db, want, snap, it) == want         # during
        at_drop.release()
        db.flush()
        assert not db.imm and len(l0(db)) == 2
        assert read(db, want, snap, it) == want         # after
        db.release_snapshot(snap)
    finally:
        at_start.release()
        at_drop.release()
        db.close()


# -- the one wait: max_write_buffer_number ----------------------------------


def test_writer_waits_at_max_write_buffer_number(tmp_path, sync_points):
    db = DB.open(str(tmp_path / "db"),
                 opts(write_buffer_size=16 << 10, max_write_buffer_number=2))
    hold = Hold(sync_points, "FlushJob::Start")
    try:
        put_all(db, rows(50))
        db.flush(NO_WAIT)
        assert hold.reached.wait(30.0)          # one immutable memtable
        done = threading.Event()

        def writer():
            put_all(db, rows(600, tag=b"x" * 100, start=1000))  # > 16 KB
            done.set()

        t = threading.Thread(target=writer, name="limit-writer")
        t.start()
        assert until(lambda: db.write_stall_state()["state"]
                     == "memtable_limit")
        assert not done.wait(0.2), "the writer passed the limit"
        assert len(db.imm) == 1                 # it did not seal a second
        assert ticker(db, st.STALL_MICROS) == 0  # accounted when it ends
        hold.release()
        t.join(30.0)
        assert done.is_set()
        waited = ticker(db, st.STALL_MEMTABLE_LIMIT_MICROS)
        assert waited >= 200_000
        assert ticker(db, st.STALL_MICROS) >= waited
        stall = db.write_stall_state()
        assert stall["last_state"] == "memtable_limit"
        assert stall["memtable_limit"] == 1
        db.flush()
        assert db.write_stall_state()["state"] == "none"
        assert db.get(b"key01599") == b"x" * 100 + b"01599"
    finally:
        hold.release()
        db.close()


# -- seal order is install order --------------------------------------------


def test_three_quick_seals_install_in_seal_order(tmp_path, sync_points):
    flushed = []

    class Watch:
        def on_flush_completed(self, db, info):
            flushed.append(info.file_number)

    db = DB.open(str(tmp_path / "db"),
                 opts(max_write_buffer_number=4, listeners=[Watch()]))
    hold = Hold(sync_points, "FlushJob::Start")
    try:
        for gen in range(3):
            put_all(db, rows(100, tag=b"g%d" % gen))
            db.flush(NO_WAIT)
        assert hold.reached.wait(30.0)
        assert len(db.imm) == 3 and not l0(db)
        assert ticker(db, st.FLUSH_UNITS_HANDED_OVER) == 3
        assert ticker(db, st.FLUSH_UNITS_INSTALLED) == 0
        hold.release()
        db.flush()
        assert not db.imm
        assert ticker(db, st.FLUSH_UNITS_INSTALLED) == 3
        files = sorted(l0(db), key=lambda f: f.number)
        assert [f.number for f in files] == flushed
        assert [(f.smallest_seqno, f.largest_seqno) for f in files] == [
            (1, 100), (101, 200), (201, 300)]
        assert db.get(b"key00007") == b"g200007"
        assert db.stats.get_histogram(st.MEMTABLE_SEAL_MICROS).count == 3
    finally:
        hold.release()
        db.close()


# -- everything that means "flushed" waits for the queue --------------------


@pytest.mark.parametrize("call", ["flush", "close", "wait_for_compactions",
                                  "compact_range", "get_live_files",
                                  "pause_background_work"])
def test_flushed_means_the_queue_is_empty(tmp_path, sync_points, call):
    path = str(tmp_path / "db")
    db = DB.open(path, opts())
    hold = Hold(sync_points, "FlushJob::Start")
    try:
        put_all(db, rows(100))
        db.flush(NO_WAIT)
        assert hold.reached.wait(30.0)
        returned = threading.Event()

        def run():
            getattr(db, call)()
            returned.set()

        t = threading.Thread(target=run, name=f"caller-{call}")
        t.start()
        assert not returned.wait(0.2), f"{call}() did not wait"
        assert len(db.imm) == 1
        hold.release()
        t.join(30.0)
        assert returned.is_set()
        assert not db.imm and not db._flush_queue
        if call == "close":
            db = DB.open(path, opts())
        assert db.versions.current.num_files() == 1
        assert db.get(b"key00042") == b"v00042"
    finally:
        hold.release()
        db.close()


# -- a failed flush ---------------------------------------------------------


class _SstFaultEnv(FaultInjectionEnv):
    """Fails the creation of table files while `fail_sst` is set."""

    fail_sst = False

    def new_writable_file(self, path: str):
        if self.fail_sst and path.endswith(".sst"):
            raise IOError_(f"injected: cannot create {path}")
        return super().new_writable_file(path)


def test_failed_flush_latches_and_resume_flushes_the_same_unit(tmp_path):
    env = _SstFaultEnv(PosixEnv())
    db = DB.open(str(tmp_path / "db"), opts(), env=env)
    try:
        acked = rows(200)
        put_all(db, acked)
        env.fail_sst = True
        with pytest.raises(IOError_, match="injected"):
            db.flush()
        unit = db._flush_queue[0]
        assert db._bg_error is not None and db._bg_error_reason == "flush"
        assert db.imm == list(unit.mems.values())   # kept, and readable
        assert db.get(b"key00100") == b"v00100"
        with pytest.raises(IOError_, match="background error"):
            db.put(b"refused", b"1")
        with pytest.raises(IOError_, match="injected"):
            db.wait_for_compactions()
        env.fail_sst = False
        db.resume()
        db.flush()
        assert db._bg_error is None and not db.imm and not db._flush_queue
        assert len(l0(db)) == 1 and l0(db)[0].num_entries == 200
        assert db.get(b"refused") is None
        assert {k: db.get(k) for k in acked} == acked
        assert ticker(db, st.FLUSH_UNITS_INSTALLED) == 1
    finally:
        env.fail_sst = False
        db.close()


def test_failed_flush_wakes_the_writer_at_the_limit(tmp_path, sync_points):
    env = _SstFaultEnv(PosixEnv())
    db = DB.open(str(tmp_path / "db"),
                 opts(write_buffer_size=16 << 10), env=env)
    hold = Hold(sync_points, "FlushJob::Start")
    try:
        put_all(db, rows(50))
        env.fail_sst = True
        db.flush(NO_WAIT)
        assert hold.reached.wait(30.0)
        raised = []

        def writer():
            try:
                put_all(db, rows(600, tag=b"x" * 100, start=1000))
            except IOError_ as e:
                raised.append(e)

        t = threading.Thread(target=writer, name="limit-writer")
        t.start()
        assert until(lambda: db._memtable_limit_waiters == 1)
        hold.release()                          # the flush now fails
        t.join(30.0)
        assert len(raised) == 1 and "injected" in str(raised[0])
        # An acknowledged write is never lost by a failed flush.
        env.fail_sst = False
        db.resume()
        db.flush()
        assert db.get(b"key00049") == b"v00049"
        assert db.get(b"key01000") == b"x" * 100 + b"01000"
    finally:
        env.fail_sst = False
        hold.release()
        db.close()


# -- a crash with a unit in flight ------------------------------------------


def test_a_db_dropped_with_a_unit_in_flight_recovers_every_write(
        tmp_path, sync_points):
    path = str(tmp_path / "db")
    db = DB.open(path, opts())
    hold = Hold(sync_points, "FlushJob::Start")
    try:
        log_number = db.versions.log_number
        first_wal = db._wal_number
        sealed = rows(200)
        put_all(db, sealed)
        db.flush(NO_WAIT)
        assert hold.reached.wait(30.0)
        later = rows(100, tag=b"w", start=150)
        put_all(db, later)
        db._wal.sync()
        # The unit is not in the MANIFEST: its WAL stays, log_number too.
        assert db.versions.log_number == log_number <= first_wal
        assert db._flush_queue[0].wal_number == db._wal_number > first_wal
        sync_points.disable_processing()         # db2 flushes at recovery
        crashed = str(tmp_path / "crashed")
        shutil.copytree(path, crashed)          # what a kill -9 leaves
        with DB.open(crashed, opts()) as db2:
            want = {**sealed, **later}
            assert {k: db2.get(k) for k in want} == want
    finally:
        hold.release()
        db.close()
    with DB.open(path, opts()) as db3:           # the clean way, for the rest
        assert db3.get(b"key00249") == b"w00249"
        assert db3.versions.log_number > first_wal


# -- several column families in one unit ------------------------------------


def test_log_number_moves_when_every_familys_table_is_installed(
        tmp_path, sync_points):
    db = DB.open(str(tmp_path / "db"), opts())
    # The second table of the unit is held; the first is built by then.
    hold = Hold(sync_points, "FlushJob::Start", skip=1)
    try:
        cf = db.create_column_family("other")
        log_number = db.versions.log_number
        put_all(db, rows(100))
        put_all(db, rows(80, tag=b"o"), cf=cf)
        db.flush(NO_WAIT)
        assert hold.reached.wait(30.0)
        unit = db._flush_queue[0]
        assert sorted(unit.mems) == [0, cf.id]
        # One family's table is on disk, neither is in the version.
        assert not l0(db) and not l0(db, cf.id)
        assert db.versions.log_number == log_number
        assert len(db._cfs[0].imm) == len(db._cfs[cf.id].imm) == 1
        hold.release()
        db.flush()
        assert len(l0(db)) == len(l0(db, cf.id)) == 1
        assert db.versions.log_number == unit.wal_number == db._wal_number
        assert not db._cfs[0].imm and not db._cfs[cf.id].imm
        assert db.get(b"key00079", cf=cf) == b"o00079"
    finally:
        hold.release()
        db.close()


# -- the staged write modes share the hand-off ------------------------------


@pytest.mark.parametrize("mode", ["unordered_write",
                                  "enable_pipelined_write"])
def test_staged_write_modes_take_the_same_hand_off(tmp_path, mode):
    db = DB.open(str(tmp_path / "db"),
                 opts(write_buffer_size=16 << 10, **{mode: True}))
    try:
        kv = rows(1500, tag=b"y" * 40)          # several write buffers
        writers = [threading.Thread(
            target=put_all, args=(db, dict(list(kv.items())[i::3])))
            for i in range(3)]
        for t in writers:
            t.start()
        for t in writers:
            t.join(60.0)
        handed = ticker(db, st.FLUSH_UNITS_HANDED_OVER)
        assert handed >= 3
        assert db._flush_thread is not None
        assert db._flush_thread.name == "db-flush"
        db.flush()
        assert not db.imm
        assert ticker(db, st.FLUSH_UNITS_INSTALLED) \
            == ticker(db, st.FLUSH_UNITS_HANDED_OVER) >= handed
        assert len(l0(db)) == ticker(db, st.FLUSH_UNITS_INSTALLED)
        assert {k: db.get(k) for k in kv} == kv
    finally:
        db.close()


def test_close_joins_the_flush_thread(tmp_path, no_thread_leaks):
    db = DB.open(str(tmp_path / "db"), opts(write_buffer_size=16 << 10))
    put_all(db, rows(1000, tag=b"z" * 40))
    t = db._flush_thread
    assert t is not None and t.is_alive()
    db.close()
    assert not t.is_alive()
