"""Native group-commit write plane (ISSUE 7): fused WAL encode + group
memtable insert (tpulsm_wb_group_commit) must be byte-for-byte
interchangeable with the Python interiors — WAL files, recovery, shipped
replication frames — across the write-mode matrix, with the async WAL
writer's fsync coalescing and fault propagation proven on top."""

import glob
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.options import Options, WriteOptions
from toplingdb_tpu.utils import statistics as st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MODES = {
    "plain": {},
    "pipelined": {"enable_pipelined_write": True},
    "unordered": {"unordered_write": True},
    "parallel": {"allow_concurrent_memtable_write": True},
}


def _plane_available() -> bool:
    from toplingdb_tpu import native

    lib = native.lib()
    return lib is not None and hasattr(lib, "tpulsm_wb_group_commit")


pytestmark = pytest.mark.skipif(not _plane_available(),
                                reason="native write plane unavailable")


def _fill(d, knob, opts_kw, n=1500, pb=8, sync_every=0):
    os.environ["TPULSM_WRITE_PLANE"] = knob
    try:
        stats = st.Statistics()
        db = DB.open(d, Options(create_if_missing=True, statistics=stats,
                                protection_bytes_per_key=pb, **opts_kw))
        for i in range(0, n, 10):
            b = WriteBatch(protection_bytes_per_key=pb)
            for j in range(i, i + 10):
                b.put(b"k%06d" % j, b"v%06d" % j)
                if j % 7 == 0:
                    b.delete(b"k%06d" % (j // 2))
            wo = WriteOptions(sync=bool(sync_every and i % sync_every == 0))
            db.write(b, wo)
        return db, stats
    finally:
        os.environ.pop("TPULSM_WRITE_PLANE", None)


def _dump(db, n=1500):
    return ([(k, db.get(b"k%06d" % k)) for k in range(n)],
            db.versions.last_sequence)


def _wal_bytes(d):
    out = {}
    for p in sorted(glob.glob(d + "/*.log")):
        with open(p, "rb") as f:
            out[os.path.basename(p)] = f.read()
    return out


@pytest.mark.parametrize("mode", sorted(MODES))
def test_plane_parity_wal_bytes_and_recovery(tmp_path, mode):
    """WAL bytes, visible contents, last_sequence, and a post-reopen dump
    must be identical between TPULSM_WRITE_PLANE=0 and =1 (protection on)."""
    d0, d1 = str(tmp_path / "p0"), str(tmp_path / "p1")
    db0, s0 = _fill(d0, "0", MODES[mode])
    db1, s1 = _fill(d1, "1", MODES[mode])
    assert _dump(db0) == _dump(db1)
    assert _wal_bytes(d0) == _wal_bytes(d1)
    assert s1.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) > 0
    assert s0.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) == 0
    assert s0.get_ticker_count(st.WRITE_GROUP_LED) > 0
    # WAL accounting parity between the two encoders.
    for t in (st.WAL_BYTES, st.WRITE_WITH_WAL):
        assert s0.get_ticker_count(t) == s1.get_ticker_count(t)
    db0.close()
    db1.close()
    with DB.open(d0, Options()) as r0, DB.open(d1, Options()) as r1:
        assert _dump(r0) == _dump(r1)


def test_plane_fallback_matrix(tmp_path):
    """Merge-heavy, wide-column, CF-prefixed, and range-delete batches keep
    the Python interiors (fallback ticker) and stay correct."""
    from toplingdb_tpu.utils.merge_operator import UInt64AddOperator

    stats = st.Statistics()
    os.environ["TPULSM_WRITE_PLANE"] = "1"
    try:
        db = DB.open(str(tmp_path / "f"),
                     Options(create_if_missing=True, statistics=stats,
                             merge_operator=UInt64AddOperator()))
        import struct

        db.put(b"point", b"v")  # native plane
        for _ in range(3):
            db.merge(b"ctr", struct.pack("<Q", 1))  # merge-heavy: fallback
        cf = db.create_column_family("other")
        db.put(b"cfk", b"cfv", cf=cf)  # CF-prefixed: fallback
        db.delete_range(b"a", b"b")    # range delete: fallback
        from toplingdb_tpu.db.wide_columns import encode_entity

        b = WriteBatch()
        b.put_entity(b"wide", encode_entity({b"c": b"1"}))
        db.write(b)                    # wide columns: fallback
        assert stats.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) >= 1
        assert stats.get_ticker_count(st.WRITE_GROUP_FALLBACKS) >= 4
        assert struct.unpack("<Q", db.get(b"ctr"))[0] == 3
        assert db.get(b"cfk", cf=cf) == b"cfv"
        db.close()
    finally:
        os.environ.pop("TPULSM_WRITE_PLANE", None)


_CRASH_SRC = textwrap.dedent("""
    import sys
    sys.path.insert(0, %(repo)r)
    import os
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options, WriteOptions
    mode = %(mode)r
    kw = {"pipelined": {"enable_pipelined_write": True},
          "unordered": {"unordered_write": True},
          "parallel": {"allow_concurrent_memtable_write": True},
          "sync": {}}[mode]
    db = DB.open(%(db)r, Options(create_if_missing=True,
                                 protection_bytes_per_key=8, **kw))
    wo = WriteOptions(sync=(mode == "sync"))
    for i in range(400):
        b = WriteBatch(protection_bytes_per_key=8)
        for j in range(5):
            b.put(b"c%%07d" %% (i * 5 + j), b"v%%07d" %% (i * 5 + j))
        db.write(b, wo)
    print("survived")  # the kill point must fire before 400 writes
""")


@pytest.mark.parametrize("mode", ["pipelined", "unordered", "parallel",
                                  "sync"])
def test_crash_after_wal_recovery_parity(tmp_path, mode):
    """kill_point crash at DBImpl::WriteImpl:AfterWAL under the native
    plane: the recovered DB must be byte-identical to the Python-path
    twin that died at the SAME (seeded) point."""
    dumps = {}
    for knob in ("0", "1"):
        d = str(tmp_path / f"c{knob}")
        src = _CRASH_SRC % {"repo": REPO, "mode": mode, "db": d}
        env = dict(os.environ, TPULSM_WRITE_PLANE=knob,
                   TPULSM_KILL_ODDS="60", TPULSM_KILL_SEED="1234",
                   TPULSM_KILL_PREFIX="DBImpl::WriteImpl:AfterWAL",
                   JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", src], env=env,
                           capture_output=True, timeout=120)
        assert r.returncode == 137, (knob, r.returncode,
                                     r.stdout, r.stderr)
        # Recover with the OTHER path than the one that wrote (replay is
        # encoder-agnostic), dump everything.
        with DB.open(d, Options(protection_bytes_per_key=8)) as db:
            dumps[knob] = (
                [(k, db.get(b"c%07d" % k)) for k in range(2000)],
                db.versions.last_sequence,
            )
        dumps[knob + "_wal"] = _wal_bytes(d)
    assert dumps["0"] == dumps["1"], mode
    assert dumps["0_wal"] == dumps["1_wal"], mode


def test_log_shipper_frame_parity(tmp_path):
    """The replication plane must see identical shipped batches from
    either encoder (PR 4's LogShipper tails the WAL both planes write)."""
    from toplingdb_tpu.replication import LogShipper

    frames = {}
    for knob in ("0", "1"):
        d = str(tmp_path / f"s{knob}")
        db, _ = _fill(d, knob, {}, n=600)
        ship = LogShipper(db)
        fs, state = ship.frames_since(None)
        frames[knob] = [(f.first_seq, f.last_seq, f.batches) for f in fs]
        db.close()
    assert frames["0"] == frames["1"]
    assert frames["0"], "no frames shipped"


def test_async_wal_fsync_coalescing(tmp_path):
    """Concurrent sync=True leaders through the async WAL writer must
    merge into shared fsyncs (WRITE_GROUP_FSYNCS_COALESCED > 0) with
    every acknowledged write durable. Pipelined mode: the durability
    barrier waits OUTSIDE _mutex, so several groups' sync tokens overlap
    in the ring; seeded fsync delays widen the window deterministically."""
    import threading

    from toplingdb_tpu.env import PosixEnv
    from toplingdb_tpu.env.fault_injection import WalWriterFaultInjector

    env = PosixEnv()
    env.wal_writer_fault = WalWriterFaultInjector(
        rate=0.5, plans=("delay",), delay_sec=0.002, ops=("sync",), seed=5)
    stats = st.Statistics()
    db = DB.open(str(tmp_path / "a"),
                 Options(create_if_missing=True, statistics=stats,
                         enable_pipelined_write=True,
                         enable_async_wal=True), env=env)
    wo = WriteOptions(sync=True)
    errs = []

    def w(t):
        try:
            for i in range(60):
                db.put(b"t%d-%04d" % (t, i), b"v", wo)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [__import__("threading").Thread(target=w, args=(t,))
          for t in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    ring = db._wal_ring
    assert ring is not None
    assert ring.fsyncs_coalesced > 0
    assert stats.get_ticker_count(st.WRITE_GROUP_FSYNCS_COALESCED) \
        == ring.fsyncs_coalesced
    # Syncs acknowledged => durable: drop unsynced bytes cannot lose them.
    db.close()
    with DB.open(str(tmp_path / "a"), Options()) as r:
        for t in range(6):
            for i in range(60):
                assert r.get(b"t%d-%04d" % (t, i)) == b"v"


def test_async_wal_fault_injection_error_and_resume(tmp_path):
    """Seeded WAL-writer-thread failures (env/fault_injection.py
    WalWriterFaultInjector): the covered group's writer gets the error, a
    HARD background error latches, resume() clears it, later writes and a
    reopen stay consistent."""
    from toplingdb_tpu.env import PosixEnv
    from toplingdb_tpu.env.env import AsyncIORing
    from toplingdb_tpu.env.fault_injection import WalWriterFaultInjector

    env = PosixEnv()
    inj = WalWriterFaultInjector(schedule={3: "fail", 6: "delay"})
    env.wal_writer_fault = inj
    d = str(tmp_path / "fi")
    db = DB.open(d, Options(create_if_missing=True, enable_async_wal=True),
                 env=env)
    assert db._wal_ring.fault_hook is inj
    wo = WriteOptions(sync=True)
    acked, failed = [], []
    for i in range(10):
        k = b"f%04d" % i
        try:
            db.put(k, b"v", wo)
            acked.append(k)
        except Exception:
            failed.append(k)
            db.resume()  # clean resume after the injected failure
    assert failed, "no injected failure surfaced"
    assert inj.injected_counts().get("fail", 0) >= 1
    for k in acked:
        assert db.get(k) == b"v"
    db.close()
    with DB.open(d, Options()) as r:
        for k in acked:
            assert r.get(k) == b"v"


def test_aio_ring_coalescing_unit():
    """AsyncIORing: N sync tokens drained together -> ONE fsync; append
    errors park per-file and surface on the next barrier, then clear."""
    from toplingdb_tpu.env.env import AsyncIORing
    from toplingdb_tpu.utils.status import IOError_

    class SlowFile:
        def __init__(self):
            self.data = b""
            self.fsyncs = 0
            self.fail_next_append = False

        def append(self, d):
            if self.fail_next_append:
                self.fail_next_append = False
                raise IOError_("boom")
            self.data += bytes(d)

        def flush(self):
            pass

        def sync(self):
            self.fsyncs += 1

    ring = AsyncIORing(capacity=64)
    f = SlowFile()
    # Stall the worker so all submissions land in one drained batch.
    import threading

    gate = threading.Event()
    ring.submit_task(gate.wait)
    toks = []
    for i in range(4):
        ring.submit_append(f, b"x%d" % i)
        toks.append(ring.submit_sync(f))
    gate.set()
    for t in toks:
        t.wait()
    assert f.data == b"x0x1x2x3"
    assert f.fsyncs == 1
    assert ring.fsyncs_coalesced == 3
    # Error propagation: failed append -> next barrier raises, then clear.
    gate2 = threading.Event()
    ring.submit_task(gate2.wait)
    f.fail_next_append = True
    atok = ring.submit_append(f, b"bad")
    btok = ring.submit_barrier(f)
    gate2.set()
    with pytest.raises(IOError_):
        atok.wait()
    with pytest.raises(IOError_):
        btok.wait()
    ring.submit_append(f, b"ok")
    ring.submit_barrier(f).wait()  # clean resume
    assert f.data.endswith(b"ok")
    ring.close()


def test_prefetch_buffer_async_readahead():
    """FilePrefetchBuffer submits the NEXT window through an AsyncIORing
    and serves sequential reads from the adopted async window."""
    from toplingdb_tpu.env.env import AsyncIORing
    from toplingdb_tpu.table.prefetch import FilePrefetchBuffer

    class CountingFile:
        def __init__(self, n):
            self.blob = bytes(range(256)) * (n // 256)
            self.reads = 0

        def read(self, off, n):
            self.reads += 1
            return self.blob[off:off + n]

        def size(self):
            return len(self.blob)

    ring = AsyncIORing(capacity=16)
    f = CountingFile(1 << 20)
    pf = FilePrefetchBuffer(f, initial_readahead=64 * 1024,
                            arm_immediately=True, aio_ring=ring)
    out = b""
    off = 0
    while off < f.size():
        chunk = pf.read(off, 4096)
        out += chunk
        off += len(chunk)
    assert out == f.blob
    assert pf.hits > pf.misses  # windows served most reads
    ring.close()


def test_db_http_view_write_plane(tmp_path):
    """/db/<name> surfaces the WRITE_GROUP_* family next to WAL_*."""
    from toplingdb_tpu.utils.config import SidePluginRepo

    repo = SidePluginRepo()
    db = repo.open_db({"path": str(tmp_path / "h"),
                       "options": {"statistics": "default"}})
    name = list(repo._dbs)[0]
    for i in range(50):
        db.put(b"h%04d" % i, b"v")
    view = repo._route(["db", name])
    assert view is not None
    t = view["tickers"]
    for key in (st.WAL_BYTES, st.WRITE_GROUP_LED,
                st.WRITE_GROUP_NATIVE_COMMITS, st.WRITE_GROUP_FALLBACKS,
                st.WRITE_GROUP_FSYNCS_COALESCED):
        assert key in t
    assert t[st.WRITE_GROUP_LED] > 0
    assert view["write_group_bytes"]["count"] > 0
    repo.close_all()


def test_watermark_bookkeeping_unordered_stress(tmp_path):
    """The deque+watermark publish bookkeeping: many small staged groups
    publish in allocation order with no lost watermark advance."""
    import threading

    db = DB.open(str(tmp_path / "w"),
                 Options(create_if_missing=True, unordered_write=True))
    errs = []

    def w(t):
        try:
            for i in range(300):
                db.put(b"u%d-%05d" % (t, i), b"x")
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [__import__("threading").Thread(target=w, args=(t,))
          for t in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    assert not db._alloc_ranges and not db._alloc_entry
    assert db.versions.last_sequence == 4 * 300
    for t in range(4):
        for i in range(300):
            assert db.get(b"u%d-%05d" % (t, i)) == b"x"
    db.close()


# -- the fused plane hands the skiplist runs (tests/test_memtable.py) -------

from test_memtable import (  # noqa: E402
    D, KEY_SHAPES, RUN_LENGTHS, SD, V, check_reads_back, make_batch, make_ops,
    replayed, sorted_rows)


@pytest.mark.parametrize("n", RUN_LENGTHS)
@pytest.mark.parametrize("shape", sorted(KEY_SHAPES))
def test_group_commit_run_insert_reads_back_sorted(tmp_path, shape, n):
    """tpulsm_wb_group_commit's apply loop, a unit a run (a batch of 5000
    fans out over the ApplyPool on disjoint units): the memtable reads back
    as the plain sorted list, entries and deletes as note_group_applied
    books them, the tickers say how much of it arrived in runs."""
    from toplingdb_tpu.tools import fuzz_native as fz

    stats = st.Statistics()
    db = DB.open(str(tmp_path / "db"), Options(
        create_if_missing=True, statistics=stats,
        write_buffer_size=256 << 20))
    try:
        ops = make_ops(shape, n)
        wb = make_batch(ops)
        db.write(wb)
        assert stats.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) == 1
        first_seq = wb.sequence()
        mem = db._cfs[0].mem
        check_reads_back(mem, sorted_rows(ops, first_seq))
        assert mem.num_entries == n
        assert mem.num_deletes == sum(t in (D, SD) for t, _, _ in ops)
        assert stats.get_ticker_count(st.MEMTABLE_INSERT_RECORDS) == n
        assert stats.get_ticker_count(st.MEMTABLE_INSERT_RUN_RECORDS) == (
            n if n >= 2 else 0)
        # The same records again with other values, as a replay would hand
        # them over (mode 2: validate, then insert): every
        # one an exact duplicate, replaced in place.
        again = replayed(ops)
        assert fz.group_commit_insert(mem._rep, make_batch(again).data(),
                                      first_seq) == n
        check_reads_back(mem, sorted_rows(again, first_seq))
        # Single writes land among them (runs of one).
        db.put(b"ab", b"one")
        db.delete(b"ab\0")
        more = [(V, b"ab", b"one"), (D, b"ab\0", b"")]
        check_reads_back(mem, sorted(sorted_rows(again, first_seq)
                                     + sorted_rows(more, first_seq + n)))
        assert stats.get_ticker_count(st.MEMTABLE_INSERT_RECORDS) == n + 2
        assert stats.get_ticker_count(st.MEMTABLE_INSERT_RUN_RECORDS) == (
            n if n >= 2 else 0)
    finally:
        db.close()
