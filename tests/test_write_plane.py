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
                st.WRITE_GROUP_NATIVE_COMMITS, st.WRITE_GROUP_ONE_CALL,
                st.WRITE_GROUP_FALLBACKS, st.WRITE_GROUP_FSYNCS_COALESCED):
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


# -- a group's sorted run shared with the apply pool's worker ---------------

def _native_const(name: str) -> int:
    """A constant of native/tpulsm_native.cc (`static const ... = <int>;`)."""
    import re

    with open(os.path.join(REPO, "toplingdb_tpu", "native",
                           "tpulsm_native.cc")) as f:
        m = re.search(r"static const \w+ %s = (\d+);" % name, f.read())
    return int(m.group(1))


PAIR_APPLY = _native_const("kPairApply")
MAX_RUN = _native_const("kMaxRun")
# Around a chunk (64 records), the cut, a run (kMaxRun) and the trie's 512.
GROUP_SIZES = sorted({1, 31, 64, 65, 255, PAIR_APPLY - 1, PAIR_APPLY, 500, 511,
                      512, 4097, 2 * MAX_RUN + PAIR_APPLY + 3})
MULTI_CORE = len(os.sched_getaffinity(0)) >= 2


def _plane_out():
    import ctypes

    return (ctypes.c_int64 * 11)()


@pytest.mark.parametrize("n", GROUP_SIZES)
@pytest.mark.parametrize("shape", ["k8", "same_key_3_times",
                                   "deletes_and_merges"])
@pytest.mark.parametrize("kind", ["skiplist", "trie"])
def test_group_apply_matches_insert_wb(kind, shape, n):
    """The fused plane's apply. Skiplist: a group's run of kPairApply
    records or more is sorted once and inserted by chunks by the calling
    thread and the pool's worker. Trie: a group of 512 records or more is
    cut into record ranges over the pool. Either rep holds exactly what its
    one-thread insert_wb makes of the same image: every (user key, seq,
    type, value), the count, the byte delta and the delete count; a replay
    of the same records (every one an exact duplicate) replaces the values
    in place on both."""
    from toplingdb_tpu.db.memtable import NativeSkipListRep, NativeTrieRep
    from toplingdb_tpu.tools import fuzz_native as fz

    rep = {"skiplist": NativeSkipListRep, "trie": NativeTrieRep}[kind]
    cut = PAIR_APPLY if kind == "skiplist" else 512
    first_seq = 7000
    ops = make_ops(shape, n)
    one, two = rep(), rep()
    for records in (ops, replayed(ops)):
        data = make_batch(records).data()
        count, delta, deletes = one.insert_wb(data, first_seq)
        out = _plane_out()
        assert fz.group_commit_insert(two, data, first_seq, out) == n == count
        assert (out[2], out[3]) == (delta, deletes)
        assert deletes == sum(t in (D, SD) for t, _, _ in records)
        assert 0 <= out[10] <= n
        if n < cut:
            assert out[10] == 0
        assert len(two) == len(one) == n
        rows = list(two.iter_all())
        assert rows == list(one.iter_all()) == sorted_rows(records, first_seq)


def test_group_apply_on_two_threads_under_readers():
    """Readers iterate and seek while 500-record groups go in on two
    threads: every row a reader meets is a whole record that was written
    (no half-linked node), in the list's order; afterwards the list holds
    every group, and the worker took records."""
    import threading

    from toplingdb_tpu.db.memtable import NativeSkipListRep
    from toplingdb_tpu.tools import fuzz_native as fz

    rep = NativeSkipListRep()
    groups, want = [], {}
    seq = 1
    for g in range(40):
        ops = make_ops("same_key_3_times", 500 + g)[:500]
        groups.append((make_batch(ops).data(), seq))
        for (uk, inv), v in sorted_rows(ops, seq):
            want[(uk, inv)] = v
        seq += len(ops)
    done = threading.Event()
    errors, worker_records = [], []

    def writer():
        try:
            out = _plane_out()
            for data, first in groups:
                assert fz.group_commit_insert(rep, data, first, out) == 500
                worker_records.append(out[10])
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))
        finally:
            done.set()

    def reader():
        try:
            while not done.is_set():
                rows = list(rep.iter_all())
                assert rows == sorted(rows)
                for skey, v in rows:
                    assert want[skey] == v
                for skey, v in rows[::53]:
                    assert rep.entry_at(rep.pos_seek_ge(skey)) == (skey, v)
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    ts = [threading.Thread(target=reader) for _ in range(2)]
    ts.append(threading.Thread(target=writer))
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
        assert not t.is_alive()
    assert not errors, errors
    assert list(rep.iter_all()) == sorted(want.items())
    assert (sum(worker_records) > 0) == MULTI_CORE


def test_concurrent_group_applies_do_not_queue_for_the_pool():
    """Two leaders apply 300-record groups at once (the staged write modes
    insert outside the DB's mutex): a group that finds the pool busy with
    the other's job goes on its own thread instead of waiting for it, and
    the list holds every record of both exactly once."""
    import threading

    from toplingdb_tpu.db.memtable import NativeSkipListRep
    from toplingdb_tpu.tools import fuzz_native as fz

    rep = NativeSkipListRep()
    per, rounds = 300, 60
    work = {w: [] for w in range(2)}
    want = []
    seq = 1
    for r in range(rounds):
        for w in range(2):
            ops = [(V, b"w%d-%08d" % (w, (r * 7919 + i * 104729) % 10**8),
                    b"v%d.%d" % (r, i)) for i in range(per)]
            work[w].append((make_batch(ops).data(), seq))
            want += sorted_rows(ops, seq)
            seq += per
    start = threading.Barrier(2)
    errors, by_worker = [], []

    def leader(w):
        try:
            out = _plane_out()
            start.wait()
            for data, first in work[w]:
                assert fz.group_commit_insert(rep, data, first, out) == per
                by_worker.append(out[10])
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    ts = [threading.Thread(target=leader, args=(w,)) for w in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
        assert not t.is_alive()
    assert not errors, errors
    assert len(rep) == len(want) == 2 * per * rounds
    assert list(rep.iter_all()) == sorted(want)
    assert len(by_worker) == 2 * rounds
    assert all(0 <= x <= per for x in by_worker)


@pytest.mark.parametrize("n", [10, 500])
def test_parallel_records_ticker(tmp_path, n):
    """memtable.insert.parallel.records books out[10]: it ticks on
    500-put batches (the worker takes chunks) and stays 0 on 10-put
    batches, which stay on the writer's thread."""
    stats = st.Statistics()
    db = DB.open(str(tmp_path / "db"), Options(
        create_if_missing=True, statistics=stats,
        write_buffer_size=256 << 20))
    try:
        for i in range(100):
            b = WriteBatch()
            for j in range(n):
                b.put(b"%08d" % ((i * 7919 + j * 104729) % 10**8), b"v" * 20)
            db.write(b)
            if n < PAIR_APPLY or stats.get_ticker_count(
                    st.MEMTABLE_INSERT_PARALLEL_RECORDS):
                break
        par = stats.get_ticker_count(st.MEMTABLE_INSERT_PARALLEL_RECORDS)
        records = stats.get_ticker_count(st.MEMTABLE_INSERT_RECORDS)
        assert records == (i + 1) * n
        assert (par > 0) == (n >= PAIR_APPLY and MULTI_CORE)
        assert par <= records
    finally:
        db.close()


# -- deferred puts reach the plane as today's wire images -------------------

class _EagerBatch(WriteBatch):
    """Today's encode: every put through _add_record at once."""

    def put(self, key, value, cf=0):
        from toplingdb_tpu.db.dbformat import ValueType

        self._add_record(ValueType.VALUE, cf, key, value)


def _crash_copy(d):
    """Copies the open DB's directory as a crash would leave it (close
    flushes the memtable and empties the WAL); returns its WAL bytes."""
    shutil.copytree(d, d + "-crash")
    return _wal_bytes(d + "-crash")


def _deferred_db(d, pb=0, **kw):
    stats = st.Statistics()
    return DB.open(d, Options(create_if_missing=True, statistics=stats,
                              protection_bytes_per_key=pb, **kw)), stats


@pytest.mark.parametrize("pb", [0, 8])
def test_deferred_batch_native_commit_and_replay(tmp_path, pb):
    """A batch whose puts are still pending at DB.write commits through the
    native group plane (not the fallback), ticks every put as deferred, and
    reads back after close and WAL replay; the WAL holds today's bytes."""
    wals = {}
    for cls in (WriteBatch, _EagerBatch):
        d = str(tmp_path / cls.__name__)
        db, stats = _deferred_db(d, pb)
        n = 0
        for i in range(0, 600, 50):
            b = cls(protection_bytes_per_key=pb)
            for j in range(i, i + 50):
                b.put(b"k%06d" % j, b"v" * (j % 200))
            n += 50
            assert bool(b._pend) == (cls is WriteBatch)
            db.write(b, WriteOptions(sync=True))
        assert stats.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) == 12
        assert stats.get_ticker_count(st.WRITE_GROUP_FALLBACKS) == 0
        assert stats.get_ticker_count(st.NUMBER_KEYS_WRITTEN) == n
        assert stats.get_ticker_count(st.WRITE_BATCH_DEFERRED_RECORDS) == (
            n if cls is WriteBatch else 0)
        wals[cls] = _crash_copy(d)
        db.close()
        for path in (d, d + "-crash"):  # after close; after WAL replay
            with DB.open(path, Options(protection_bytes_per_key=pb)) as r:
                for j in range(600):
                    assert r.get(b"k%06d" % j) == b"v" * (j % 200)
    assert wals[WriteBatch] == wals[_EagerBatch]
    assert any(wals[WriteBatch].values())


def test_deferred_group_frames_todays_wal_record(tmp_path):
    """Three pending batches led as ONE group frame one WAL record, byte
    for byte the record the same batches encoded at once frame."""
    import threading
    import time

    wals = {}
    for cls in (WriteBatch, _EagerBatch):
        d = str(tmp_path / cls.__name__)
        db, stats = _deferred_db(d, pb=8)
        snapshot = db._snapshot_group

        def snap(leader, db=db, snapshot=snapshot):
            deadline = time.monotonic() + 30
            while len(db._writers) < 3 and time.monotonic() < deadline:
                time.sleep(0.001)
            return snapshot(leader)

        db._snapshot_group = snap
        batches = []
        for w in range(3):
            b = cls(protection_bytes_per_key=8)
            for j in range(40):
                b.put(b"g%d-%04d" % (w, j), b"x" * (j * 7 % 150))
            batches.append(b)
        ts = []
        for w, b in enumerate(batches):
            t = threading.Thread(target=db.write, args=(b,))
            t.start()
            ts.append(t)
            while len(db._writers) < w + 1 and t.is_alive():
                time.sleep(0.001)  # queue in order: w0 leads, w1, w2
        for t in ts:
            t.join(60)
        assert stats.get_ticker_count(st.WRITE_GROUP_LED) == 1
        assert stats.get_ticker_count(st.WRITE_GROUP_FOLLOWERS) == 2
        assert stats.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) == 1
        assert stats.get_ticker_count(st.WRITE_BATCH_DEFERRED_RECORDS) == (
            120 if cls is WriteBatch else 0)
        db.flush_wal(sync=True)
        wals[cls] = _crash_copy(d)
        db.close()
        with DB.open(d + "-crash", Options(protection_bytes_per_key=8)) as r:
            assert r.get(b"g2-0039") == b"x" * (39 * 7 % 150)
    assert wals[WriteBatch] == wals[_EagerBatch]
    assert any(wals[WriteBatch].values())


def test_deferred_ticker_silent_for_merges(tmp_path):
    """A merge-only stream never takes the deferred path."""
    import struct

    from toplingdb_tpu.utils.merge_operator import UInt64AddOperator

    db, stats = _deferred_db(str(tmp_path / "m"),
                             merge_operator=UInt64AddOperator())
    for i in range(10):
        b = WriteBatch()
        for j in range(20):
            b.merge(b"c%03d" % j, struct.pack("<Q", 1))
        db.write(b)
    assert stats.get_ticker_count(st.NUMBER_KEYS_WRITTEN) == 200
    assert stats.get_ticker_count(st.WRITE_BATCH_DEFERRED_RECORDS) == 0
    assert struct.unpack("<Q", db.get(b"c007"))[0] == 10
    db.close()


# -- a group committed in one native call: frame, WAL write, insert ---------

def _one_call_db(d, path, monkeypatch, **kw):
    """A DB whose groups take `path`: "python" (the Python interiors),
    "two_calls" (the plane's frame call, Python's WAL append, the insert
    call: the WAL file's type hidden from the one-call test until
    monkeypatch.undo()) or "one_call"."""
    from toplingdb_tpu.db import db as db_mod

    monkeypatch.setenv("TPULSM_WRITE_PLANE", "0" if path == "python" else "1")
    if path == "two_calls":
        monkeypatch.setattr(db_mod, "_PosixWritable", type("Hidden", (), {}))
    stats = st.Statistics()
    db = DB.open(d, Options(create_if_missing=True, statistics=stats,
                            write_buffer_size=64 << 20, **kw))
    return db, stats


def _one_call_batches(per):
    """Batches of one 1,140-byte put (the YCSB update) or of 500 8B/20B
    puts (db_bench's), three phases of them."""
    out = []
    for phase in range(3):
        for g in range(60 if per == 1 else 8):
            b = WriteBatch()
            pairs = []
            for j in range(per):
                i = (phase * 1000 + g) * per + j
                pairs.append((b"user%d" % (i * 2654435761 % 10**9)
                              if per == 1 else b"%08d" % (i * 7919 % 10**8),
                              bytes([i % 251]) * (1140 if per == 1 else 20)))
                b.put(*pairs[-1])
            out.append((phase, b, pairs))
    return out


@pytest.mark.parametrize("per", [1, 500])
@pytest.mark.parametrize("fmt", ["classic", "recyclable"])
def test_one_call_wal_parity_and_recovery(tmp_path, monkeypatch, fmt, per):
    """The one-call commit writes the WAL byte for byte as the plane's two
    calls and the Python interiors do, in the classic and the recyclable
    format (the third phase into a reused WAL file), for groups of one put
    and of 500; the file's size and the iostats byte count agree; every
    acknowledged put reads back after WAL replay."""
    kw = {"recycle_log_file_num": 2} if fmt == "recyclable" else {}
    seen = {}
    for path in ("python", "two_calls", "one_call"):
        d = str(tmp_path / path)
        db, stats = _one_call_db(d, path, monkeypatch, **kw)
        io_bytes = 0
        st.perf_level = 1
        try:
            phase_seen = 0
            for phase, b, _ in _one_call_batches(per):
                if phase != phase_seen:
                    db.flush()  # a new WAL; the second reuses the first
                    phase_seen = phase
                ctx = st.iostats_context()
                before = ctx.bytes_written
                db.write(b)
                io_bytes += ctx.bytes_written - before
        finally:
            st.perf_level = 0
            monkeypatch.undo()
        wf = db._wal._f
        assert ("+" in wf._f.mode) == (fmt == "recyclable")
        led = stats.get_ticker_count(st.WRITE_GROUP_LED)
        assert led == 3 * (60 if per == 1 else 8)
        assert stats.get_ticker_count(st.WRITE_GROUP_ONE_CALL) == (
            led if path == "one_call" else 0)
        assert stats.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) == (
            0 if path == "python" else led)
        wal = _crash_copy(d)
        seen[path] = (wal, wf.file_size(), io_bytes,
                      stats.get_ticker_count(st.WAL_BYTES),
                      db.versions.last_sequence)
        if fmt == "classic":
            assert wf.file_size() == max(len(v) for v in wal.values())
        db.close()
        with DB.open(d + "-crash", Options()) as r:
            want = {k: v for _, _, pairs in _one_call_batches(per)
                    for k, v in pairs}
            assert all(r.get(k) == v for k, v in want.items())
            assert r.versions.last_sequence == seen[path][-1]
    assert seen["python"] == seen["two_calls"] == seen["one_call"]
    assert seen["one_call"][2] > 0


@pytest.mark.parametrize("fd", ["read_only", "dev_full", "torn_pipe"])
def test_one_call_failed_wal_write_inserts_nothing(tmp_path, fd):
    """A WAL write that fails inside the one call (the WAL's descriptor
    pointed at a read-only file, at /dev/full, or at a full non-blocking
    pipe that takes part of the record) raises IOError_ with nothing in the
    memtable and last_sequence unmoved. Where nothing reached the file, the
    file is as it was and the next put goes through; after WAL replay the
    failed put is not there and the others are. A torn record that cannot
    be cut off again (a pipe has no offset) latches a FATAL background
    error: no later record may follow it."""
    from toplingdb_tpu.utils.status import IOError_, NoSpace, Severity

    if fd == "dev_full" and not os.path.exists("/dev/full"):
        pytest.skip("no /dev/full")
    d = str(tmp_path / "db")
    stats = st.Statistics()
    db = DB.open(d, Options(create_if_missing=True, statistics=stats))
    torn = fd == "torn_pipe"
    try:
        db.put(b"before", b"v")
        mem = db._cfs[0].mem
        seq0, n0 = db.versions.last_sequence, mem.num_entries
        wf = db._wal._f
        size0 = wf.file_size()
        wal_fd = wf._f.fileno()
        saved = os.dup(wal_fd)
        if fd == "read_only":
            bad, extra = os.open(os.devnull, os.O_RDONLY), None
        elif fd == "dev_full":
            bad, extra = os.open("/dev/full", os.O_WRONLY), None
        else:
            extra, bad = os.pipe()
            os.set_blocking(bad, False)
        os.dup2(bad, wal_fd)
        try:
            with pytest.raises(IOError_) as err:
                db.put(b"lost", b"x" * (1 << 20 if torn else 1140))
        finally:
            os.dup2(saved, wal_fd)
            for f in (saved, bad, extra):
                if f is not None:
                    os.close(f)
        assert isinstance(err.value, NoSpace) == (fd == "dev_full")
        assert db.versions.last_sequence == seq0
        assert mem.num_entries == n0
        assert db.get(b"lost") is None
        if torn:
            assert wf.file_size() > size0
            assert db._bg_error_severity == Severity.FATAL_ERROR
            with pytest.raises(IOError_):
                db.put(b"after", b"w")
        else:
            assert wf.file_size() == size0
            db.put(b"after", b"w")  # publishes a sequence above the failed
            assert db.get(b"lost") is None and db.get(b"after") == b"w"
            assert stats.get_ticker_count(st.WRITE_GROUP_ONE_CALL) == 2
        _crash_copy(d)
    finally:
        db.close()
    with DB.open(d + "-crash", Options()) as r:
        assert r.get(b"before") == b"v" and r.get(b"lost") is None
        assert r.get(b"after") == (None if torn else b"w")


@pytest.mark.parametrize("case", ["posix", "async_wal", "fault_env",
                                  "kill_point", "sync", "merge_heavy"])
def test_one_call_ticker(tmp_path, monkeypatch, case):
    """write.group.one.call counts each group once on the plain POSIX WAL
    and stays 0 where the one call would skip what the two calls do: the
    async WAL writer's ring, a fault-injection env's file, an armed kill
    point between WAL and insert, a sync group, a merge-heavy group (which
    never reaches the plane)."""
    import struct

    from toplingdb_tpu.env import PosixEnv
    from toplingdb_tpu.env.fault_injection import FaultInjectionEnv
    from toplingdb_tpu.utils import kill_point
    from toplingdb_tpu.utils.merge_operator import UInt64AddOperator

    kw = {"async_wal": {"enable_async_wal": True},
          "merge_heavy": {"merge_operator": UInt64AddOperator()}}.get(
              case, {})
    env = FaultInjectionEnv(PosixEnv()) if case == "fault_env" else None
    if case == "kill_point":
        # Armed at the marker, at odds it never fires at.
        monkeypatch.setenv("TPULSM_KILL_ODDS", str(10**15))
        monkeypatch.setenv("TPULSM_KILL_SEED", "7")
        monkeypatch.setenv("TPULSM_KILL_PREFIX", "DBImpl::WriteImpl:AfterWAL")
    kill_point.reset_for_tests()
    stats = st.Statistics()
    db = DB.open(str(tmp_path / "db"),
                 Options(create_if_missing=True, statistics=stats, **kw),
                 **({"env": env} if env is not None else {}))
    wo = WriteOptions(sync=case == "sync")
    try:
        for i in range(30):
            if case == "merge_heavy":
                db.merge(b"ctr", struct.pack("<Q", 1), wo)
            else:
                db.put(b"k%04d" % i, b"v%d" % i, wo)
        led = stats.get_ticker_count(st.WRITE_GROUP_LED)
        assert led == 30
        assert stats.get_ticker_count(st.WRITE_GROUP_ONE_CALL) == (
            led if case == "posix" else 0)
        assert stats.get_ticker_count(st.WRITE_GROUP_NATIVE_COMMITS) == (
            0 if case == "merge_heavy" else led)
        if case == "merge_heavy":
            assert struct.unpack("<Q", db.get(b"ctr"))[0] == 30
        else:
            assert db.get(b"k0029") == b"v29"
    finally:
        db.close()
        monkeypatch.undo()
        kill_point.reset_for_tests()
