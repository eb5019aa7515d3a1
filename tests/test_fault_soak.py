"""Fault-injection soak (promoted from session soak testing; complements
the targeted fault tests): cycles of injected append/sync failures during
synced writes — every ACKNOWLEDGED write must survive the faults, resume,
and a clean reopen; failed writes must not corrupt anything."""

import random
import shutil
import tempfile

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.env import PosixEnv
from toplingdb_tpu.env.fault_injection import FaultInjectionEnv
from toplingdb_tpu.options import Options, WriteOptions
from toplingdb_tpu.utils.status import IOError_


def _fail_the_next_manifest_sync(db, fe):
    """Arm a sync fault around the flush thread's next install: its table
    is built and synced, the MANIFEST record is appended, and the sync of
    the MANIFEST fails, which is the one failure resume() refuses."""
    install = db._install_flush_tables

    def armed(built, log_number):
        del db._install_flush_tables  # this install only
        fe.fail_ops = {"sync"}
        try:
            return install(built, log_number)
        finally:
            fe.fail_ops = set()

    db._install_flush_tables = armed


@pytest.mark.parametrize("seed", [0, 3])
def test_intermittent_io_faults_preserve_acknowledged_writes(seed):
    """Since the flush runs on its own thread a fault of the window may
    meet the writer (the put is rejected), the flush or a compaction (a
    background error that resume() clears) or a MANIFEST write (FATAL:
    resume() refuses, and the DB is closed and opened again, as the error
    says). Which of them, timing decides; cycles 1 and 2 make the last two
    happen in every run. Whatever happened: every acknowledged write reads back, and
    no rejected write shows while the DB stays open. A reopen after a FATAL
    error reads the log again, and a write whose sync failed AFTER its
    append is in the log: it may come back then, once, and is the key's
    value from there on."""
    rng = random.Random(seed)
    fe = FaultInjectionEnv(PosixEnv())
    root = tempfile.mkdtemp(prefix=f"faultt{seed}_")
    d = root + "/db"

    def open_db():
        return DB.open(d, Options(write_buffer_size=8 * 1024,
                                  level0_file_num_compaction_trigger=3),
                       env=fe)

    db = open_db()
    model = {}
    touched = set()
    in_log = {}  # key -> rejected values since its last acknowledged write
    wo = WriteOptions(sync=True)
    resumed = reopened = 0

    def check(where, log_read_again=False):
        for k in sorted(touched):
            got = db.get(k)
            if log_read_again and got in in_log.get(k, ()):
                model[k] = got
            assert got == model.get(k), (where, k, got, model.get(k))
        if log_read_again:
            in_log.clear()

    try:
        for cycle in range(6):
            for _ in range(rng.randrange(50, 200)):
                k = b"k%04d" % rng.randrange(500)
                v = b"v%06d" % rng.randrange(10 ** 6)
                db.put(k, v, wo)
                model[k] = v
                touched.add(k)
                in_log.pop(k, None)
            if cycle == 1:  # the flush thread's table: resume() clears it
                # A memtable the puts above sealed may still be on the
                # flush thread: its MANIFEST append would take the fault
                # (FATAL) in place of this flush's table.
                db.wait_for_compactions()
                fe.fail_ops = {"append"}
                with pytest.raises(IOError_, match="injected append error"):
                    db.flush()
                fe.fail_ops = set()
            elif cycle == 2:  # its MANIFEST sync: resume() refuses
                _fail_the_next_manifest_sync(db, fe)
                with pytest.raises(IOError_, match="injected sync error"):
                    db.flush()
            else:
                fe.fail_ops = {rng.choice(["append", "sync"])}
                for _ in range(rng.randrange(5, 30)):
                    k = b"k%04d" % rng.randrange(500)
                    v = b"F%06d" % rng.randrange(10 ** 6)
                    touched.add(k)
                    try:
                        db.put(k, v, wo)
                        model[k] = v  # acknowledged despite faults
                        in_log.pop(k, None)
                    except Exception:
                        in_log.setdefault(k, set()).add(v)  # rejected
                fe.fail_ops = set()
            # What the window left running ends first, in success or in a
            # latched error: one latched after resume() would stay.
            try:
                db.wait_for_compactions()
            except Exception:
                pass
            failed = db.get_property("tpulsm.bg-error-severity") != "NO_ERROR"
            try:
                db.resume()
                resumed += failed
            except IOError_ as e:
                assert "reopen the DB" in str(e)
                check((cycle, "before the reopen"))
                db.close()
                db = open_db()
                reopened += 1
                check((cycle, "reopened"), log_read_again=True)
            db.wait_for_compactions()
            check(cycle)
        assert reopened >= 1 and resumed >= 1
        db.close()
        with DB.open(d, Options()) as db:  # reopen on the REAL env
            check("real env")
    finally:
        shutil.rmtree(root, ignore_errors=True)


# ===========================================================================
# dcompact chaos: injected worker failures must never change bytes on disk
# ===========================================================================

import hashlib
import os

from toplingdb_tpu.compaction.dcompact_service import (
    DcompactWorkerService,
    HttpCompactionExecutorFactory,
)
from toplingdb_tpu.compaction.executor import (
    SubprocessCompactionExecutorFactory,
)
from toplingdb_tpu.compaction.resilience import (
    DcompactFaultInjector,
    DcompactOptions,
)
from toplingdb_tpu.utils import statistics as st
from toplingdb_tpu.utils.statistics import Statistics

FROZEN_TIME = 1_700_000_000.0


def _freeze_time(monkeypatch):
    """Pin time.time() so SST properties (creation_time) are identical
    between a fault run and its no-fault twin; params carry the frozen
    stamp to workers. os mtimes (leases/heartbeats) stay real."""
    import time as _time

    monkeypatch.setattr(_time, "time", lambda: FROZEN_TIME)


def _bottom_level_hashes(db):
    """sha256 of every bottom-level SST, sorted — file NUMBERS may differ
    between runs (failed attempts burn different counters), bytes must
    not."""
    from toplingdb_tpu.db import filename as fn

    v = db.versions.cf_current(0)
    out = []
    for f in v.files[v.num_levels - 1]:
        p = fn.table_file_name(db.dbname, f.number)
        out.append(hashlib.sha256(open(p, "rb").read()).hexdigest())
    return sorted(out)


def _chaos_policy(**kw):
    base = dict(max_attempts=3, backoff_base=0.005, backoff_jitter=0.1,
                attempt_timeout=120.0, breaker_failure_threshold=2,
                breaker_reset_timeout=0.15, local_pin_failures=10 ** 6,
                lease_sec=5.0)
    base.update(kw)
    return DcompactOptions(**base)


def _run_matrix_workload(root, factory, stats):
    opts = Options(write_buffer_size=1 << 14, disable_auto_compactions=True,
                   compaction_executor_factory=factory, statistics=stats,
                   dcompact=getattr(factory, "policy", None))
    db = DB.open(root, opts)
    model = {}
    for i in range(1600):
        k = b"mk%05d" % (i % 500)
        v = b"mv%07d" % i
        db.put(k, v)
        model[k] = v
        if i % 400 == 399:
            db.flush()
    db.flush()
    db.compact_range()
    assert db._bg_error is None
    bad = [k for k, v in model.items() if db.get(k) != v]
    assert not bad, bad[:3]
    hashes = _bottom_level_hashes(db)
    db.close()
    return hashes


@pytest.mark.parametrize("plan", ["drop", "kill", "truncate", "corrupt",
                                  "delay"])
def test_dcompact_chaos_matrix_byte_parity(tmp_path, monkeypatch, plan):
    """Chaos matrix over the HTTP transport: request dropped, worker
    killed mid-job, results truncated, results corrupted, response
    delayed. Every faulted run must end byte-identical to the no-fault
    twin, with the failure attributed as a retry (delay alone succeeds
    first try)."""
    _freeze_time(monkeypatch)
    svc = DcompactWorkerService(device="cpu")
    port = svc.start()
    try:
        clean_stats = Statistics()
        clean = _run_matrix_workload(
            str(tmp_path / "clean"),
            HttpCompactionExecutorFactory([f"http://127.0.0.1:{port}"],
                                          policy=_chaos_policy()),
            clean_stats)

        stats = Statistics()
        inj = DcompactFaultInjector(schedule={0: plan}, delay_sec=0.05)
        fac = HttpCompactionExecutorFactory(
            [f"http://127.0.0.1:{port}"], policy=_chaos_policy(),
            fault_injector=inj)
        faulty = _run_matrix_workload(str(tmp_path / "fault"), fac, stats)

        assert faulty == clean and clean, (plan, clean, faulty)
        t = stats.tickers()
        if plan == "delay":
            assert t.get(st.DCOMPACTION_RETRIES, 0) == 0
        else:
            assert t.get(st.DCOMPACTION_RETRIES, 0) == 1
            assert t[st.DCOMPACTION_ATTEMPTS] == \
                clean_stats.tickers()[st.DCOMPACTION_ATTEMPTS] + 1
        assert t.get(st.DCOMPACTION_JOB_FAILURES, 0) == 0
        assert t.get(st.DCOMPACTION_FALLBACK_LOCAL, 0) == 0
    finally:
        svc.stop()


def test_dcompact_worker_kill_9_subprocess_retries(tmp_path, monkeypatch):
    """REAL process death: the worker subprocess os._exit(137)s mid-job
    (heartbeat written, partial output on disk, no results.json). The
    attempt's partial state is swept, the retry succeeds, bytes match the
    no-fault twin."""
    _freeze_time(monkeypatch)
    clean = _run_matrix_workload(
        str(tmp_path / "clean"),
        SubprocessCompactionExecutorFactory(device="cpu",
                                            policy=_chaos_policy()),
        Statistics())
    stats = Statistics()
    inj = DcompactFaultInjector(schedule={0: "kill"})
    faulty = _run_matrix_workload(
        str(tmp_path / "fault"),
        SubprocessCompactionExecutorFactory(
            device="cpu", policy=_chaos_policy(), fault_injector=inj),
        stats)
    assert faulty == clean and clean
    t = stats.tickers()
    assert t.get(st.DCOMPACTION_RETRIES, 0) == 1
    assert inj.injected_counts() == {"kill": 1}
    # The killed attempt left no residue behind (swept on failure).
    dc = str(tmp_path / "fault" / "dcompact")
    leftovers = []
    for r, _d, fs in os.walk(dc):
        leftovers += [os.path.join(r, f) for f in fs]
    assert leftovers == [], leftovers


def test_dcompact_chaos_soak_30pct_byte_parity(tmp_path, monkeypatch):
    """Acceptance: a real DB under write load with auto compactions
    against a flaky two-worker dcompact fleet failing ~30% of attempts
    (drop/kill/truncate/corrupt) finishes the workload with bottom-level
    SSTs byte-identical to a no-fault run, zero background-error
    escalation, and every failed attempt attributed in DCOMPACTION_*
    statistics."""
    _freeze_time(monkeypatch)

    def soak(root, services, injector, stats):
        urls = [f"http://127.0.0.1:{p}" for p in
                (s.start() for s in services)]
        policy = _chaos_policy()
        fac = HttpCompactionExecutorFactory(
            urls, policy=policy, fault_injector=injector)
        opts = Options(write_buffer_size=1 << 14,
                       level0_file_num_compaction_trigger=2,
                       max_background_jobs=2,
                       compaction_executor_factory=fac, statistics=stats,
                       dcompact=policy)
        db = DB.open(root, opts)
        model = {}
        for i in range(6000):
            k = b"sk%05d" % (i % 700)
            v = b"sv%07d" % i
            db.put(k, v)
            model[k] = v
            if i % 500 == 499:
                db.flush()
        db.flush()
        db.wait_for_compactions()
        db.compact_range()
        assert db._bg_error is None, db._bg_error  # no HARD/FATAL escalation
        bad = [k for k, v in model.items() if db.get(k) != v]
        assert not bad, bad[:3]
        hashes = _bottom_level_hashes(db)
        db.close()
        for s in services:
            s.stop()
        return hashes

    clean = soak(str(tmp_path / "clean"),
                 [DcompactWorkerService(device="cpu") for _ in range(2)],
                 None, Statistics())

    # ~30% of attempts fail; the first three ordinals are forced so the
    # structural outcomes are guaranteed regardless of background timing:
    # job 1 fails all 3 attempts (-> local fallback + job failure), and
    # with two URLs round-robin its attempts land A,B,A — two consecutive
    # failures on A open A's breaker (threshold 2).
    inj = DcompactFaultInjector(
        schedule={0: "drop", 1: "drop", 2: "drop"},
        rate=0.3, plans=("drop", "kill", "truncate", "corrupt"), seed=1234)
    stats = Statistics()
    faulty = soak(str(tmp_path / "fault"),
                  [DcompactWorkerService(device="cpu") for _ in range(2)],
                  inj, stats)

    assert faulty == clean and clean, (clean, faulty)
    t = stats.tickers()
    n_injected = sum(inj.injected_counts().values())
    assert n_injected >= 3
    # Every injected fault surfaced as exactly one failed attempt, and
    # every failed attempt is attributed: it either retried or exhausted
    # its job.
    assert t.get(st.DCOMPACTION_RETRIES, 0) > 0
    assert t.get(st.DCOMPACTION_FALLBACK_LOCAL, 0) > 0
    assert t.get(st.DCOMPACTION_BREAKER_OPEN, 0) > 0
    assert t[st.DCOMPACTION_RETRIES] + t[st.DCOMPACTION_JOB_FAILURES] \
        == n_injected
    assert t[st.DCOMPACTION_FALLBACK_LOCAL] == \
        t[st.DCOMPACTION_JOB_FAILURES] + \
        t.get(st.DCOMPACTION_BREAKER_SKIPPED, 0) + \
        t.get(st.DCOMPACTION_DEADLINE_EXCEEDED, 0)
    assert stats.get_histogram(st.DCOMPACTION_ATTEMPT_MICROS).count == \
        t[st.DCOMPACTION_ATTEMPTS]
