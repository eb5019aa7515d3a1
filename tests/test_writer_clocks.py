"""The writer's own clocks inside DB.write (write.native.micros,
write.gil.wait.micros from the native plane's out[8..9]; none of them a
system call), every thread of a process by CPU and run-queue wait
(telemetry.thread_times: the DB's tpulsm.thread-times, the dcompact
service's /stats?threads=1; the writer's CPU over a window is the led
writer's delta there), and lint rule 5 (no declared name that nobody
records)."""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request

import pytest

from toplingdb_tpu import native
from toplingdb_tpu.db.db import DB
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.options import Options
from toplingdb_tpu.tools import check_telemetry
from toplingdb_tpu.utils import statistics as st
from toplingdb_tpu.utils import telemetry

CLOCKS = (st.WRITE_NATIVE_MICROS, st.WRITE_GIL_WAIT_MICROS)


def _plane_available() -> bool:
    lib = native.lib()
    return lib is not None and hasattr(lib, "tpulsm_wb_group_commit")


needs_plane = pytest.mark.skipif(not _plane_available(),
                                 reason="native write plane not built")


def _open(tmp_path, name="db", **kw):
    stats = st.Statistics()
    return (DB.open(str(tmp_path / name), Options(statistics=stats, **kw)),
            stats)


def _batch(i: int, n: int = 500) -> WriteBatch:
    wb = WriteBatch()
    for j in range(n):
        wb.put(b"%08d" % (i * n + j), b"v" * 20)
    return wb


def _clocks(stats) -> dict:
    t = stats.tickers()
    return {k: t.get(k, 0) for k in CLOCKS}


def _thread_times(db) -> dict:
    return json.loads(db.get_property("tpulsm.thread-times"))


def _led(doc: dict) -> dict:
    led = [e for e in doc["threads"] if e["led_writer"]]
    assert len(led) == 1, doc
    return led[0]


def _write_checked(db, stats, n: int = 40) -> None:
    """n batches of 500, each holding native + gil.wait <= wall, wall
    being db.write.micros."""
    h = stats.get_histogram(st.DB_WRITE_MICROS)
    for i in range(n):
        c0, w0 = _clocks(stats), h.sum
        db.write(_batch(i))
        c1, wall = _clocks(stats), h.sum - w0
        d = {k: c1[k] - c0[k] for k in CLOCKS}
        assert d[st.WRITE_NATIVE_MICROS] > 0
        assert (d[st.WRITE_NATIVE_MICROS] + d[st.WRITE_GIL_WAIT_MICROS]
                <= wall + 1e-6), (d, wall)


@needs_plane
def test_gil_hog_raises_gil_wait(tmp_path):
    """(a) A pure-Python loop on another thread holds the GIL whenever the
    writer's native calls release it: the wait to get it back rises at
    least tenfold over the same batches on a quiet process."""

    def run(name: str, hog: bool) -> int:
        db, stats = _open(tmp_path, name)
        stop = threading.Event()

        def spin():
            x = 0
            while not stop.is_set():
                x += 1

        th = threading.Thread(target=spin, name="gil-hog")
        if hog:
            th.start()
        try:
            for i in range(200):
                db.write(_batch(i))
        finally:
            stop.set()
            if hog:
                th.join(timeout=30)
                assert not th.is_alive()
            db.close()
        return _clocks(stats)[st.WRITE_GIL_WAIT_MICROS]

    quiet = run("quiet", hog=False)
    held = run("hog", hog=True)
    assert held > 0
    assert held >= 10 * quiet, (held, quiet)


@needs_plane
@pytest.mark.parametrize("pipelined", [False, True])
def test_identities_hold_on_every_batch(tmp_path, pipelined):
    """(b) native + gil.wait <= wall, batch by batch, over 20,000 puts,
    wall being db.write.micros; also on the staged path, whose frame and
    insert calls the leader makes in two steps. The writer's CPU over the
    40 batches, the led writer's cpu_ns delta in tpulsm.thread-times, is
    never negative and, on a kernel with schedstat, within the window's
    wall."""
    db, stats = _open(tmp_path, enable_pipelined_write=pipelined)
    try:
        db.write(_batch(40))  # the led mark set before the window opens
        a = _thread_times(db)
        _write_checked(db, stats)
        b = _thread_times(db)
    finally:
        db.close()
    la, lb = _led(a), _led(b)
    assert la["tid"] == lb["tid"] == threading.get_native_id()
    cpu = lb["cpu_ns"] - la["cpu_ns"]
    assert cpu >= 0
    if not lb["coarse"]:
        assert cpu <= b["monotonic_ns"] - a["monotonic_ns"], (cpu, a, b)
    t = stats.tickers()
    assert t[st.WRITE_GROUP_NATIVE_COMMITS] == 41
    assert t[st.MEMTABLE_INSERT_RECORDS] == 20_500


@needs_plane
@pytest.mark.parametrize("pipelined", [False, True])
def test_write_path_makes_no_clock_syscall(tmp_path, monkeypatch,
                                           pipelined):
    """DB.write with statistics on calls neither time.thread_time_ns nor
    threading.get_native_id (each a system call), on the native plane and
    on the staged path, and its clocks keep their identities."""
    db, stats = _open(tmp_path, enable_pipelined_write=pipelined,
                      write_buffer_size=64 << 20)  # no flush, no new thread

    def syscall(*_):
        raise AssertionError("a system call on the writer's path")

    me = threading.get_native_id()
    try:
        monkeypatch.setattr(time, "thread_time_ns", syscall)
        monkeypatch.setattr(threading, "get_native_id", syscall)
        _write_checked(db, stats)
        assert _led(_thread_times(db))["tid"] == me
    finally:
        monkeypatch.undo()
        db.close()
    t = stats.tickers()
    assert t[st.WRITE_GROUP_NATIVE_COMMITS] == 40
    assert stats.get_histogram(st.DB_WRITE_MICROS).count == 40


def test_led_writer_resolved_at_read(tmp_path):
    """The led mark is the thread's ident, resolved to its kernel tid when
    tpulsm.thread-times is read: a writer on another thread is marked while
    it lives, and resolves to None once it has exited."""
    db, _ = _open(tmp_path)
    wrote, release = threading.Event(), threading.Event()
    seen = {}

    def writer():
        db.write(_batch(0))
        seen["tid"] = threading.get_native_id()
        wrote.set()
        release.wait(timeout=30)

    th = threading.Thread(target=writer, name="side-writer")
    try:
        th.start()
        assert wrote.wait(timeout=30)
        live = _thread_times(db)
        release.set()
        th.join(timeout=30)
        assert not th.is_alive()
        gone = _thread_times(db)
    finally:
        release.set()
        db.close()
    led = _led(live)
    assert led["tid"] == seen["tid"] == live["led_writer_tid"]
    assert led["name"] == "side-writer"
    assert seen["tid"] != threading.get_native_id()
    assert gone["led_writer_tid"] is None
    assert not any(e["led_writer"] for e in gone["threads"])


def _check_doc(doc: dict) -> None:
    assert set(doc) == {"pid", "monotonic_ns", "time_ns", "led_writer_tid",
                        "gc_pause_us", "gc_collections", "threads"}
    assert doc["pid"] == os.getpid()
    for e in doc["threads"]:
        assert set(e) == {"tid", "name", "python", "led_writer", "cpu_ns",
                          "runq_ns", "timeslices", "coarse"}
        assert e["cpu_ns"] >= 0 and e["runq_ns"] >= 0


def _never_decrease(a: dict, b: dict) -> None:
    before = {e["tid"]: e for e in a["threads"]}
    for e in b["threads"]:
        old = before.get(e["tid"])
        if old is None:
            continue
        for k in ("cpu_ns", "runq_ns", "timeslices"):
            assert e[k] >= old[k], (k, old, e)
    assert b["monotonic_ns"] > a["monotonic_ns"]
    assert b["gc_pause_us"] >= a["gc_pause_us"]
    assert b["gc_collections"] >= a["gc_collections"]


def test_thread_times_property_and_service_stats(tmp_path):
    """(c) tpulsm.thread-times names the flush thread and marks the led
    writer, its counters never decrease; the service's /stats?threads=1
    carries the same document, and plain /stats keeps exactly its keys."""
    db, _ = _open(tmp_path)
    try:
        db.write(_batch(0))
        db.flush()
        first = json.loads(db.get_property("tpulsm.thread-times"))
        for i in range(1, 20):
            db.write(_batch(i))
        second = json.loads(db.get_property("tpulsm.thread-times"))
    finally:
        db.close()
    for doc in (first, second):
        _check_doc(doc)
        names = {e["name"] for e in doc["threads"]}
        assert "db-flush" in names
        led = [e for e in doc["threads"] if e["led_writer"]]
        assert len(led) == 1
        assert led[0]["tid"] == threading.get_native_id() \
            == doc["led_writer_tid"]
        assert led[0]["name"] == threading.current_thread().name
    _never_decrease(first, second)

    from toplingdb_tpu.compaction.dcompact_service import (
        JOB_SUM_COUNTERS, DcompactWorkerService)

    svc = DcompactWorkerService(device="cpu")
    port = svc.start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=30) as r:
                return json.loads(r.read())

        plain = get("/stats")
        a = get("/stats?threads=1")
        b = get("/stats?threads=1")
    finally:
        svc.stop()
    keys = {"device", "jax", "jobs_done", "jobs_failed",
            "jobs_left_pipeline", *JOB_SUM_COUNTERS}
    assert set(plain) == keys
    assert set(a) == keys | {"threads"}
    for doc in (a["threads"], b["threads"]):
        _check_doc(doc)
        assert doc["led_writer_tid"] is None
        assert any(e["python"] and e["name"] != "MainThread"
                   for e in doc["threads"])  # the server's own threads
    _never_decrease(a["threads"], b["threads"])


def test_thread_times_coarse_without_schedstat(monkeypatch):
    """Where the kernel has no schedstat: utime + stime from stat, flagged
    coarse, no run-queue time."""
    real = telemetry._read
    monkeypatch.setattr(
        telemetry, "_read",
        lambda p: None if p.endswith("schedstat") else real(p))
    doc = telemetry.thread_times()
    _check_doc(doc)
    assert doc["threads"]
    assert all(e["coarse"] and e["runq_ns"] == 0 for e in doc["threads"])
    me = [e for e in doc["threads"]
          if e["tid"] == threading.get_native_id()]
    assert me and me[0]["name"] == threading.current_thread().name


@needs_plane
def test_zero_stamps_record_nothing(tmp_path):
    """(d) A native side that leaves out[8..9] at 0 (an older .so) books no
    native or GIL time; the wall, the Python side's own, still runs."""
    db, stats = _open(tmp_path)
    real = db._resolve_write_plane()

    def unstamped(*args):
        rc = real(*args)
        out = args[-1]
        out[8] = 0
        out[9] = 0
        return rc

    db._write_plane = unstamped
    try:
        for i in range(20):
            db.write(_batch(i))
    finally:
        db.close()
    t = stats.tickers()
    assert t[st.WRITE_GROUP_NATIVE_COMMITS] == 20
    assert st.WRITE_NATIVE_MICROS not in t
    assert st.WRITE_GIL_WAIT_MICROS not in t
    h = stats.get_histogram(st.DB_WRITE_MICROS)
    assert h.count == 20 and h.sum > 0


def test_lint_rule5_unrecorded_names(tmp_path):
    """(e) Rule 5 names a declared statistic that no code uses, counts the
    constant, a getattr by name and the string alike, and is clean on the
    tree."""
    pkg = tmp_path / "pkg"
    (pkg / "utils").mkdir(parents=True)
    decl = pkg / "utils" / "statistics.py"
    decl.write_text('A = "a.x"\nB = "b.x"\nC = "c.x"\nD = "d.x"\n'
                    'def f(s):\n    s.record_tick(A)\n')
    (pkg / "user.py").write_text(
        'from utils import statistics as st\n'
        'def g(s):\n    s.record_tick("b.x")\n'
        '    s.record_tick(getattr(st, "C"))\n')
    assert check_telemetry.unrecorded_stat_names(str(pkg),
                                                 str(decl)) == ["D"]
    (pkg / "more.py").write_text('def h(s):\n    s.record_tick(st.D)\n')
    assert check_telemetry.unrecorded_stat_names(str(pkg), str(decl)) == []
    assert check_telemetry.run() == []
