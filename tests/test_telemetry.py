"""Telemetry plane (utils/telemetry.py + the instrumentation it feeds):
tracer unit behavior, write/get/flush/compaction span trees, native
interior timings, cross-process stitching (dcompact HTTP worker,
replication follower acks incl. the dropped-ack degradation), the
/metrics–/traces–/stats_history HTTP surface, PerfContext chunk-path
parity, the IOStats Env feed, event-log correlation + ldb dump_events,
and the check_telemetry name lint."""

import json
import os
import re
import threading
import time
import urllib.request

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.options import Options, ReadOptions, WriteOptions
from toplingdb_tpu.utils import statistics as st
from toplingdb_tpu.utils import telemetry as tm
from toplingdb_tpu.utils.statistics import Statistics


def topts(**kw):
    kw.setdefault("create_if_missing", True)
    kw.setdefault("trace_sample_every", 1)
    return Options(**kw)


def fill(db, n=300, vlen=24):
    for i in range(n):
        db.put(b"key%06d" % i, b"v" * vlen)


# ---------------------------------------------------------------------------
# Tracer unit behavior
# ---------------------------------------------------------------------------


def test_sampling_one_in_n_and_ring_bound():
    tr = tm.Tracer(sample_every=4, ring=8)
    done = 0
    for _ in range(64):
        sp = tr.maybe_sample("db.get")
        if sp is not None:
            sp.finish()
            done += 1
    assert done == 16
    s = tr.status()
    assert s["traces_retained"] == 8  # ring bound, not 16
    assert s["traces_started"] == 16
    assert len(tr._by_id) == 8  # the stitch index tracks the ring
    assert not tr._active


def test_slow_backstop_and_slow_filter():
    tr = tm.Tracer(sample_every=0, slow_usec=1000)
    tr.note_slow("db.get", 5000, key="k")
    fast = tm.Tracer(sample_every=1, slow_usec=10_000_000)
    sp = fast.start("db.write")
    sp.finish()
    assert [t.slow for t in tr.finished()] == [True]
    assert [t.slow for t in fast.finished()] == [False]
    assert tr.finished(slow_only=True)[0].dur_us == 5000


def test_span_tree_and_chrome_export():
    tr = tm.Tracer(sample_every=1)
    root = tr.start("db.write", records=3)
    with tm.span("write.wal_frame", group=2):
        time.sleep(0.002)
        tm.span_event("native.wal_frame", 1500, bytes=64)
    root.finish()
    t = tr.finished()[0]
    names = [s.name for s in t.spans]
    assert names == ["db.write", "write.wal_frame", "native.wal_frame"]
    wal = t.spans[1]
    assert wal.parent_id == t.root.span_id and wal.dur_us >= 2000
    chrome = tr.chrome_trace(t.trace_id)
    assert {e["name"] for e in chrome["traceEvents"]} == set(names)
    assert all(e["ph"] == "X" and e["dur"] >= 1
               for e in chrome["traceEvents"])
    assert chrome["otherData"]["trace_id"] == t.trace_id
    json.dumps(chrome)  # exportable


def test_cross_thread_span_under_and_remote_stitch():
    tr = tm.Tracer(sample_every=1, proc="db")
    root = tr.start("compaction")
    handle = tm.current_handle()
    sp = tm.span_under(handle, "pipeline.merge_gc", shard=3)
    sp.finish()
    tm.span_event_under(handle, "pipeline.scan", 777, shard=0)
    root.finish()
    # Remote spans: known trace stitches, evicted/unknown drops silently.
    n = tr.attach_remote([
        {"name": "dcompact.worker", "trace_id": root.trace_id,
         "span_id": 1, "parent_id": root.span_id, "start_us": 0,
         "dur_us": 5, "proc": "dcompact-worker", "tags": {}},
        {"name": "dcompact.worker", "trace_id": "feedfacedeadbeef",
         "dur_us": 5},
    ])
    assert n == 1
    t = tr.get_trace(root.trace_id)
    assert {s.name for s in t.spans} == {
        "compaction", "pipeline.merge_gc", "pipeline.scan",
        "dcompact.worker"}
    assert {s.proc for s in t.spans} == {"db", "dcompact-worker"}
    assert tr.status()["remote_spans_dropped"] == 1


# ---------------------------------------------------------------------------
# The mirror (real spans only), nesting below span_under, open spans
# ---------------------------------------------------------------------------


class _Mirror:
    """Stands in for jax.profiler.TraceAnnotation: logs each enter and
    exit with the thread it happened on."""

    log: list = []

    def __init__(self, name, **tags):
        self.name, self.tags = name, tags

    def __enter__(self):
        _Mirror.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *a):
        _Mirror.log.append(("exit", self.name, threading.get_ident()))

    def set_metadata(self, **kw):
        _Mirror.log.append(("meta", self.name, kw))


@pytest.fixture
def mirror():
    """The logging mirror for one test; the process's own (the profiler's
    annotation, once a test has loaded the device path) comes back after."""
    before = tm._mirror
    _Mirror.log = []
    tm.set_mirror(_Mirror)
    yield _Mirror
    tm.set_mirror(before)


def test_mirror_sees_every_real_span_once_nested_on_its_thread(mirror):
    tr = tm.Tracer(sample_every=1)
    root = tr.start("dcompact.worker", job_id=7)
    handle = tm.current_handle()
    with tm.span("pipeline.plan"):
        pass

    def reader():
        with tm.span_under(handle, "pipeline.scan", shard=0):
            with tm.span("runtime.gc_pause"):  # nests below span_under
                pass
        assert tm.current_span() is None

    t = threading.Thread(target=reader)
    t.start()
    t.join()
    root.tag(input_records=9)
    root.finish()

    me = threading.get_ident()
    steps = [(kind, name) for kind, name, _ in mirror.log]
    assert steps == [
        ("enter", "dcompact.worker"),
        ("enter", "pipeline.plan"), ("exit", "pipeline.plan"),
        ("enter", "pipeline.scan"), ("enter", "runtime.gc_pause"),
        ("exit", "runtime.gc_pause"), ("exit", "pipeline.scan"),
        ("meta", "dcompact.worker"), ("exit", "dcompact.worker")]
    threads = {name: {th for kind, n, th in mirror.log
                      if n == name and kind != "meta"}
               for name in ("dcompact.worker", "pipeline.plan",
                            "pipeline.scan", "runtime.gc_pause")}
    assert threads["dcompact.worker"] == threads["pipeline.plan"] == {me}
    assert threads["pipeline.scan"] == threads["runtime.gc_pause"]
    assert threads["pipeline.scan"] != {me}
    assert ("meta", "dcompact.worker", {"input_records": 9}) in mirror.log
    # The mirror nests as the tree does.
    (trace,) = tr.finished()
    by_name = {s.name: s for s in trace.spans}
    assert by_name["runtime.gc_pause"].parent_id == \
        by_name["pipeline.scan"].span_id
    assert by_name["pipeline.scan"].parent_id == root.span_id
    assert by_name["pipeline.scan"].tid != root.tid


@pytest.mark.parametrize("record", ["span_event", "span_event_under",
                                    "note_slow"])
def test_back_dated_spans_never_reach_the_mirror(mirror, record):
    tr = tm.Tracer(sample_every=1, slow_usec=1)
    if record == "note_slow":
        tr.note_slow("db.get", 5000)
        assert mirror.log == []
        return
    root = tr.start("db.write")
    if record == "span_event":
        tm.span_event("native.wal_frame", 1500)
    else:
        tm.span_event_under(tm.current_handle(), "native.wal_frame", 1500)
    root.finish()
    assert [name for _k, name, _t in mirror.log] == ["db.write", "db.write"]
    (trace,) = tr.finished()
    assert [s.name for s in trace.spans] == ["db.write", "native.wal_frame"]
    assert trace.spans[1].dur_us == 1500


def test_without_a_mirror_spans_cost_no_annotation(mirror):
    tm.set_mirror(None)
    tr = tm.Tracer(sample_every=1)
    with tr.start("db.write") as root:
        with tm.span("write.wal_frame") as sp:
            assert sp._mirror is None and root._mirror is None
    assert mirror.log == []


def test_span_left_open_ends_with_its_parent_and_finish_is_idempotent():
    tr = tm.Tracer(sample_every=1)
    with pytest.raises(RuntimeError):
        with tr.start("dcompact.worker") as root:
            left_open = tm.span("compaction.prepare")
            raise RuntimeError("boom")
    assert tm.current_span() is None
    assert not left_open._open and left_open.dur_us <= root.dur_us
    assert "boom" in root.tags["error"]
    dur = root.dur_us
    root.finish()  # a second finish changes nothing, retires nothing
    assert root.dur_us == dur and len(tr.finished()) == 1


def test_open_span_exports_its_time_so_far():
    tr = tm.Tracer(sample_every=1)
    root = tr.start("dcompact.worker")
    time.sleep(0.003)
    d = tr.export_trace(root.trace_id)[0]
    assert d["name"] == "dcompact.worker" and d["dur_us"] >= 3000
    assert d["tid"] == threading.get_ident()
    root.finish()
    assert tr.export_trace(root.trace_id)[0]["dur_us"] == root.dur_us
    assert tm.Span.from_dict(d).tid == d["tid"]


@pytest.mark.parametrize("module", ["toplingdb_tpu.utils.telemetry",
                                    "toplingdb_tpu.db.db",
                                    "toplingdb_tpu.compaction.worker"])
def test_importing_the_db_side_loads_no_jax(module):
    """The DB process and the harness never import JAX; the mirror is
    installed by ops/device_runtime.py, which only a process that holds a
    chip loads."""
    import subprocess
    import sys

    code = (f"import sys, {module}; "
            "from toplingdb_tpu.utils import telemetry; "
            "assert telemetry._mirror is None; "
            "sys.exit(int('jax' in sys.modules))")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-500:]


def test_device_runtime_installs_the_profilers_annotation():
    import jax

    from toplingdb_tpu.ops import device_runtime  # noqa: F401

    assert tm._mirror is jax.profiler.TraceAnnotation


# ---------------------------------------------------------------------------
# Engine instrumentation: write / get / flush / compaction
# ---------------------------------------------------------------------------


def test_write_get_flush_span_trees(tmp_path):
    db = DB.open(str(tmp_path / "db"), topts(statistics=Statistics()))
    try:
        fill(db, 200)
        assert db.get(b"key000007") == b"v" * 24
        db.multi_get([b"key000001", b"key000002"])
        db.flush()
        traces = {t.name: t for t in db.tracer.finished(limit=300)}
        assert {"db.write", "db.get", "db.multiget", "flush"} <= set(traces)
        wt = traces["db.write"]
        wnames = {s.name for s in wt.spans}
        assert "write.wal_frame" in wnames
        assert "write.memtable_apply" in wnames
        ft = traces["flush"]
        assert "flush.build_table" in {s.name for s in ft.spans}
        # seq → ctx propagation map is populated and bounded
        assert db.tracer.status()["seq_ctx_entries"] <= 1024
        assert db.tracer.ctxs_in_range(1, 10)
    finally:
        db.close()


def test_native_interior_spans_when_plane_available(tmp_path):
    from toplingdb_tpu import native

    if native.lib() is None:
        pytest.skip("no native lib")
    db = DB.open(str(tmp_path / "db"), topts())
    try:
        from toplingdb_tpu.db.write_batch import WriteBatch

        b = WriteBatch()
        for i in range(50):
            b.put(b"nk%05d" % i, b"v" * 32)
        db.write(b)
        wt = [t for t in db.tracer.finished(limit=50)
              if t.name == "db.write"][0]
        names = {s.name for s in wt.spans}
        if db._write_plane:  # plane resolved: interiors must surface
            assert "native.memtable_insert" in names
            # 50 records stay on the writer's thread (under kPairApply).
            ins = [s for s in wt.spans if s.name == "native.memtable_insert"]
            assert ins[0].tags["threads"] == 1
    finally:
        db.close()


def test_compaction_trace_modes_and_phases(tmp_path):
    db = DB.open(str(tmp_path / "db"),
                 topts(write_buffer_size=16 << 10,
                       statistics=Statistics()))
    try:
        for i in range(1200):
            db.put(b"c%06d" % (i % 400), b"v%06d" % i)
            if i % 300 == 299:
                db.flush()
        db.compact_range()
        comps = [t for t in db.tracer.finished(limit=300)
                 if t.name == "compaction"]
        assert comps
        t = comps[0]
        assert t.root.tags.get("mode") in (
            "serial", "columnar", "device", "pipelined", "remote")
        child_names = {s.name for s in t.spans} - {"compaction"}
        assert child_names & {
            "compaction.subcompaction", "compaction.input_scan",
            "compaction.compute", "compaction.encode_write",
            "pipeline.scan", "pipeline.merge_gc",
            "pipeline.encode_write"}
    finally:
        db.close()


def test_trace_ring_is_bounded_under_load(tmp_path):
    db = DB.open(str(tmp_path / "db"), topts(trace_ring=16))
    try:
        fill(db, 400)
        s = db.tracer.status()
        assert s["traces_retained"] <= 16
        assert len(db.tracer._by_id) <= 16
        assert s["traces_active"] == 0
    finally:
        db.close()


def test_slow_unsampled_write_leaves_root_trace(tmp_path):
    db = DB.open(str(tmp_path / "db"),
                 Options(create_if_missing=True, trace_sample_every=0,
                         trace_slow_usec=1))
    try:
        db.put(b"a", b"b")  # any write beats a 1µs threshold
        ts = db.tracer.finished()
        assert ts and ts[0].slow and ts[0].name == "db.write"
        assert len(ts[0].spans) == 1  # root-only backstop
    finally:
        db.close()


# ---------------------------------------------------------------------------
# Cross-process: dcompact HTTP worker stitching
# ---------------------------------------------------------------------------


def test_dcompact_http_job_stitches_worker_spans(tmp_path, monkeypatch):
    from toplingdb_tpu.compaction.dcompact_service import (
        DcompactWorkerService, HttpCompactionExecutorFactory,
    )
    from toplingdb_tpu.compaction.resilience import DcompactOptions
    from toplingdb_tpu.ops import compaction_kernels as ck

    # Engage the 3-stage pipeline inside the (in-process) worker so the
    # stitched waterfall is of a PIPELINED remote job (the acceptance
    # shape) — the shard rule would leave a test-sized job one shard, and
    # so to the serial path.
    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 4)
    svc = DcompactWorkerService(device="cpu-jax")
    port = svc.start()
    fac = HttpCompactionExecutorFactory(
        [f"http://127.0.0.1:{port}"],
        policy=DcompactOptions(max_attempts=2, lease_sec=5.0))
    db = DB.open(str(tmp_path / "db"),
                 topts(write_buffer_size=1 << 14,
                       disable_auto_compactions=True,
                       compaction_executor_factory=fac,
                       statistics=Statistics()))
    try:
        for i in range(2400):
            db.put(b"key%05d" % (i % 800), b"val%07d" % i)
            if i % 300 == 299:
                db.flush()
        db.flush()
        db.compact_range()
        assert db.get(b"key00799") == b"val%07d" % 2399
        comps = [t for t in db.tracer.finished(limit=300)
                 if t.name == "compaction"]
        stitched = [t for t in comps
                    if any(s.proc == "dcompact-worker" for s in t.spans)]
        assert stitched, "no compaction trace carries worker spans"
        t = stitched[0]
        worker_spans = [s for s in t.spans if s.proc == "dcompact-worker"]
        names = {s.name for s in worker_spans}
        assert "dcompact.worker" in names
        # every worker span belongs to the SAME trace id (one waterfall)
        assert {s.trace_id for s in worker_spans} == {t.trace_id}
        # the worker's job parents under the service's request span, and
        # that under the DB-side compaction root
        wroot = next(s for s in worker_spans
                     if s.name == "dcompact.worker")
        request = next(s for s in worker_spans
                       if s.name == "dcompact.request")
        assert wroot.parent_id == request.span_id
        assert request.parent_id == t.root.span_id
        assert t.root.tags.get("mode") == "remote"
        # the PIPELINED interior stages recorded inside the worker:
        # per-shard scan/merge spans plus writer chunks
        assert {"pipeline.scan", "pipeline.merge_gc"} <= names
    finally:
        db.close()
        svc.stop()


# ---------------------------------------------------------------------------
# Cross-process: replication follower ack stitching + dropped-ack
# ---------------------------------------------------------------------------


def test_replication_write_stitches_follower_apply(tmp_path):
    from toplingdb_tpu.replication.follower import FollowerDB
    from toplingdb_tpu.replication.log_shipper import (
        LocalTransport, LogShipper,
    )

    src = str(tmp_path / "db")
    db = DB.open(src, topts(statistics=Statistics()))
    fol = None
    try:
        ship = LogShipper(db)
        fol = FollowerDB.open(src, transport=LocalTransport(ship),
                              mode="shared")
        db.put(b"rk1", b"rv1", WriteOptions(sync=True))
        db.put(b"rk2", b"rv2")
        assert fol.catch_up() > 0      # applies + banks the spans
        assert fol._span_outbox
        fol.catch_up()                 # the ack pull ships them back
        assert not fol._span_outbox
        writes = [t for t in db.tracer.finished(limit=100)
                  if t.name == "db.write"]
        stitched = [t for t in writes
                    if any(s.name == "follower.apply" for s in t.spans)]
        assert stitched, "no write trace carries a follower span"
        t = stitched[0]
        fs = next(s for s in t.spans if s.name == "follower.apply")
        assert fs.proc == "follower"
        assert fs.parent_id == t.root.span_id
        assert fs.trace_id == t.trace_id
    finally:
        if fol is not None:
            fol.close()
        db.close()


def test_dropped_ack_degrades_to_primary_only(tmp_path):
    from toplingdb_tpu.env.fault_injection import ShipFaultInjector
    from toplingdb_tpu.replication.follower import FollowerDB
    from toplingdb_tpu.replication.log_shipper import (
        FaultyTransport, LocalTransport, LogShipper,
    )

    src = str(tmp_path / "db")
    db = DB.open(src, topts(statistics=Statistics()))
    fol = None
    try:
        ship = LogShipper(db)
        # Pull 0 delivers frames; pull 1 (the ack carrier) drops.
        inj = ShipFaultInjector(schedule={1: "drop"})
        fol = FollowerDB.open(src,
                              transport=FaultyTransport(
                                  LocalTransport(ship), inj),
                              mode="shared")
        db.put(b"dk1", b"dv1")
        assert fol.catch_up() > 0
        assert fol._span_outbox
        fol.catch_up()  # dropped: spans lost WITH the exchange
        assert not fol._span_outbox  # no leak: outbox cleared regardless
        writes = [t for t in db.tracer.finished(limit=100)
                  if t.name == "db.write"]
        assert writes
        assert all(
            all(s.name != "follower.apply" for s in t.spans)
            for t in writes), "dropped ack must leave primary-only traces"
        # later rounds keep working (no error latched anywhere)
        db.put(b"dk2", b"dv2")
        assert fol.catch_up() > 0
    finally:
        if fol is not None:
            fol.close()
        db.close()


# ---------------------------------------------------------------------------
# HTTP surface: /metrics gauges + parse, /traces, /stats_history
# ---------------------------------------------------------------------------

# name{labels} value  |  # comment — the Prometheus text shapes we emit.
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+0-9.eEinfa]+$")


def _parse_prometheus(text):
    samples = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            assert line.startswith(("# TYPE ", "# HELP ")), line
            continue
        assert _PROM_SAMPLE.match(line), f"bad exposition line: {line!r}"
        samples.append(line.split(" ")[0])
    return samples


def test_http_metrics_traces_stats_history(tmp_path):
    from toplingdb_tpu.utils.config import SidePluginRepo

    repo = SidePluginRepo()
    db = repo.open_db({"path": str(tmp_path / "db"),
                       "options": {"create_if_missing": True,
                                   "trace_sample_every": 1,
                                   "write_buffer_size": 1 << 20}},
                      name="main")
    port = repo.start_http()
    base = f"http://127.0.0.1:{port}"
    try:
        fill(db, 300)
        db.get(b"key000001")
        db.flush()
        db.persist_stats()

        # /metrics: parses as Prometheus text; counters AND gauges present
        with urllib.request.urlopen(f"{base}/metrics") as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        names = _parse_prometheus(text)
        joined = "\n".join(names)
        assert 'tpulsm_bytes_written{db="main"}' in joined
        assert 'tpulsm_level_files{db="main",level="0"}' in joined
        assert 'tpulsm_last_sequence{db="main"}' in joined
        assert "tpulsm_trace_ring_retained" in joined
        assert "tpulsm_db_write_micros_count" in joined

        # /traces/main: summaries; /traces/main/<id>: Chrome trace JSON
        with urllib.request.urlopen(f"{base}/traces/main") as r:
            body = json.loads(r.read())
        assert body["tracer"]["sample_every"] == 1
        assert body["traces"]
        tid = body["traces"][0]["trace_id"]
        with urllib.request.urlopen(f"{base}/traces/main/{tid}") as r:
            chrome = json.loads(r.read())
        assert chrome["traceEvents"]
        with urllib.request.urlopen(f"{base}/view/traces/main") as r:
            html = r.read().decode()
        assert "waterfall" in html or "traces: main" in html

        # /stats_history/main?window=
        with urllib.request.urlopen(
                f"{base}/stats_history/main?window=3600") as r:
            hist = json.loads(r.read())
        assert hist["n_samples"] >= 1
        assert any("number.keys.written" in s["tickers"]
                   for s in hist["samples"])
        with urllib.request.urlopen(
                f"{base}/stats_history/main?window=-1") as r:
            pass
    finally:
        repo.stop_http()
        db.close()


# ---------------------------------------------------------------------------
# PerfContext / IOStats satellites
# ---------------------------------------------------------------------------


def test_perfcontext_chunk_vs_per_entry_parity(tmp_path):
    """The scan plane's windowed tpulsm_scan_blocks reads must feed
    block_read_count/block_read_byte exactly like the per-entry path."""
    saved = os.environ.get("TPULSM_ITER_CHUNK")
    db = DB.open(str(tmp_path / "db"),
                 Options(create_if_missing=True,
                         write_buffer_size=32 << 10))
    try:
        import random

        rng = random.Random(3)
        for i in range(3000):
            db.put(b"key%06d" % rng.randrange(3000), b"v%06d" % i)
        db.flush()
        db.wait_for_compactions()

        def scan_counts(chunk):
            os.environ["TPULSM_ITER_CHUNK"] = chunk
            st.perf_level = 1
            st.perf_context().reset()
            it = db.new_iterator()
            it.seek_to_first()
            n = sum(1 for _ in it.entries())
            ctx = st.perf_context()
            st.perf_level = 0
            return n, ctx.block_read_count, ctx.block_read_byte

        n0, c0, b0 = scan_counts("0")
        n1, c1, b1 = scan_counts("1")
        assert n0 == n1 > 1000
        assert c0 == c1 > 0
        assert b0 == b1 > 0
    finally:
        st.perf_level = 0
        if saved is None:
            os.environ.pop("TPULSM_ITER_CHUNK", None)
        else:
            os.environ["TPULSM_ITER_CHUNK"] = saved
        db.close()


def test_iostats_context_fed_by_posix_env(tmp_path):
    st.perf_level = 2
    try:
        ctx = st.iostats_context()
        ctx.reset()
        db = DB.open(str(tmp_path / "db"), Options(create_if_missing=True))
        db.put(b"iok", b"iov" * 10, WriteOptions(sync=True))
        db.flush()
        db.close()
        assert ctx.bytes_written > 0
        assert ctx.fsync_nanos > 0
        ctx.reset()
        db = DB.open(str(tmp_path / "db"), Options(create_if_missing=False))
        db.close()
        assert ctx.bytes_read > 0  # recovery read the MANIFEST/WAL back
        d = ctx.to_dict()
        assert set(d) == {"bytes_written", "bytes_read", "write_nanos",
                          "read_nanos", "fsync_nanos"}
    finally:
        st.perf_level = 0


# ---------------------------------------------------------------------------
# Event log: trace correlation, stats_dump, ldb dump_events
# ---------------------------------------------------------------------------


def test_event_log_correlation_and_dump_events(tmp_path, capsys):
    from toplingdb_tpu.tools.ldb import main as ldb_main

    d = str(tmp_path / "db")
    db = DB.open(d, topts(statistics=Statistics()))
    t_mid = None
    try:
        fill(db, 50)
        db.flush()
        time.sleep(0.01)
        t_mid = time.time()
        time.sleep(0.01)
        db.put(b"late", b"entry")
        db.flush()
        # stats_dump line through the dump hook (thread path covered by
        # the scheduler's own loop; the hook is what the knob adds).
        db.persist_stats()
        db._log_stats_dump()
    finally:
        db.close()

    assert ldb_main(["--db", d, "dump_events"]) == 0
    out = capsys.readouterr().out
    events = [json.loads(l) for l in out.splitlines()
              if l.startswith("{")]
    kinds = {e["event"] for e in events}
    assert "flush_finished" in kinds
    assert "stats_dump" in kinds
    flushes = [e for e in events if e["event"] == "flush_finished"]
    assert any("trace_id" in e for e in flushes), \
        "flush events must correlate to their trace"
    # --since filters on time_micros
    assert ldb_main(["--db", d, f"--since={t_mid}", "dump_events"]) == 0
    out2 = capsys.readouterr().out
    later = [json.loads(l) for l in out2.splitlines() if l.startswith("{")]
    assert 0 < len(later) < len(events)
    assert all(e["time_micros"] >= int(t_mid * 1e6) for e in later)


def test_stats_dump_scheduler_thread(tmp_path):
    d = str(tmp_path / "db")
    db = DB.open(d, Options(create_if_missing=True,
                            statistics=Statistics(),
                            stats_dump_period_sec=1))
    try:
        fill(db, 50)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if db.stats_history.last_sample() is not None:
                break
            time.sleep(0.05)
        assert db.stats_history.last_sample() is not None
    finally:
        db.close()
    from toplingdb_tpu.env import default_env

    log = default_env().read_file(f"{d}/LOG").decode()
    assert '"event": "stats_dump"' in log


# ---------------------------------------------------------------------------
# check_telemetry lint
# ---------------------------------------------------------------------------


def test_check_telemetry_lint_clean():
    from toplingdb_tpu.tools import check_telemetry

    assert check_telemetry.run() == []


def test_check_telemetry_catches_forked_names(tmp_path):
    from toplingdb_tpu.tools import check_telemetry as ct

    bad = tmp_path / "bad.py"
    bad.write_text(
        "def f(stats, st):\n"
        "    stats.record_tick('no.such.ticker')\n"
        "    stats.record_in_histogram(st.NOT_A_REAL_CONSTANT, 1)\n"
        "    span('rogue.span.name')\n"
    )
    values, attrs = ct.declared_stat_names()
    names = ct.span_names_in_architecture(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert names  # the ARCHITECTURE table is discoverable
    assert "db.write" in names and "pipeline.scan" in names
    vio = ct.check_file(str(bad), values, attrs, names)
    assert len(vio) == 3
    assert any("no.such.ticker" in v for v in vio)
    assert any("NOT_A_REAL_CONSTANT" in v for v in vio)
    assert any("rogue.span.name" in v for v in vio)
