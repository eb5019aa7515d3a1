"""One dcompact job's span stream (ISSUE 25): the worker records every job
where the work happens, keeps it in the service's ring, returns it on
request, counts what the per-layer metrics need, and puts the same spans on
the profiler's clock."""

import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from toplingdb_tpu.compaction import worker
from toplingdb_tpu.db.dbformat import BYTEWISE, InternalKeyComparator, ValueType
from toplingdb_tpu.env import default_env
from toplingdb_tpu.table import format as fmt
from toplingdb_tpu.table.builder import TableOptions
from toplingdb_tpu.utils import telemetry as tm

ICMP = InternalKeyComparator()

# The span set of one pipelined device job (ISSUE 25's table). The two that
# exist only when a thread really waits are asserted apart.
JOB_SPANS = {
    "dcompact.worker", "compaction.prepare", "pipeline.plan",
    "pipeline.spawn", "sst.open", "pipeline.join",
    "pipeline.scan", "pipeline.chunk_prepare", "pipeline.upload",
    "pipeline.dispatch", "pipeline.merge_gc", "pipeline.unpack",
    "pipeline.encode_write", "sst.build_data", "sst.finish_file",
    "sst.sync_close", "compaction.finish", "dcompact.results",
}
WAIT_SPANS = {"pipeline.wait_scan", "pipeline.wait_writer", "pipeline.stall"}


def make_job(tmp_path, name="job", runs=3, rows=3000, seq_gap=0, trace=None,
             max_output_file_size=2 ** 62, last_run_key_len=8, snapshots=(),
             device="cpu-jax"):
    """A job dir with `runs` input SSTs of 8 B keys / 20 B values (half the
    keys overwritten across runs) and its params.json. `seq_gap` is added
    to the sequence numbers of half the last run's rows, so that every
    chunk of that file spans it. `last_run_key_len` other than 8 gives the
    last run longer keys (the same digits, then '0's): a job of two key
    lengths, which the pipeline refuses."""
    from toplingdb_tpu.ops.columnar_io import ColumnarKV, write_tables_columnar

    env = default_env()
    job_dir = str(tmp_path / name)
    in_dir = os.path.join(job_dir, "in")
    os.makedirs(in_dir)
    os.makedirs(os.path.join(job_dir, "out"))
    topts = TableOptions(block_size=4096, compression=fmt.NO_COMPRESSION)
    rng = np.random.default_rng(7)
    counter = [0]

    def alloc():
        counter[0] += 1
        return counter[0]

    paths = []
    for run in range(runs):
        draws = rng.integers(0, rows * runs // 2, rows, dtype=np.int64)
        seqs = np.arange(run * rows + 1, (run + 1) * rows + 1,
                         dtype=np.uint64)
        if run == runs - 1:
            seqs[rows // 2:] += np.uint64(seq_gap)
        vts = np.full(rows, int(ValueType.VALUE), dtype=np.uint64)
        uk = last_run_key_len if run == runs - 1 else 8
        ik = np.full((rows, uk + 8), ord("0"), dtype=np.uint8)
        for j in range(8):
            ik[:, 7 - j] = (draws // 10 ** j) % 10 + ord("0")
        packed = (seqs << np.uint64(8)) | vts
        ik[:, uk:] = packed[:, None] >> (np.arange(8) * 8).astype(
            np.uint64)[None, :] & np.uint64(0xFF)
        s = np.lexsort((np.iinfo(np.int64).max - seqs.view(np.int64), draws))
        vlens = np.full(rows, 20, dtype=np.int32)
        kv = ColumnarKV(
            np.ascontiguousarray(ik[s]).reshape(-1),
            np.arange(rows, dtype=np.int32) * (uk + 8),
            np.full(rows, uk + 8, dtype=np.int32),
            np.full(rows * 20, ord("v"), dtype=np.uint8),
            (np.arange(rows, dtype=np.int32) * 20), vlens,
        )
        for _fnum, path, *_rest in write_tables_columnar(
                env, in_dir, alloc, ICMP, topts, kv,
                np.arange(rows, dtype=np.int32),
                np.full(rows, -1, dtype=np.int64),
                vts.astype(np.int32)[s], seqs[s], [], creation_time=1):
            paths.append(path)
    params = dict(
        job_id=1, attempt=0, dbname=in_dir,
        output_dir=os.path.join(job_dir, "out"), input_files=paths,
        output_level=1, bottommost=True,
        max_output_file_size=max_output_file_size,
        snapshots=list(snapshots),
        comparator=BYTEWISE.name(), merge_operator=None,
        compaction_filter=None, compression=fmt.NO_COMPRESSION,
        block_size=4096, creation_time=1_700_000_000, lease_sec=0.0,
        device=device, trace=trace)
    with open(os.path.join(job_dir, "params.json"), "w") as f:
        json.dump(params, f)
    return job_dir


@pytest.fixture
def pipelined(monkeypatch):
    """A test-sized job runs pipelined (the shard rule would leave it one
    shard, and so to the serial program)."""
    from toplingdb_tpu.ops import compaction_kernels as ck

    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 4)


def run_under_request(tracer, job_dir, ctx=None):
    """What the service's handler does around a job."""
    with tracer.start_from(ctx, "dcompact.request"):
        return worker.run_job(job_dir)


def results_of(job_dir):
    with open(os.path.join(job_dir, "results.json")) as f:
        return json.load(f)


def test_unsampled_job_leaves_its_whole_span_set_in_the_ring(
        tmp_path, pipelined, monkeypatch):
    from toplingdb_tpu import native
    from toplingdb_tpu.ops import columnar_io

    # Each reader holds its last shard's decode until the writer has built
    # a first run of blocks, so that the overlap asserted below is certain.
    wrote = threading.Event()
    calls = threading.local()  # a reader thread scans one file's 4 shards
    lib = native.lib()
    scan_blocks = lib.tpulsm_scan_blocks
    add_section = columnar_io._ColumnarSST.add_framed_section_arrays

    def held_scan_blocks(*a):
        calls.n = getattr(calls, "n", 0) + 1
        if calls.n == 4:
            wrote.wait(30)
        return scan_blocks(*a)

    def telling_add_section(self, *a, **kw):
        wrote.set()
        return add_section(self, *a, **kw)

    monkeypatch.setattr(lib, "tpulsm_scan_blocks", held_scan_blocks)
    monkeypatch.setattr(columnar_io._ColumnarSST,
                        "add_framed_section_arrays", telling_add_section)
    tracer = tm.Tracer(proc="dcompact-worker")
    job_dir = make_job(tmp_path, max_output_file_size=64 << 10)
    wrote.clear()  # building the inputs wrote sections too
    assert run_under_request(tracer, job_dir) == 0
    assert wrote.is_set()

    (trace,) = tracer.finished()
    assert trace.name == "dcompact.request"
    names = {s.name for s in trace.spans}
    assert JOB_SPANS <= names, JOB_SPANS - names
    assert names - JOB_SPANS - WAIT_SPANS <= {
        "dcompact.request", "runtime.gc_pause", "compaction.queue_wait"}

    # A tree: children inside their parents (a microsecond of rounding).
    by_id = {s.span_id: s for s in trace.spans}
    for s in trace.spans:
        if s is trace.root or s.name == "compaction.queue_wait":
            continue
        parent = by_id[s.parent_id]
        assert parent.start_us <= s.start_us + 2, (s.name, parent.name)
        assert s.start_us + s.dur_us <= parent.start_us + parent.dur_us + 2, \
            (s.name, parent.name)
    wroot = next(s for s in trace.spans if s.name == "dcompact.worker")
    assert wroot.parent_id == trace.root.span_id
    assert wroot.tags["pipelined"] is True and wroot.tags["input_records"] == 9000
    sst = [s for s in trace.spans
           if s.name.startswith("sst.") and s.name != "sst.open"]
    assert {by_id[s.parent_id].name for s in sst} == {"pipeline.encode_write"}

    # Real starts: the stages are seen to overlap, which a duration
    # back-dated from the moment of recording could not show.
    scans = [s for s in trace.spans if s.name == "pipeline.scan"]
    writes = [s for s in trace.spans if s.name == "pipeline.encode_write"]
    assert len(scans) == 12  # a span a file and shard, on reader threads
    assert {s.tid for s in writes} == {wroot.tid}
    assert wroot.tid not in {s.tid for s in scans}
    computes = [s for s in trace.spans if s.name == "pipeline.merge_gc"]
    assert len(computes) == 4 and len({s.tid for s in computes}) == 1
    assert any(w.start_us < r.start_us + r.dur_us
               and r.start_us < w.start_us + w.dur_us
               for r in scans for w in writes)

    # The counters of the per-layer metrics travel in results.json, which
    # carries no spans nobody asked for.
    res = results_of(job_dir)
    assert res["spans"] == []
    st = res["stats"]
    assert st["pipelined"] is True and st["pipeline_exit"] == ""
    assert st["h2d_bytes"] > 0 and st["d2h_bytes"] > 0
    up = [s for s in trace.spans if s.name == "pipeline.upload"]
    assert sum(s.tags["h2d_bytes"] for s in up) == st["h2d_bytes"]
    assert sum(s.tags["d2h_bytes"] for s in computes) == st["d2h_bytes"]
    for key in ("gc_pause_usec", "gc_collections", "stall_wait_scan_usec",
                "stall_wait_writer_usec"):
        assert st[key] >= 0
    for wait, key in (("pipeline.wait_scan", "stall_wait_scan_usec"),
                      ("pipeline.wait_writer", "stall_wait_writer_usec"),
                      ("pipeline.stall", "pipeline_stall_usec")):
        spans_us = sum(s.dur_us for s in trace.spans if s.name == wait)
        assert abs(spans_us - st[key]) <= 2000 + 0.2 * st[key], wait
    disp = [s for s in trace.spans if s.name == "pipeline.dispatch"]
    assert sum(s.tags["compiled"] for s in disp) == st["jit_compiles"]


def test_sampled_job_returns_its_spans_in_one_write(tmp_path, pipelined,
                                                    monkeypatch):
    ctx = {"trace_id": "feedface00000001", "span_id": 77, "sampled": 1}
    job_dir = make_job(tmp_path, trace=ctx)
    writes = []
    real_open = open

    def counting_open(path, mode="r", *a, **kw):
        if str(path).endswith("results.json") and "w" in mode:
            writes.append(path)
        return real_open(path, mode, *a, **kw)

    monkeypatch.setattr("builtins.open", counting_open)
    tracer = tm.Tracer(proc="dcompact-worker")
    assert run_under_request(tracer, job_dir, ctx) == 0
    monkeypatch.undo()
    assert len(writes) == 1

    spans = results_of(job_dir)["spans"]
    names = {s["name"] for s in spans}
    assert JOB_SPANS - {"dcompact.results"} <= names
    assert {s["trace_id"] for s in spans} == {ctx["trace_id"]}
    assert {s["proc"] for s in spans} == {"dcompact-worker"}
    request = next(s for s in spans if s["name"] == "dcompact.request")
    assert request["parent_id"] == ctx["span_id"]
    # Written while they still ran: their time so far, not zero.
    wroot = next(s for s in spans if s["name"] == "dcompact.worker")
    assert wroot["dur_us"] > 0 and request["dur_us"] >= wroot["dur_us"]

    # A worker process of its own (no service above it) does the same.
    job2 = make_job(tmp_path, name="job2", trace=ctx)
    assert worker.run_job(job2) == 0
    spans2 = results_of(job2)["spans"]
    assert "dcompact.request" not in {s["name"] for s in spans2}
    wroot2 = next(s for s in spans2 if s["name"] == "dcompact.worker")
    assert wroot2["parent_id"] == ctx["span_id"]
    assert tm.current_span() is None


def test_job_that_leaves_the_pipeline_says_why(tmp_path, pipelined):
    job_dir = make_job(tmp_path, last_run_key_len=12)
    tracer = tm.Tracer(proc="dcompact-worker")
    assert run_under_request(tracer, job_dir) == 0
    st = results_of(job_dir)["stats"]
    assert st["pipelined"] is False
    assert st["pipeline_exit"] == (
        "PipelineIneligible: non-uniform key length")
    assert st["output_records"] > 0
    (trace,) = tracer.finished()
    names = {s.name for s in trace.spans}
    # The serial program's path, under the same names for the same work.
    assert {"compaction.input_scan", "pipeline.encode_write",
            "compaction.finish", "sst.sync_close"} <= names
    errors = [s.tags["error"] for s in trace.spans
              if s.name == "pipeline.chunk_prepare" and "error" in s.tags]
    assert errors and "PipelineIneligible" in errors[0]


def output_rows(job_dir):
    """Every (internal key, value) of a finished job's outputs, in order."""
    from toplingdb_tpu.table.factory import open_table

    env = default_env()
    rows = []
    for d in results_of(job_dir)["output_files"]:
        r = open_table(
            env.new_random_access_file(
                os.path.join(job_dir, "out", d["path"])),
            ICMP, TableOptions(block_size=4096,
                               compression=fmt.NO_COMPRESSION))
        it = r.new_iterator()
        it.seek_to_first()
        rows.extend(it.entries())
    return rows


@pytest.mark.parametrize("gap", [(1 << 24) - 1, 1 << 24, (1 << 32) + 5,
                                 1 << 55],
                         ids=["2^24-1", "2^24", "2^32+5", "2^55"])
def test_a_file_range_of_any_sequence_span_runs_pipelined(
        tmp_path, pipelined, gap):
    """The trailers go to the device as they are: every chunk of the last
    file holds rows on both sides of `gap` sequence numbers, a snapshot
    lies below the gap and one above it, and the job stays on the
    pipelined plane with the CPU worker's output, row for row."""
    snaps = [7000, gap + 8200]  # the last run is 6001..7500, gap+7501..
    job_dir = make_job(tmp_path, seq_gap=gap, snapshots=snaps)
    assert worker.run_job(job_dir) == 0
    st = results_of(job_dir)["stats"]
    assert st["pipelined"] is True and st["pipeline_exit"] == ""
    assert st["input_records"] == 9000
    cpu_dir = make_job(tmp_path, name="cpu", seq_gap=gap, snapshots=snaps,
                       device="cpu")
    assert worker.run_job(cpu_dir) == 0
    assert results_of(cpu_dir)["stats"]["device"] == "cpu"
    got, want = output_rows(job_dir), output_rows(cpu_dir)
    assert len(want) == st["output_records"] > 0
    assert got == want
    # Survivors of all three stripes: under the lower snapshot (zeroed, the
    # job is bottommost), between the two, and over the upper one.
    seqs = [int.from_bytes(k[-8:], "little") >> 8 for k, _ in want]
    assert any(q == 0 for q in seqs)
    assert any(7000 < q <= gap + 8200 for q in seqs)
    assert any(q > gap + 8200 for q in seqs)


def test_failed_job_keeps_its_error_in_the_ring(tmp_path):
    job_dir = make_job(tmp_path, runs=1, rows=300)
    with open(os.path.join(job_dir, "params.json")) as f:
        params = json.load(f)
    params["comparator"] = "no.such.comparator"
    with open(os.path.join(job_dir, "params.json"), "w") as f:
        json.dump(params, f)
    tracer = tm.Tracer(proc="dcompact-worker")
    with pytest.raises(ValueError):
        run_under_request(tracer, job_dir)
    (trace,) = tracer.finished()
    wroot = next(s for s in trace.spans if s.name == "dcompact.worker")
    assert "no.such.comparator" in wroot.tags["error"]
    assert "error" in trace.root.tags
    # The span an exception passed by ended with its parent.
    prep = next(s for s in trace.spans if s.name == "compaction.prepare")
    assert prep.dur_us <= wroot.dur_us
    assert tm.current_span() is None
    assert not os.path.exists(os.path.join(job_dir, "results.json"))


def test_failed_job_is_counted_on_both_sides(tmp_path, monkeypatch):
    """PERF.md §7 (PR 24): a job that dies on the service answers 500. The
    service's `jobs_failed` and the DB's `dcompaction.job.failures` both
    count it, with no fallback and one attempt, and the ring keeps it."""
    from toplingdb_tpu.compaction.dcompact_service import (
        DcompactWorkerService, HttpCompactionExecutorFactory,
    )
    from toplingdb_tpu.compaction.resilience import DcompactOptions
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils import statistics as st
    from toplingdb_tpu.utils.status import IOError_

    def dies(job_dir):
        with tm.span("dcompact.worker"):
            raise MemoryError("RESOURCE_EXHAUSTED: XLA failed to allocate")

    monkeypatch.setattr(worker, "run_job", dies)
    svc = DcompactWorkerService(device="cpu-jax")
    port = svc.start()
    stats = st.Statistics()
    # The attempts a job gets are the DB's (`Options.dcompact`); the
    # factory's policy sets its breakers and timeouts only.
    fac = HttpCompactionExecutorFactory(
        [f"http://127.0.0.1:{port}"], allow_fallback=False)
    db = DB.open(str(tmp_path / "db"), Options(
        create_if_missing=True, write_buffer_size=1 << 14,
        disable_auto_compactions=True, compaction_executor_factory=fac,
        dcompact=DcompactOptions(max_attempts=1), statistics=stats))
    try:
        for i in range(1200):
            db.put(b"key%05d" % (i % 400), b"val%07d" % i)
            if i % 300 == 299:
                db.flush()
        with pytest.raises(IOError_, match="HTTP Error 500"):
            db.compact_range()
        failed = svc.jobs_failed
        assert failed >= 1 and svc.jobs_done == 0
        assert stats.get_ticker_count(st.DCOMPACTION_JOB_FAILURES) == failed
        assert stats.get_ticker_count(st.DCOMPACTION_FALLBACK_LOCAL) == 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as r:
            doc = json.loads(r.read())
        assert doc["jobs_failed"] == failed
        assert doc["jobs_left_pipeline"] == 0
        traces = svc.tracer.finished()
        assert len(traces) == failed
        for t in traces:
            assert "MemoryError" in t.root.tags["error"]
            assert any(s.name == "dcompact.worker" and "error" in s.tags
                       for s in t.spans)
    finally:
        try:
            db.close()
        except Exception:
            pass  # the background error of the failed compaction
        svc.stop()


def test_service_serves_its_ring(tmp_path, pipelined):
    from toplingdb_tpu.compaction.dcompact_service import DcompactWorkerService

    svc = DcompactWorkerService(device="cpu-jax")
    port = svc.start()
    url = f"http://127.0.0.1:{port}"

    def get(path):
        with urllib.request.urlopen(url + path, timeout=30) as r:
            return json.loads(r.read())

    try:
        for name, key_len in (("a", 8), ("b", 12)):
            job_dir = make_job(tmp_path, name=name,
                               last_run_key_len=key_len)
            req = urllib.request.Request(
                url + "/dcompact",
                data=json.dumps({"job_dir": job_dir}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            with urllib.request.urlopen(req, timeout=300) as r:
                reply = json.loads(r.read())
            assert reply["status"] == "ok" and reply["spans"] == []
        # A request's span ends after its reply is written: wait for it.
        deadline = time.time() + 10
        while True:
            doc = get("/traces")
            if doc["tracer"]["traces_retained"] == 2 or time.time() > deadline:
                break
            time.sleep(0.02)
        assert doc["tracer"]["traces_retained"] == 2
        assert [t["name"] for t in doc["traces"]] == ["dcompact.request"] * 2
        assert all(t["n_spans"] > 10 for t in doc["traces"])
        chrome = get("/traces/" + doc["traces"][-1]["trace_id"])
        events = chrome["traceEvents"]
        assert JOB_SPANS <= {e["name"] for e in events}
        assert len({e["tid"] for e in events}) >= 3  # a lane a thread
        stats = get("/stats")
        assert stats["jobs_done"] == 2 and stats["jobs_left_pipeline"] == 1
        with pytest.raises(urllib.error.HTTPError) as err:
            get("/traces/nosuchtrace")
        assert err.value.code == 404
    finally:
        svc.stop()


def test_program_spans_are_on_the_profilers_clock(tmp_path, pipelined):
    """With a profiler session the program's spans are in the trace's host
    plane, each on its own thread, and every stand-in device op of the job
    lies inside its `dcompact.worker` interval."""
    import jax
    from jax.profiler import ProfileData

    from toplingdb_tpu.ops import device_runtime  # noqa: F401 — the mirror

    tracer = tm.Tracer(proc="dcompact-worker")
    warm = make_job(tmp_path, name="warm")
    assert run_under_request(tracer, warm) == 0  # compiles outside the trace
    job_dir = make_job(tmp_path)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        assert run_under_request(tracer, job_dir) == 0
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    spans, ops = {}, []
    for i, line in enumerate(host.lines):
        standin = line.name.startswith(("tf_XLAPjRtCpuClient",
                                        "tf_XLAEigen"))
        for e in line.events:
            if "." in e.name and e.name.split(".")[0] in (
                    "dcompact", "compaction", "pipeline", "sst", "runtime"):
                spans.setdefault(e.name, []).append(
                    (i, e.start_ns, e.duration_ns, dict(e.stats)))
            elif standin and e.duration_ns > 0 \
                    and not e.name.startswith("end: "):
                ops.append((e.start_ns, e.duration_ns))
    assert JOB_SPANS | {"dcompact.request"} <= set(spans), \
        JOB_SPANS - set(spans)
    assert "compaction.queue_wait" not in spans  # back-dated: never mirrored
    (job,) = spans["dcompact.worker"]
    assert job[3]["device"] == "cpu-jax" and job[3]["input_records"] == 9000
    assert ops
    for start, dur in ops:
        assert job[1] <= start and start + dur <= job[1] + job[2]
    # A line a thread (the OS may hand a finished reader's id to the
    # compute thread): the writer is the job's own thread, readers and
    # compute are not.
    assert {s[0] for s in spans["sst.sync_close"]} == {job[0]}
    assert job[0] not in {s[0] for s in spans["pipeline.scan"]}
    assert job[0] not in {s[0] for s in spans["pipeline.merge_gc"]}
    up = spans["pipeline.upload"][0][3]
    assert up["h2d_bytes"] > 0


def test_fused_shard_program_names_its_steps():
    """`jax.named_scope` around each step of the fused shard program: the
    lowered text carries all five, so the device's trace can rank device
    time by step and not by XLA's fusion numbers."""
    import re

    from toplingdb_tpu.ops import compaction_kernels as ck

    p = 1024
    snap_hi, snap_lo = ck._split_snapshots([])
    lowered = ck._fused_uniform_shard_impl.lower(
        np.zeros(p * 8, np.uint8), np.zeros(p, np.uint32),
        np.zeros(p, np.uint32),
        np.zeros(1, np.uint32), np.zeros(1, np.uint32), snap_hi, snap_lo,
        np.int32(1000), 2, 8, np.bool_(True), False)
    text = lowered.as_text(debug_info=True)
    scopes = set(re.findall(
        r'"jit\(_fused_uniform_shard_impl\)/(\w+)[/"]', text))
    assert {"encode_words", "sort", "gc_mask", "compact", "pack"} <= scopes


def test_gc_watch_records_a_pause_under_what_it_interrupted():
    import gc

    tm.watch_gc()
    tm.watch_gc()  # idempotent
    assert gc.callbacks.count(tm._on_gc) == 1
    us0, n0 = tm.gc_totals()
    tracer = tm.Tracer()
    with tracer.start("dcompact.worker"):
        with tm.span("pipeline.encode_write", chunk=0) as ew:
            gc.collect(0)  # generation 0: not counted, no span
            gc.collect(2)
    us1, n1 = tm.gc_totals()
    assert n1 == n0 + 1 and us1 >= us0
    (trace,) = tracer.finished()
    (pause,) = [s for s in trace.spans if s.name == "runtime.gc_pause"]
    assert pause.parent_id == ew.span_id
    assert pause.tags["generation"] == 2 and "collected" in pause.tags
    gc.collect(2)  # no span active: counted, not recorded
    assert tm.gc_totals()[1] == n1 + 1
    assert threading.current_thread() is threading.main_thread()


def test_writer_sizes_its_section_buffer_before_the_readers_land(tmp_path):
    """The stall PR 25's spans found: a streamed writer used to size its
    section buffer from the sum of length arrays that the readers were
    still filling; fresh pages read 0, the buffer came out at 64 KB and a
    chunk took a hundred native calls (`sst.build_data`) where two do. The
    buffers' own sizes do not depend on who got there first."""
    from toplingdb_tpu.ops.columnar_io import ColumnarKV, write_tables_columnar

    n = 120_000
    ik = np.empty((n, 16), dtype=np.uint8)
    keys = np.arange(n, dtype=np.int64)
    for j in range(8):
        ik[:, 7 - j] = (keys // 10 ** j) % 10 + ord("0")
    seqs = np.arange(1, n + 1, dtype=np.uint64)
    packed = (seqs << np.uint64(8)) | np.uint64(int(ValueType.VALUE))
    ik[:, 8:] = packed[:, None] >> (np.arange(8) * 8).astype(
        np.uint64)[None, :] & np.uint64(0xFF)
    vals = np.random.default_rng(3).integers(97, 123, n * 20, dtype=np.uint8)

    def write(name, lens_land_late):
        kv = ColumnarKV(
            ik.reshape(-1).copy(), np.arange(n, dtype=np.int32) * 16,
            np.full(n, 16, dtype=np.int32), vals,
            np.arange(n, dtype=np.int32) * 20, np.full(n, 20, dtype=np.int32))
        if lens_land_late:
            kv.key_lens[:] = 0
            kv.val_lens[:] = 0

        def chunks():
            kv.key_lens[:] = 16  # the readers land after the set-up
            kv.val_lens[:] = 20
            for c in range(4):
                yield np.arange(c * n // 4, (c + 1) * n // 4, dtype=np.int32)

        out = tmp_path / name
        out.mkdir()
        counter = iter(range(1, 100))
        tracer = tm.Tracer()
        with tracer.start("dcompact.worker"):
            files = write_tables_columnar(
                default_env(), str(out), lambda: next(counter), ICMP,
                TableOptions(block_size=4096,
                             compression=fmt.SNAPPY_COMPRESSION),
                kv, chunks(), np.full(n, -1, dtype=np.int64),
                np.full(n, int(ValueType.VALUE), dtype=np.int32),
                seqs.copy(), [], 1)
        (trace,) = tracer.finished()
        calls = sum(s.name == "sst.build_data" for s in trace.spans)
        return calls, [open(path, "rb").read() for _n, path, *_ in files]

    calls, data = write("early", lens_land_late=False)
    late_calls, late_data = write("late", lens_land_late=True)
    assert late_calls == calls <= 8
    assert late_data == data
