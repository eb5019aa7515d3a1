"""The benchmark's own tests (CPU; `python -m pytest benchmark/tests -q`).

They are not part of the repository's tier-1 suite. What they hold:
the window rule on scripted clocks, the plain half of the trace reduction
on a hand-made trace and on a piece of a recorded chip trace, the plain
reader and reference against the program's own CPU path, a rehearsal of
each cell (the shipped one, and the job cell that waits in
held/compact-jobs.json), the no-TPU exit, the control and the faults (an
answer altered where it is produced; a job off the pipelined data plane),
and that a configuration, a traffic mix and a per-layer metric are each
added as files, with no edit.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import run as bench_run  # noqa: E402
from lib import reference, sst_plain, trace_reduce  # noqa: E402
from lib import span_reduce  # noqa: E402
from lib.workload import Workload  # noqa: E402

OVERWRITE = "dbbench-c2-8b20b.overwrite"
# What every served cell reports of the client, the DB front end, the LSM,
# the executor boundary and the compiler (the cells' own tests follow it).
SERVED_METRICS = ("client.put_loop_share", "client.build_us_per_op",
                  "db.stall_share", "db.write_us_per_op",
                  "db.write_batch_p50_ms", "db.write_batch_p95_ms",
                  "db.write_batch_p99_ms", "lsm.write_amp",
                  "compactor.busy_share", "compile.in_window.serve")
JOBS = "dbbench-c2-8b20b.compact-jobs"   # held: not in BENCHMARK.json
HELD = "compact-jobs"


def run_cell(workload, *extra, root=ROOT, seconds="2", seed="2147483659",
             rehearse=True, scale="0.05"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"),
           "--workload", workload, "--seed", seed, "--seconds", seconds]
    if rehearse:
        cmd += ["--rehearse-cpu", scale]
    if workload == JOBS:
        cmd += ["--held", HELD]
    p = subprocess.run(cmd + list(extra), cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines else None)


# -- the window rule, on scripted clocks -----------------------------------


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def load_kind(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "kind_" + name, os.path.join(BENCH, "traffic", "kinds", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_job_window_closes_when_the_job_in_flight_completes():
    jobs = load_kind("jobs")
    clock = Clock()
    walls = [3.0, 4.0, 2.5]            # a scripted service: job j takes this
    handed = []

    def post(job_dir):
        clock.t += walls[int(job_dir) % 3]
        return {"dir": job_dir}

    dirs = [str(i - 1) for i in range(50)]   # dirs[0] is the warm-up
    done, span = jobs.replay(
        post, dirs, 3, seconds=10.0, clock=clock,
        completed=lambda r, job, reply: handed.append((r, job, reply["dir"])))
    assert handed == [(1, 0, "0"), (2, 1, "1"), (3, 2, "2"), (4, 0, "3")]
    # 3 + 4 + 2.5 = 9.5 < 10: the fourth job is in flight at 10 s and
    # closes the window at 12.5 s; rate = whole jobs over the whole span.
    assert [j for j, _, _ in done] == [0, 1, 2, 0]
    assert span == pytest.approx(12.5)
    assert sum(w for _, w, _ in done) == pytest.approx(span)


def test_put_window_closes_on_the_first_batch_boundary_after_seconds():
    puts = load_kind("puts")
    clock = Clock()
    written = []

    def write(batch):
        clock.t += 0.3
        written.append(batch.count())

    kb, vb = b"k" * 8 * 4000, b"v" * 20 * 4000
    lat, span, w = puts.write_window(write, kb, vb, 0, 4000, 100, 1.0,
                                     clock=clock)
    assert w == 400 and written == [100] * 4     # 0.9 s < 1.0 <= 1.2 s
    assert span == pytest.approx(1.2) and sum(lat) == pytest.approx(1.2)
    clock.t = 0.0                                # the stream ends first
    lat, span, w = puts.write_window(write, kb, vb, 0, 250, 100, 1.0,
                                     clock=clock)
    assert w == 200 and span == pytest.approx(0.6)


# -- the streams' ceiling ----------------------------------------------------

CLOSED_LOOP = {"puts": "max_puts_per_s", "merges": "max_operands_per_s",
               "puts_zip": "max_puts_per_s"}
# A fixed number, not the ledger's (which moves under the test with every
# PR): PERF.md section 2 has the rule a `benchmark` issue applies, at least
# 1.5 times the cell's highest ledger median, as soon as one passes 400,000.
CEILING = 600_000


def closed_loop_mixes():
    out = []
    for f in sorted(os.listdir(os.path.join(BENCH, "traffic"))):
        if f.endswith(".json"):
            with open(os.path.join(BENCH, "traffic", f)) as fh:
                mix = json.load(fh)
            if mix["kind"] in CLOSED_LOOP:
                out.append((f[:-5], mix))
    return out


@pytest.mark.parametrize("name,mix", [
    pytest.param(n, m, id=n) for n, m in closed_loop_mixes()])
def test_every_closed_loop_mix_names_the_one_ceiling(name, mix):
    key = CLOSED_LOOP[mix["kind"]]
    assert mix[key] == CEILING, name
    assert "1.5 times" in mix["notes"][key] and "400,000" in mix["notes"][key]
    served = [c for c in bench_run.load_bench()["workloads"]
              if c["traffic"] == name]
    assert served, name                    # no mix without its cell


def test_the_three_closed_loop_mixes_are_there():
    assert sorted(m["kind"] for _, m in closed_loop_mixes()) == sorted(
        CLOSED_LOOP)


class InstantDB:
    """A DB whose writes return at once, on a clock that does not move."""

    def __init__(self):
        self.operands = self.ranges = 0

    def write(self, batch):
        self.operands += batch.count()

    def delete_range(self, begin, end):
        self.ranges += 1


@pytest.mark.parametrize("kind", ["puts", "merges"])
def test_a_writer_that_outruns_the_stream_is_told_so(kind):
    """A write that returns at once consumes the whole stream before
    `seconds`; the kinds' one rule then says the stream ran out."""
    from lib import dbside, dbside_merge

    clock, db = Clock(), InstantDB()
    first, end, per_batch, seconds = 1000, 6000, 500, 40.0
    if kind == "puts":
        kb, vb = b"k" * 8 * end, b"v" * 20 * end
        lat, span, w = load_kind("puts").write_window(
            db.write, kb, vb, first, end, per_batch, seconds, clock=clock)
    else:
        kb, vb = b"k" * 16 * end, b"v" * 8 * end
        tb = te = b"t" * 16 * 2
        lat, span, w, t = dbside_merge.merge_window(
            db, kb, vb, tb, te, [2000, 4000], 0, first, end, per_batch,
            seconds, clock=clock)
        assert (t, db.ranges) == (2, 2)
    assert w == end and db.operands == end - first
    assert len(lat) == (end - first) // per_batch and span < seconds
    assert dbside.stream_ran_out(w, per_batch, end, span, seconds)
    # The window's own close (a batch boundary at or after `seconds`) with
    # stream to spare is no such run, nor is one that ends on both at once.
    assert not dbside.stream_ran_out(w - per_batch, per_batch, end, seconds,
                                     seconds)
    assert not dbside.stream_ran_out(w, per_batch, end, seconds, seconds)


def test_a_starved_mix_is_refused_as_stream_ran_out(tmp_path):
    """The refusal stays: the overwrite cell on a mix (added as a file)
    whose stream is a few batches compares `stream_ran_out` 1."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "benchmark"
    with open(b / "traffic" / "overwrite.json") as f:
        mix = json.load(f)
    mix["max_puts_per_s"] = 1000
    with open(b / "traffic" / "starved.json", "w") as f:
        json.dump(mix, f)
    bench = bench_run.load_bench()
    cell = "dbbench-c2-8b20b.starved"
    bench["workloads"].append({
        "name": cell, "config": "dbbench-c2-8b20b", "traffic": "starved",
        "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if OVERWRITE in m.get("workloads", []):
            m["workloads"].append(cell)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    p, line = run_cell(cell, "--trace", "0", root=str(tmp_path))
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["compared"]["stream_ran_out"] == [1, 0]
    assert line["attempted"] == 2000 and "raise max_puts_per_s" in p.stderr
    assert "compared stream_ran_out: 1 (limit 0)" in p.stderr


# -- the front end in units a claim can predict in --------------------------


def test_the_two_times_an_operation_add_up_to_the_window():
    facts = {"window_s": 40.0, "in_write_s": 25.0, "out_of_write_s": 15.0,
             "window_ops": 12_500_000,
             "write_batch_s": np.array([0.001, 0.002, 0.003, 0.004, 0.1])}
    bench = bench_run.load_bench()
    cell = bench_run.find_cell(bench, OVERWRITE)
    got = bench_run.read_per_layer(bench, cell, facts, {"write_ops_s"})
    build = got["client.build_us_per_op"]["value"]
    write = got["db.write_us_per_op"]["value"]
    assert build == pytest.approx(1.2) and write == pytest.approx(2.0)
    assert build + write == pytest.approx(
        1e6 / (facts["window_ops"] / facts["window_s"]))
    assert got["db.write_batch_p50_ms"]["value"] == pytest.approx(3.0)
    assert got["client.put_loop_share"]["value"] == pytest.approx(37.5)
    # Nothing to read gives nothing, never 0.
    less = bench_run.read_per_layer(bench, cell, {"window_s": 40.0},
                                    {"write_ops_s"})
    assert not {"client.build_us_per_op", "db.write_us_per_op",
                "db.write_batch_p50_ms"} & set(less)


def test_the_front_end_metrics_are_read_in_every_served_cell():
    bench = bench_run.load_bench()
    cells = [c["name"] for c in bench["workloads"]]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert len(per_layer) == 18
    assert not [n for n in per_layer if n.startswith("universal.")
                or n == "compile.in_window.merge"]
    for name in SERVED_METRICS:
        assert per_layer[name]["workloads"] == cells, name
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
             if f.endswith(".json")}
    held = {m["name"] for m in bench_run.load_bench(HELD)["per_layer"]}
    assert files == held                   # no metric file without its entry


# -- the trace reduction -----------------------------------------------------


def test_reduce_hand_made_trace():
    ms = 1_000_000
    events = {
        "host": [[trace_reduce.WINDOW_OPEN, 0, 10],
                 [trace_reduce.JOB, 100 * ms, 400 * ms],
                 [trace_reduce.JOB, 600 * ms, 200 * ms],
                 [trace_reduce.WINDOW_CLOSE, 1000 * ms - 10, 10]],
        "device_ops": {"/device:TPU:0": [
            ["fusion.1", 200 * ms, 100 * ms],
            ["fusion.2", 250 * ms, 100 * ms],      # overlaps fusion.1
            ["fusion.1", 400 * ms, 50 * ms],
            ["copy", 900 * ms, 50 * ms]]},         # outside any job
    }
    s = trace_reduce.reduce(events)
    assert s["window_s"] == pytest.approx(1.0)
    assert s["busy_s"] == pytest.approx(0.25)       # 150 + 50 + 50 ms
    assert s["busy_in_jobs_chip_s"] == pytest.approx(0.2)
    assert s["jobs_seen"] == 2
    gaps = dict(s["idle_gaps"])
    assert gaps["job: before first op"] == pytest.approx(0.1)
    assert gaps["job: between ops"] == pytest.approx(0.05)
    assert gaps["job: after last op"] == pytest.approx(0.05)
    assert gaps["job: no op"] == pytest.approx(0.2)
    assert gaps["no_job"] == pytest.approx(0.35)    # 0.4 off jobs - copy
    assert sum(gaps.values()) + s["busy_s"] == pytest.approx(s["window_s"])
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(0.15)]


def test_reduce_recorded_trace():
    """A piece of a chip trace of this benchmark (tests/data/README.md),
    as `span_reduce` hands it to the plain half."""
    with open(os.path.join(HERE, "data", "span_events.json")) as f:
        rec = json.load(f)
    s = trace_reduce.reduce(span_reduce.as_trace_reduce_events(rec["events"]))
    for key, want in rec["expected_plain"].items():
        if isinstance(want, float):
            assert s[key] == pytest.approx(want, rel=1e-9), key
        else:
            assert s[key] == want, key
    gaps = dict(s["idle_gaps"])
    assert sum(gaps.values()) + s["busy_s"] == pytest.approx(s["window_s"])
    assert gaps["job: before first op"] > 0 and gaps["job: after last op"] > 0


# -- the plain reader and the reference, against the program's CPU path ------


@pytest.fixture(scope="module")
def small_job(tmp_path_factory):
    """One real job of a tiny LSM, run by the program's per-entry CPU
    worker (another path of the program: a witness, not the reference)."""
    from toplingdb_tpu.compaction import worker
    from toplingdb_tpu.compaction.executor import CompactionParams
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db import dbformat
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableOptions

    from lib import dbside

    tmp = str(tmp_path_factory.mktemp("job"))
    wl = Workload(30_000, 30_000, 7)
    kb, vb = wl.encode(0, 60_000)
    db = DB.open(os.path.join(tmp, "db"), Options(
        create_if_missing=True, compression=fmt.SNAPPY_COMPRESSION,
        table_options=TableOptions(block_size=4096),
        write_buffer_size=64 << 20, disable_auto_compactions=True))
    for lo in (0, 20_000, 40_000):
        dbside.put_batches(db, kb[8 * lo:], vb[20 * lo:], 20_000, 1000)
        db.flush()
    db.close()
    inputs = sorted(
        os.path.join(tmp, "db", f) for f in os.listdir(os.path.join(tmp, "db"))
        if f.endswith(".sst"))
    assert len(inputs) == 3
    job = os.path.join(tmp, "job")
    os.makedirs(os.path.join(job, "out"))
    with open(os.path.join(job, "params.json"), "w") as f:
        f.write(CompactionParams(
            job_id=1, attempt=0, dbname=os.path.join(tmp, "db"),
            output_dir=os.path.join(job, "out"), input_files=inputs,
            output_level=2, bottommost=True, max_output_file_size=1 << 20,
            snapshots=[], comparator=dbformat.BYTEWISE.name(),
            merge_operator=None, compaction_filter=None,
            compression=fmt.SNAPPY_COMPRESSION, block_size=4096,
            creation_time=1_700_000_000, lease_sec=0.0,
            device="cpu").to_json())
    assert worker.run_job(job) == 0
    return job, wl, inputs


def test_plain_reader_reads_what_the_program_reads(small_job):
    from toplingdb_tpu.db import dbformat
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableOptions
    from toplingdb_tpu.table.factory import open_table

    _job, _wl, inputs = small_job
    keys, vals = sst_plain.read_rows(inputs[0])
    r = open_table(default_env().new_random_access_file(inputs[0]),
                   dbformat.InternalKeyComparator(dbformat.BYTEWISE),
                   TableOptions(block_size=4096))
    it = r.new_iterator()
    it.seek_to_first()
    theirs = list(it.entries())
    assert len(theirs) == len(keys) == 20_000
    assert [k for k, _ in theirs] == [bytes(k) for k in keys]
    assert [v for _, v in theirs] == [bytes(v) for v in vals]


def test_reference_agrees_with_the_cpu_worker_and_sees_a_lost_row(small_job):
    job, wl, _inputs = small_job
    got = reference.compare_job(job, wl)
    assert got["rows_in"] == 60_000 and got["rows_out"] == got["rows_expected"]
    assert (got["rows_wrong"], got["rows_not_from_seed"],
            got["records_misreported"]) == (0, 0, 0)
    other_seed = Workload(30_000, 30_000, 8)
    assert reference.compare_job(job, other_seed)["rows_not_from_seed"] > 0
    # A survivor that is not the newest version: the reference disagrees.
    u = np.array([5, 5, 6], np.uint64)
    seq = np.array([3, 9, 4], np.uint64)
    eu, eseq, _, ev = reference.expected_output(
        u, seq, np.ones(3, np.uint8), np.arange(60, dtype=np.uint8)
        .reshape(3, 20), bottommost=False)
    assert eu.tolist() == [5, 6] and eseq.tolist() == [9, 4]
    assert ev[0].tolist() == list(range(20, 40))


# -- whole runs ---------------------------------------------------------------


def well_formed(line, per_layer=False):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "compared"
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    if per_layer:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("workload", [OVERWRITE, JOBS])
def test_rehearsal_of_each_cell(workload, trace):
    p, line = run_cell(workload, "--trace", trace)
    assert p.returncode == 4, p.stderr[-2000:]      # never a pass
    well_formed(line, per_layer=trace == "1")
    assert all(v <= lim for v, lim in line["compared"].values()), \
        line["compared"]
    bench = bench_run.load_bench(HELD)
    if trace == "0":
        want = {m["name"] for m in bench["end_to_end"]
                if workload in m.get("workloads", [workload])}
        assert set(line["metrics"]) == want
        assert all(m["value"] > 0 for m in line["metrics"].values())
    else:
        # Every per-layer metric of the cell that needs no device trace.
        want = {m["name"] for m in bench["per_layer"]
                if workload in m["workloads"]
                and m["source"] != "device_trace"}
        assert set(line["metrics"]) == want
    if workload == JOBS:
        assert line["compared"]["jobs_left_pipeline"] == [0, 0]
        if trace == "1":  # the counters of `job_stats` reached the readers
            for name in ("plane.scan_wait_share", "runtime.gc_pause_share",
                         "plane.writer_backpressure_share"):
                assert line["metrics"][name]["value"] >= 0.0
            assert line["metrics"]["plane.h2d_bytes_per_row"]["value"] >= 12
            assert 0 < line["metrics"]["plane.writer_busy_share"]["value"] \
                < 100
    assert "compared " in p.stderr.strip().splitlines()[-1]


def test_without_a_tpu_there_is_no_result():
    p, line = run_cell(JOBS, "--trace", "0", rehearse=False)
    assert p.returncode not in (0, 4) and line is None
    assert "cpu" in p.stderr                        # names what JAX found


def test_outside_the_repo_there_is_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", OVERWRITE, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_control_and_fault_an_answer_altered_where_it_is_produced():
    """The service with a survivor dropped where the device path produces
    it, in the real service's place: the job cell's comparison with the
    plain reference sees it (the control of that cell), and with one in
    twenty dropped the served cell's reads meet lost writes."""
    fault = ["--launcher", "faulty_service.py", "--launcher-arg=--fault"]
    _, line = run_cell(JOBS, "--trace", "0", *fault,
                       "--launcher-arg=drop-row")
    assert line["correct"] is False
    assert line["compared"]["rows_wrong"][0] > 0
    # The same fault in early runs only: the reference reads the newest run
    # of each job and finds nothing, the hashes of every run's output do.
    _, line = run_cell(JOBS, "--trace", "0", *fault,
                       "--launcher-arg=drop-row-early", seconds="3")
    assert line["correct"] is False
    assert line["compared"]["rows_wrong"][0] == 0
    assert line["compared"]["runs_unlike_checked"][0] > 0
    _, line = run_cell(OVERWRITE, "--trace", "0", *fault,
                       "--launcher-arg=drop-5pct", seconds="4")
    assert line["correct"] is False
    c = line["compared"]
    assert c["read_mismatches"][0] + c["reopen_read_mismatches"][0] > 0


def test_fault_a_job_off_the_pipelined_data_plane():
    """Every job refused by the pipelined data plane: the serial program
    gives the right rows, and the run is still not correct. At a tenth of
    the cell's size one job of the set is over the plane's row floor (those
    under it leave by design and are not counted); without the fault the
    same run reads 0."""
    fault = ["--launcher", "faulty_service.py", "--launcher-arg=--fault",
             "--launcher-arg=leave-pipeline"]
    for extra, left in ((fault, True), ([], False)):
        _, line = run_cell(JOBS, "--trace", "0", *extra, scale="0.1")
        c = line["compared"]
        assert c["rows_wrong"] == [0, 0]
        assert c["runs_unlike_checked"] == [0, 0]
        assert (c["jobs_left_pipeline"][0] > 0) == left, c
        assert c["jobs_left_pipeline"][1] == 0


def test_a_config_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """A later PR adds a deployment, a traffic mix and a per-layer metric
    as new files and new entries of BENCHMARK.json; no file that exists is
    edited (checked by digest)."""
    import hashlib

    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))

    def digests():
        return {os.path.relpath(os.path.join(d, f), tmp_path):
                hashlib.sha1(open(os.path.join(d, f), "rb").read()).hexdigest()
                for d, _, fs in os.walk(tmp_path / "benchmark") for f in fs}

    before = digests()
    b = tmp_path / "benchmark"
    cfg = json.load(open(b / "configs" / "dbbench-c2-8b20b.json"))
    cfg["name"] = "dummy-config"
    cfg["keys"] = 2_000_000
    json.dump(cfg, open(b / "configs" / "dummy-config.json", "w"))
    mix = json.load(open(b / "traffic" / "compact-jobs.json"))
    mix["load_overwrite_share"] = 1.0
    # A counter that a later program will report, and this one does not.
    mix["job_stats"] = ["h2d_bytes", "shards_on_chip_3"]
    json.dump(mix, open(b / "traffic" / "dummy-mix.json", "w"))
    json.dump({"reader": "fact", "args": {"of": "rows_out"}},
              open(b / "metrics" / "dummy.rows_out.json", "w"))
    json.dump({"reader": "ratio", "args": {
        "num": "sum.shards_on_chip_3", "den": "rows_in"}},
        open(b / "metrics" / "dummy.chip3_share.json", "w"))
    bench = bench_run.load_bench(HELD)   # the held entries, pasted back
    bench["configs"].append({
        "name": "dummy-config", "source": "a test",
        "file": "benchmark/configs/dummy-config.json", "reduced": ["keys"],
        "why": "a test"})
    bench["workloads"].append({
        "name": "dummy-config.dummy-mix", "config": "dummy-config",
        "traffic": "dummy-mix", "chips": 1, "why": "a test"})
    next(m for m in bench["end_to_end"] if m["name"] == "compact_MBps")[
        "workloads"].append("dummy-config.dummy-mix")
    bench["per_layer"].append({
        "name": "dummy.rows_out", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "compact_MBps", "workloads": ["dummy-config.dummy-mix"]})
    for name in ("dummy.chip3_share", "plane.h2d_bytes_per_row"):
        bench["per_layer"].append({
            **bench["per_layer"][-1], "name": name, "unit": "x"})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))
    p, line = run_cell("dummy-config.dummy-mix", "--trace", "1",
                       root=str(tmp_path))
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["metrics"]["dummy.rows_out"]["value"] > 0
    # The counter the service lacks: its metric is left out, nothing fails.
    assert "dummy.chip3_share" not in line["metrics"]
    assert line["metrics"]["plane.h2d_bytes_per_row"]["value"] > 0
    assert line["failed"] == 0 and all(
        v is not None and v <= lim for v, lim in line["compared"].values())
    after = digests()
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "benchmark/configs/dummy-config.json",
        "benchmark/traffic/dummy-mix.json",
        "benchmark/metrics/dummy.rows_out.json",
        "benchmark/metrics/dummy.chip3_share.json"}


def test_the_held_cell_pastes_back():
    """held/compact-jobs.json holds whole entries of BENCHMARK.json: with
    them no name is there twice, every metric's cells exist, and every
    per-layer metric has its file."""
    bench = bench_run.load_bench(HELD)
    cells = [c["name"] for c in bench["workloads"]]
    assert JOBS in cells and JOBS not in [
        c["name"] for c in bench_run.load_bench()["workloads"]]
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells), m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            BENCH, "metrics", m["name"] + ".json")), m["name"]
