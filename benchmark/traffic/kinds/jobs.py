"""Traffic kind `jobs`: the dcompact service as its submitter sees it.

Set-up: this deployment's LSM is loaded (every key once, then a share of
overwrite draws; in bulk batches, it is not timed) with every compaction
above the configuration's floor sent to the service; each remote job's
input SSTs and parameters are kept (hard links). Those jobs are the set, and
their run during the load is the warm-up: every row bucket the window meets
has run once on this service. Every job directory the window can use is on
disk (the run's scratch directory, under `TMPDIR`) before it opens.

The window opens before a job is posted and closes when the job in flight
at `--seconds` completes: jobs are replayed round-robin through
`POST /dcompact`, one in flight. The rate is the raw user key-value bytes of
all input rows of the jobs completed, over that whole span.

Afterwards: the output files of every run were hashed when its reply came
(on a thread of the submitter's, beside the next job), and each run's hashes
are compared with those of the newest run of its job; that run's output SSTs
are read back with the plain reader and compared row by row with the plain
reference's survivors of the same input SSTs, for every job of the set; and
the witnesses that the chip did the work.
"""

from __future__ import annotations

import hashlib
import os
import queue
import shutil
import threading
import time

import numpy as np

from toplingdb_tpu.compaction.executor import CompactionParams
from toplingdb_tpu.db.db import DB

from lib import dbside, reference
from lib.workload import KEY_BYTES, RAW_KV_BYTES, VALUE_BYTES, Workload

# What the kind itself compares or derives a fact from: every service
# reports these. The mix's "job_stats" names further counters of a job's
# reply; one that a service does not report reads None, and the metrics over
# it are left out.
CORE_STATS = ("work_time_usec", "input_scan_usec", "encode_write_usec",
              "device_wait_usec", "jit_compiles", "input_records",
              "output_records", "mesh_chips", "mesh_fallbacks",
              "host_compute_usec", "pipelined", "device")
PHASES = ("work_time_usec", "input_scan_usec", "device_wait_usec",
          "encode_write_usec")


class OutputKeeper:
    """The submitter's housekeeping, off the window's thread: when a run's
    reply is in, hash its output files (in the reply's order), then remove
    the output of the run of the same job that was kept till now. So every
    run's bytes are read once, and the newest run of each job stays on disk
    for the reference."""

    def __init__(self, dirs):
        self.dirs = dirs
        self.digest = {}                # run -> hash of its output files
        self.newest = {}                # job -> its newest run
        self.error = None
        self._q = queue.Queue()
        self._t = threading.Thread(target=self._work, daemon=True)
        self._t.start()

    def completed(self, r: int, job: int, reply: dict) -> None:
        self._q.put((r, job, [f["path"] for f in reply["output_files"]]))

    def _work(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            try:
                r, job, names = item
                out = os.path.join(self.dirs[r], "out")
                h = hashlib.blake2b(digest_size=16)
                for name in names:
                    with open(os.path.join(out, name), "rb") as f:
                        while chunk := f.read(1 << 20):
                            h.update(chunk)
                    h.update(b"|")
                self.digest[r] = h.hexdigest()
                stale = self.newest.get(job)
                self.newest[job] = r
                if stale is not None:
                    shutil.rmtree(os.path.join(self.dirs[stale], "out"),
                                  ignore_errors=True)
            except Exception as e:      # judged after the window
                self.error = self.error or e

    def finish(self) -> None:
        self._q.put(None)
        self._t.join()
        if self.error is not None:
            raise self.error


def replay(post, dirs, n_jobs: int, seconds: float,
           clock=time.perf_counter, completed=lambda r, job, reply: None):
    """The window: run r (1-based; dirs[0] was the warm-up) replays job
    (r - 1) % n_jobs, one in flight. It closes when the job in flight at
    `seconds` completes, or when the prepared directories run out.
    `completed(r, job, reply)` hands each reply to the submitter's
    housekeeping (inside the span). Returns ([(job index, submitter's
    wall, reply)], span): both of whole jobs."""
    done = []
    c0 = clock()
    for r in range(1, len(dirs)):
        a = clock()
        res = post(dirs[r])
        b = clock()
        done.append(((r - 1) % n_jobs, b - a, res))
        completed(r, (r - 1) % n_jobs, res)
        if clock() - c0 >= seconds:
            break
    return done, clock() - c0


def drive(run) -> dict:
    tr = run.traffic
    sizes = dbside.lsm_sizes(run.config, run.scale)
    n = sizes["keys"]
    n_over = int(n * tr["load_overwrite_share"])
    wl = Workload(n, n_over, run.seed)
    kb, vb = wl.encode(0, n + n_over)
    run.wait_service()

    stats = dbside.JobStatistics()
    dbdir = os.path.join(run.workdir, "db")
    capture_dir = os.path.join(run.workdir, "job-inputs")
    os.makedirs(capture_dir)
    factory = dbside.TimedFactory(run.svc.url, run.device, sizes["min_input"],
                                  dbname=dbdir, capture_dir=capture_dir)
    db = DB.open(dbdir, dbside.options(run.config, sizes, stats, factory))
    try:
        # A service with a cold compile cache spends minutes in its first
        # job. The load waits for that job, so that the rest of it meets
        # the service a warm run meets and leaves the same set of jobs.
        head = int(tr["load_waits_for_first_job_at"] * min(1.0, run.scale))
        dbside.put_batches(db, kb, vb, head, tr["load_puts_per_batch"])
        deadline = time.time() + 900
        while not any(s.remote for s in stats.jobs):
            if time.time() > deadline:
                raise RuntimeError(f"no remote job {head} puts into the load")
            time.sleep(0.05)
        dbside.put_batches(db, kb[KEY_BYTES * head:], vb[VALUE_BYTES * head:],
                           n + n_over - head, tr["load_puts_per_batch"])
        db.flush()
        db.wait_for_compactions()
    finally:
        db.close()
    load = dbside.device_witnesses(stats.jobs, run.device)
    min_rows = int(tr["min_job_rows"] * min(1.0, run.scale))
    jobs = [c for c in factory.captured if c["rows"] >= min_rows]
    jobs = jobs[-tr["max_jobs"]:]
    if not jobs:
        raise RuntimeError(
            f"the load made no remote job of >= {min_rows} rows "
            f"({len(factory.captured)} captured)")
    run.facts["notes"] = [
        f"load: {n}+{n_over} puts, {len(stats.jobs)} jobs, "
        f"{load['remote_jobs']} remote, {len(jobs)} in the set with rows "
        f"{[c['rows'] for c in jobs]}; compiled in set-up: "
        f"{sum(s.jit_compiles for s in stats.jobs)}"]

    max_runs = tr["max_runs"]
    dirs = []
    for r in range(max_runs + 1):                # run 0 is the warm-up post
        d = os.path.join(run.workdir, "runs", f"r{r:03d}")
        os.makedirs(os.path.join(d, "out"))
        job = jobs[(r - 1) % len(jobs)]
        params = CompactionParams(**{
            **job["params"], "device": run.device,
            "output_dir": os.path.join(d, "out")})
        with open(os.path.join(d, "params.json"), "w") as f:
            f.write(params.to_json())
        dirs.append(d)
    run.svc.post_job(dirs[0])                    # the submitter's own path

    # ---- the window --------------------------------------------------
    keeper = OutputKeeper(dirs)
    run.window_open()
    done, span = replay(run.svc.post_job, dirs, len(jobs), run.seconds,
                        completed=keeper.completed)
    run.window_close()
    keeper.finish()
    run.attempted = len(done)
    ran_out = len(done) >= max_runs and span < run.seconds

    # ---- facts for the readers -----------------------------------------
    stat_keys = CORE_STATS + tuple(
        k for k in tr.get("job_stats", []) if k not in CORE_STATS)
    per_run = [{"job": j, "wall_s": wall,
                **{k: res["stats"].get(k) for k in stat_keys}}
               for j, wall, res in done]
    rows_in = sum(jobs[j]["rows"] for j, _, _ in done)
    rows_out = sum(p["output_records"] for p in per_run)
    run.facts.update(
        window_s=span, runs=per_run, rows_in=rows_in, rows_out=rows_out,
        key_bytes=run.config["key_bytes"],
        submit_wall_s=sum(p["wall_s"] for p in per_run),
        work_s=sum(p["work_time_usec"] for p in per_run) / 1e6,
        host_stage_s=sum(p["input_scan_usec"] + p["encode_write_usec"]
                         for p in per_run) / 1e6,
        device_wait_s=sum(p["device_wait_usec"] for p in per_run) / 1e6,
        jit_compiles=sum(p["jit_compiles"] for p in per_run),
        device_kind=run.dev["kind"])
    for k in stat_keys:                  # "sum.<counter>" over the window's
        values = [p[k] for p in per_run]  # jobs, where every job reports it
        if all(type(v) in (int, float) for v in values):
            run.facts["sum." + k] = sum(values)
    left = sorted({p["pipeline_exit"] for p in per_run
                   if p.get("pipeline_exit")})
    if left:
        run.facts["notes"].append(f"jobs left the pipeline: {left}")

    # ---- what is compared ------------------------------------------------
    unlike = misreported = off_device = 0
    for r, ((j, _, res), p) in enumerate(zip(done, per_run), start=1):
        unlike += keeper.digest[r] != keeper.digest[keeper.newest[j]]
        misreported += p["input_records"] != jobs[j]["rows"]
        off_device += (
            res["status"] != "ok"
            or dbside.off_device(run.device, p["device"], p["input_records"],
                                 p["pipelined"], p["host_compute_usec"])
            or (run.cell["chips"] > 1
                and p["input_records"] >= dbside.PIPELINE_FLOOR_ROWS
                and (p["mesh_chips"] != run.cell["chips"]
                     or p["mesh_fallbacks"] != 0)))
    totals = {"rows_wrong": 0, "rows_not_from_seed": 0,
              "records_misreported": 0}
    t_ref = time.time()
    checked_rows = 0
    for j in sorted(keeper.newest):
        got = reference.compare_job(dirs[keeper.newest[j]], wl)
        checked_rows += got["rows_in"]
        for k in totals:
            totals[k] += got[k]
    run.facts["notes"].append(
        f"reference: jobs {sorted(keeper.newest)}, {checked_rows} input "
        f"rows, {time.time() - t_ref:.1f}s; submitter's wall of each run, "
        f"s: {[round(p['wall_s'], 2) for p in per_run]}")
    run.facts["notes"] += _slow_runs(per_run)
    shutil.rmtree(os.path.join(run.workdir, "runs"), ignore_errors=True)
    svc_stats = run.svc.get("/stats")
    run.compare("rows_wrong", totals["rows_wrong"])
    run.compare("rows_not_from_seed", totals["rows_not_from_seed"])
    run.compare("records_misreported",
                totals["records_misreported"] + misreported)
    run.compare("runs_unlike_checked", unlike)
    run.compare("jobs_off_device", off_device + load["jobs_off_device"])
    # Jobs the service ran off the pipelined data plane, in all its life,
    # less those that are under the plane's row floor (they leave it by
    # design: the load's smallest, and every job of a rehearsal).
    left = svc_stats.get("jobs_left_pipeline")
    sent = [s.input_records for s in stats.jobs if s.remote] \
        + [jobs[-1]["rows"]] + [jobs[j]["rows"] for j, _, _ in done]
    run.compare("jobs_left_pipeline", None if left is None else left - sum(
        rows < dbside.PIPELINE_FLOOR_ROWS for rows in sent))
    run.compare("remote_job_failures",
                svc_stats["jobs_failed"] + factory.failed)
    run.compare("jobs_unchecked", int(not keeper.newest))
    run.compare("runs_ran_out", int(ran_out))
    return {"compact_MBps": rows_in * RAW_KV_BYTES / 1e6 / span}


def _slow_runs(per_run) -> list:
    """A line for each run that took over 1.5 times the median of its job:
    which phase of the worker grew."""
    notes = []
    for i, p in enumerate(per_run):
        same = sorted(q["wall_s"] for q in per_run if q["job"] == p["job"])
        typical = next((q for q in per_run if q["job"] == p["job"]
                        and q["wall_s"] == same[len(same) // 2]), p)
        if p["wall_s"] > 1.5 * typical["wall_s"]:
            notes.append(
                f"slow run {i} of job {p['job']}: wall {p['wall_s']:.2f}s "
                f"against {typical['wall_s']:.2f}s; work, scan, device wait, "
                f"encode+write ms: {[p[k] // 1000 for k in PHASES]} against "
                f"{[typical[k] // 1000 for k in PHASES]}")
    return notes
