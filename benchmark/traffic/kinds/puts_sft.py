"""Traffic kind `puts_sft`: the served path of the SingleFastTable
deployment from the client's side (db_bench fillrandom, then overwrite,
leveled, every level's files in the SingleFastTable format, every
compaction over min_remote_input_bytes on the TPU dcompact service).

It is the `puts` kind (whose window loop, reads and helpers it uses as they
are) with what that kind has no field for: the DB opens with the table
format of the configuration (`lib/dbside_sft.py`), the set-up leaves a
settled tree as the `puts_zip` kind's does, and `correct` holds the
deployment's own guarantees.

Set-up: all keys once in a seeded order (in bulk batches: it is not
timed), the load waiting out every compaction it triggers before it
writes on, so that a service that compiles for minutes in its first job
and one that does not leave the same tree; then the overwrite stream
itself, untimed, until the memtable that the load left part-filled is full
and flushes of its own accord; wait for the compactions. So every sorted
run is of a whole write buffer, as in a deployment that has been running,
and the window opens with no compaction pending or running. The window's
puts are encoded before it opens; inside it only `WriteBatch.put` and
`DB.write` run, closed loop, one writer.

The window opens at a batch boundary and closes at the first batch boundary
at or after `--seconds`. The rate is all acknowledged puts over that whole
span.

Afterwards: reads against the seed's oracle, before and after a close and
reopen (every key is answered out of the memtable or a SingleFastTable);
the window's largest remote job, its inputs kept by hard link, posted to
the service once more and its output, read by `lib/sft_plain.py`, compared
row by row with the plain reference's survivors (`lib/reference_sft.py`);
that every file every flush and every compaction of the run installed is a
SingleFastTable; and the witnesses that the chip did every remote
compaction, on the pipelined plane from its row floor on.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import sys
import time

import numpy as np

from toplingdb_tpu.compaction.executor import CompactionParams
from toplingdb_tpu.db.db import DB
from toplingdb_tpu.utils import statistics as st

from lib import dbside, dbside_sft, reference_sft
from lib.workload import Workload

_spec = importlib.util.spec_from_file_location(
    "bench_traffic_kinds_puts",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "puts.py"))
puts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(puts)

EXIT_PROGRAM_LACKS = 5


def drive(run) -> dict:
    lacks = dbside_sft.program_lacks()
    if lacks:
        # Before anything is loaded or compiled: a program that cannot
        # plan a SingleFastTable input fails this cell at once.
        print(f"no result: this checkout's program cannot run the cell "
              f"({lacks})", file=sys.stderr, flush=True)
        raise SystemExit(EXIT_PROGRAM_LACKS)
    tr, cfg = run.traffic, run.config
    sizes = dbside.lsm_sizes(cfg, run.scale)
    n = sizes["keys"]
    per_batch = cfg["batch_size"]
    # The stream is sized for a rate no run has come near.
    max_puts = int(tr["max_puts_per_s"] * run.seconds)
    max_puts -= max_puts % per_batch
    wl = Workload(n, max_puts, run.seed)
    kb, vb = wl.encode(0, n + max_puts)        # made while the service starts
    run.wait_service()

    stats = dbside.JobStatistics()
    dbdir = os.path.join(run.workdir, "db")
    keep_dir = os.path.join(run.workdir, "largest-job")
    os.makedirs(keep_dir)
    factory = dbside_sft.SftJobFactory(
        run.svc.url, run.device, sizes["min_input"], dbdir, keep_dir,
        cfg["table"]["format"])
    witness = dbside_sft.OutputWitness()
    opts = dbside_sft.options(cfg, sizes, stats, factory, witness)
    db = DB.open(dbdir, opts)
    try:
        _load(db, kb, vb, n, tr["load_puts_per_batch"])
        # The flush before the window is the memtable's own (the merges
        # kind's reason: `DB.flush()` here would leave a sorted run of part
        # of a write buffer, whose merge falls under
        # min_remote_input_bytes and runs in the DB process).
        flushed = stats.get_ticker_count(st.FLUSH_WRITE_BYTES)
        first = n
        while (stats.get_ticker_count(st.FLUSH_WRITE_BYTES) == flushed
               and first + 2 * per_batch <= n + max_puts):
            _, _, first = puts.write_window(
                db.write, kb, vb, first, n + max_puts, per_batch, 0.0)
        db.wait_for_compactions()
        warm_jobs = len(stats.jobs)
        warm_seen = len(witness.jobs)
        warm = dbside.device_witnesses(stats.jobs, run.device)
        run.facts["notes"] = [
            f"preload: {n} keys and {first - n} puts (to the memtable's "
            f"own flush), {warm_jobs} jobs, {warm['remote_jobs']} remote; "
            f"compiled in set-up: "
            f"{sum(s.jit_compiles for s in stats.jobs)}; programs first "
            f"met: {puts._first_met(stats.jobs)}; the tree: "
            f"{_levels(db)}"]

        # ---- the window ------------------------------------------------
        t_before = stats.tickers()
        flush_before = stats.get_histogram(st.FLUSH_TIME_MICROS).sum
        flushes_before = witness.flushes
        spans_before = len(factory.spans)
        factory.watch()
        t0 = run.window_open()
        lat, span, w = puts.write_window(
            db.write, kb, vb, first, n + max_puts, per_batch, run.seconds)
        t_after = stats.tickers()
        flush_s = (stats.get_histogram(st.FLUSH_TIME_MICROS).sum
                   - flush_before) / 1e6
        t1 = t0 + span
        run.window_close()
        factory.watch(False)
        n_puts = w - first
        run.attempted = n_puts
        ran_out = dbside.stream_ran_out(w, per_batch, n + max_puts, span,
                                        run.seconds)
        if ran_out:
            run.facts["notes"].append(
                f"the encoded stream of {max_puts} puts ran out after "
                f"{span:.1f}s: raise max_puts_per_s")

        win_jobs = stats.jobs[warm_jobs:]
        remote = [s for s in win_jobs if s.remote]
        local = [s for s in win_jobs if not s.remote]
        seen = witness.jobs[warm_seen:]
        run.facts["notes"].append(
            f"window: {n_puts} puts in {span:.3f}s, last sequence {w}, "
            f"{witness.flushes - flushes_before} flushes in "
            f"{flush_s:.2f}s, "
            f"{len(win_jobs)} jobs, {len(remote)} remote (rows, rows out "
            f"of SingleFastTables, SingleFastTables written, compiled, "
            f"loaded from the cache): "
            f"{[(s.input_records, s.sft_input_rows, s.sft_output_files, s.jit_compiles, s.jit_cache_hits) for s in remote]}; "
            f"by level (from, to, rows, where, ms): "
            f"{[_job_line(j) for j in seen]}; "
            f"in the DB process: {len(local)} "
            f"{[(s.input_records, s.sft_output_files) for s in local]}; "
            f"begun there in the window (s into it, from, to, rows, "
            f"bytes): "
            f"{[(round(t - t0, 1), *rest) for t, *rest in factory.kept_local if t0 <= t <= t1]}")

        # ---- facts for the readers -------------------------------------
        lat_a = np.asarray(lat)
        in_write = float(lat_a.sum())
        delta = {k: t_after.get(k, 0) - t_before.get(k, 0)
                 for k in puts.TICKERS}
        busy = puts._covered(factory.spans[spans_before:], t0, t1)
        run.facts.update(
            window_s=span, in_write_s=in_write,
            out_of_write_s=span - in_write,
            window_ops=n_puts, write_batch_s=lat_a,
            stall_s=delta[st.STALL_MICROS] / 1e6,
            storage_write_bytes=(delta[st.FLUSH_WRITE_BYTES]
                                 + delta[st.COMPACT_WRITE_BYTES]),
            user_write_bytes=delta[st.BYTES_WRITTEN],
            remote_busy_s=busy, flush_s=flush_s,
            jit_compiles=sum(s.jit_compiles for s in remote))
        for k in tr.get("job_stats", []):   # "sum.<counter>" over the
            values = [getattr(s, k, None) for s in remote]  # window's jobs
            if remote and all(type(v) in (int, float) for v in values):
                run.facts["sum." + k] = sum(values)
        work = sum(s.work_time_usec for s in remote) / 1e6
        run.facts["notes"].append(
            f"window, by layer: {lat_a.sum() / span:.1%} of it in DB.write, "
            f"{delta[st.STALL_MICROS] / 1e6:.2f}s stalled, the flush thread "
            f"at work {flush_s / span:.1%}, a remote job in flight "
            f"{busy / span:.1%}, p50/p95/p99 of a batch "
            f"{[round(float(x) * 1e3, 3) for x in np.percentile(lat_a, [50, 95, 99])]} ms; "
            f"the remote jobs wrote "
            f"{sum(s.sft_output_files for s in remote)} SingleFastTables "
            f"({sum(s.sft_output_bytes for s in remote)} B for "
            f"{sum(s.sft_output_rows for s in remote)} rows; build "
            f"{sum(s.sft_build_usec for s in remote) / 1e6:.2f}s; scan "
            f"{sum(s.sft_scan_usec for s in remote) / 1e6:.2f}s, a sum over "
            f"reader threads; work {work:.2f}s)")

        # ---- what is compared ------------------------------------------
        t_chk = time.time()
        last = wl.last_write(w)
        rng = np.random.default_rng([run.seed, 1])
        run.compare("read_mismatches", puts._read_mismatches(
            db, wl, last, rng, n, w, tr["checks"]))
        db.wait_for_compactions()
        db.close()
        db = DB.open(dbdir, opts)               # WAL + MANIFEST recovery
        run.compare("reopen_read_mismatches", puts._read_mismatches(
            db, wl, last, rng, n, w, tr["checks"]))
        run.facts["notes"].append(
            f"reads against the oracle, twice: {time.time() - t_chk:.1f}s; "
            f"the tree: {_levels(db)}; compactions that ended after the "
            f"window (from, to, rows, where, ms): "
            f"{[_job_line(j) for j in witness.jobs[warm_seen + len(seen):]]}; "
            f"the remote ones (rows, work ms, sft scan ms, sft build ms, "
            f"compiled): "
            f"{[(s.input_records, s.work_time_usec // 1000, s.sft_scan_usec // 1000, s.sft_build_usec // 1000, s.jit_compiles) for s in stats.jobs[warm_jobs + len(win_jobs):] if s.remote]}")
        got, sent_rows = _check_largest_job(run, factory, wl)
        run.compare("rows_wrong", got["rows_wrong"])
        run.compare("records_misreported", got["records_misreported"])
        run.compare("outputs_not_single_fast",
                    witness.outputs_not_single_fast()
                    + (got["outputs_not_single_fast"] or 0))
        tick = stats.tickers()
        svc_stats = run.svc.get("/stats")
        run.compare("fallback_local",
                    tick.get(st.DCOMPACTION_FALLBACK_LOCAL, 0))
        run.compare("remote_job_failures",
                    tick.get(st.DCOMPACTION_JOB_FAILURES, 0)
                    + svc_stats["jobs_failed"] + factory.failed)
        run.compare("jobs_off_device", dbside.device_witnesses(
            stats.jobs, run.device)["jobs_off_device"])
        # Jobs the service ran off the pipelined plane, in all its life,
        # less those of one shard's rows: they run the same device program
        # serially, by design (the guarantee names that floor).
        left = svc_stats.get("jobs_left_pipeline")
        sent = [s.input_records for s in stats.jobs if s.remote] + sent_rows
        floor = dbside.PIPELINE_FLOOR_ROWS
        run.compare("jobs_left_pipeline", None if left is None else left
                    - sum(r < floor for r in sent))
        exits = sorted({s.pipeline_exit for s in stats.jobs
                        if s.remote and s.pipeline_exit})
        run.facts["notes"].append(
            f"remote jobs under the plane's floor of {floor} rows: "
            f"{sum(s.input_records < floor for s in remote)} of the "
            f"window's {len(remote)}, {sum(r < floor for r in sent)} of all "
            f"{len(sent)}; why jobs left the pipeline: {exits}; the "
            f"service's sums: "
            f"{ {k: svc_stats.get(k) for k in dbside_sft.COUNTERS} }; "
            f"files installed by {witness.flushes} flushes and "
            f"{len(witness.jobs)} compactions that are no SingleFastTable: "
            f"{witness.outputs_not_single_fast()}")
        run.compare("window_without_remote_job", int(not remote))
        run.compare("window_without_sft_input_rows", int(
            not sum(s.sft_input_rows for s in remote)))
        run.compare("stream_ran_out", int(ran_out))
    except BaseException:
        # A write that fails on a background error says only "HTTP Error
        # 500": what the service said of the job is in its log.
        print(f"the service's last words: {run.svc.last_words()}",
              file=sys.stderr, flush=True)
        raise
    finally:
        db.close()
    return {"write_ops_s": n_puts / span}


def _load(db, kb: bytes, vb: bytes, n: int, per_batch: int) -> None:
    """The fill: `dbside.put_batches` a batch at a time, waiting after each
    for the compactions it may have triggered (a flush follows a write,
    and the pick follows the flush), so the tree it leaves does not depend
    on how long a job took."""
    K, V = puts.KEY_BYTES, puts.VALUE_BYTES
    for b0 in range(0, n, per_batch):
        b1 = min(b0 + per_batch, n)
        dbside.put_batches(db, kb[K * b0:K * b1], vb[V * b0:V * b1],
                           b1 - b0, per_batch)
        db.wait_for_compactions()


def _job_line(j: dict) -> tuple:
    return (j["from"], j["level"], j["rows"], j["device"], j["ms"])


def _levels(db) -> list:
    """(level, files, SingleFastTables among them) of every populated
    level."""
    from toplingdb_tpu.db import filename
    from lib import sft_plain

    out = []
    version = db.versions.current
    for level, files in enumerate(version.files):
        if files:
            out.append((level, len(files), sum(
                sft_plain.is_single_fast_table(
                    filename.table_file_name(db.dbname, f.number))
                for f in files)))
    return out


def _check_largest_job(run, factory, wl):
    """Post the window's largest remote job to the service once more (its
    inputs were kept by hard link) and compare its output with the plain
    reference's survivors. Returns (counts, rows of the jobs posted)."""
    job = factory.largest
    if job is None:  # `window_without_remote_job` says so
        return {"rows_wrong": None, "records_misreported": None,
                "outputs_not_single_fast": None}, []
    d = os.path.join(run.workdir, "largest-job-run")
    os.makedirs(os.path.join(d, "out"))
    params = CompactionParams(**{
        **job["params"], "device": run.device,
        "output_dir": os.path.join(d, "out")})
    with open(os.path.join(d, "params.json"), "w") as f:
        f.write(params.to_json())
    t0 = time.time()
    reply = run.svc.post_job(d)
    t1 = time.time()
    got = reference_sft.compare_job(d, wl)
    if reply["status"] != "ok":
        got["rows_wrong"] = None
    run.facts["notes"].append(
        f"reference: the window's largest remote job, "
        f"{got['rows_in']} input rows ({got['sft_rows_in']} out of "
        f"{got['sft_inputs']} SingleFastTables) -> {got['rows_out']} "
        f"(expected {got['rows_expected']}) in {got['outputs']} files, "
        f"{got['outputs_not_single_fast']} of them no SingleFastTable; "
        f"rows no write of "
        f"the seed made: {got['rows_not_from_seed']}; posted again "
        f"{t1 - t0:.1f}s, compared {time.time() - t1:.1f}s")
    shutil.rmtree(d, ignore_errors=True)
    return got, [job["rows"]]
