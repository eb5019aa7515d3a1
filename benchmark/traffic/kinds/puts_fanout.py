"""Traffic kind `puts_fanout`: the cold-level deployment served by one
dcompact service that runs one CompactionJob a chip (`--chips 4 --workers
4`), from the client's side.

It is the `puts_zip` kind (whose load, window, reads, reference and
witnesses it uses as they are) with what that kind has no field for: the DB
runs `lsm.max_background_jobs` compactions at once, so that several remote
jobs are in flight together; the service's chip pool is read at the
window's two edges (`/stats`: the time integral of the jobs holding a
grant, the waits for one, each chip's hold time and job count), and
`correct` also holds the fan-out's own guarantees:

  rows_wrong_overlapped  the job that ran beside the compared one for the
                         longest time, on another chip (inputs kept by hard
                         link as they began), posted again together with
                         the compared one, so that the two run side by side
                         once more, and its output compared row by row with
                         the plain reference (`lib/reference_zip.py`);
  jobs_off_grant         remote jobs whose shards ran on a chip their grant
                         did not name (or that name no grant or no chip);
  window_chips_unused    chips that held no job in the window.

A program whose service has no chip grants (no placement counters) refuses
the cell with exit code 5 before anything is loaded.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import sys
import threading
import time

import numpy as np

from toplingdb_tpu.compaction.executor import CompactionParams
from toplingdb_tpu.db import filename
from toplingdb_tpu.db.db import DB
from toplingdb_tpu.utils import statistics as st

from lib import dbside, dbside_zip, reference_zip
from lib.workload import Workload

_spec = importlib.util.spec_from_file_location(
    "bench_traffic_kinds_puts_zip",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "puts_zip.py"))
puts_zip = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(puts_zip)
puts = puts_zip.puts

EXIT_PROGRAM_LACKS = 5
POOL_COUNTERS = ("jobs_running_usec", "grant_wait_usec", "chip_held_usec",
                 "chip_jobs")


def program_lacks() -> str:
    """What the checkout's program lacks to run this cell, or "": the cold
    format's counters, a job's placement in its stats, and the pool's
    counters (a service without them runs every job on the chips of one
    grant, whatever `--workers` says)."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats
    from toplingdb_tpu.compaction.dcompact_service import ChipPool

    lacks = dbside_zip.program_lacks()
    if lacks:
        return lacks
    for name in ("devices_granted", "devices_used"):
        if name not in CompactionStats.__dataclass_fields__:
            return f"CompactionStats has no {name}"
    if not hasattr(ChipPool, "counters"):
        return "the dcompact service's chip pool keeps no counters"
    return ""


def _refuse(why: str) -> None:
    print(f"no result: this checkout's program cannot run the cell ({why})",
          file=sys.stderr, flush=True)
    raise SystemExit(EXIT_PROGRAM_LACKS)


class FanoutJobFactory(dbside_zip.ColdJobFactory):
    """The cold cell's factory, also keeping, while `watch()` is on, the
    inputs (by hard link) and the interval of every remote job that may
    yet turn out to have run beside the kept largest one, and the chips
    each ran on (from its reply)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.entries: list[dict] = []
        self.largest_entry = None
        self._mu = threading.Lock()
        self._n = 0

    def new_executor(self, compaction):
        kept = self.kept
        ex = super().new_executor(compaction)
        if ex is None or not self.watching:
            return ex
        with self._mu:
            self._n += 1
            links = []
            for _, f in compaction.all_inputs():
                src = filename.table_file_name(self.dbname, f.number)
                dst = os.path.join(self.keep_dir, f"o{self._n:03d}-"
                                   + os.path.basename(src))
                os.link(src, dst)
                links.append(dst)
            params = dbside.job_params(
                1000 + self._n, self.dbname, links, compaction.output_level,
                compaction.bottommost, compaction.max_output_file_size)
            if compaction.bottommost:
                params["table_format"] = "zip"
            entry = {"rows": sum(f.num_entries
                                 for _, f in compaction.all_inputs()),
                     "links": links, "params": params, "t0": time.time(),
                     "t1": None, "devices": []}
            self.entries.append(entry)
            if self.kept != kept:          # the new largest
                self.largest_entry = entry
                self._prune()
        execute = ex.execute

        def watched(*a, **kw):
            entry["t0"] = time.time()
            try:
                out = execute(*a, **kw)
                entry["devices"] = list(getattr(out[1], "devices_used", []))
                return out
            finally:
                with self._mu:
                    entry["t1"] = time.time()
                    self._prune()

        ex.execute = watched
        return ex

    def _overlap(self, a, b) -> float:
        now = time.time()
        return (min(a["t1"] or now, b["t1"] or now)
                - max(a["t0"], b["t0"]))

    def _prune(self) -> None:
        """Drop (and unlink) finished jobs that did not run beside the
        largest: no later largest can have run beside them either."""
        big = self.largest_entry
        keep = []
        for e in self.entries:
            if (e["t1"] is None or e is big
                    or (big is not None and self._overlap(e, big) > 0)):
                keep.append(e)
            else:
                for p in e["links"]:
                    os.unlink(p)
        self.entries = keep

    def beside_largest(self):
        """The finished job that ran beside the largest for the longest
        time on chips of its own, or None."""
        big = self.largest_entry
        if big is None or not big["devices"]:
            return None
        cands = [e for e in self.entries
                 if e is not big and e["t1"] is not None and e["devices"]
                 and not set(e["devices"]) & set(big["devices"])
                 and self._overlap(e, big) > 0]
        return max(cands, key=lambda e: self._overlap(e, big), default=None)


def _pool(svc) -> dict:
    """The service's pool counters, and when they were read."""
    t = time.time()
    s = svc.get("/stats")
    return {"t": (t + time.time()) / 2,
            **{k: s.get(k) for k in POOL_COUNTERS}}


def _off_grant(granted, used) -> bool:
    return not granted or not used or not set(used) <= set(granted)


def drive(run) -> dict:
    lacks = program_lacks()
    if lacks:
        _refuse(lacks)     # before anything is loaded or compiled
    tr, cfg = run.traffic, run.config
    sizes = dbside.lsm_sizes(cfg, run.scale)
    n = sizes["keys"]
    per_batch = cfg["batch_size"]
    max_puts = int(tr["max_puts_per_s"] * run.seconds)
    max_puts -= max_puts % per_batch
    wl = Workload(n, max_puts, run.seed)
    kb, vb = wl.encode(0, n + max_puts)        # made while the service starts
    run.wait_service()
    p0 = _pool(run.svc)
    if any(p0[k] is None for k in POOL_COUNTERS):
        _refuse("the service's /stats has no chip-pool counters")
    chips = len(p0["chip_held_usec"])

    stats = dbside.JobStatistics()
    dbdir = os.path.join(run.workdir, "db")
    keep_dir = os.path.join(run.workdir, "largest-job")
    os.makedirs(keep_dir)
    factory = FanoutJobFactory(run.svc.url, run.device, sizes["min_input"],
                               dbdir, keep_dir)
    witness = dbside_zip.OutputWitness(factory)
    opts = dbside_zip.options(cfg, sizes, stats, factory, witness)
    opts.max_background_jobs = cfg["lsm"]["max_background_jobs"]
    db = DB.open(dbdir, opts)
    try:
        puts_zip._load(db, kb, vb, n, tr["load_puts_per_batch"])
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
        p1 = _pool(run.svc)
        run.facts["notes"] = [
            f"preload: {n} keys and {first - n} puts (to the memtable's "
            f"own flush), {warm_jobs} jobs, {warm['remote_jobs']} remote; "
            f"compiled in set-up: "
            f"{sum(s.jit_compiles for s in stats.jobs)}; programs first "
            f"met: {puts._first_met(stats.jobs)}; jobs by chip in set-up: "
            f"{[b - a for a, b in zip(p0['chip_jobs'], p1['chip_jobs'])]}; "
            f"the tree: {puts_zip._levels(db)}"]

        # ---- the window ------------------------------------------------
        t_before = stats.tickers()
        spans_before = len(factory.spans)
        factory.watch()
        t0 = run.window_open()
        pa = _pool(run.svc)
        lat, span, w = puts.write_window(
            db.write, kb, vb, first, n + max_puts, per_batch, run.seconds)
        pb = _pool(run.svc)
        t_after = stats.tickers()
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
        held = [b - a for a, b in zip(pa["chip_held_usec"],
                                      pb["chip_held_usec"])]
        took = [b - a for a, b in zip(pa["chip_jobs"], pb["chip_jobs"])]
        window_us = (pb["t"] - pa["t"]) * 1e6
        run.facts["notes"].append(
            f"window: {n_puts} puts in {span:.3f}s, last sequence {w}, "
            f"{len(win_jobs)} jobs, {len(remote)} remote (rows, rows out "
            f"of ZipTables, chips granted, chips used, compiled): "
            f"{[(s.input_records, s.zip_input_rows, s.devices_granted, s.devices_used, s.jit_compiles) for s in remote]}; "
            f"by level (from, to, rows, bottommost, where, ms): "
            f"{[puts_zip._job_line(j) for j in seen]}; "
            f"in the DB process: {len(local)}; the pool over "
            f"{window_us / 1e6:.3f}s: jobs taken by chip {took}, held by "
            f"chip {[round(h / window_us, 3) for h in held]} of the window, "
            f"jobs in flight {(pb['jobs_running_usec'] - pa['jobs_running_usec']) / window_us:.3f} "
            f"on the mean, grant waits "
            f"{(pb['grant_wait_usec'] - pa['grant_wait_usec']) / 1e6:.3f}s")

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
            remote_busy_s=busy,
            jit_compiles=sum(s.jit_compiles for s in remote),
            **{"fanout.window_usec": window_us,
               "fanout.jobs_running_usec":
                   pb["jobs_running_usec"] - pa["jobs_running_usec"],
               "fanout.grant_wait_usec":
                   pb["grant_wait_usec"] - pa["grant_wait_usec"],
               "fanout.chip_held_min_usec": min(held)})
        for k in tr.get("job_stats", []):
            values = [getattr(s, k, None) for s in remote]
            if remote and all(type(v) in (int, float) for v in values):
                run.facts["sum." + k] = sum(values)
        cold = [s for s in remote if s.zip_output_files]
        run.facts["notes"].append(
            f"window, by layer: {lat_a.sum() / span:.1%} of it in DB.write, "
            f"{delta[st.STALL_MICROS] / 1e6:.2f}s stalled, a remote job in "
            f"flight {busy / span:.1%}, p50/p95/p99 of a batch "
            f"{[round(float(x) * 1e3, 3) for x in np.percentile(lat_a, [50, 95, 99])]} ms; "
            f"{len(cold)} of the {len(remote)} remote jobs wrote ZipTables "
            f"({sum(s.zip_output_files for s in cold)} files; encode "
            f"{sum(s.zip_encode_usec for s in cold) / 1e6:.2f}s, of it "
            f"dictionary training "
            f"{sum(s.zip_dict_train_usec for s in cold) / 1e6:.2f}s; scan "
            f"{sum(s.zip_scan_usec for s in remote) / 1e6:.2f}s; work "
            f"{sum(s.work_time_usec for s in remote) / 1e6:.2f}s)")

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
            f"the tree: {puts_zip._levels(db)}")
        got, beside, replies = _check_pair(run, factory, wl)
        run.compare("rows_wrong", got["rows_wrong"])
        run.compare("rows_wrong_overlapped", beside["rows_wrong"])
        run.compare("records_misreported", got["records_misreported"]
                    + (beside["records_misreported"] or 0))
        run.compare("bottommost_outputs_not_zip",
                    witness.bottommost_outputs_not_zip()
                    + (got["outputs_not_zip"] or 0))
        tick = stats.tickers()
        svc_stats = run.svc.get("/stats")
        run.compare("fallback_local",
                    tick.get(st.DCOMPACTION_FALLBACK_LOCAL, 0))
        run.compare("remote_job_failures",
                    tick.get(st.DCOMPACTION_JOB_FAILURES, 0)
                    + svc_stats["jobs_failed"] + factory.failed)
        run.compare("jobs_off_device", dbside.device_witnesses(
            stats.jobs, run.device)["jobs_off_device"])
        all_remote = [s for s in stats.jobs if s.remote]
        run.compare("jobs_off_grant", sum(
            _off_grant(s.devices_granted, s.devices_used)
            for s in all_remote) + sum(
            _off_grant(r["stats"].get("devices_granted"),
                       r["stats"].get("devices_used")) for r in replies))
        left = svc_stats.get("jobs_left_pipeline")
        sent = [s.input_records for s in all_remote] + [
            r["stats"].get("input_records", 0) for r in replies]
        floor = dbside.PIPELINE_FLOOR_ROWS
        run.compare("jobs_left_pipeline", None if left is None else left
                    - sum(r < floor for r in sent))
        run.compare("window_without_remote_job", int(not remote))
        run.compare("window_without_zip_input_rows", int(
            not sum(s.zip_input_rows for s in remote)))
        run.compare("window_chips_unused", sum(h <= 0 for h in held)
                    + max(0, run.cell["chips"] - chips))
        run.compare("stream_ran_out", int(ran_out))
    finally:
        db.close()
    return {"write_ops_s": n_puts / span}


def _post(run, entry, name: str) -> tuple[str, dict]:
    """One kept job in a directory of its own, posted to the service."""
    d = os.path.join(run.workdir, name)
    os.makedirs(os.path.join(d, "out"))
    params = CompactionParams(**{
        **entry["params"], "device": run.device,
        "output_dir": os.path.join(d, "out")})
    with open(os.path.join(d, "params.json"), "w") as f:
        f.write(params.to_json())
    return d, run.svc.post_job(d)


def _check_pair(run, factory, wl):
    """Post the window's largest remote bottommost job with a ZipTable
    among its inputs and the job that ran beside it on another chip, both
    at once, and compare each output with the plain reference's survivors.
    Returns (the largest's counts, the other's counts, the replies)."""
    none = {"rows_wrong": None, "records_misreported": None,
            "outputs_not_zip": None}
    big = factory.largest_entry
    if big is None:       # `window_without_zip_input_rows` says so
        return dict(none), dict(none), []
    other = factory.beside_largest()
    pair = [("largest-job-run", big)] + (
        [("beside-job-run", other)] if other is not None else [])
    out: list = [None] * len(pair)
    failed: list = []

    def one(i):
        try:
            out[i] = _post(run, pair[i][1], pair[i][0])
        except Exception as e:  # a failed post is a wrong job
            failed.append(repr(e)[:300])

    t0 = time.time()
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(pair))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t1 = time.time()
    counts, replies = [], []
    for res in out:
        if res is None:
            counts.append(dict(none))
            continue
        d, reply = res
        got = reference_zip.compare_job(d, wl)
        if reply["status"] != "ok":
            got["rows_wrong"] = None
        counts.append(got)
        replies.append(reply)
        shutil.rmtree(d, ignore_errors=True)
    got = counts[0]
    beside = counts[1] if len(counts) > 1 else dict(none)
    run.facts["notes"].append(
        f"reference: the window's largest bottommost job with a zip input, "
        f"{got.get('rows_in')} input rows -> {got.get('rows_out')} "
        f"(expected {got.get('rows_expected')}), first on chips "
        f"{big['devices']}; beside it on chips "
        f"{other['devices'] if other else None} for "
        f"{factory._overlap(other, big) if other else 0:.1f}s: "
        f"{beside.get('rows_in')} input rows -> {beside.get('rows_out')} "
        f"(expected {beside.get('rows_expected')}); posted again together "
        f"on chips {[r['stats'].get('devices_used') for r in replies]} in "
        f"{t1 - t0:.1f}s; failures: {failed}")
    return got, beside, replies
