"""Traffic kind `ycsb`: YCSB core workload A on the served path, from the
client's side (the RocksDB binding's calls, one client thread).

Set-up: YCSB's load phase, every record once in hashed key order, in bulk
batches (not timed); after each batch the load waits out the compactions it
triggered, so the sorted runs it leaves do not depend on how long a job
took. Then the operation stream itself, untimed (`settle`): to the
memtable's own flush and on, flush by flush, until L0 stands one file short
of its compaction trigger, then a third of a write buffer more, so that
every window's first flush starts an L0->L1 job of two mixed-length shards;
the compactions are waited out after each flush. Every operation is drawn
before the window opens: its kind (read or update, half each), its record
(YCSB's scrambled Zipfian generator), the field an update writes and the
update's 100-byte value (`lib/reference_ycsb.py`).

The window, closed loop: a read is one `DB.get` of the whole record
(`readallfields=true`); an update is what `RocksDBClient.update` does, one
`DB.get`, the field replaced, one `DB.put` of the record (WAL on, no sync).
The harness clock covers each `DB.get` and each `DB.put`. The window closes
at the first operation boundary at or after `--seconds`; `write_ops_s` is
the acknowledged updates over the whole span.

Afterwards: the answers of a seeded eighth of the window's reads and of
every update's read against the oracle at that point of the stream; reads,
a multi_get and scans against the final state, before and after a close and
reopen; the window's largest remote job posted once more and its output
compared row by row with the plain reference's survivors; and the
witnesses that the chip ran every remote job, on the pipelined plane from
its row floor on, by the mixed-length shard program.
"""

from __future__ import annotations

import os
import shutil
import sys
import time

import numpy as np

from toplingdb_tpu.compaction.executor import CompactionParams
from toplingdb_tpu.db.db import DB
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.options import Options
from toplingdb_tpu.table import format as fmt
from toplingdb_tpu.table.builder import TableOptions
from toplingdb_tpu.utils import statistics as st

from lib import dbside, dbside_merge
from lib import reference_ycsb as ry

EXIT_PROGRAM_LACKS = 5
TICKERS = (st.BYTES_WRITTEN, st.FLUSH_WRITE_BYTES, st.COMPACT_WRITE_BYTES,
           st.STALL_MICROS, st.DCOMPACTION_FALLBACK_LOCAL,
           st.DCOMPACTION_JOB_FAILURES)
CODECS = {"none": fmt.NO_COMPRESSION, "lz4": fmt.LZ4_COMPRESSION}
VALUE = ry.FIELD_BYTES


def program_lacks() -> str:
    """What the checkout's program lacks to run this cell, or "". Without
    the mixed-length shard program every L0->L1 job (two shards of YCSB's
    keys) leaves the device plane for the per-entry path, which compiles
    for minutes on the chip: such a checkout refuses the cell at once."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats

    if "varlen_shard_rows" not in CompactionStats.__dataclass_fields__:
        return ("CompactionStats has no varlen_shard_rows: no device "
                "program for shards of keys of mixed length")
    return ""


def lsm_sizes(config: dict, scale: float) -> dict:
    """The configuration's sizes; a rehearsal shrinks them with the record
    count so that the same job shapes appear at a tiny size."""
    lsm = config["lsm"]
    f = min(1.0, scale)
    return {
        "records": max(2000, int(config["recordcount"] * f)),
        "write_buffer": max(64 << 10, int(lsm["write_buffer_bytes"] * f)),
        "target_file": max(64 << 10, int(lsm["target_file_bytes"] * f)),
        "level_base": max(256 << 10, int(lsm["level_base_bytes"] * f)),
        "min_input": max(16 << 10, int(lsm["min_remote_input_bytes"] * f)),
    }


def options(config: dict, sizes: dict, stats, factory) -> Options:
    """The DB's options. The block cache is the read engine's own: a
    point read runs in one native call (`DB._native_get`) unless a
    Python `block_cache` is configured, which would send every get down
    the per-level Python chain; so none is, and the native engine's LRU of
    decompressed blocks is held to the configuration's bytes instead."""
    from toplingdb_tpu import native

    lib = native.lib()
    if lib is not None and hasattr(lib, "tpulsm_block_cache_config"):
        lib.tpulsm_block_cache_config(config["block_cache_bytes"], None)
    lsm, table = config["lsm"], config["table"]
    return Options(
        create_if_missing=True,
        compression_per_level=[CODECS[c]
                               for c in table["compression_per_level"]],
        table_options=TableOptions(block_size=table["block_bytes"],
                                   filter_policy=None),
        write_buffer_size=sizes["write_buffer"],
        max_write_buffer_number=lsm["max_write_buffer_number"],
        target_file_size_base=sizes["target_file"],
        max_bytes_for_level_base=sizes["level_base"],
        max_bytes_for_level_multiplier=lsm["level_multiplier"],
        level0_file_num_compaction_trigger=lsm["l0_compaction_trigger"],
        num_levels=lsm["num_levels"],
        dcompact=dbside.ONE_ATTEMPT,
        statistics=stats,
        compaction_executor_factory=factory)


class JobFactory(dbside_merge.LargestJobFactory):
    """The largest-job factory, its kept job posted again with the codec
    and the filter the DB gave the original (the output level's)."""

    def __init__(self, url, device, min_input_bytes, dbname, keep_dir):
        super().__init__(url, device, min_input_bytes, dbname, keep_dir,
                         None)
        self.codec_for_level = None

    def _keep_if_largest(self, compaction) -> None:
        kept = self.kept
        super()._keep_if_largest(compaction)
        if self.kept != kept:
            self.largest["params"].update(
                compression=self.codec_for_level(compaction.output_level),
                filter_policy="")


def load(db, keys, n: int, per_batch: int, fill, stats) -> None:
    """YCSB's load phase: record i (its key keys[i], every field stamped
    i + 1), per_batch records a WriteBatch, the compactions each batch
    triggers waited out. A remote job of two shards or more that comes
    back without the mixed-length counters ends the run at once."""
    R = ry.RECORD_BYTES
    chunk = 20 * per_batch
    seen = 0
    for c0 in range(0, n, chunk):
        c1 = min(n, c0 + chunk)
        stamps = np.repeat(np.arange(c0 + 1, c1 + 1)[:, None], ry.FIELDS, 1)
        buf = ry.record_bytes(stamps, fill).tobytes()
        for b0 in range(c0, c1, per_batch):
            wb = WriteBatch()
            for j in range(b0, min(b0 + per_batch, c1)):
                o = (j - c0) * R
                wb.put(keys[j], buf[o:o + R])
            db.write(wb)
            db.wait_for_compactions()
            for s in stats.jobs[seen:]:
                if (s.remote and s.input_records >= dbside.PIPELINE_FLOOR_ROWS
                        and not getattr(s, "varlen_shard_rows", 0)):
                    print(f"no result: a load job of {s.input_records} rows "
                          f"came back without varlen_shard_rows (left the "
                          f"pipeline: {s.pipeline_exit!r})", file=sys.stderr,
                          flush=True)
                    raise SystemExit(EXIT_PROGRAM_LACKS)
            seen = len(stats.jobs)


class Ops:
    """The run's operations as the loop reads them: plain lists (a numpy
    scalar a lookup is slower than the get it feeds), the updates' values
    back to back, and the answers kept for the oracle."""

    def __init__(self, stream: ry.Stream, keys: list, sample: int, seed: int):
        self.kind = stream.kind.tolist()
        self.key = [keys[r] for r in stream.record.tolist()]
        self.off = [ry.field_offset(f) for f in stream.field.tolist()]
        self.values = stream.update_values(0, len(stream))
        self.sample = sample
        self.phase = seed % sample
        self.kept: dict = {}        # op -> the record its get returned
        self.missing: list = []     # ops whose get found no record


def op_loop(db, ops: Ops, first: int, u: int, end: int, seconds: float,
            stop=None, clock=time.perf_counter):
    """Operations first..end-1, closed loop: a get, and for an update the
    field replaced and a put. `u` is the ordinal of the first update among
    them. Closes at the first operation boundary at or after `seconds`, at
    the stream's end, or (the set-up's use) where `stop()` holds, asked
    every 256 operations. Returns (each get's latency, each put's, span,
    next operation, next update ordinal)."""
    get, put = db.get, db.put
    kind, key, off, vals = ops.kind, ops.key, ops.off, ops.values
    kept, missing = ops.kept, ops.missing
    sample, phase = ops.sample, ops.phase
    lat_get, lat_put = [], []
    i = first
    c0 = b = clock()
    while i < end:
        k = key[i]
        a = clock()
        rec = get(k)
        b = clock()
        lat_get.append(b - a)
        if rec is None:
            missing.append(i)
        if kind[i]:
            kept[i] = rec
            if rec is not None:
                o = off[i]
                new = rec[:o] + vals[VALUE * u:VALUE * u + VALUE] + \
                    rec[o + VALUE:]
                a = clock()
                put(k, new)
                b = clock()
                lat_put.append(b - a)
            u += 1
        elif i % sample == phase:
            kept[i] = rec
        i += 1
        if b - c0 >= seconds or (stop is not None and not i & 255
                                 and stop()):
            break
    return lat_get, lat_put, b - c0, i, u


def settle(db, ops, stream, stats, n_ops: int, sizes: dict, trigger: int):
    """The set-up's run of the stream after the load: on to the memtable's
    own flush, and again until a flush leaves L0 one file short of its
    compaction trigger (the load's compactions waited out each time); then
    a third of a write buffer of updates more. So the window's first flush
    comes two thirds of a write buffer in and starts an L0->L1 job (two
    shards of mixed-length keys) whatever the operation rate within reason,
    in every run alike. Returns (next operation, next update ordinal)."""
    first = u = 0
    while first < n_ops:
        flushed = stats.get_ticker_count(st.FLUSH_WRITE_BYTES)
        _, _, _, first, u = op_loop(
            db, ops, first, u, n_ops, float("inf"), stop=lambda: stats.
            get_ticker_count(st.FLUSH_WRITE_BYTES) != flushed)
        db.wait_for_compactions()
        if int(db.get_property("tpulsm.num-files-at-level0")) == trigger - 1:
            break
    # An entry of the memtable: the record, its key, the skiplist node.
    more = int(sizes["write_buffer"] / (ry.RECORD_BYTES + 64) / 3)
    upd = np.cumsum(stream.kind[first:n_ops])
    end = first + int(np.searchsorted(upd, more)) + 1
    _, _, _, first, u = op_loop(db, ops, first, u, min(end, n_ops),
                                float("inf"))
    return first, u


def drive(run) -> dict:
    lacks = program_lacks()
    if lacks:
        print(f"no result: this checkout's program cannot run the cell "
              f"({lacks})", file=sys.stderr, flush=True)
        raise SystemExit(EXIT_PROGRAM_LACKS)
    tr, cfg = run.traffic, run.config
    sizes = lsm_sizes(cfg, run.scale)
    n = sizes["records"]
    # The stream: the window at a rate no run has come near, and the
    # set-up's run through its flushes (up to three write buffers of
    # updates, and as many reads).
    fill_ops = int(6 * sizes["write_buffer"] / ry.RECORD_BYTES)
    n_ops = int(tr["max_ops_per_s"] * run.seconds) + fill_ops
    keys = ry.user_keys(n)                     # made while the service starts
    stream = ry.Stream(n, n_ops, run.seed)
    ops = Ops(stream, keys, tr["in_window_read_sample"], run.seed)
    run.wait_service()

    stats = dbside.JobStatistics()
    dbdir = os.path.join(run.workdir, "db")
    keep_dir = os.path.join(run.workdir, "largest-job")
    os.makedirs(keep_dir)
    factory = JobFactory(run.svc.url, run.device, sizes["min_input"], dbdir,
                         keep_dir)
    opts = options(cfg, sizes, stats, factory)
    factory.codec_for_level = opts.compression_for_level
    db = DB.open(dbdir, opts)
    try:
        t_load = time.time()
        load(db, keys, n, tr["load_records_per_batch"], stream.fill, stats)
        t_load = time.time() - t_load
        load_jobs = len(stats.jobs)
        t_fill = time.time()
        first, u = settle(db, ops, stream, stats, n_ops, sizes,
                          cfg["lsm"]["l0_compaction_trigger"])
        t_fill = time.time() - t_fill
        warm_jobs = len(stats.jobs)
        warm = dbside.device_witnesses(stats.jobs, run.device)
        run.facts["notes"] = [
            f"preload: {n} records in {t_load:.1f}s ({load_jobs} jobs), then "
            f"{first} operations in {t_fill:.1f}s (to the flush that leaves "
            f"L0 one file short of its trigger, and a third of a write "
            f"buffer on), {warm_jobs} jobs, {warm['remote_jobs']} remote; "
            f"compiled in set-up: {sum(s.jit_compiles for s in stats.jobs)}"
            f" ({sum(s.jit_compile_usec for s in stats.jobs) / 1e6:.1f}s); "
            f"programs first met: "
            f"{sum(s.jit_compiles + s.jit_cache_hits for s in stats.jobs if s.remote)}; "
            f"set-up jobs (rows, varlen shards, work s, remote): "
            f"{[(s.input_records, s.varlen_shards, round(s.work_time_usec / 1e6, 2), s.remote) for s in stats.jobs]}"]

        # ---- the window ------------------------------------------------
        ops.kept.clear()            # the set-up's answers are not checked
        t_before = stats.tickers()
        flush_before = stats.get_histogram(st.FLUSH_TIME_MICROS).sum
        spans_before = len(factory.spans)
        missing_before = len(ops.missing)
        factory.watch()
        t0 = run.window_open()
        lat_get, lat_put, span, w, _ = op_loop(db, ops, first, u, n_ops,
                                               run.seconds)
        t_after = stats.tickers()
        flush_s = (stats.get_histogram(st.FLUSH_TIME_MICROS).sum
                   - flush_before) / 1e6
        t1 = t0 + span
        run.window_close()
        factory.watch(False)
        updates = len(lat_put)
        run.attempted = w - first
        run.failed = len(ops.missing) - missing_before
        ran_out = w >= n_ops and span < run.seconds
        if ran_out:
            run.facts["notes"].append(
                f"the drawn stream of {n_ops} operations ran out after "
                f"{span:.1f}s: raise max_ops_per_s")

        win_jobs = stats.jobs[warm_jobs:]
        remote = [s for s in win_jobs if s.remote]
        run.facts["notes"].append(
            f"window: {w - first} operations ({updates} updates acknowledged)"
            f" in {span:.3f}s, {len(win_jobs)} jobs (rows, varlen shards, "
            f"output rows, compiled, loaded from the cache): "
            f"{[(s.input_records, s.varlen_shards, s.output_records, s.jit_compiles, s.jit_cache_hits) for s in remote]}")

        # ---- facts for the readers -------------------------------------
        put_a = np.asarray(lat_put)
        get_a = np.asarray(lat_get)
        in_write = float(put_a.sum())
        get_s = float(get_a.sum())
        delta = {k: t_after.get(k, 0) - t_before.get(k, 0) for k in TICKERS}
        busy = _covered(factory.spans[spans_before:], t0, t1)
        run.facts.update(
            window_s=span, in_write_s=in_write,
            out_of_write_s=span - in_write - get_s,
            window_ops=updates, write_batch_s=put_a,
            get_s=get_s, gets=len(get_a), flush_s=flush_s,
            get_latency_s=get_a,
            stall_s=delta[st.STALL_MICROS] / 1e6,
            storage_write_bytes=(delta[st.FLUSH_WRITE_BYTES]
                                 + delta[st.COMPACT_WRITE_BYTES]),
            user_write_bytes=delta[st.BYTES_WRITTEN],
            remote_busy_s=busy,
            jit_compiles=sum(s.jit_compiles for s in remote))
        for k in tr.get("job_stats", []):   # "sum.<counter>" over the
            values = [getattr(s, k, None) for s in remote]  # window's jobs
            if remote and all(type(v) in (int, float) for v in values):
                run.facts["sum." + k] = sum(values)
        if run.facts.get("sum.varlen_shard_rows"):
            run.facts.update(
                rows_in=run.facts["sum.input_records"],
                rows_out=run.facts["sum.output_records"],
                key_bytes=(run.facts["sum.varlen_key_bytes"]
                           / run.facts["sum.varlen_shard_rows"]),
                device_kind=run.dev["kind"])
        run.facts["notes"].append(
            f"window, by layer: {get_s / span:.1%} of it in DB.get "
            f"({len(get_a)}), {in_write / span:.1%} in DB.put, "
            f"{flush_s / span:.1%} flushing, "
            f"{delta[st.STALL_MICROS] / 1e6:.2f}s stalled, a remote job in "
            f"flight {busy / span:.1%}; p50/p99 of a get "
            f"{[round(float(x) * 1e3, 4) for x in np.percentile(get_a, [50, 99])]} ms, "
            f"of a put {[round(float(x) * 1e3, 4) for x in np.percentile(put_a, [50, 99])] if updates else []} ms")

        # ---- what is compared ------------------------------------------
        t_chk = time.time()
        not_applied = [i for i in ops.missing if stream.kind[i]]
        oracle = ry.Oracle(stream, w, not_applied)
        run.compare("read_mismatches_in_window",
                    _window_mismatches(ops, stream, oracle, first, w))
        rng = np.random.default_rng([run.seed, 1])
        pool = stream.record[first:w][stream.kind[first:w] == ry.UPDATE]
        run.compare("read_mismatches", _read_mismatches(
            db, keys, oracle, pool, rng, tr["checks"]))
        db.wait_for_compactions()
        db.close()
        db = DB.open(dbdir, opts)               # WAL + MANIFEST recovery
        run.compare("reopen_read_mismatches", _read_mismatches(
            db, keys, oracle, pool, rng, tr["checks"]))
        run.facts["notes"].append(
            f"reads against the oracle: {time.time() - t_chk:.1f}s")
        got = _check_largest_job(run, factory)
        run.compare("rows_wrong", got["rows_wrong"])
        run.compare("records_misreported", got["records_misreported"])
        run.compare("compared_job_short_of_two_varlen_shards",
                    got["short_of_two_shards"])
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
        # less those of one shard's rows: they run the same program on the
        # serial branch, by design.
        left = svc_stats.get("jobs_left_pipeline")
        sent = [s.input_records for s in stats.jobs if s.remote] + (
            [got["rows_sent"]] if got["rows_sent"] else [])
        floor = dbside.PIPELINE_FLOOR_ROWS
        run.compare("jobs_left_pipeline", None if left is None else left
                    - sum(r < floor for r in sent))
        exits = sorted({s.pipeline_exit for s in stats.jobs
                        if s.remote and s.pipeline_exit})
        if exits:
            run.facts["notes"].append(f"jobs left the pipeline: {exits}")
        run.compare("window_without_remote_job", int(not remote))
        run.compare("window_without_varlen_shards", int(
            not run.facts.get("sum.varlen_shard_rows")))
        run.compare("stream_ran_out", int(ran_out))
    finally:
        db.close()
    return {"write_ops_s": updates / span}


def _covered(spans, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] inside the union of the spans."""
    total, end = 0.0, lo
    for a, b in sorted(spans):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def _window_mismatches(ops, stream, oracle, first: int, w: int) -> int:
    """The kept answers of the window's operations (a seeded share of the
    reads, every update's read) against the oracle at their own point of
    the stream."""
    at = np.array(sorted(i for i in ops.kept if first <= i < w), np.int64)
    if not len(at):
        return 0
    want = oracle.expected(stream.record[at], at)
    return ry.mismatches([ops.kept[int(i)] for i in at], list(want))


def _read_mismatches(db, keys, oracle, pool, rng, checks) -> int:
    """Point gets, one multi_get and forward scans against the final
    state: records drawn half from the window's updates and half from all
    records, a share of keys no record has."""
    n = oracle.stream.n
    skeys = np.array(keys, dtype="S32")
    order = np.argsort(skeys, kind="stable")
    sorted_keys = skeys[order]
    bad = 0

    def probe(m):
        recs = rng.integers(0, n, m)
        if len(pool):
            half = rng.random(m) < 0.5
            recs[half] = rng.choice(pool, int(half.sum()))
        want = list(oracle.expected(recs))
        ks = [keys[r] for r in recs.tolist()]
        for j in np.flatnonzero(rng.random(m) < checks["miss_share"]):
            k = b"user%d" % ry.fnvhash64(n + int(rng.integers(0, 1 << 40)))
            p = int(np.searchsorted(sorted_keys, k))
            if p < n and sorted_keys[p] == k:
                continue  # a record's key after all
            ks[j], want[j] = k, None
        return ks, want

    ks, want = probe(checks["gets"])
    bad += ry.mismatches([db.get(k) for k in ks], want)
    ks, want = probe(checks["multi_get"])
    got = db.multi_get(ks)
    bad += abs(len(got) - len(want)) + ry.mismatches(list(got), want)

    rows = min(checks["scan_rows"], n)
    for _ in range(checks["scans"]):
        start = int(rng.integers(0, max(1, n - rows)))
        recs = order[start:start + rows]
        want = list(oracle.expected(recs))
        it = db.new_iterator()
        it.seek(keys[int(recs[0])])
        got = []
        for r in recs.tolist():
            if not it.valid():
                bad += len(recs) - len(got)
                break
            bad += it.key() != keys[r]
            got.append(it.value())
            it.next()
        bad += ry.mismatches(got, want[:len(got)])
    return int(bad)


def _check_largest_job(run, factory) -> dict:
    """Post the window's largest remote job to the service once more (its
    inputs were kept by hard link) and compare its output with the plain
    reference's survivors."""
    job = factory.largest
    if job is None:  # `window_without_remote_job` says so
        return {"rows_wrong": None, "records_misreported": None,
                "short_of_two_shards": None, "rows_sent": 0}
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
    got = ry.compare_job(d)
    if reply["status"] != "ok":
        got["rows_wrong"] = None
    run.facts["notes"].append(
        f"reference: the window's largest job, {got['rows_in']} input rows "
        f"-> {got['rows_out']} (expected {got['rows_expected']}), "
        f"{got['varlen_shards']} mixed-length shards, output level "
        f"{job['params']['output_level']}, codec "
        f"{job['params']['compression']}; posted again {t1 - t0:.1f}s, "
        f"compared {time.time() - t1:.1f}s")
    shutil.rmtree(d, ignore_errors=True)
    got["short_of_two_shards"] = max(0, 2 - got["varlen_shards"])
    got["rows_sent"] = job["rows"]
    return got
