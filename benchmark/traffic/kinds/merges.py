"""Traffic kind `merges`: the served path of the merge deployment from the
client's side (db_bench fillrandom, then mergerandom, under universal
compaction with the uint64add operator and DeleteRange).

Set-up: all keys once in a seeded order as `Put`s of 8-byte counters (in
bulk batches: it is not timed), one `DeleteRange` after every
`writes_per_range_tombstone`-th; then the merge stream itself, untimed,
until the memtable that the load left part-filled is full and flushes of
its own accord (db_bench's `fillrandom,mergerandom` writes on into the
fill's memtable too); wait for the compactions. So every sorted run is of
a whole write buffer, as in a deployment that has been running. The load
lets every compaction it triggers finish before it writes on, so that a
service that compiles for minutes in its first job and one that does not
leave the same sorted runs. The remote jobs of that load carry range
tombstones, so they run the program the window's jobs meet. The window's
operands are encoded before it opens; inside it only `WriteBatch.merge`,
`DB.write` and `DB.delete_range` run, closed loop, one writer.

The window opens at a batch boundary and closes at the first batch boundary
at or after `--seconds`. The rate is all acknowledged operands (the
DeleteRanges are not counted) over that whole span.

Afterwards: reads against the plain reference's oracle
(`lib/reference_merge.py`), before and after a close and reopen; the
window's largest remote job, its inputs kept by hard link, posted to the
service once more and its output compared row by row with the reference's
survivors; and the witnesses that the chip did every remote compaction,
on the pipelined plane from its row floor on.
"""

from __future__ import annotations

import os
import shutil
import sys
import time

import numpy as np

from toplingdb_tpu.compaction.executor import CompactionParams
from toplingdb_tpu.db.db import DB
from toplingdb_tpu.utils import statistics as st

from lib import dbside, dbside_merge, reference_merge
from lib.workload_merge import KEY_BYTES, MergeWorkload, key_bytes

EXIT_PROGRAM_LACKS = 5
TICKERS = (st.BYTES_WRITTEN, st.FLUSH_WRITE_BYTES, st.COMPACT_WRITE_BYTES,
           st.STALL_MICROS, st.DCOMPACTION_FALLBACK_LOCAL,
           st.DCOMPACTION_JOB_FAILURES)


def drive(run) -> dict:
    lacks = dbside_merge.program_lacks()
    if lacks:
        # Before anything is loaded or compiled: a program that cannot
        # fold operands on the pipelined plane fails this cell at once.
        print(f"no result: this checkout's program cannot run the cell "
              f"({lacks})", file=sys.stderr, flush=True)
        raise SystemExit(EXIT_PROGRAM_LACKS)
    tr, cfg = run.traffic, run.config
    sizes = dbside_merge.lsm_sizes(cfg, run.scale)
    n = sizes["keys"]
    per_batch = cfg["batch_size"]
    rt = cfg["range_tombstones"]
    # The stream is sized for a rate no run has come near.
    max_ops = int(tr["max_operands_per_s"] * run.seconds)
    max_ops -= max_ops % per_batch
    wl = MergeWorkload(n, max_ops, run.seed,
                       rt["writes_per_range_tombstone"],
                       rt["range_tombstone_width"])
    kb, vb = wl.encode(0, n + max_ops)         # made while the service starts
    tb, te, _ = wl.tombstones(n + max_ops)
    tomb_at = wl.tomb_at.tolist()
    run.wait_service()

    stats = dbside.JobStatistics()
    dbdir = os.path.join(run.workdir, "db")
    keep_dir = os.path.join(run.workdir, "largest-job")
    os.makedirs(keep_dir)
    factory = dbside_merge.LargestJobFactory(
        run.svc.url, run.device, sizes["min_input"], dbdir, keep_dir,
        cfg["merge_operator"])
    opts = dbside_merge.options(cfg, sizes, stats, factory)
    db = DB.open(dbdir, opts)
    try:
        t_next = dbside_merge.load(db, kb, vb, tb, te, tomb_at, n,
                                   tr["load_puts_per_batch"])
        # The flush before the window is the memtable's own: the stream
        # runs on, untimed, until the memtable the load left part-filled
        # is full and flushed. `DB.flush()` here would leave a sorted run
        # of part of a write buffer, which no running deployment holds and
        # whose merge with the next flush falls under
        # min_remote_input_bytes: one compaction in the DB process, beside
        # the writer, for 14 s of a 40 s window (PERF.md section 6).
        flushed = stats.get_ticker_count(st.FLUSH_WRITE_BYTES)
        _, _, first, t_next = dbside_merge.merge_window(
            db, kb, vb, tb, te, tomb_at, t_next, n, n + max_ops, per_batch,
            float("inf"), stop=lambda: stats.get_ticker_count(
                st.FLUSH_WRITE_BYTES) != flushed)
        db.wait_for_compactions()
        warm_jobs = len(stats.jobs)
        warm = dbside.device_witnesses(stats.jobs, run.device)
        run.facts["notes"] = [
            f"preload: {n} keys and {first - n} operands (to the "
            f"memtable's own flush), {t_next} range tombstones, "
            f"{warm_jobs} jobs, {warm['remote_jobs']} remote; compiled in "
            f"set-up: "
            f"{sum(s.jit_compiles for s in stats.jobs)}; programs first "
            f"met: {_first_met(stats.jobs)}"]

        # ---- the window ------------------------------------------------
        t_before = stats.tickers()
        spans_before = len(factory.spans)
        factory.watch()
        t0 = run.window_open()
        lat, span, w, t_next = dbside_merge.merge_window(
            db, kb, vb, tb, te, tomb_at, t_next, first, n + max_ops,
            per_batch, run.seconds)
        t_after = stats.tickers()
        last_seq = db.latest_sequence_number()
        t1 = t0 + span
        run.window_close()
        factory.watch(False)
        operands = w - first
        run.attempted = operands
        ran_out = dbside.stream_ran_out(w, per_batch, n + max_ops, span,
                                        run.seconds)
        if ran_out:
            run.facts["notes"].append(
                f"the encoded stream of {max_ops} operands ran out after "
                f"{span:.1f}s: raise max_operands_per_s")

        win_jobs = stats.jobs[warm_jobs:]
        remote = [s for s in win_jobs if s.remote]
        run.facts["notes"].append(
            f"window: {operands} operands and "
            f"{t_next - int(np.searchsorted(wl.tomb_at, first, 'right'))} "
            f"DeleteRanges in {span:.3f}s, last sequence {last_seq} "
            f"(2^24 is {1 << 24}), {len(win_jobs)} jobs (rows, operand "
            f"rows, folded away, compiled, loaded from the cache): "
            f"{[(s.input_records, s.merge_operand_rows, s.merge_rows_folded, s.jit_compiles, s.jit_cache_hits) for s in remote]}")

        # ---- facts for the readers -------------------------------------
        lat_a = np.asarray(lat)
        in_write = float(lat_a.sum())
        delta = {k: t_after.get(k, 0) - t_before.get(k, 0) for k in TICKERS}
        busy = _covered(factory.spans[spans_before:], t0, t1)
        run.facts.update(
            window_s=span, in_write_s=in_write,
            out_of_write_s=span - in_write,
            window_ops=operands, write_batch_s=lat_a,
            stall_s=delta[st.STALL_MICROS] / 1e6,
            storage_write_bytes=(delta[st.FLUSH_WRITE_BYTES]
                                 + delta[st.COMPACT_WRITE_BYTES]),
            user_write_bytes=delta[st.BYTES_WRITTEN],
            remote_busy_s=busy, last_sequence=last_seq,
            jit_compiles=sum(s.jit_compiles for s in remote))
        for k in tr.get("job_stats", []):   # "sum.<counter>" over the
            values = [getattr(s, k, None) for s in remote]  # window's jobs
            if remote and all(type(v) in (int, float) for v in values):
                run.facts["sum." + k] = sum(values)
        run.facts["notes"].append(
            f"window, by layer: {lat_a.sum() / span:.1%} of it in DB.write, "
            f"{delta[st.STALL_MICROS] / 1e6:.2f}s stalled, a remote job in "
            f"flight {busy / span:.1%}, p50/p95/p99 of a batch "
            f"{[round(float(x) * 1e3, 3) for x in np.percentile(lat_a, [50, 95, 99])]} ms")

        # ---- what is compared ------------------------------------------
        t_chk = time.time()
        oracle = reference_merge.Oracle(wl, w)
        rng = np.random.default_rng([run.seed, 1])
        run.compare("read_mismatches",
                    _read_mismatches(db, wl, oracle, rng, n, w, tr["checks"]))
        db.wait_for_compactions()
        db.close()
        db = DB.open(dbdir, opts)               # WAL + MANIFEST recovery
        run.compare("reopen_read_mismatches",
                    _read_mismatches(db, wl, oracle, rng, n, w, tr["checks"]))
        run.facts["notes"].append(
            f"reads against the oracle, twice: {time.time() - t_chk:.1f}s")
        got, sent_rows = _check_largest_job(run, factory)
        run.compare("rows_wrong", got["rows_wrong"])
        run.compare("records_misreported", got["records_misreported"])
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
        # serially, by design (the guarantee names that floor). Among the
        # window's jobs there is as a rule none: the notes count them.
        left = svc_stats.get("jobs_left_pipeline")
        sent = [s.input_records for s in stats.jobs if s.remote] + sent_rows
        floor = dbside.PIPELINE_FLOOR_ROWS
        run.compare("jobs_left_pipeline", None if left is None else left
                    - sum(r < floor for r in sent))
        run.facts["notes"].append(
            f"remote jobs under the plane's floor of {floor} rows: "
            f"{sum(s.input_records < floor for s in remote)} of the "
            f"window's {len(remote)}, {sum(r < floor for r in sent)} of all "
            f"{len(sent)}")
        after = [s for s in stats.jobs[warm_jobs + len(win_jobs):]
                 if s.remote]
        run.facts["notes"].append(
            f"after the window: {len(after)} remote jobs (rows, compiled, "
            f"loaded from the cache): "
            f"{[(s.input_records, s.jit_compiles, s.jit_cache_hits) for s in after]}")
        exits = sorted({s.pipeline_exit for s in stats.jobs
                        if s.remote and s.pipeline_exit})
        if exits:
            run.facts["notes"].append(f"jobs left the pipeline: {exits}")
        run.compare("window_without_remote_job", int(not remote))
        run.compare("window_without_merge_rows", int(
            not sum(s.merge_operand_rows for s in remote)))
        run.compare("stream_ran_out", int(ran_out))
    finally:
        db.close()
    return {"write_ops_s": operands / span}


def _first_met(jobs) -> int:
    """Programs a set of jobs asked XLA for: compiled, or loaded from the
    persistent cache (on a cold cache each of these is a compile)."""
    return sum(s.jit_compiles + s.jit_cache_hits for s in jobs if s.remote)


def _covered(spans, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] inside the union of the spans."""
    total, end = 0.0, lo
    for a, b in sorted(spans):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def _check_largest_job(run, factory):
    """Post the window's largest remote job to the service once more (its
    inputs were kept by hard link) and compare its output with the plain
    reference's survivors. Returns (counts, rows of the jobs posted)."""
    job = factory.largest
    if job is None:  # `window_without_remote_job` says so
        return {"rows_wrong": None, "records_misreported": None}, []
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
    got = reference_merge.compare_job(d)
    if reply["status"] != "ok":
        got["rows_wrong"] = None
    run.facts["notes"].append(
        f"reference: the window's largest job, {got['rows_in']} input rows "
        f"({got['operand_rows_in']} operands, {got['tombstones_in']} range "
        f"tombstones) -> {got['rows_out']} (expected "
        f"{got['rows_expected']}), bottommost "
        f"{job['params']['bottommost']}; posted again {t1 - t0:.1f}s, "
        f"compared {time.time() - t1:.1f}s")
    shutil.rmtree(d, ignore_errors=True)
    return got, [job["rows"]]


def _read_mismatches(db, wl, oracle, rng, n, w, checks) -> int:
    """Point gets, one multi_get and forward scans, each answer against
    the oracle; keys drawn from the window's own writes and from the
    ranges its DeleteRanges covered, some never written."""
    K = KEY_BYTES
    bad = 0

    def probe(m):
        writes = rng.integers(n, w, m)          # writes of the window
        keys = wl.key_of[writes].copy()
        if len(oracle.tomb_lo):                 # a tenth from deleted ranges
            t = rng.random(m) < 0.1
            keys[t] = (rng.choice(oracle.tomb_lo, int(t.sum()))
                       + rng.integers(0, wl.width, int(t.sum()),
                                      dtype=np.uint64))
        miss = rng.random(m) < checks["miss_share"]
        keys[miss] += np.uint64(wl.n)           # never written
        return keys

    keys = probe(checks["gets"])
    want = oracle.expected(keys)
    kb = key_bytes(keys).tobytes()
    for i, x in enumerate(want):
        bad += db.get(kb[K * i:K * i + K]) != x

    keys = probe(checks["multi_get"])
    want = oracle.expected(keys)
    kb = key_bytes(keys).tobytes()
    got = db.multi_get([kb[K * i:K * i + K] for i in range(len(keys))])
    bad += abs(len(got) - len(want))
    bad += sum(g != x for g, x in zip(got, want))

    rows = min(checks["scan_rows"], wl.n)
    present = np.flatnonzero(oracle.present).astype(np.uint64)
    for s in range(checks["scans"]):
        if len(oracle.tomb_lo) and rng.random() < checks[
                "tombstone_scan_share"]:
            start = max(0, int(rng.choice(oracle.tomb_lo)) - wl.width)
        else:
            start = int(rng.integers(0, max(1, wl.n - rows)))
        first = int(np.searchsorted(present, start))
        ks = present[first:first + rows]        # what the scan must meet
        want_v = oracle.expected(ks)
        want_k = key_bytes(ks).tobytes()
        it = db.new_iterator()
        it.seek(key_bytes(np.array([start], np.uint64)).tobytes())
        for i in range(len(ks)):
            if not it.valid():
                bad += len(ks) - i
                break
            bad += (it.key() != want_k[K * i:K * i + K]
                    or it.value() != want_v[i])
            it.next()
    return int(bad)
