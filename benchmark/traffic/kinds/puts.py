"""Traffic kind `puts`: the served path from the client's side.

Set-up: all keys once in a seeded order (fillrandom, in bulk batches: it is
not timed), flush, wait for the compactions. The remote jobs of that load
run the program the window's jobs meet. The window's puts are encoded
before it opens (keys and values as two byte strings); inside it only
`WriteBatch.put` and `DB.write` run, closed loop, one writer.

The window opens at a batch boundary and closes at the first batch boundary
at or after `--seconds`. Rates are all acknowledged puts over that whole
span.

Afterwards: reads of the window's acknowledged writes against the seed's
oracle, before and after a close and reopen, and the witnesses that the
chip did the compactions.
"""

from __future__ import annotations

import os
import time

import numpy as np

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.utils import statistics as st

from lib import dbside
from lib.workload import KEY_BYTES, VALUE_BYTES, Workload

TICKERS = (st.BYTES_WRITTEN, st.FLUSH_WRITE_BYTES, st.COMPACT_WRITE_BYTES,
           st.STALL_MICROS, st.DCOMPACTION_FALLBACK_LOCAL,
           st.DCOMPACTION_JOB_FAILURES)


def write_window(write, kb: bytes, vb: bytes, first: int, end: int,
                 per_batch: int, seconds: float, clock=time.perf_counter):
    """The window: pre-encoded writes first.. go out per_batch to a
    WriteBatch, closed loop. It closes at the first batch boundary at or
    after `seconds`, or when the stream ends. Returns (each batch's
    latency, span, next write index)."""
    K, V = KEY_BYTES, VALUE_BYTES
    lat = []
    w = first
    c0 = clock()
    while True:
        wb = WriteBatch()
        for j in range(w, w + per_batch):
            wb.put(kb[K * j:K * j + K], vb[V * j:V * j + V])
        a = clock()
        write(wb)
        b = clock()
        lat.append(b - a)
        w += per_batch
        if w + per_batch > end or b - c0 >= seconds:
            return lat, b - c0, w


def drive(run) -> dict:
    tr = run.traffic
    sizes = dbside.lsm_sizes(run.config, run.scale)
    n = sizes["keys"]
    per_batch = run.config["batch_size"]
    # The stream is sized for a rate no run has come near.
    max_puts = int(tr["max_puts_per_s"] * run.seconds)
    max_puts -= max_puts % per_batch
    wl = Workload(n, max_puts, run.seed)
    kb, vb = wl.encode(0, n + max_puts)        # made while the service starts
    run.wait_service()

    stats = dbside.JobStatistics()
    dbdir = os.path.join(run.workdir, "db")
    factory = dbside.TimedFactory(run.svc.url, run.device, sizes["min_input"])
    opts = dbside.options(run.config, sizes, stats, factory)
    db = DB.open(dbdir, opts)
    try:
        dbside.put_batches(db, kb, vb, n, tr["load_puts_per_batch"])
        db.flush()
        db.wait_for_compactions()
        warm_jobs = len(stats.jobs)
        warm = dbside.device_witnesses(stats.jobs, run.device)
        run.facts["notes"] = [
            f"preload: {n} keys, {warm_jobs} jobs, {warm['remote_jobs']} "
            f"remote; compiled in set-up: "
            f"{sum(s.jit_compiles for s in stats.jobs)}; programs first "
            f"met: {_first_met(stats.jobs)}"]

        # ---- the window ------------------------------------------------
        t_before = stats.tickers()
        spans_before = len(factory.spans)
        t0 = run.window_open()
        lat, span, w = write_window(
            db.write, kb, vb, n, n + max_puts, per_batch, run.seconds)
        t_after = stats.tickers()
        t1 = t0 + span
        run.window_close()
        puts = w - n
        run.attempted = puts
        ran_out = dbside.stream_ran_out(w, per_batch, n + max_puts, span,
                                        run.seconds)
        if ran_out:
            run.facts["notes"].append(
                f"the encoded stream of {max_puts} puts ran out after "
                f"{span:.1f}s: raise max_puts_per_s")

        win_jobs = stats.jobs[warm_jobs:]
        run.facts["notes"].append(
            f"window: {puts} puts in {span:.3f}s, last sequence {w}, "
            f"{len(win_jobs)} jobs (rows, compiled, loaded from the cache): "
            f"{[(s.input_records, s.jit_compiles, s.jit_cache_hits) for s in win_jobs if s.remote]}")

        # ---- facts for the readers -------------------------------------
        lat_a = np.asarray(lat)
        in_write = float(lat_a.sum())
        delta = {k: t_after.get(k, 0) - t_before.get(k, 0) for k in TICKERS}
        busy = _covered(factory.spans[spans_before:], t0, t1)
        run.facts.update(
            window_s=span, in_write_s=in_write,
            out_of_write_s=span - in_write,
            window_ops=puts, write_batch_s=lat_a,
            stall_s=delta[st.STALL_MICROS] / 1e6,
            storage_write_bytes=(delta[st.FLUSH_WRITE_BYTES]
                                 + delta[st.COMPACT_WRITE_BYTES]),
            user_write_bytes=delta[st.BYTES_WRITTEN],
            remote_busy_s=busy,
            jit_compiles=sum(s.jit_compiles for s in win_jobs
                             if s.remote))
        run.facts["notes"].append(
            f"window, by layer: {lat_a.sum() / span:.1%} of it in DB.write, "
            f"{delta[st.STALL_MICROS] / 1e6:.2f}s stalled, a remote job in "
            f"flight {busy / span:.1%}, p50/p95/p99 of a batch "
            f"{[round(float(x) * 1e3, 3) for x in np.percentile(lat_a, [50, 95, 99])]} ms")

        # ---- what is compared ------------------------------------------
        last = wl.last_write(w)
        rng = np.random.default_rng([run.seed, 1])
        run.compare("read_mismatches",
                    _read_mismatches(db, wl, last, rng, n, w, tr["checks"]))
        db.wait_for_compactions()
        db.close()
        db = DB.open(dbdir, opts)               # WAL + MANIFEST recovery
        run.compare("reopen_read_mismatches",
                    _read_mismatches(db, wl, last, rng, n, w, tr["checks"]))
        tick = stats.tickers()
        run.compare("fallback_local",
                    tick.get(st.DCOMPACTION_FALLBACK_LOCAL, 0))
        run.compare("remote_job_failures",
                    tick.get(st.DCOMPACTION_JOB_FAILURES, 0)
                    + run.svc.get("/stats")["jobs_failed"] + factory.failed)
        run.compare("jobs_off_device", dbside.device_witnesses(
            stats.jobs, run.device)["jobs_off_device"])
        run.compare("window_without_remote_job",
                    int(not any(s.remote for s in win_jobs)))
        run.compare("stream_ran_out", int(ran_out))
    finally:
        db.close()
    return {"write_ops_s": puts / span}


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


def _read_mismatches(db, wl, last, rng, n, w, checks) -> int:
    """Point gets, one multi_get and forward scans, each answer against
    the oracle; keys drawn from the window's own writes, some never
    written."""
    bad = 0

    def probe(m):
        writes = rng.integers(n, w, m)          # writes of the window
        keys = wl.key_of[writes].copy()
        miss = rng.random(m) < checks["miss_share"]
        keys[miss] += np.uint64(wl.n)           # never written
        return keys

    keys = probe(checks["gets"])
    want = wl.expected(keys, last)
    kb = wl.key_bytes(keys).tobytes()
    for i, x in enumerate(want):
        bad += db.get(kb[8 * i:8 * i + 8]) != x

    keys = probe(checks["multi_get"])
    want = wl.expected(keys, last)
    kb = wl.key_bytes(keys).tobytes()
    got = db.multi_get([kb[8 * i:8 * i + 8] for i in range(len(keys))])
    bad += abs(len(got) - len(want))
    bad += sum(g != x for g, x in zip(got, want))

    rows = min(checks["scan_rows"], wl.n)
    for _ in range(checks["scans"]):
        start = int(rng.integers(0, max(1, wl.n - rows)))
        ks = np.arange(start, start + rows, dtype=np.uint64)
        want_v = wl.expected(ks, last)
        want_k = wl.key_bytes(ks).tobytes()
        it = db.new_iterator()
        it.seek(want_k[0:8])
        for i in range(rows):
            if not it.valid():
                bad += rows - i
                break
            bad += (it.key() != want_k[8 * i:8 * i + 8]
                    or it.value() != want_v[i])
            it.next()
    return int(bad)
