"""Benchmark: L2+ compaction throughput per chip (the BASELINE.json metric).

Workload: fillrandom-style overwrite stream (8B keys, 20B values, 2x
overwrite factor) pre-built into 4 sorted input runs (real SSTs, SNAPPY
compressed — the reference db_bench default the 24.34s manual-compact
baseline ran with), then ONE compaction job — merge + MVCC GC + SST encode
— executed through the device data plane (ops/device_compaction) on the
available chip, end-to-end including SST read and write.

Honest accounting: the metric numerator is RAW USER KV BYTES (8B key +
20B value = 28B/entry), matching the baseline's definition (2.8 GB of user
data / 24.34 s = ~115 MB/s on a 16-core Xeon 8369HB) — NOT file bytes,
which carry trailers/framing and would inflate the ratio ~30%.

Prints ONE JSON line:
  {"metric": "l2_compaction_MBps_per_chip", "value": ..., "unit": "MB/s",
   "vs_baseline": ...}
with `detail` rows: a NO_COMPRESSION + a zstd compaction variant, a
bottommost ZipTable emission run, multi-thread fillrandom (plain vs
unordered+concurrent-memtable) and readrandom ops/s through the full DB
(sustained multi-job flush+compaction sequence), and the DB's write
amplification over that sequence.

Env knobs: BENCH_N (compaction entries, default 10_000_000), BENCH_DB_N
(DB-path entries, default 1_000_000), BENCH_DEVICE (tpu|cpu-jax|cpu),
BENCH_RUNS (timed repetitions, default 3; best kept), BENCH_FAST=1 (skip
the detail variants; headline metric only).
"""

import json
import os
import shutil
import sys
import tempfile
import time

BASELINE_MBPS = 115.0  # reference manual compact: 2.8 GB raw / 24.34 s
RAW_PER_ENTRY = 28     # 8B user key + 20B value (the baseline's accounting)


def fill_phase_detail(detail, stats):
    """phase_breakdown + top_phases from a CompactionStats — NUMERIC values
    only in the sort, excluding the derived overlap row (it is not a busy
    phase; it is sum(phases) - wall under the pipelined data plane)."""
    detail["phase_breakdown"] = stats.phase_dict()
    phases = {k: v for k, v in detail["phase_breakdown"].items()
              if k not in ("work_time_s", "pipeline_overlap_s")
              and isinstance(v, (int, float))}
    detail["top_phases"] = sorted(phases, key=phases.get, reverse=True)[:2]


def build_inputs(env, dbdir, icmp, n_entries, topts, num_runs=4, seed=1234):
    """Vectorized input builder: 8B keys / 20B values, ~2x overwrite
    factor, one sorted run per file, written through the native columnar
    writer (byte-identical to TableBuilder per tests/test_columnar_writer)."""
    import numpy as np

    from toplingdb_tpu.db.dbformat import ValueType
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.ops.columnar_io import ColumnarKV, write_tables_columnar

    rng = np.random.default_rng(seed)
    key_space = max(n_entries // 2, 1)  # ~2x overwrite factor
    per_run = n_entries // num_runs
    metas = []
    counter = [9]

    def alloc():
        counter[0] += 1
        return counter[0]

    for run in range(num_runs):
        n = per_run
        draws = rng.integers(0, key_space, n, dtype=np.int64)
        seqs = np.arange(run * per_run + 1, run * per_run + n + 1,
                         dtype=np.uint64)
        # 8 ASCII decimal digits per key ("%08d"), then the 8B trailer.
        ik = np.empty((n, 16), dtype=np.uint8)
        for j in range(8):
            ik[:, 7 - j] = (draws // 10 ** j) % 10 + ord("0")
        packed = (seqs << np.uint64(8)) | np.uint64(int(ValueType.VALUE))
        ik[:, 8:] = packed[:, None] >> (np.arange(8) * 8).astype(
            np.uint64)[None, :] & np.uint64(0xFF)
        vals = np.full((n, 20), ord("v"), dtype=np.uint8)
        vals[:, 19] = (seqs % 10 + ord("0")).astype(np.uint8)
        # user key asc, seqno desc
        s = np.lexsort((np.iinfo(np.int64).max - seqs.view(np.int64), draws))
        kv = ColumnarKV(
            np.ascontiguousarray(ik[s]).reshape(-1),
            np.arange(n, dtype=np.int32) * 16,
            np.full(n, 16, dtype=np.int32),
            np.ascontiguousarray(vals[s]).reshape(-1),
            np.arange(n, dtype=np.int32) * 20,
            np.full(n, 20, dtype=np.int32),
        )
        files = write_tables_columnar(
            env, dbdir, alloc, icmp, topts, kv,
            np.arange(n, dtype=np.int32),
            np.full(n, -1, dtype=np.int64),
            np.full(n, int(ValueType.VALUE), dtype=np.int32),
            seqs[s], [], creation_time=1,
        )
        for fnum, path, props, smallest, largest, _sel in files:
            metas.append(FileMetaData(
                number=fnum, file_size=env.get_file_size(path),
                smallest=smallest, largest=largest,
                smallest_seqno=props.smallest_seqno,
                largest_seqno=props.largest_seqno,
            ))
    return metas


def time_compaction(env, base, icmp, metas, topts, out_topts, device, runs,
                    alloc_base):
    """Best-of-N wall of one L0->L2 job; returns (dt, stats, input_bytes)."""
    from toplingdb_tpu.compaction.compaction_job import run_compaction_to_tables
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db import filename as fn
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.ops.device_compaction import run_device_compaction

    tc = TableCache(env, base, icmp, topts)
    counter = [alloc_base]

    def alloc():
        counter[0] += 1
        return counter[0]

    best = None
    run_times = []
    for _ in range(runs):
        c = Compaction(
            level=0, output_level=2, inputs=list(metas), bottommost=True,
            max_output_file_size=1 << 62,
        )
        t0 = time.time()
        if device in ("tpu", "cpu-jax"):
            outputs, stats = run_device_compaction(
                env, base, icmp, c, tc, out_topts, [],
                new_file_number=alloc, creation_time=1,
                device_name=device,
            )
        else:
            outputs, stats = run_compaction_to_tables(
                env, base, icmp, c, tc, out_topts, [], new_file_number=alloc,
                creation_time=1,
            )
        dt = time.time() - t0
        run_times.append(round(dt, 3))
        if best is None or dt < best[0]:
            best = (dt, stats)
        for m in outputs:
            env.delete_file(fn.table_file_name(base, m.number))
    return best[0], best[1], sum(m.file_size for m in metas), run_times


def replication_rows(detail):
    """readwhilewriting_replica_ops: router read throughput while a writer
    hammers the primary, reads served by a tailing follower (the
    replication plane's whole point: read fan-out off the primary's write
    path); replication_lag_ms from the ship→apply lag histogram."""
    import random as _r
    import threading

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.replication import (
        FollowerDB, LocalTransport, LogShipper, ReplicaRouter,
    )
    from toplingdb_tpu.utils import statistics as st

    d = tempfile.mkdtemp(prefix="benchrepl_", dir="/dev/shm"
                         if os.path.isdir("/dev/shm") else None)
    stats = st.Statistics()
    db = DB.open(d, Options(create_if_missing=True,
                            write_buffer_size=64 << 20, statistics=stats))
    n_seed = 20_000
    for i in range(0, n_seed, 500):
        b = WriteBatch()
        for j in range(i, i + 500):
            b.put(b"%016d" % j, b"v" * 64)
        db.write(b)
    ship = LogShipper(db)
    fol = FollowerDB.open(d, Options(statistics=stats),
                          transport=LocalTransport(ship), mode="shared")
    fol.start_tailing(interval=0.002)
    router = ReplicaRouter(db, [fol])
    stop = threading.Event()

    def writer():
        i = n_seed
        while not stop.is_set():
            b = WriteBatch()
            for j in range(i, i + 100):
                b.put(b"%016d" % (j % (2 * n_seed)), b"w" * 64)
            router.write(b)
            i += 100

    wt = threading.Thread(target=writer)
    wt.start()
    rng = _r.Random(17)
    t0 = time.time()
    reads = 0
    try:
        while time.time() - t0 < 2.0:
            for _ in range(200):
                router.get(b"%016d" % rng.randrange(n_seed))
            reads += 200
    finally:
        stop.set()
        wt.join()
    dt = time.time() - t0
    detail["readwhilewriting_replica_ops"] = round(reads / dt)
    fr = stats.get_ticker_count(st.ROUTER_FOLLOWER_READS)
    pr = stats.get_ticker_count(st.ROUTER_PRIMARY_READS)
    if fr + pr:
        detail["replica_read_pct"] = round(100 * fr / (fr + pr), 1)
    h = stats.get_histogram(st.REPLICATION_LAG_MICROS)
    if h.count:
        detail["replication_lag_ms"] = round(h.average / 1000, 3)
    fol.close()
    db.close()
    shutil.rmtree(d, ignore_errors=True)


def sharding_rows(detail):
    """1 vs 4 local shards through the ShardRouter: prebuilt per-shard
    WriteBatches pushed by 4 writer threads (the native write plane
    releases the GIL for frame+insert, so independent shard primaries
    genuinely overlap), then readrandom through the router; finally a
    hot-tenant admission check — one rate-limited tenant hammering shard
    s0 while siblings keep writing, sibling throughput must hold."""
    import random as _r
    import threading

    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.sharding import (
        AdmissionController, TenantQuota, open_local_cluster,
    )

    n_keys = 200_000
    vlen = 400
    bs = 250
    T = 4

    def bounds(nsh):
        step = n_keys // nsh
        return [(f"s{i}",
                 None if i == 0 else b"%016d" % (i * step),
                 None if i == nsh - 1 else b"%016d" % ((i + 1) * step))
                for i in range(nsh)]

    def mkbatches(nsh):
        per = n_keys // nsh
        out = []
        for i in range(nsh):
            keys = list(range(i * per, (i + 1) * per))
            _r.Random(i).shuffle(keys)
            out.append([
                _mk_batch(keys[j:j + bs], vlen, WriteBatch)
                for j in range(0, per, bs)
            ])
        return out

    def run(nsh):
        d = tempfile.mkdtemp(prefix=f"benchshard{nsh}_", dir="/dev/shm"
                             if os.path.isdir("/dev/shm") else None)
        # Small memtables so the fill actually flushes + compacts: the
        # scaling story is N independent LSM pipelines, not N memtables.
        router = open_local_cluster(
            d, bounds(nsh),
            options_factory=lambda n: Options(create_if_missing=True,
                                              write_buffer_size=8 << 20))
        batches = mkbatches(nsh)

        def wfill(t):
            if nsh == 1:
                mine, shard = batches[0][t::T], "s0"
            else:
                mine, shard = batches[t % nsh], f"s{t % nsh}"
            for b in mine:
                router.write(b, shard=shard)

        threads = [threading.Thread(target=wfill, args=(t,))
                   for t in range(T)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fill_ops = n_keys / (time.time() - t0)

        stop = threading.Event()
        counts = [0] * T

        def rrd(t):
            rng = _r.Random(100 + t)
            while not stop.is_set():
                for _ in range(100):
                    router.get(b"%016d" % rng.randrange(n_keys))
                counts[t] += 100

        threads = [threading.Thread(target=rrd, args=(t,))
                   for t in range(T)]
        t0 = time.time()
        for t in threads:
            t.start()
        time.sleep(1.2)
        stop.set()
        for t in threads:
            t.join()
        read_ops = sum(counts) / (time.time() - t0)
        router.close()
        shutil.rmtree(d, ignore_errors=True)
        return fill_ops, read_ops

    f1, r1 = run(1)
    f4, r4 = run(4)
    detail["fillrandom_1shard_ops_s"] = round(f1)
    detail["fillrandom_4shard_ops_s"] = round(f4)
    detail["readrandom_1shard_ops_s"] = round(r1)
    detail["readrandom_4shard_ops_s"] = round(r4)
    detail["shard_scaling_x"] = round(f4 / max(1.0, f1), 2)

    # -- hot-tenant isolation: siblings keep their throughput -------------
    # Fair comparison: SAME thread count in both phases (a 4th GIL-bound
    # thread alone costs ~25% in-process, which a multi-process deployment
    # would not see) — the 4th tenant goes from in-quota pacing to
    # flooding, and admission shedding must keep the siblings level.
    # Fresh cluster per phase + interleaved best-of-2 (the integrity_rows
    # pattern) to damp scheduler noise.
    from toplingdb_tpu.utils.status import Busy

    def sibling_phase(flood: bool, dur: float = 1.2):
        d = tempfile.mkdtemp(prefix="benchshardht_", dir="/dev/shm"
                             if os.path.isdir("/dev/shm") else None)
        adm = AdmissionController()
        adm.set_quota("hot", TenantQuota(write_ops_per_sec=500,
                                         max_wait=0.0))
        router = open_local_cluster(
            d, bounds(4), admission=adm,
            options_factory=lambda n: Options(create_if_missing=True,
                                              write_buffer_size=64 << 20))
        stop = threading.Event()
        sib = [0] * 3
        hot = [0, 0]  # served, shed

        def sib_writer(t):
            shard = t + 1  # shards s1..s3
            step = n_keys // 4
            i = shard * step
            while not stop.is_set():
                b = _mk_batch(range(i, i + 100), vlen, WriteBatch,
                              lo=shard * step, hi=(shard + 1) * step)
                router.write(b, shard=f"s{shard}", tenant=f"sib{t}")
                sib[t] += 100
                i += 100

        def hot_writer():
            rng = _r.Random(9)
            while not stop.is_set():
                try:
                    router.put(b"%016d" % rng.randrange(n_keys // 4),
                               b"h" * vlen, tenant="hot")
                    hot[0] += 1
                except Busy:
                    hot[1] += 1
                    time.sleep(0.001)  # client backoff after a shed
                if not flood:
                    time.sleep(1 / 400)  # a well-behaved tenant's pacing

        threads = [threading.Thread(target=sib_writer, args=(t,))
                   for t in range(3)]
        threads.append(threading.Thread(target=hot_writer))
        t0 = time.time()
        for t in threads:
            t.start()
        time.sleep(dur)
        stop.set()
        for t in threads:
            t.join()
        rate = sum(sib) / (time.time() - t0)
        router.close()
        shutil.rmtree(d, ignore_errors=True)
        return rate, hot

    sib_base = sib_loaded = 0.0
    hot = [0, 0]
    for _ in range(2):
        rate, _h = sibling_phase(flood=False)
        sib_base = max(sib_base, rate)
        rate, h = sibling_phase(flood=True)
        if rate > sib_loaded:
            sib_loaded, hot = rate, h
    detail["sibling_base_ops_s"] = round(sib_base)
    detail["sibling_with_hot_ops_s"] = round(sib_loaded)
    detail["sibling_keep_pct"] = round(100 * sib_loaded
                                       / max(1.0, sib_base), 1)
    detail["hot_tenant_served_ops"] = hot[0]
    detail["hot_tenant_shed_ops"] = hot[1]


_FLEET_DRIVER = """
import random
import sys
import time

from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.sharding.fleet import FleetRouter
from toplingdb_tpu.sharding.lease import LeaseClient

co_url, shard = sys.argv[1], sys.argv[2]
lo, hi, bs, vlen, seed = (int(a) for a in sys.argv[3:8])
keys = list(range(lo, hi))
random.Random(seed).shuffle(keys)
v = b"s" * vlen
batches = []
for j in range(0, len(keys), bs):
    b = WriteBatch()
    for k in keys[j:j + bs]:
        b.put(b"%016d" % k, v)
    batches.append(b)
router = FleetRouter(LeaseClient(co_url), map_lease=60.0)
print("READY", flush=True)   # batches prebuilt; wait for the gun
sys.stdin.readline()
for b in batches:
    router.write(b, shard=shard)
"""


def fleet_rows(detail):
    """1-process vs 4-process out-of-process fleet fillrandom: a real
    lease-coordinator process plus one ShardServer process per shard,
    prebuilt per-shard WriteBatches pushed over HTTP through the
    FleetRouter by 4 driver PROCESSES (one client process cannot feed
    4 servers — its GIL becomes the bottleneck and the measurement
    flattens). Everything here genuinely overlaps across cores, so the
    4-process fleet must sustain at least the in-process plane's
    shard_scaling_x despite paying the HTTP hop."""
    import subprocess

    from toplingdb_tpu.sharding.fleet import FleetSupervisor
    from toplingdb_tpu.sharding.shard_map import ShardMap

    n_keys = 100_000
    vlen = 400
    bs = 250
    T = 4

    def bounds(nsh):
        step = n_keys // nsh
        return [(f"s{i}",
                 None if i == 0 else b"%016d" % (i * step),
                 None if i == nsh - 1 else b"%016d" % ((i + 1) * step))
                for i in range(nsh)]

    def run(nsh):
        d = tempfile.mkdtemp(prefix=f"benchfleet{nsh}_", dir="/dev/shm"
                             if os.path.isdir("/dev/shm") else None)
        co_proc, co_url = FleetSupervisor.start_coordinator(
            os.path.join(d, "lease.jsonl"), ttl=30.0)
        sup = FleetSupervisor(co_url, lease_ttl=30.0)
        drivers = []
        try:
            sup.coordinator.install_map(
                ShardMap.from_bounds(bounds(nsh)).to_config(), {})
            members = [sup.spawn_server(f"s{i}", os.path.join(d, f"s{i}"))
                       for i in range(nsh)]
            doc = sup.coordinator.get_map()
            sup.coordinator.cas_map(doc["version"], doc["map"],
                                    {m.shard: m.url for m in members})
            # One driver process per writer: disjoint key slices, each
            # slice entirely inside one shard's range.
            per = n_keys // T
            step = n_keys // max(nsh, 1)
            for t in range(T):
                if nsh == 1:
                    shard, lo, hi = "s0", t * per, (t + 1) * per
                else:
                    i = t % nsh
                    shard, lo, hi = f"s{i}", i * step, (i + 1) * step
                drivers.append(subprocess.Popen(
                    [sys.executable, "-c", _FLEET_DRIVER, co_url, shard,
                     str(lo), str(hi), str(bs), str(vlen), str(t)],
                    env=FleetSupervisor._proc_env(),
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE))
            for p in drivers:  # all batches built before the clock starts
                assert p.stdout.readline().strip() == b"READY"
            t0 = time.time()
            for p in drivers:
                p.stdin.write(b"\n")
                p.stdin.flush()
            for p in drivers:
                if p.wait() != 0:
                    raise RuntimeError("fleet fill driver failed")
            return n_keys / (time.time() - t0)
        finally:
            for p in drivers:
                if p.poll() is None:
                    p.kill()
                    p.wait()
            sup.stop_all()
            co_proc.terminate()
            try:
                co_proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 - bench teardown
                co_proc.kill()
                co_proc.wait()
            shutil.rmtree(d, ignore_errors=True)

    f1 = run(1)
    f4 = run(4)
    detail["fleet_fill_1proc_ops_s"] = round(f1)
    detail["fleet_fill_4proc_ops_s"] = round(f4)
    detail["fleet_scaling_x"] = round(f4 / max(1.0, f1), 2)


def _mk_batch(keys, vlen, WriteBatch, lo=None, hi=None):
    b = WriteBatch()
    v = b"s" * vlen
    for k in keys:
        if hi is not None:
            k = lo + (k - lo) % (hi - lo)
        b.put(b"%016d" % k, v)
    return b


def integrity_rows(detail, n_db):
    """Integrity-plane rows: protected fillrandom (per-entry protection
    computed at WriteBatch build + fused re-verify at memtable insert)
    vs an unprotected twin, and the scrubber's sweep throughput over the
    protected DB's SSTs. Plain/protected runs are INTERLEAVED and the
    best of each kept — the overhead row divides two measurements, so
    machine drift between them would otherwise read as fake overhead."""
    import threading

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options

    n = max(50_000, min(200_000, n_db // 5))
    n_threads = int(os.environ.get("BENCH_THREADS", "4"))
    per_thread = n // n_threads
    batch = 100

    def fill(pb):
        d = tempfile.mkdtemp(prefix="benchint_", dir="/dev/shm"
                             if os.path.isdir("/dev/shm") else None)
        db = DB.open(d, Options(create_if_missing=True,
                                write_buffer_size=8 << 20,
                                protection_bytes_per_key=pb,
                                integrity_scrub_bytes_per_sec=0))
        errs = []

        def worker(t):
            try:
                for i in range(0, per_thread, batch):
                    b = WriteBatch()
                    for j in range(i, i + batch):
                        k = (t * per_thread + j) * 2654435761 % (n * 2)
                        b.put(b"%016d" % k, b"v" * 20)
                    db.write(b)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(n_threads)]
        t0 = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.time() - t0
        assert not errs, errs
        return db, d, n / dt

    best_plain = best_prot = 0.0
    scrub_db = scrub_dir = None
    for _ in range(3):
        db, d, rate = fill(0)
        best_plain = max(best_plain, rate)
        db.close()
        shutil.rmtree(d, ignore_errors=True)
        db, d, rate = fill(8)
        best_prot = max(best_prot, rate)
        if scrub_db is not None:
            scrub_db.close()
            shutil.rmtree(scrub_dir, ignore_errors=True)
        scrub_db, scrub_dir = db, d

    user_bytes_per_entry = 36  # 16B key + 20B value (this row's workload)
    detail["fillrandom_protected_MBps"] = round(
        best_prot * user_bytes_per_entry / 1e6, 2)
    detail["fillrandom_plain_twin_MBps"] = round(
        best_plain * user_bytes_per_entry / 1e6, 2)
    detail["protection_overhead_pct"] = round(
        100 * (1 - best_prot / best_plain), 1)

    # Scrubber sweep rate: every live SST re-read from disk and its
    # whole-file checksum compared against the MANIFEST — the background
    # pass's work, unpaced (the default 32 MiB/s token bucket would
    # measure the pacer, not the scrubber).
    scrub_db.flush()
    scrub_db.wait_for_compactions()
    rep = scrub_db.scrub()
    if rep.get("bytes_verified") and rep.get("pass_micros"):
        detail["integrity_scrub_MBps"] = round(
            rep["bytes_verified"] / rep["pass_micros"], 2)
    detail["integrity_scrub_corruptions"] = len(rep.get("corruptions", ()))
    scrub_db.close()
    shutil.rmtree(scrub_dir, ignore_errors=True)


def observability_rows(detail, n_db):
    """Telemetry-plane overhead rows: fillrandom/readrandom twins with
    tracing off / sampled 1-in-64 / always-on. All three modes run as
    fine-grained INTERLEAVED segments on the SAME DB instance (separate
    twin DBs drift by several percent from layout/compaction timing
    alone, which would swamp a ~1% effect); Statistics is attached —
    the repo-served rockside-role DB this plane exists for always
    carries a stats sink, so that is the measured baseline. Gate:
    sampled <= 2% (`trace_overhead_pct`)."""
    import itertools as _it

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils import telemetry as _tm
    from toplingdb_tpu.utils.statistics import Statistics

    n = max(60_000, min(240_000, n_db // 5))
    batch = 100
    seg = 3000  # ops per timed segment before rotating modes
    keys = [b"%016d" % ((i * 2654435761) % (n * 2)) for i in range(n)]

    d = tempfile.mkdtemp(prefix="benchobs_", dir="/dev/shm"
                         if os.path.isdir("/dev/shm") else None)
    db = DB.open(d, Options(create_if_missing=True,
                            write_buffer_size=1 << 30,
                            statistics=Statistics()))

    def make_state(se):
        if se == 0:
            return (None, None)
        tr = _tm.Tracer(sample_every=se)
        return (tr, _it.cycle([0] * (se - 1) + [1]).__next__)

    import gc

    modes = ("off", "sampled", "always")
    states = {"off": make_state(0), "sampled": make_state(64),
              "always": make_state(1)}
    spent = {m: [0.0, 0] for m in modes}  # wall, ops (fill)
    rspent = {m: [0.0, 0] for m in modes}  # wall, ops (read)

    def set_mode(m):
        # Collect OUTSIDE the timed region so one mode's allocation debt
        # (always-on churns a trace per op) never bills a neighbor.
        gc.collect(0)
        db.tracer, db._trace_sched = states[m]

    def fill_seg(m, s0, hi):
        set_mode(m)
        t0 = time.perf_counter()
        for i in range(s0, hi, batch):
            b = WriteBatch()
            for k in keys[i:i + batch]:
                b.put(k, b"v" * 20)
            db.write(b)
        spent[m][0] += time.perf_counter() - t0
        spent[m][1] += hi - s0

    def read_seg(m, s0, hi):
        set_mode(m)
        t0 = time.perf_counter()
        for i in range(s0, hi):
            db.get(keys[(i * 7919) % n])
        rspent[m][0] += time.perf_counter() - t0
        rspent[m][1] += hi - s0

    try:
        # The GATED pair (off vs sampled) alternates in balanced A/B
        # order on one DB; always-on — informational, and heavy enough
        # to pollute neighbors — runs as its own tail slice.
        n_ab = n * 3 // 4
        for idx, s0 in enumerate(range(0, n_ab, seg)):
            fill_seg(("off", "sampled")[(idx + idx // 2) % 2],
                     s0, min(s0 + seg, n_ab))
        for s0 in range(n_ab, n, seg):
            fill_seg("always", s0, min(s0 + seg, n))
        # readrandom reads SST-resident data (the workload's normal
        # shape): flush so gets walk bloom + table, not just memtable.
        set_mode("off")
        db.flush()
        db.wait_for_compactions()
        nr = min(2 * n, 300_000)
        for i in range(0, nr, seg):
            db.get(keys[(i * 7919) % n])  # keep caches warm at rotation
        nr_ab = nr * 3 // 4
        for idx, s0 in enumerate(range(0, nr_ab, seg)):
            read_seg(("off", "sampled")[(idx + idx // 2) % 2],
                     s0, min(s0 + seg, nr_ab))
        for s0 in range(nr_ab, nr, seg):
            read_seg("always", s0, min(s0 + seg, nr))
    finally:
        db.tracer = None
        db._trace_sched = None
        db.close()
        shutil.rmtree(d, ignore_errors=True)

    for m in modes:
        detail[f"fillrandom_trace_{m}_ops_s"] = round(
            spent[m][1] / spent[m][0])
        detail[f"readrandom_trace_{m}_ops_s"] = round(
            rspent[m][1] / rspent[m][0])
    overhead = max(
        100 * (1 - detail["fillrandom_trace_sampled_ops_s"]
               / detail["fillrandom_trace_off_ops_s"]),
        100 * (1 - detail["readrandom_trace_sampled_ops_s"]
               / detail["readrandom_trace_off_ops_s"]),
    )
    detail["trace_overhead_pct"] = round(max(0.0, overhead), 2)


def health_rows(detail, n_db):
    """Health-plane overhead rows (ISSUE 12): fillrandom/readrandom with
    cumulative-only histograms vs windowed histograms + a live SLO
    engine, as interleaved A/B segments on the SAME DB (the
    observability_rows pattern — twin DBs drift more than the effect
    measured). The 'win' mode over-counts SLO cost on purpose: one full
    evaluation per ~3000-op segment, far more frequent than any real
    slo_eval_period_sec. Gate: `health_overhead_pct` <= 2, computed as
    the median win/cum rate ratio over adjacent segment pairs (robust to
    background-compaction spikes that whipsaw an aggregate mean)."""
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils import statistics as _st
    from toplingdb_tpu.utils.slo import SLOEngine, SLOSpec
    from toplingdb_tpu.utils.statistics import Statistics

    n = max(60_000, min(240_000, n_db // 5))
    batch = 100
    seg = 3000
    segs = {"fill": [], "read": []}  # (mode, ops_per_sec) per segment
    keys = [b"%016d" % ((i * 2654435761) % (n * 2)) for i in range(n)]

    d = tempfile.mkdtemp(prefix="benchhp_", dir="/dev/shm"
                         if os.path.isdir("/dev/shm") else None)
    cum = Statistics(histogram_window_sec=0)
    win = Statistics(histogram_window_sec=60.0)
    engine = SLOEngine(win, [
        SLOSpec(name="get-p99", kind="latency",
                histogram=_st.DB_GET_MICROS, objective=0.99,
                threshold_usec=10_000),
        SLOSpec(name="write-p99", kind="latency",
                histogram=_st.DB_WRITE_MICROS, objective=0.99,
                threshold_usec=50_000),
        SLOSpec(name="stall", kind="stall", objective=0.999),
    ], db_name="bench")
    # Opened with the cumulative sink; the windowed twin swaps in per
    # segment (every hot-path histogram add resolves through db.stats).
    db = DB.open(d, Options(create_if_missing=True,
                            write_buffer_size=1 << 30, statistics=cum))
    import gc

    modes = ("cum", "win")
    sinks = {"cum": cum, "win": win}
    spent = {m: [0.0, 0] for m in modes}   # wall, ops (fill)
    rspent = {m: [0.0, 0] for m in modes}  # wall, ops (read)

    def set_mode(m):
        gc.collect(0)
        db.stats = sinks[m]

    def fill_seg(m, s0, hi):
        set_mode(m)
        t0 = time.perf_counter()
        for i in range(s0, hi, batch):
            b = WriteBatch()
            for k in keys[i:i + batch]:
                b.put(k, b"v" * 20)
            db.write(b)
        if m == "win":
            engine.evaluate()
        dt = time.perf_counter() - t0
        spent[m][0] += dt
        spent[m][1] += hi - s0
        segs["fill"].append((m, (hi - s0) / dt))

    def read_seg(m, s0, hi):
        set_mode(m)
        t0 = time.perf_counter()
        for i in range(s0, hi):
            db.get(keys[(i * 7919) % n])
        if m == "win":
            engine.evaluate()
        dt = time.perf_counter() - t0
        rspent[m][0] += dt
        rspent[m][1] += hi - s0
        segs["read"].append((m, (hi - s0) / dt))

    try:
        for idx, s0 in enumerate(range(0, n, seg)):
            fill_seg(("cum", "win")[(idx + idx // 2) % 2],
                     s0, min(s0 + seg, n))
        set_mode("cum")
        db.flush()
        db.wait_for_compactions()
        nr = min(2 * n, 300_000)
        for i in range(0, nr, seg):
            db.get(keys[(i * 7919) % n])  # warm caches at rotation
        for idx, s0 in enumerate(range(0, nr, seg)):
            read_seg(("cum", "win")[(idx + idx // 2) % 2],
                     s0, min(s0 + seg, nr))
    finally:
        db.stats = cum
        db.close()
        shutil.rmtree(d, ignore_errors=True)

    for m in modes:
        detail[f"fillrandom_hist_{m}_ops_s"] = round(
            spent[m][1] / spent[m][0])
        detail[f"readrandom_hist_{m}_ops_s"] = round(
            rspent[m][1] / rspent[m][0])

    def paired_overhead(rows):
        # The interleave pattern is cum,win,win,cum,... — every adjacent
        # pair holds one segment of each mode, in alternating order, so
        # the per-pair win/cum rate ratio cancels slow drift (compaction
        # debt) and the MEDIAN over pairs shrugs off the occasional
        # background-compaction spike that dominates an aggregate mean.
        ratios = []
        for (ma, ra), (mb, rb) in zip(rows[::2], rows[1::2]):
            if ma == mb:
                continue
            w, c = (ra, rb) if ma == "win" else (rb, ra)
            ratios.append(w / c)
        if not ratios:
            return 0.0
        ratios.sort()
        return 100 * (1 - ratios[len(ratios) // 2])

    overhead = max(paired_overhead(segs["fill"]),
                   paired_overhead(segs["read"]))
    detail["health_overhead_pct"] = round(max(0.0, overhead), 2)


def concurrency_rows(detail, n_db):
    """Concurrency-plane overhead rows (ISSUE 13).

    `lock_factory_overhead_pct`: off-mode `ccy.Lock(name)` hands back a
    PLAIN threading.Lock, so an acquire/release spin through it must
    price identically to a raw lock — best-of interleaved reps, gate
    <= 1%.

    `lock_debug_overhead_pct`: fillrandom with every DB lock created as
    an instrumented debug wrapper vs a plain twin. Lock mode is fixed at
    creation time, so this is a twin-DB A/B: the same key segments run
    on both DBs in alternating order and the MEDIAN per-segment rate
    ratio sets the row (the health_rows drift argument). Reported as
    slowdown-minus-one percent; gate <= 100 (debug stays within 2x).
    The debug twin doubles as a soak: a lock inversion anywhere on the
    write path would raise out of this row."""
    import threading

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils import concurrency as ccy

    # -- factory microbench (off mode) -----------------------------------
    raw = threading.Lock()
    fac = ccy.Lock("bench.concurrency_rows.fac")
    spins = 200_000

    def spin(lk):
        t0 = time.perf_counter()
        for _ in range(spins):
            with lk:
                pass
        return time.perf_counter() - t0

    best = {"raw": float("inf"), "fac": float("inf")}
    for rep in range(7):
        order = (("raw", raw), ("fac", fac)) if rep % 2 == 0 \
            else (("fac", fac), ("raw", raw))
        for name, lk in order:
            best[name] = min(best[name], spin(lk))
    detail["lock_factory_overhead_pct"] = round(
        max(0.0, 100.0 * (best["fac"] / best["raw"] - 1.0)), 2)

    # -- debug-wrapper fillrandom A/B (twin DBs) --------------------------
    n = max(40_000, min(120_000, n_db // 10))
    seg = 2000
    batch = 100
    keys = [b"%016d" % ((i * 2654435761) % (n * 2)) for i in range(n)]

    ccy.reset_lock_graph()
    dbs = {}
    try:
        for mode in ("off", "dbg"):
            d = tempfile.mkdtemp(prefix=f"benchccy_{mode}_",
                                 dir="/dev/shm"
                                 if os.path.isdir("/dev/shm") else None)
            ccy.set_debug(mode == "dbg")
            try:
                dbs[mode] = (DB.open(d, Options(create_if_missing=True,
                                                write_buffer_size=1 << 30)),
                             d)
            finally:
                ccy.set_debug(False)

        spent = {m: [0.0, 0] for m in ("off", "dbg")}
        ratios = []

        def fill_seg(mode, s0, hi):
            db = dbs[mode][0]
            t0 = time.perf_counter()
            for i in range(s0, hi, batch):
                b = WriteBatch()
                for k in keys[i:i + batch]:
                    b.put(k, b"v" * 20)
                db.write(b)
            dt = time.perf_counter() - t0
            spent[mode][0] += dt
            spent[mode][1] += hi - s0
            return (hi - s0) / dt

        for idx, s0 in enumerate(range(0, n, seg)):
            hi = min(s0 + seg, n)
            order = ("off", "dbg") if idx % 2 == 0 else ("dbg", "off")
            rates = {m: fill_seg(m, s0, hi) for m in order}
            ratios.append(rates["dbg"] / rates["off"])

        for m in ("off", "dbg"):
            detail[f"fillrandom_lock_{m}_ops_s"] = round(
                spent[m][1] / spent[m][0])
        ratios.sort()
        median = ratios[len(ratios) // 2]
        detail["lock_debug_overhead_pct"] = round(
            max(0.0, 100.0 * (1.0 / median - 1.0)), 2)
        detail["lock_debug_edges"] = len(ccy.lock_order_edges())
    finally:
        for db, d in dbs.values():
            try:
                db.close()
            finally:
                shutil.rmtree(d, ignore_errors=True)
        ccy.set_debug(False)
        ccy.reset_lock_graph()


def disk_pressure_rows(detail, n_db):
    """Storage-pressure plane overhead (ISSUE 20): fillrandom with the
    whole plane armed — a byte budget, the flush/compaction preflight
    math that budget enables, per-file manager accounting on every
    install/delete, and a HOT free-space poller (20ms cadence, far
    faster than any real deployment) — vs the plain twin with no
    manager at all. Interleaved best-of so drift can't read as
    overhead. Gate: `disk_pressure_overhead_pct` <= 1."""
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options

    n = max(60_000, min(200_000, n_db // 2))
    keys = [b"%016d" % ((i * 2654435761) % (n * 2)) for i in range(n)]

    def fill(armed):
        opts = Options(create_if_missing=True, write_buffer_size=1 << 22,
                       level0_file_num_compaction_trigger=4)
        if armed:
            opts.max_allowed_space_usage = 1 << 40  # never binds
            opts.free_space_poll_period_sec = 0.02
        d = tempfile.mkdtemp(prefix="benchdp_", dir="/dev/shm"
                             if os.path.isdir("/dev/shm") else None)
        db = DB.open(d, opts)
        try:
            t0 = time.perf_counter()
            for i in range(0, n, 100):
                b = WriteBatch()
                for k in keys[i:i + 100]:
                    b.put(k, b"v" * 20)
                db.write(b)
            dt = time.perf_counter() - t0
            if armed:
                assert db._sfm is not None and db.disk_pressure() == "ok"
        finally:
            db.close()
            shutil.rmtree(d, ignore_errors=True)
        return n / dt

    best = {"on": 0.0, "off": 0.0}
    for r in range(3):
        for mode in (("on", "off"), ("off", "on"))[r % 2]:
            best[mode] = max(best[mode], fill(mode == "on"))
    detail["fillrandom_disk_pressure_ops_s"] = round(best["on"])
    detail["fillrandom_disk_plain_ops_s"] = round(best["off"])
    detail["disk_pressure_overhead_pct"] = round(
        max(0.0, 100 * (1 - best["on"] / best["off"])), 2)


def write_plane_rows(detail, n_db):
    """Native group-commit write plane rows (ISSUE 7): protected WAL-on
    write-PATH fillrandom (prebuilt mixed-size batches so the row
    isolates queue + WAL + protection + memtable insert) with
    TPULSM_WRITE_PLANE=1 vs the =0 serial twin; a coalesced-fsync sync
    row (async WAL writer merging concurrent leaders' fsync barriers)
    vs inline-fsync; and an 8-writer concurrent run with its twin.
    Runs are interleaved best-of like integrity_rows: the headline
    divides two measurements, so drift must not read as speedup."""
    import threading

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options, WriteOptions

    n = max(100_000, min(1_000_000, n_db))

    def fill(knob, nt, sync=False, async_wal=False, batch_sizes=(100, 1000),
             on_disk=False, pipelined=False):
        saved = os.environ.get("TPULSM_WRITE_PLANE")
        os.environ["TPULSM_WRITE_PLANE"] = knob
        try:
            per = n // nt
            allb = []
            for t in range(nt):
                bs, i, si = [], 0, 0
                while i < per:
                    bsz = min(batch_sizes[si % len(batch_sizes)], per - i)
                    si += 1
                    b = WriteBatch(protection_bytes_per_key=8)
                    for j in range(i, i + bsz):
                        k = ((t * per + j) * 2654435761) % (n * 2)
                        b.put(b"%016d" % k, b"v" * 20)
                    bs.append(b)
                    i += bsz
                allb.append(bs)
            # Sync rows run on REAL disk (fsync on tmpfs is a no-op, which
            # would measure nothing); throughput rows stay on /dev/shm.
            d = tempfile.mkdtemp(prefix="benchwp_", dir=None if on_disk else (
                "/dev/shm" if os.path.isdir("/dev/shm") else None))
            db = DB.open(d, Options(create_if_missing=True,
                                    write_buffer_size=1 << 30,
                                    protection_bytes_per_key=8,
                                    enable_async_wal=async_wal,
                                    enable_pipelined_write=pipelined))
            wo = WriteOptions(sync=sync)
            errs = []

            def w(bs):
                try:
                    for b in bs:
                        db.write(b, wo)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=w, args=(bs,)) for bs in allb]
            t0 = time.time()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            dt = time.time() - t0
            assert not errs, errs
            db.close()
            shutil.rmtree(d, ignore_errors=True)
            return (nt * per) / dt
        finally:
            if saved is None:
                os.environ.pop("TPULSM_WRITE_PLANE", None)
            else:
                os.environ["TPULSM_WRITE_PLANE"] = saved

    rows = {
        "fillrandom_native_plane_ops_s": lambda: fill("1", 4),
        "fillrandom_plane_off_ops_s": lambda: fill("0", 4),
        "fillrandom_8w_ops_s": lambda: fill("1", 8),
        "fillrandom_8w_plane_off_ops_s": lambda: fill("0", 8),
    }
    best = {k: 0.0 for k in rows}
    for _ in range(3):
        for k, f in rows.items():
            best[k] = max(best[k], f())
    for k, v in best.items():
        detail[k] = round(v)

    # Sync rows at reduced scale (each group pays durability): coalesced
    # fsyncs through the async WAL writer vs inline per-group fsync.
    saved_n = n
    n = max(2_000, saved_n // 50)  # fill() closes over n
    # Pipelined: the durability barrier waits OUTSIDE the commit mutex, so
    # concurrent leaders' sync tokens overlap in the ring and coalesce.
    sync_rows = {
        "fillrandom_sync_ops_s": lambda: fill(
            "1", 4, sync=True, async_wal=True, on_disk=True,
            pipelined=True),
        "fillrandom_sync_inline_ops_s": lambda: fill(
            "1", 4, sync=True, async_wal=False, on_disk=True,
            pipelined=True),
    }
    sbest = {k: 0.0 for k in sync_rows}
    for _ in range(2):
        for k, f in sync_rows.items():
            sbest[k] = max(sbest[k], f())
    for k, v in sbest.items():
        detail[k] = round(v)
    n = saved_n


def async_read_rows(detail):
    """Cold-cache multireadrandom: batched block fan-out through the
    reader rings (TPULSM_ASYNC_READS=1) vs the serial sync twin (=0).

    Cold means tiny block cache + fresh file handles (the DB is
    reopened per run). Both twins run on a DelayedReadEnv modeling
    device read latency: on a page-cache-warm box a real pread is ~µs,
    so there is nothing to overlap — and the wrapped handles also keep
    both twins off the native fast chains (same Python walk), so the
    0/1 ratio isolates ring fan-out + coalescing, nothing else.
    Byte parity across the twins is asserted every run. Interleaved
    best-of, like write_plane_rows: the headline divides two
    measurements, so drift must not read as speedup."""
    import random as _r

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.env.fault_injection import DelayedReadEnv
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils.cache import LRUCache

    n = 30_000
    d = tempfile.mkdtemp(prefix="benchar_", dir="/dev/shm"
                         if os.path.isdir("/dev/shm") else None)
    db = DB.open(d, Options(create_if_missing=True,
                            write_buffer_size=128 * 1024))
    for i in range(n):
        db.put(b"%016d" % ((i * 2654435761) % (n * 2)), b"value-%016d" % i)
    db.flush()
    db.wait_for_compactions()
    db.close()
    rng = _r.Random(11)
    probes = [b"%016d" % ((rng.randrange(n) * 2654435761) % (n * 2))
              for _ in range(4096)]

    def run(knob):
        saved = os.environ.get("TPULSM_ASYNC_READS")
        os.environ["TPULSM_ASYNC_READS"] = knob
        try:
            env = DelayedReadEnv(default_env(), delay_sec=0.0002)
            dbr = DB.open(d, Options(block_cache=LRUCache(64 * 1024)),
                          env=env)
            t0 = time.time()
            out = [dbr.multi_get(probes[i:i + 128])
                   for i in range(0, len(probes), 128)]
            dt = time.time() - t0
            dbr.close()
            return len(probes) / dt, out
        finally:
            if saved is None:
                os.environ.pop("TPULSM_ASYNC_READS", None)
            else:
                os.environ["TPULSM_ASYNC_READS"] = saved

    best = {"1": 0.0, "0": 0.0}
    view = {}
    for _ in range(3):
        for knob in ("1", "0"):
            r, out = run(knob)
            best[knob] = max(best[knob], r)
            if knob in view:
                assert out == view[knob], "async/sync drift across runs"
            view[knob] = out
    assert view["1"] == view["0"], "async read plane parity violation"
    detail["multireadrandom_cold_ops_s"] = round(best["1"])
    detail["multireadrandom_cold_sync_ops_s"] = round(best["0"])
    detail["async_read_speedup_x"] = round(best["1"] / max(1.0, best["0"]),
                                           2)
    detail["async_read_delay_model_us"] = 200
    if os.cpu_count() == 1:
        # One core executes the ring threads serially: report the twin
        # ratio with its provenance instead of a hollow multi-core claim.
        detail["async_read_speedup_source"] = "1-core-host"
    shutil.rmtree(d, ignore_errors=True)


def storage_rows(detail):
    """Disaggregated SST storage (storage/): shard-migration wall-clock
    copy vs reference at 2 shard sizes, dcompact bytes shipped in store
    mode, and cold reads through the cache tier.

    The migration destination lives on a DIFFERENT filesystem than the
    source (/dev/shm vs disk) so the copy baseline pays real byte
    movement — same-fs restores hardlink, which would understate what a
    cross-node bootstrap costs. Reference mode swaps manifests + refs
    regardless of filesystem, so its wall-clock should be ~flat in
    shard size; migration_ref_speedup_x is the large-size copy/ref
    ratio."""
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.sharding import ShardMigration, open_local_cluster

    shm = "/dev/shm" if os.path.isdir("/dev/shm") else None
    vlen = 400

    def migrate(n_keys, shared):
        src_root = tempfile.mkdtemp(prefix="benchstore_", dir=shm)
        dest_root = tempfile.mkdtemp(prefix="benchstore_dst_",
                                     dir="/var/tmp")
        spec = os.path.join(src_root, "store") if shared else None

        def of(_name):
            return Options(create_if_missing=True,
                           write_buffer_size=1 << 20, shared_store=spec)

        r = open_local_cluster(src_root, [("s", None, None)],
                               options_factory=of)
        try:
            db = r._serving("s").primary
            v = b"s" * vlen
            for lo in range(0, n_keys, 1000):
                b = WriteBatch()
                for i in range(lo, min(lo + 1000, n_keys)):
                    b.put(b"%012d" % i, v)
                db.write(b)
            db.flush()
            db.compact_range()
            t0 = time.time()
            ShardMigration(r, "s", os.path.join(dest_root, "new")).run()
            return time.time() - t0
        finally:
            r.close()
            shutil.rmtree(src_root, ignore_errors=True)
            shutil.rmtree(dest_root, ignore_errors=True)

    small, large = 25_000, 100_000
    copy_s = migrate(small, shared=False)
    copy_l = migrate(large, shared=False)
    ref_s = migrate(small, shared=True)
    ref_l = migrate(large, shared=True)
    detail["migration_copy_small_s"] = round(copy_s, 3)
    detail["migration_copy_large_s"] = round(copy_l, 3)
    detail["migration_ref_small_s"] = round(ref_s, 3)
    detail["migration_ref_large_s"] = round(ref_l, 3)
    # ~1.0 when reference bootstrap is truly metadata-only.
    detail["migration_ref_flatness_x"] = round(ref_l / max(1e-6, ref_s), 2)
    detail["migration_ref_speedup_x"] = round(copy_l / max(1e-6, ref_l), 2)

    # -- dcompact store mode: zero SST bytes on the job transport ------
    from toplingdb_tpu.compaction.executor import (
        SubprocessCompactionExecutorFactory,
    )

    d = tempfile.mkdtemp(prefix="benchstore_dc_", dir=shm)
    shipped = []

    class Recording(SubprocessCompactionExecutorFactory):
        def new_executor(self, compaction):
            ex = super().new_executor(compaction)
            orig = ex.execute

            def execute(db, compaction, snapshots, new_file_number):
                outputs, stats = orig(db, compaction, snapshots,
                                      new_file_number)
                shipped.append(stats.sst_bytes_shipped)
                return outputs, stats

            ex.execute = execute
            return ex

    opts = Options(create_if_missing=True, write_buffer_size=256 << 10,
                   shared_store=os.path.join(d, "store"),
                   compaction_executor_factory=Recording(
                       device="cpu", job_root=os.path.join(d, "jobs")))
    db = DB.open(os.path.join(d, "db"), opts)
    try:
        v = b"s" * vlen
        for lo in (0, 4000):
            b = WriteBatch()
            for i in range(lo, lo + 4000):
                b.put(b"%012d" % i, v)
            db.write(b)
            db.flush()
        db.compact_range()
        db.wait_for_compactions()
        detail["dcompact_store_jobs"] = len(shipped)
        detail["dcompact_store_sst_bytes_shipped"] = sum(shipped)

        # -- cold reads through the cache tier -------------------------
        # A reference-restored twin of the DB: every table is a store
        # ref, so the first touch is a cold fetch (tier miss -> store),
        # after which reads run on local bytes.
        from toplingdb_tpu.utilities.checkpoint import Checkpoint

        ck = os.path.join(d, "ckpt")
        Checkpoint.create(db, ck)
        cold_dir = os.path.join(d, "cold")
        Checkpoint(ck, db.env).restore_to(cold_dir)
        db2 = DB.open(cold_dir, Options(create_if_missing=False),
                      env=db.env)
        try:
            import random as _r

            rng = _r.Random(7)
            keys = [b"%012d" % rng.randrange(8000) for _ in range(20_000)]
            t0 = time.time()
            for k in keys:
                assert db2.get(k) is not None
            detail["store_cold_read_ops_s"] = round(
                len(keys) / (time.time() - t0))
        finally:
            db2.close()
    finally:
        db.close()
        shutil.rmtree(d, ignore_errors=True)


def db_path_rows(detail, n_db):
    """Sustained multi-job DB rows: multi-thread fillrandom (plain vs
    unordered+concurrent), readrandom, write amplification."""
    import threading

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils import statistics as st

    n_threads = int(os.environ.get("BENCH_THREADS", "4"))
    per_thread = n_db // n_threads
    batch = 100

    def fill(opts_kw):
        d = tempfile.mkdtemp(prefix="benchdb_", dir="/dev/shm"
                             if os.path.isdir("/dev/shm") else None)
        stats = st.Statistics()
        opts = Options(create_if_missing=True,
                       write_buffer_size=8 << 20,
                       statistics=stats, **opts_kw)
        db = DB.open(d, opts)
        errs = []

        def worker(t):
            try:
                for i in range(0, per_thread, batch):
                    b = WriteBatch()
                    for j in range(i, i + batch):
                        k = (t * per_thread + j) * 2654435761 % (n_db * 2)
                        b.put(b"%016d" % k, b"v" * 20)
                    db.write(b)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(n_threads)]
        t0 = time.time()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.time() - t0
        assert not errs, errs
        return db, d, dt

    # plain group commit
    db, d, dt = fill({})
    detail["fillrandom_ops_s"] = round(n_threads * per_thread / dt)
    db.close()
    shutil.rmtree(d, ignore_errors=True)

    # CSPP-role trie memtable (reference README.md:50's headline rep)
    db2, d2, dt2 = fill({"memtable_rep": "cspp"})
    detail["fillrandom_cspp_ops_s"] = round(n_threads * per_thread / dt2)
    db2.close()
    shutil.rmtree(d2, ignore_errors=True)

    # unordered + concurrent native memtable insert (the write levers)
    db, d, dt = fill({"unordered_write": True,
                      "allow_concurrent_memtable_write": True})
    detail["fillrandom_unordered_ops_s"] = round(n_threads * per_thread / dt)
    # Drain this (kept-open) DB's background queue BEFORE the write-path
    # rows: timing them against leftover flush/compaction load understates
    # the write path by 3-4x.
    db.flush()
    db.wait_for_compactions()

    # Write-PATH rows: batches prebuilt, so the measurement isolates
    # queue + WAL + memtable insert (what the unordered/concurrent levers
    # actually target; 100B values so native work dominates Python).
    def prebuilt_rows():
        n_wp = max(10_000, n_db // 2)
        per = n_wp // n_threads

        def mkbatches():
            out = []
            for t in range(n_threads):
                bs = []
                for i in range(0, per, 500):
                    b = WriteBatch()
                    for j in range(i, i + 500):
                        k = (t * per + j) * 2654435761 % (n_db * 2)
                        b.put(b"%016d" % k, b"w" * 100)
                    bs.append(b)
                out.append(bs)
            return out

        for label, kw in (("fillrandom_100B_path_ops_s", {}),
                          ("fillrandom_100B_path_unordered_ops_s",
                           {"unordered_write": True,
                            "allow_concurrent_memtable_write": True})):
            batches = mkbatches()
            d2 = tempfile.mkdtemp(prefix="benchwp_", dir="/dev/shm"
                                  if os.path.isdir("/dev/shm") else None)
            db2 = DB.open(d2, Options(create_if_missing=True,
                                      write_buffer_size=256 << 20, **kw))
            errs2 = []

            def w2(bs):
                try:
                    for b in bs:
                        db2.write(b)
                except Exception as e:  # noqa: BLE001
                    errs2.append(e)

            ts2 = [threading.Thread(target=w2, args=(bs,)) for bs in batches]
            t0 = time.time()
            for t in ts2:
                t.start()
            for t in ts2:
                t.join()
            dt2 = time.time() - t0
            assert not errs2, errs2
            detail[label] = round(n_threads * per / dt2)
            db2.close()
            shutil.rmtree(d2, ignore_errors=True)

    prebuilt_rows()

    # sustained flush+compaction sequence: wait out the bg queue, then
    # write amp = (flush + compaction bytes written) / user bytes.
    db.flush()
    db.wait_for_compactions()
    stats = db.stats
    user_bytes = stats.get_ticker_count(st.BYTES_WRITTEN)
    flush_bytes = stats.get_ticker_count(st.FLUSH_WRITE_BYTES)
    comp_bytes = stats.get_ticker_count(st.COMPACT_WRITE_BYTES)
    if user_bytes:
        detail["write_amplification"] = round(
            (user_bytes + flush_bytes + comp_bytes) / user_bytes, 2)
    detail["compaction_read_bytes"] = stats.get_ticker_count(
        st.COMPACT_READ_BYTES)

    # readrandom through the full read path (memtable + levels).
    import random as _r

    rng = _r.Random(5)
    probes = [b"%016d" % ((rng.randrange(n_db) * 2654435761) % (n_db * 2))
              for _ in range(min(100_000, n_db))]
    # Stats-ON rate first (the reference's db_bench runs with statistics
    # DISABLED by default, so the headline readrandom row below measures
    # stats-off on a reopen; this row records the instrumented cost).
    n_warm = min(20_000, len(probes))
    for k in probes[:n_warm]:
        db.get(k)
    t0 = time.time()
    for k in probes[:n_warm]:
        db.get(k)
    detail["readrandom_stats_ops_s"] = round(n_warm / (time.time() - t0))
    db.close()

    db = DB.open(d, Options())  # stats-off: reference db_bench parity
    for k in probes[:n_warm]:
        db.get(k)
    t0 = time.time()
    hits = 0
    for k in probes:
        if db.get(k) is not None:
            hits += 1
    dt = time.time() - t0
    detail["readrandom_ops_s"] = round(len(probes) / dt)
    detail["readrandom_hit_pct"] = round(100 * hits / len(probes), 1)

    # multireadrandom (reference db_bench workload): batched native
    # MultiGet, one GIL-released chain walk per 128-key batch.
    db.multi_get(probes[:n_warm])
    t0 = time.time()
    batches = [db.multi_get(probes[i:i + 128])
               for i in range(0, len(probes), 128)]
    dt_mg = time.time() - t0
    detail["multireadrandom_ops_s"] = round(len(probes) / dt_mg)
    mg_hits = sum(v is not None for b in batches for v in b)
    detail["multireadrandom_hit_pct"] = round(
        100 * mg_hits / len(probes), 1)

    # readseq / seekrandom (reference db_bench workloads): the chunked
    # scan plane (TPULSM_ITER_CHUNK=1, the default) vs the per-entry
    # path (=0) on the same multi-level DB; byte-identical output is
    # asserted so the ratio is pure data-plane.
    def _scan_all():
        it = db.new_iterator()
        it.seek_to_first()
        c = by = 0
        while it.valid():
            by += len(it.key()) + len(it.value())
            c += 1
            it.next()
        return c, by

    saved_chunk = os.environ.get("TPULSM_ITER_CHUNK")
    try:
        os.environ["TPULSM_ITER_CHUNK"] = "1"
        _scan_all()  # warm the page cache for a fair serial comparison
        t0 = time.time()
        c_c, by_c = _scan_all()
        dt_c = time.time() - t0
        os.environ["TPULSM_ITER_CHUNK"] = "0"
        t0 = time.time()
        c_s, by_s = _scan_all()
        dt_s = time.time() - t0
        assert (c_c, by_c) == (c_s, by_s), "scan-plane output mismatch"
        detail["readseq_MBps"] = round(by_c / dt_c / 1e6, 2)
        detail["readseq_serial_MBps"] = round(by_s / dt_s / 1e6, 2)
        detail["readseq_entries_s"] = round(c_c / dt_c)
        detail["readseq_speedup"] = round(dt_s / dt_c, 2)
        sk = probes[: min(20_000, len(probes))]
        for label, knob in (("seekrandom_ops", "1"),
                            ("seekrandom_serial_ops", "0")):
            os.environ["TPULSM_ITER_CHUNK"] = knob
            it = db.new_iterator()
            for k in sk[:2000]:
                it.seek(k)
            t0 = time.time()
            for k in sk:
                it.seek(k)
            detail[label] = round(len(sk) / (time.time() - t0))
    finally:
        if saved_chunk is None:
            os.environ.pop("TPULSM_ITER_CHUNK", None)
        else:
            os.environ["TPULSM_ITER_CHUNK"] = saved_chunk
    db.close()
    shutil.rmtree(d, ignore_errors=True)

    # Zip data plane read rows: the same keyspace rebuilt with
    # bottommost_format="zip" so readrandom probes compressed value
    # groups (native zip Get — one mini-group inflate per hit, never a
    # whole-file inflate) and readseq runs the zip scan window
    # (ZipTableReader.scan_columnar). Block-table twins are the
    # readrandom_ops_s / readseq_MBps rows above.
    try:
        n_z = min(n_db, 200_000)
        dz = tempfile.mkdtemp(prefix="benchdb_zip_", dir="/dev/shm"
                              if os.path.isdir("/dev/shm") else None)
        dbz = DB.open(dz, Options(create_if_missing=True,
                                  write_buffer_size=8 << 20,
                                  bottommost_format="zip",
                                  disable_auto_compactions=True))
        for i in range(0, n_z, 1000):
            b = WriteBatch()
            for j in range(i, min(i + 1000, n_z)):
                k = (j * 2654435761) % (n_z * 2)
                b.put(b"%016d" % k, b"value-%016d" % j)
            dbz.write(b)
        dbz.flush()
        dbz.compact_range()  # -> bottommost zip tables
        rngz = _r.Random(9)
        pz = [b"%016d" % ((rngz.randrange(n_z) * 2654435761) % (n_z * 2))
              for _ in range(min(20_000, n_z))]
        for k in pz[:2000]:
            dbz.get(k)
        t0 = time.time()
        hz = sum(dbz.get(k) is not None for k in pz)
        detail["readrandom_zip_ops_s"] = round(len(pz) / (time.time() - t0))
        detail["readrandom_zip_hit_pct"] = round(100 * hz / len(pz), 1)

        def _scan_zip():
            it = dbz.new_iterator()
            it.seek_to_first()
            c = by = 0
            while it.valid():
                by += len(it.key()) + len(it.value())
                c += 1
                it.next()
            return c, by

        _scan_zip()  # warm
        t0 = time.time()
        c_z, by_z = _scan_zip()
        dt_z = time.time() - t0
        detail["readseq_zip_MBps"] = round(by_z / dt_z / 1e6, 2)
        detail["readseq_zip_entries_s"] = round(c_z / dt_z)
        dbz.close()
        shutil.rmtree(dz, ignore_errors=True)
    except Exception as e:  # noqa: BLE001
        detail["zip_read_rows_error"] = repr(e)[:120]


def main():
    n_entries = int(os.environ.get("BENCH_N", "10000000"))
    n_db = int(os.environ.get("BENCH_DB_N", "1000000"))
    device = os.environ.get("BENCH_DEVICE", "tpu")
    runs = int(os.environ.get("BENCH_RUNS", "3"))
    fast = os.environ.get("BENCH_FAST") == "1"

    if device in ("tpu", "cpu-jax"):
        # BENCH_DEVICE names the platform JAX must report; anything else
        # is an error here, never a CPU number under a device metric.
        from toplingdb_tpu.ops import device_runtime

        device_runtime.require_device(device)

    import dataclasses

    from toplingdb_tpu.db.dbformat import InternalKeyComparator
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableOptions

    icmp = InternalKeyComparator()
    env = default_env()
    base = tempfile.mkdtemp(prefix="bench_", dir="/dev/shm"
                            if os.path.isdir("/dev/shm") else None)
    raw_bytes = RAW_PER_ENTRY * n_entries
    detail = {
        "device": device,
        "n_entries": n_entries,
        "raw_kv_bytes": raw_bytes,
        "metric_note": "MB/s of raw user KV (28B/entry), baseline's units",
    }

    # Headline: snappy-compressed inputs+outputs (the reference db_bench
    # default config the 24.34s baseline ran with).
    from toplingdb_tpu.utils import codecs

    if not codecs.available("snappy"):
        raise RuntimeError("the headline is a snappy job and the snappy "
                           "codec is not available")
    topts = TableOptions(block_size=4096,
                         compression=fmt.SNAPPY_COMPRESSION)
    t0 = time.time()
    metas = build_inputs(env, base, icmp, n_entries, topts)
    detail["input_build_s"] = round(time.time() - t0, 2)

    dt, stats, input_file_bytes, run_times = time_compaction(
        env, base, icmp, metas, topts, topts, device, runs, 1000)
    detail["headline_run_times_s"] = run_times  # all N, not just best
    fill_phase_detail(detail, stats)
    mbps = raw_bytes / dt / 1e6
    detail["wall_s"] = round(dt, 3)
    detail["input_file_bytes"] = input_file_bytes
    detail["compression"] = "snappy"
    detail["input_records"] = stats.input_records
    detail["output_records"] = stats.output_records

    if not fast:
        # Variant rows at 1/10 scale (shape-compile reuse; bounded wall).
        n_small = max(1, n_entries // 10)
        sbase = tempfile.mkdtemp(prefix="bench_s_", dir="/dev/shm"
                                 if os.path.isdir("/dev/shm") else None)
        sm = {}
        t_none = TableOptions(block_size=4096)
        sm["none"] = build_inputs(env, sbase, icmp, n_small, t_none)
        dt2, _, _, _ = time_compaction(env, sbase, icmp, sm["none"], t_none,
                                       t_none, device, max(1, runs - 1), 5000)
        detail["compaction_nocomp_MBps"] = round(
            RAW_PER_ENTRY * n_small / dt2 / 1e6, 2)
        # Same job with the pipeline forced OFF: the serial comparator for
        # compaction_nocomp_MBps (which runs pipelined by default).
        saved_pipe = os.environ.get("TPULSM_PIPELINE")
        os.environ["TPULSM_PIPELINE"] = "0"
        try:
            dt2s, _, _, _ = time_compaction(
                env, sbase, icmp, sm["none"], t_none, t_none, device,
                max(1, runs - 1), 5200)
            detail["compaction_nocomp_serial_MBps"] = round(
                RAW_PER_ENTRY * n_small / dt2s / 1e6, 2)
        finally:
            if saved_pipe is None:
                os.environ.pop("TPULSM_PIPELINE", None)
            else:
                os.environ["TPULSM_PIPELINE"] = saved_pipe
        if device in ("tpu", "cpu-jax"):
            # Same job with FULL on-device block assembly
            # (TPULSM_DEVICE_BLOCKS=1; single shard, uncompressed — its
            # eligibility envelope). Both rows land in the detail so the
            # default can be chosen from measured data per link class.
            saved = {k: os.environ.get(k) for k in
                     ("TPULSM_DEVICE_BLOCKS", "TPULSM_DEVICE_SHARDS")}
            os.environ["TPULSM_DEVICE_BLOCKS"] = "1"
            os.environ["TPULSM_DEVICE_SHARDS"] = "1"
            try:
                dt2b, _, _, _ = time_compaction(
                    env, sbase, icmp, sm["none"], t_none, t_none, device,
                    max(1, runs - 1), 5500)
                detail["compaction_nocomp_deviceblocks_MBps"] = round(
                    RAW_PER_ENTRY * n_small / dt2b / 1e6, 2)
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
        if codecs.available("zstd"):
            t_z = dataclasses.replace(t_none,
                                      compression=fmt.ZSTD_COMPRESSION)
            dt3, _, _, _ = time_compaction(env, sbase, icmp, sm["none"],
                                           t_none, t_z, device,
                                           max(1, runs - 1), 6000)
            detail["compaction_zstd_out_MBps"] = round(
                RAW_PER_ENTRY * n_small / dt3 / 1e6, 2)
        # ZipTable emission (searchable-compression bottommost output).
        # The batched native zip plane (tpulsm_zip_* kernels inside the
        # pipeline's encode stage) builds these at full scale; the serial
        # twin (TPULSM_ZIP_PLANE=0: per-entry Python ZipTableBuilder)
        # runs at reduced scale so its cost doesn't dominate the round.
        zbase = tempfile.mkdtemp(prefix="bench_z_", dir="/dev/shm"
                                 if os.path.isdir("/dev/shm") else None)
        zm = build_inputs(env, zbase, icmp, n_small, t_none)
        t_zip = dataclasses.replace(t_none, format="zip")
        dt4, _, _, _ = time_compaction(env, zbase, icmp, zm, t_none,
                                       t_zip, device, max(1, runs - 1),
                                       7000)
        detail["compaction_zip_out_MBps"] = round(
            RAW_PER_ENTRY * n_small / dt4 / 1e6, 2)
        shutil.rmtree(zbase, ignore_errors=True)
        n_zs = max(1, n_small // 5)
        zsbase = tempfile.mkdtemp(prefix="bench_zs_", dir="/dev/shm"
                                  if os.path.isdir("/dev/shm") else None)
        zsm = build_inputs(env, zsbase, icmp, n_zs, t_none)
        saved_zp = os.environ.get("TPULSM_ZIP_PLANE")
        os.environ["TPULSM_ZIP_PLANE"] = "0"
        try:
            dt5, _, _, _ = time_compaction(env, zsbase, icmp, zsm, t_none,
                                           t_zip, device, 1, 7500)
            detail["compaction_zip_serial_MBps"] = round(
                RAW_PER_ENTRY * n_zs / dt5 / 1e6, 2)
        finally:
            if saved_zp is None:
                os.environ.pop("TPULSM_ZIP_PLANE", None)
            else:
                os.environ["TPULSM_ZIP_PLANE"] = saved_zp
        shutil.rmtree(zsbase, ignore_errors=True)
        shutil.rmtree(sbase, ignore_errors=True)

        db_path_rows(detail, n_db)

        try:
            write_plane_rows(detail, n_db)
        except Exception as e:  # noqa: BLE001
            detail["write_plane_rows_error"] = repr(e)[:120]

        try:
            replication_rows(detail)
        except Exception as e:  # noqa: BLE001
            detail["replication_rows_error"] = repr(e)[:120]

        try:
            integrity_rows(detail, n_db)
        except Exception as e:  # noqa: BLE001
            detail["integrity_rows_error"] = repr(e)[:120]

        try:
            observability_rows(detail, n_db)
        except Exception as e:  # noqa: BLE001
            detail["observability_rows_error"] = repr(e)[:120]

        try:
            health_rows(detail, n_db)
        except Exception as e:  # noqa: BLE001
            detail["health_rows_error"] = repr(e)[:120]

        try:
            sharding_rows(detail)
        except Exception as e:  # noqa: BLE001
            detail["sharding_rows_error"] = repr(e)[:120]

        try:
            fleet_rows(detail)
        except Exception as e:  # noqa: BLE001
            detail["fleet_rows_error"] = repr(e)[:120]

        try:
            concurrency_rows(detail, n_db)
        except Exception as e:  # noqa: BLE001
            detail["concurrency_rows_error"] = repr(e)[:120]

        try:
            async_read_rows(detail)
        except Exception as e:  # noqa: BLE001
            detail["async_read_rows_error"] = repr(e)[:120]

        try:
            storage_rows(detail)
        except Exception as e:  # noqa: BLE001
            detail["storage_rows_error"] = repr(e)[:120]

        try:
            disk_pressure_rows(detail, n_db)
        except Exception as e:  # noqa: BLE001
            detail["disk_pressure_rows_error"] = repr(e)[:120]

        # Range-axis weak-scaling of the distributed GC step (VERDICT r04
        # item 10): a subprocess because virtual device counts must be set
        # before the jax backend exists. Failure just drops the row.
        import subprocess as _sp

        try:
            out = _sp.run(
                [sys.executable, "-m",
                 "toplingdb_tpu.parallel.scaling_probe",
                 "--rows-per-device", "32768", "--devices", "8",
                 "--repeats", "2"],
                capture_output=True, timeout=600, cwd=os.path.dirname(
                    os.path.abspath(__file__)))
            if out.returncode == 0 and out.stdout:
                detail["range_weak_scaling"] = json.loads(
                    out.stdout.decode().strip().splitlines()[-1]
                )["weak_scaling"]
        except Exception as e:  # noqa: BLE001
            detail["range_weak_scaling_error"] = str(e)[:120]

        # MEASURED mesh compaction (§2.2.4): the MULTICHIP dry-run
        # promoted — the same uniform shard set through the mesh shard
        # runner at 1 chip vs 8. Exit 3 = skip (environment), not error.
        try:
            out = _sp.run(
                [sys.executable, "-m",
                 "toplingdb_tpu.parallel.scaling_probe",
                 "--mode", "mesh",
                 "--rows-per-device", "16384", "--devices", "8",
                 "--repeats", "2"],
                capture_output=True, timeout=600, cwd=os.path.dirname(
                    os.path.abspath(__file__)))
            if out.returncode == 0 and out.stdout:
                rows = json.loads(
                    out.stdout.decode().strip().splitlines()[-1]
                )["mesh_compact"]
                detail["mesh_compact"] = rows
                base = rows[0]["rows_per_s"]
                if base and len(rows) > 1:
                    detail["compaction_mesh_MBps"] = rows[-1]["MBps"]
                    detail["mesh_scaling_x"] = round(
                        rows[-1]["rows_per_s"] / base, 2)
            elif out.returncode == 3 and out.stdout:
                detail["mesh_compact_skip"] = json.loads(
                    out.stdout.decode().strip().splitlines()[-1]
                ).get("skip", "")[:120]
        except Exception as e:  # noqa: BLE001
            detail["mesh_compact_error"] = str(e)[:120]

    # Record layout (VERDICT r05 weak #1): the driver captures only the
    # LAST ~2000 chars of stdout, so the headline keys must be the FINAL
    # keys of the line (json.dumps preserves dict insertion order) and the
    # whole line must stay ≤ 1800 bytes — otherwise the tail keeps the
    # detail blob and drops "value", making the round's perf work
    # officially invisible.
    def make_record(det):
        return {
            "metric": "l2_compaction_MBps_per_chip",
            "unit": "MB/s",
            "detail": det,
            # headline keys LAST so a tail capture always preserves them
            "value": round(mbps, 2),
            "vs_baseline": round(mbps / BASELINE_MBPS, 4),
            "device": device,
            # Pipelined-data-plane headline rows: measured scan/compute/
            # encode overlap of the headline job, and the pipelined
            # nocomp variant (its serial twin is
            # detail.compaction_nocomp_serial_MBps).
            "pipeline_overlap_s": detail.get("phase_breakdown", {}).get(
                "pipeline_overlap_s", 0.0),
            "compaction_pipelined_MBps": detail.get(
                "compaction_nocomp_MBps"),
            # Chunked scan-plane headline rows (serial twins are
            # detail.readseq_serial_MBps / detail.seekrandom_serial_ops).
            "readseq_MBps": detail.get("readseq_MBps"),
            "seekrandom_ops": detail.get("seekrandom_ops"),
            # Replication plane: router read rate under a concurrent
            # writer (detail.readwhilewriting_replica_ops is the row) and
            # mean ship→apply lag of the tailing follower.
            "replication_lag_ms": detail.get("replication_lag_ms"),
            # Native group-commit write plane (serial twin is
            # detail.fillrandom_plane_off_ops_s; sync twin is
            # detail.fillrandom_sync_inline_ops_s).
            "fillrandom_native_plane_ops_s": detail.get(
                "fillrandom_native_plane_ops_s"),
            "fillrandom_sync_ops_s": detail.get("fillrandom_sync_ops_s"),
            # Telemetry plane: sampled (1-in-64) tracing cost vs the
            # tracing-off twin (gate: <= 2%).
            "trace_overhead_pct": detail.get("trace_overhead_pct"),
            # Health plane: windowed histograms + per-segment SLO
            # evaluation vs cumulative-only twin (gate: <= 2%).
            "health_overhead_pct": detail.get("health_overhead_pct"),
            # Sharding plane: 4-shard vs 1-shard router fillrandom ratio
            # (detail has the per-config ops/s + hot-tenant isolation).
            "shard_scaling_x": detail.get("shard_scaling_x"),
            # Out-of-process fleet: 4 ShardServer processes vs 1 through
            # the FleetRouter's HTTP data plane (gate: >= in-process
            # shard_scaling_x — no shared GIL across primaries).
            "fleet_scaling_x": detail.get("fleet_scaling_x"),
            # Concurrency plane: off-mode factories must price as raw
            # locks (gate: <= 1%) and debug-instrumented fillrandom must
            # stay within 2x of plain (gate: <= 100).
            "lock_factory_overhead_pct": detail.get(
                "lock_factory_overhead_pct"),
            "lock_debug_overhead_pct": detail.get(
                "lock_debug_overhead_pct"),
            # Searchable-compression zip data plane: batched native zip
            # emission inside the compaction pipeline (serial twin is
            # detail.compaction_zip_serial_MBps) and compressed-block
            # reads without whole-file inflate (block-table twins are
            # readrandom_ops_s / readseq_MBps).
            "compaction_zip_out_MBps": detail.get(
                "compaction_zip_out_MBps"),
            "readrandom_zip_ops_s": detail.get("readrandom_zip_ops_s"),
            "readseq_zip_MBps": detail.get("readseq_zip_MBps"),
            # Mesh compaction execution mode (§2.2.4): the MULTICHIP
            # dry-run promoted to a measured row — the same shard set at
            # 8 chips (1-chip twin is detail.mesh_compact[0]). On virtual
            # CPU devices the chips share one host threadpool, so
            # mesh_scaling_x reports ~1x there; >=4x is the real-chip
            # expectation.
            "compaction_mesh_MBps": detail.get("compaction_mesh_MBps"),
            "mesh_scaling_x": detail.get("mesh_scaling_x"),
            # Async read plane (§2.2.5): cold-cache batched MultiGet
            # through the reader rings vs its sync twin
            # (detail.multireadrandom_cold_ops_s /
            # detail.multireadrandom_cold_sync_ops_s; both on the
            # 200µs DelayedReadEnv latency model, byte parity asserted).
            # On a 1-core host the rings serialize:
            # detail.async_read_speedup_source tags that provenance.
            "async_read_speedup_x": detail.get("async_read_speedup_x"),
            # Disaggregated SST storage (storage/): large-shard migration
            # bootstrap, cross-filesystem byte copy vs metadata-only
            # store references (flatness twin is
            # detail.migration_ref_flatness_x; dcompact store mode ships
            # detail.dcompact_store_sst_bytes_shipped == 0).
            "migration_ref_speedup_x": detail.get(
                "migration_ref_speedup_x"),
            # Storage-pressure plane (§2.5.1): fillrandom with budget +
            # manager accounting + hot free-space poller vs the no-manager
            # twin (detail.fillrandom_disk_plain_ops_s; gate: <= 1%).
            "disk_pressure_overhead_pct": detail.get(
                "disk_pressure_overhead_pct"),
        }

    line = json.dumps(make_record(detail))
    if len(line) > 1800:
        slim = {k: detail[k] for k in (
            "n_entries", "raw_kv_bytes", "wall_s", "headline_run_times_s",
            "phase_breakdown", "compression",
            "readwhilewriting_replica_ops",
            "replica_read_pct", "shard_scaling_x", "fleet_scaling_x",
            "sibling_keep_pct", "fillrandom_4shard_ops_s",
            "compaction_zip_serial_MBps") if k in detail}
        slim["detail_truncated"] = True
        line = json.dumps(make_record(slim))
    if len(line) > 1800:
        line = json.dumps(make_record({"detail_truncated": True}))
    json.loads(line)  # hard guarantee: the printed record parses
    assert len(line) <= 1800, len(line)
    print(line)
    shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    main()
