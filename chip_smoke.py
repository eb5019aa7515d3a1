#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls: a DB
process that serves writes and reads while ONE dcompact worker service,
which owns the TPU, runs its compactions.

  deployment  db_bench fillrandom -> overwrite, BASELINE.json config 2 as
              BASELINE.md records it (topling-bench/db_bench-xeon-8369hb.md):
              8 B keys, 20 B values, snappy, leveled, block_size 4096, WAL
              on, db_bench's default LSM sizes. N distinct keys in a seeded
              order, N overwrites of seeded draws, then compact_range().
              The source's 100M keys are cut (see `reduced` in the record).
  layout      this process is the DB and NEVER imports JAX (asserted). It
              starts one child at a time that may touch the chip:
              `python -m toplingdb_tpu.compaction.dcompact_service
              --device tpu`, later a fresh `compaction.worker`, last a
              kernel check. The DB opens with HttpCompactionExecutorFactory
              (allow_fallback=False): a device failure fails the run, it
              is never turned into a local CPU compaction.
  checks      every get / multi_get / scan, before and after a close and
              reopen, against a plain oracle computed from the seed alone
              (last writer per key, numpy only); one real job of >= 2^21
              rows re-run through the job-dir protocol on the TPU service
              and on a CPU worker, outputs equal as bytes; the DB's
              DCOMPACTION_* tickers and per-job CompactionStats as
              witnesses that the chip did the work; a made job whose
              oldest file holds zeroed rows beside rows written after 2^32,
              held to the same parity; a fresh TPU worker a job that must
              compile nothing (persistent compile cache); each Pallas
              kernel compiled by Mosaic against numpy.

Without a TPU it exits non-zero, says which platform JAX found, and prints
no result. `--rehearse-cpu` drives the same phases at a tiny size on
XLA:CPU: its record says platform=cpu and `"ok": false`, and it exits with
code 4 — a rehearsal can not be read as a pass.

Last line of stdout on a pass:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
preceded by one line with the full record (also written to
chiprun_out/chip_smoke.json). Wall times in it are observations of a
smoke, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

import numpy as np

# In a directory that holds this file and nothing else of the repo the run
# ends here, non-zero, with no result.
from toplingdb_tpu import native
from toplingdb_tpu.compaction.dcompact_service import (
    HttpCompactionExecutorFactory,
)
from toplingdb_tpu.compaction.executor import CompactionParams
from toplingdb_tpu.compaction.resilience import DcompactOptions
from toplingdb_tpu.db import dbformat, filename
from toplingdb_tpu.db.db import DB
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.options import Options
from toplingdb_tpu.table import format as fmt
from toplingdb_tpu.table.builder import TableOptions
from toplingdb_tpu.utils import codecs
from toplingdb_tpu.utils import statistics as st
from toplingdb_tpu.utils.cache import LRUCache

HERE = os.path.dirname(os.path.abspath(__file__))

SOURCE_KEYS = 100_000_000      # the source's key count (BASELINE.json config 2)
DEFAULT_KEYS = 10_000_000      # the smoke's cut of it
BIG_JOB_ROWS = 1 << 21         # the byte-parity job is at least this large
# Above this many input rows a job has >= 2 pipeline shards of <= 2^19
# rows (ops/compaction_kernels.py shard_count), so it must run pipelined.
PIPELINE_FLOOR_ROWS = (1 << 19) + 1
SPAN_JOB_ROWS = 600_000        # the sequence-span job: two shards
EXIT_REHEARSAL = 4             # --rehearse-cpu completed; never a pass

_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xC2B2AE3D27D4EB4F)


# --------------------------------------------------------------------------
# The workload and its oracle: numpy and the seed, no code of the package.
# --------------------------------------------------------------------------


class Workload:
    """Write w (0-based) puts key_of[w]; writes 0..n-1 are a seeded
    permutation of the n distinct keys (fillrandom), writes n..2n-1 are
    seeded draws with replacement (overwrite). Keys are db_bench's: the
    key number as 8 big-endian bytes. A value is 20 bytes made from the
    seed, the key number and the write index, so the oracle can name the
    exact bytes the last writer of every key left."""

    def __init__(self, n_keys: int, seed: int):
        self.n = n_keys
        self.seed = np.uint64(seed)
        rng = np.random.default_rng(seed)
        self.key_of = np.concatenate([
            rng.permutation(n_keys).astype(np.uint64),
            rng.integers(0, n_keys, n_keys, dtype=np.uint64),
        ])
        # Oracle: the last write index of each key. Write indexes only
        # grow, so the last writer is the maximum over a key's writes.
        self.last_write = np.zeros(n_keys, dtype=np.uint64)
        np.maximum.at(self.last_write, self.key_of.astype(np.int64),
                      np.arange(2 * n_keys, dtype=np.uint64))

    @staticmethod
    def key_bytes(keys: np.ndarray) -> np.ndarray:
        """[m] key numbers -> [m, 8] uint8, big-endian."""
        return keys.astype(">u8").view(np.uint8).reshape(len(keys), 8)

    def value_bytes(self, keys: np.ndarray, writes: np.ndarray) -> np.ndarray:
        """[m, 20] uint8: LE64(write) | LE64(mix(seed, key, write)) | vvvv."""
        m = len(keys)
        out = np.full((m, 20), ord("v"), dtype=np.uint8)
        w = writes.astype("<u8")
        mix = ((keys.astype(np.uint64) * _M1)
               ^ (writes.astype(np.uint64) * _M2)) + self.seed
        out[:, 0:8] = w.view(np.uint8).reshape(m, 8)
        out[:, 8:16] = mix.astype("<u8").view(np.uint8).reshape(m, 8)
        return out

    def expected(self, keys: np.ndarray) -> list:
        """Oracle answers for key numbers: value bytes, None past n."""
        keys = np.asarray(keys, dtype=np.uint64)
        live = keys < np.uint64(self.n)
        vals = self.value_bytes(
            keys[live], self.last_write[keys[live].astype(np.int64)])
        blob = vals.tobytes()
        it = (blob[i:i + 20] for i in range(0, len(blob), 20))
        return [next(it) if ok else None for ok in live]


# --------------------------------------------------------------------------
# Phase bookkeeping: a failed phase records itself AND ends the run.
# --------------------------------------------------------------------------


class Record:
    def __init__(self):
        self.phases: list[dict] = []
        self.facts: dict = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        row = {"phase": name, "ok": False}
        self.phases.append(row)
        t0 = time.time()
        print(f"[smoke] {name} ...", file=sys.stderr, flush=True)
        try:
            yield row
            row["ok"] = True
        except BaseException as e:
            row["error"] = f"{type(e).__name__}: {e}"[:800]
            raise
        finally:
            row["wall_s"] = round(time.time() - t0, 3)
            print(f"[smoke] {name}: {'ok' if row['ok'] else 'FAILED'} "
                  f"{row['wall_s']}s", file=sys.stderr, flush=True)

    def all_ok(self) -> bool:
        return bool(self.phases) and all(p["ok"] for p in self.phases)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# --------------------------------------------------------------------------
# Children: at most one of them touches the chip at a time.
# --------------------------------------------------------------------------


class Service:
    """One `dcompact_service` child. It owns the chip until stop()."""

    def __init__(self, device: str, workdir: str, tag: str, env: dict,
                 chips: int = 0):
        self.log = os.path.join(workdir, f"service-{tag}.log")
        cmd = [sys.executable, "-m",
               "toplingdb_tpu.compaction.dcompact_service",
               "--device", device, "--port", "0", "--host", "127.0.0.1"]
        if chips:
            cmd += ["--chips", str(chips)]
        self._logf = open(self.log, "wb")
        self.proc = subprocess.Popen(cmd, cwd=HERE, env=env,
                                     stdout=subprocess.PIPE,
                                     stderr=self._logf)
        self.url = ""

    def wait_listening(self, timeout: float = 600.0) -> dict:
        """Block until the child prints "listening" (it has then checked
        the requested device against JAX); returns what /health says JAX
        reports. Raises with the child's last words if it exits first."""
        import select

        deadline = time.time() + timeout
        line = b""
        while time.time() < deadline:
            if self.proc.poll() is not None:
                break
            if select.select([self.proc.stdout], [], [], 0.5)[0]:
                line = self.proc.stdout.readline()
                if b"listening on" in line:
                    port = int(line.split(b"listening on ")[1]
                               .split()[0].rsplit(b":", 1)[1])
                    self.url = f"http://127.0.0.1:{port}"
                    return self.get("/health")
        raise RuntimeError(
            "dcompact_service did not come up "
            f"(exit code {self.proc.poll()}): {self.last_words()}")

    def last_words(self) -> str:
        self._logf.flush()
        with open(self.log, "rb") as f:
            return f.read()[-1500:].decode("utf-8", "replace").strip()

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(self.url + path, timeout=30) as r:
            return json.loads(r.read())

    def post_job(self, job_dir: str, timeout: float = 1800.0) -> dict:
        req = urllib.request.Request(
            self.url + "/dcompact",
            data=json.dumps({"job_dir": job_dir}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            raise RuntimeError(
                f"job failed on the service: {e.read()[:800]!r}; "
                f"service log: {self.last_words()}") from e

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._logf.close()


def spawn_worker(job_dir: str, env: dict, log_path: str):
    """`python -m toplingdb_tpu.compaction.worker --job-dir` as a child."""
    logf = open(log_path, "wb")
    return subprocess.Popen(
        [sys.executable, "-m", "toplingdb_tpu.compaction.worker",
         "--job-dir", job_dir], cwd=HERE, env=env, stdout=logf, stderr=logf)


def worker_result(proc, job_dir: str, log_path: str, timeout: float) -> dict:
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RuntimeError(f"worker on {job_dir} ran past {timeout}s")
    with open(log_path, "rb") as f:
        tail = f.read()[-1500:].decode("utf-8", "replace")
    check(rc == 0, f"worker exit code {rc}: {tail}")
    with open(os.path.join(job_dir, "results.json")) as f:
        res = json.load(f)
    check(res["status"] == "ok", f"worker status {res['status']!r}")
    return res


# --------------------------------------------------------------------------
# The byte-parity job: one real job's inputs, re-run through the job-dir
# protocol with the same CompactionParams on each side.
# --------------------------------------------------------------------------


def job_params(snapshots=(), **facts) -> dict:
    return dict(
        job_id=1, attempt=0, output_dir="", snapshots=list(snapshots),
        comparator=dbformat.BYTEWISE.name(), merge_operator=None,
        compaction_filter=None, compression=fmt.SNAPPY_COMPRESSION,
        block_size=4096, creation_time=1_700_000_000, lease_sec=0.0, **facts)


def write_job(job_dir: str, captured: dict, device: str) -> None:
    os.makedirs(os.path.join(job_dir, "out"))
    params = CompactionParams(
        **{**captured["params"], "device": device,
           "output_dir": os.path.join(job_dir, "out")})
    with open(os.path.join(job_dir, "params.json"), "w") as f:
        f.write(params.to_json())


def job_outputs(job_dir: str, results: dict) -> list[tuple[str, bytes]]:
    out = []
    for d in results["output_files"]:
        with open(os.path.join(job_dir, "out", d["path"]), "rb") as f:
            out.append((d["path"], f.read()))
    return out


def check_same_bytes(a, b, what: str) -> int:
    check([n for n, _ in a] == [n for n, _ in b],
          f"{what}: output file lists differ "
          f"{[n for n, _ in a]} vs {[n for n, _ in b]}")
    for (name, xa), (_, xb) in zip(a, b):
        check(xa == xb, f"{what}: {name} differs "
                        f"({len(xa)} vs {len(xb)} bytes)")
    return sum(len(x) for _, x in a)


def span_job(in_dir: str, rows: int, seed: int) -> dict:
    """A job a DB of this size never sends: three sorted runs of 8 B keys
    and 20 B values, `rows` rows in all. The oldest (half the rows) is a
    bottom-level file of a DB that has taken 2^32 writes: two rows in
    three zeroed (sequence 0), the others written after 2^24, one after
    2^32, interleaved, so every file range a shard takes spans the lot.
    The newer runs overwrite draws of all keys; one snapshot is held."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops.columnar_io import ColumnarKV, write_tables_columnar

    os.makedirs(in_dir)
    rng = np.random.default_rng(seed)
    half, quarter = rows // 2, rows // 4
    past24, past32 = (1 << 24) + 1, (1 << 32) + 5
    seqs0 = np.where(rng.random(half) < 2 / 3, 0,
                     past24 + rng.permutation(half)).astype(np.uint64)
    seqs0[0] = past32
    runs = [(np.arange(half, dtype=np.uint64) * np.uint64(2), seqs0)]
    for base in (half, half + quarter):  # never key 0: its row is newest
        runs.append((rng.integers(1, rows, quarter, dtype=np.uint64),
                     np.arange(past24 + base, past24 + base + quarter,
                               dtype=np.uint64)))
    fnum = iter(range(1, 100))
    paths = []
    for keys, seqs in runs:
        m = len(keys)
        s = np.lexsort((~seqs, keys))  # internal-key order: newest first
        ik = np.empty((m, 16), dtype=np.uint8)
        ik[:, :8] = Workload.key_bytes(keys[s])
        ik[:, 8:] = ((seqs[s] << np.uint64(8)) | np.uint64(1)).astype(
            "<u8").view(np.uint8).reshape(m, 8)  # 1: ValueType.VALUE
        at = np.arange(m, dtype=np.int32)
        kv = ColumnarKV(ik.reshape(-1), at * 16, np.full(m, 16, np.int32),
                        np.tile(ik[:, :10], 2).reshape(-1), at * 20,
                        np.full(m, 20, np.int32))
        paths += [f[1] for f in write_tables_columnar(
            default_env(), in_dir, fnum.__next__,
            dbformat.InternalKeyComparator(), TableOptions(block_size=4096),
            kv, at, np.full(m, -1, np.int64), np.ones(m, np.int32), seqs[s],
            [], creation_time=1)]
    return {"rows": half + 2 * quarter, "params": job_params(
        dbname=in_dir, input_files=paths, output_level=6, bottommost=True,
        max_output_file_size=4 << 20, snapshots=[past24 + half // 2])}


# --------------------------------------------------------------------------
# The kernel check child (`--child kernels`): the only code of this file
# that imports JAX. Runs after every other chip holder has exited.
# --------------------------------------------------------------------------


def child_kernels(device: str) -> int:
    import jax.numpy as jnp

    from toplingdb_tpu.ops import device_runtime
    from toplingdb_tpu.ops import pallas_kernels as pk

    device_runtime.require_device(device)
    compiled = device != "cpu-jax"  # Mosaic on the chip, interpreter here
    out = {"device": device_runtime.describe_devices(),
           "interpret": not compiled}

    rng = np.random.default_rng(3)
    n = 3000
    lens = rng.integers(1, 31, n).astype(np.int32)
    mat = rng.integers(0, 4, (n, 32)).astype(np.uint8)  # long shared runs
    mat[np.arange(32)[None, :] >= lens[:, None]] = 0
    order = np.lexsort(tuple(mat[:, j] for j in range(31, -1, -1)))
    mat, lens = mat[order], lens[order]
    got = pk.shared_prefix_lengths(mat, lens, interpret=not compiled)
    neq = mat[1:] != mat[:-1]
    first = np.where(neq.any(axis=1), neq.argmax(axis=1), 32)
    want = np.concatenate([[0], np.minimum(
        first, np.minimum(lens[1:], lens[:-1]))])
    check(np.array_equal(got, want), "shared_prefix_lengths != numpy")
    out["shared_prefix_lengths"] = "compiled, matches numpy" if compiled \
        else "interpreted, matches numpy"

    n, s = 1 << 16, 64
    seq = np.sort(rng.integers(0, 1 << 40, n).astype(np.uint64))[::-1]
    snap = np.full(s, 1 << 56, np.uint64)
    snap[:5] = np.sort(rng.integers(0, 1 << 40, 5).astype(np.uint64))
    tomb = np.where(rng.random(n) < 0.3,
                    rng.integers(0, 1 << 40, n).astype(np.uint64),
                    np.uint64(0))
    vtype = rng.choice([0, 1, 2, 7], n).astype(np.int32)
    new_key = rng.random(n) < 0.4
    new_key[0] = True
    hi = lambda x: jnp.asarray((x >> np.uint64(32)).astype(np.uint32))
    lo = lambda x: jnp.asarray((x & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    pseq = np.roll(seq, 1)
    stripe, fis, covered, cx = pk.gc_rows(
        hi(seq), lo(seq), hi(pseq), lo(pseq), jnp.asarray(new_key),
        hi(tomb), lo(tomb), jnp.asarray(vtype), hi(snap), lo(snap),
        interpret=not compiled)
    w_stripe = np.searchsorted(snap, seq, side="left")
    w_fis = new_key | (w_stripe != np.roll(w_stripe, 1))
    w_cov = (tomb != 0) & (tomb > seq) & (
        np.searchsorted(snap, tomb, side="left") == w_stripe)
    check(np.array_equal(np.asarray(stripe), w_stripe), "gc_rows stripe")
    check(np.array_equal(np.asarray(fis) | new_key, w_fis), "gc_rows fis")
    check(np.array_equal(np.asarray(covered), w_cov), "gc_rows covered")
    check(np.array_equal(np.asarray(cx), (vtype == 2) | (vtype == 7)),
          "gc_rows complex")
    out["gc_rows"] = out["shared_prefix_lengths"]
    print(json.dumps(out))
    return 0


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------


class CapturingFactory(HttpCompactionExecutorFactory):
    """HttpCompactionExecutorFactory that also keeps the inputs of the
    first job of >= min_rows rows alive (hard links) with the facts its
    CompactionParams need — the real job the parity phase re-runs."""

    def __init__(self, url: str, device: str, capture: dict,
                 min_rows: int, min_input_bytes: int):
        # A smoke fails fast: one attempt, no retry dressing a failure up,
        # and no fallback to a local CPU compaction.
        super().__init__([url], device=device, allow_fallback=False,
                         min_input_bytes=min_input_bytes,
                         policy=DcompactOptions(max_attempts=1))
        self.capture = capture
        self.min_rows = min_rows

    def new_executor(self, compaction):
        cap = self.capture
        rows = sum(f.num_entries for _, f in compaction.all_inputs())
        if "params" not in cap and rows >= self.min_rows:
            links = []
            for _, f in compaction.all_inputs():
                src = filename.table_file_name(cap["dbname"], f.number)
                dst = os.path.join(cap["dir"], os.path.basename(src))
                os.link(src, dst)
                links.append(dst)
            cap["rows"] = rows
            cap["params"] = job_params(
                dbname=cap["dbname"], input_files=links,
                output_level=compaction.output_level,
                bottommost=compaction.bottommost,
                max_output_file_size=compaction.max_output_file_size)
        return super().new_executor(compaction)


class JobStatistics(st.Statistics):
    """The DB's Statistics, also keeping each job's CompactionStats (the
    per-job witnesses: pipelined, host_compute_usec, device)."""

    def __init__(self):
        super().__init__()
        self.jobs = []

    def record_compaction(self, stats):
        self.jobs.append(stats)
        super().record_compaction(stats)


def kill(proc) -> None:
    if proc.poll() is None:
        proc.kill()
        proc.wait()


def run(args, rec: Record, workdir: str,
        cleanup: contextlib.ExitStack) -> dict:
    """The phases, in order. Whatever it starts or opens it also hands to
    `cleanup`, which main() unwinds on every way out."""
    device = "cpu-jax" if args.rehearse_cpu else "tpu"
    env = dict(os.environ)
    if args.rehearse_cpu:
        env["JAX_PLATFORMS"] = "cpu"
    cpu_env = dict(env, JAX_PLATFORMS="cpu")
    n = args.keys
    scale = n / DEFAULT_KEYS
    # db_bench's default LSM sizes; a rehearsal shrinks them with the key
    # count so the same job shapes appear at a tiny size.
    mb = 1 << 20
    wbuf = max(256 << 10, int(64 * mb * min(1.0, scale)))
    big_rows = BIG_JOB_ROWS if not args.rehearse_cpu else max(1, n // 4)
    rec.facts.update(
        keys=n, writes=2 * n, key_bytes=8, value_bytes=20, seed=args.seed,
        compression="snappy", block_size=4096, write_buffer_bytes=wbuf,
        block_cache_bytes=32 * mb, raw_kv_bytes=28 * n,
        reduced=[f"keys cut from the source's {SOURCE_KEYS} to {n} "
                 f"({SOURCE_KEYS / n:g}x): one chip, 1200 s"],
        rehearsal=bool(args.rehearse_cpu))

    with rec.phase("native_build") as ph:
        # *.so is ignored by git and staleness goes by mtime: build both
        # libraries from the sources of THIS checkout, in this run.
        ndir = os.path.dirname(native.__file__)
        for so in ([os.path.join(ndir, "_tpulsm_native.so")]
                   + glob.glob(os.path.join(ndir, "tpulsm_fastget.*.so"))):
            if os.path.exists(so):
                os.remove(so)
        check(native.lib() is not None, "tpulsm_native.cc did not build")
        check(native.fastget() is not None, "fastget.c did not build")
        check(codecs.available("snappy"), "snappy codec missing")
        ph["built"] = ["_tpulsm_native.so", "tpulsm_fastget.so"]

    with rec.phase("service_start") as ph:
        svc = Service(device, workdir, "a", env)
        cleanup.callback(svc.stop)
        health = svc.wait_listening()
        dev = health["jax"]
        ph["device"] = dev
        want = "cpu" if args.rehearse_cpu else "tpu"
        check(dev["platform"] == want,
              f"service runs on {dev['platform']!r}, not {want!r}")
    rec.facts["device"] = dev

    with rec.phase("workload_and_oracle"):
        wl = Workload(n, args.seed)

    dbdir = os.path.join(workdir, "db")
    capture = {"dbname": dbdir, "dir": os.path.join(workdir, "job-inputs")}
    os.makedirs(capture["dir"])
    stats = JobStatistics()

    def options():
        return Options(
            create_if_missing=True, compression=fmt.SNAPPY_COMPRESSION,
            table_options=TableOptions(block_size=4096),
            write_buffer_size=wbuf,
            max_bytes_for_level_base=4 * wbuf,
            target_file_size_base=wbuf,
            block_cache=LRUCache(32 * mb), statistics=stats,
            # Jobs under a memtable's worth of input stay in the DB
            # process (the reference's ShouldRunLocal policy); everything
            # larger goes to the chip or fails.
            compaction_executor_factory=CapturingFactory(
                svc.url, device, capture, big_rows,
                min_input_bytes=wbuf // 4),
        )

    def write_range(db, lo, hi):
        """Writes lo..hi-1 of the workload, 1000 puts to a WriteBatch."""
        step = 200_000
        for c0 in range(lo, hi, step):
            c1 = min(c0 + step, hi)
            keys = wl.key_of[c0:c1]
            kb = wl.key_bytes(keys).tobytes()
            vb = wl.value_bytes(
                keys, np.arange(c0, c1, dtype=np.uint64)).tobytes()
            for b0 in range(0, c1 - c0, 1000):
                wb = WriteBatch()
                for j in range(b0, min(b0 + 1000, c1 - c0)):
                    wb.put(kb[8 * j:8 * j + 8], vb[20 * j:20 * j + 20])
                db.write(wb)

    cpu_worker = {}

    def start_cpu_worker(tag="job", job=capture):
        """The CPU side of a parity job needs no chip and takes about a
        minute of per-entry Python: start it as soon as the job has been
        captured, beside the load."""
        if "params" in job and tag not in cpu_worker:
            cpu_dir = os.path.join(workdir, f"{tag}-cpu")
            log = os.path.join(workdir, f"worker-{tag}-cpu.log")
            write_job(cpu_dir, job, "cpu")
            w = spawn_worker(cpu_dir, cpu_env, log)
            cpu_worker[tag] = (w, cpu_dir, log)
            cleanup.callback(kill, w)

    def parity(ph, tag, job):
        """`job` through the TPU service and through a CPU worker: outputs
        equal as bytes; above the pipeline's floor, pipelined to its end
        with no program of its own. Returns the service's outputs."""
        ph["rows"] = job["rows"]
        tpu_dir = os.path.join(workdir, f"{tag}-tpu")
        write_job(tpu_dir, job, device)
        start_cpu_worker(tag, job)
        t0 = time.time()
        res_tpu = svc.post_job(tpu_dir)
        ph["tpu_job_s"] = round(time.time() - t0, 2)
        res_cpu = worker_result(*cpu_worker[tag], timeout=1500)
        ph["cpu_job_s"] = round(res_cpu["work_time_usec"] / 1e6, 2)
        out_tpu = job_outputs(tpu_dir, res_tpu)
        ph["output_files"] = len(out_tpu)
        ph["output_bytes"] = check_same_bytes(
            out_tpu, job_outputs(cpu_worker[tag][1], res_cpu),
            f"{tag}: TPU service vs CPU")
        st_tpu = ph["tpu_stats"] = {k: res_tpu["stats"][k] for k in (
            "device", "pipelined", "pipeline_exit", "host_compute_usec",
            "input_records", "output_records", "jit_compiles",
            "jit_cache_hits")}
        check(st_tpu["device"] == device, "service job device")
        check(res_cpu["stats"]["device"] == "cpu", "cpu job device")
        check(st_tpu["input_records"] == job["rows"], f"{tag}: input rows")
        if job["rows"] >= PIPELINE_FLOOR_ROWS:
            check(st_tpu["pipelined"] and st_tpu["pipeline_exit"] == "",
                  f"{tag} left the pipeline: {st_tpu['pipeline_exit']!r}")
            check(st_tpu["host_compute_usec"] == 0,
                  f"{tag} used the host twin")
            check(st_tpu["jit_compiles"] == 0,
                  f"{tag} met a program the load had not compiled")
        return out_tpu

    open_db = []  # the DB while it is open, so a failed phase closes it

    def close_db():
        while open_db:
            open_db.pop().close()

    cleanup.callback(close_db)  # unwinds before svc.stop: close may compact
    db = DB.open(dbdir, options())
    open_db.append(db)
    with rec.phase("fillrandom") as ph:
        write_range(db, 0, n)
        ph["jobs_so_far"] = len(stats.jobs)
    start_cpu_worker()
    with rec.phase("overwrite") as ph:
        write_range(db, n, 2 * n)
        db.flush()
        db.wait_for_compactions()
        ph["jobs_so_far"] = len(stats.jobs)
    start_cpu_worker()

    with rec.phase("queries_midway") as ph:
        # Data still spread over memtable-flushed L0 files and levels.
        ph.update(run_queries(db, wl, args.seed + 1, gets=2000, mget=1000,
                              scan=10_000))

    with rec.phase("compact_range") as ph:
        db.compact_range()
        db.wait_for_compactions()
        ph["jobs"] = len(stats.jobs)

    with rec.phase("queries") as ph:
        ph.update(run_queries(db, wl, args.seed + 2, gets=10_000,
                              mget=2000, scan=min(100_000, n)))

    with rec.phase("reopen_and_queries") as ph:
        close_db()
        db = DB.open(dbdir, options())  # WAL + MANIFEST recovery
        open_db.append(db)
        ph.update(run_queries(db, wl, args.seed + 3, gets=2000, mget=1000,
                              scan=min(20_000, n)))
        close_db()

    span = span_job(
        os.path.join(workdir, "span-inputs"),
        SPAN_JOB_ROWS if not args.rehearse_cpu else max(4000, n // 16),
        args.seed + 4)
    start_cpu_worker("span", span)

    with rec.phase("witnesses") as ph:
        t = stats.tickers()
        big = [s for s in stats.jobs
               if s.remote and s.input_records >= PIPELINE_FLOOR_ROWS]
        ph.update(
            dcompaction_read_bytes=t.get(st.DCOMPACTION_READ_BYTES, 0),
            dcompaction_fallback_local=t.get(
                st.DCOMPACTION_FALLBACK_LOCAL, 0),
            dcompaction_job_failures=t.get(st.DCOMPACTION_JOB_FAILURES, 0),
            device_wait_samples=stats.get_histogram(
                st.COMPACTION_DEVICE_WAIT_MICROS).count,
            jobs_total=len(stats.jobs),
            jobs_remote=sum(s.remote for s in stats.jobs),
            jobs_above_pipeline_floor=len(big),
            largest_job_rows=max(
                (s.input_records for s in stats.jobs), default=0),
            devices_reported=sorted({s.device for s in stats.jobs
                                     if s.remote}),
            jit_compiles=sum(s.jit_compiles for s in stats.jobs),
            jit_cache_hits=sum(s.jit_cache_hits for s in stats.jobs),
            jit_compile_s=round(sum(
                s.jit_compile_usec for s in stats.jobs) / 1e6, 2),
            device_wait_s=round(sum(
                s.device_wait_usec for s in stats.jobs) / 1e6, 2),
            service=svc.get("/stats"),
        )
        check(ph["dcompaction_read_bytes"] > 0, "no job ran remotely")
        check(ph["dcompaction_fallback_local"] == 0, "a job fell back")
        check(ph["dcompaction_job_failures"] == 0, "a remote job failed")
        check(ph["device_wait_samples"] > 0, "no device wait recorded")
        check(ph["devices_reported"] == [device],
              f"jobs report devices {ph['devices_reported']}")
        check(ph["service"]["jobs_failed"] == 0, "the service failed a job")
        if not args.rehearse_cpu:
            check(ph["largest_job_rows"] >= BIG_JOB_ROWS,
                  f"largest job has {ph['largest_job_rows']} rows")
            check(big, "no job above the pipeline's row floor")
        for s in big:
            check(s.pipelined, f"{s.input_records}-row job not pipelined")
            check(s.host_compute_usec == 0,
                  f"{s.input_records}-row job used the host twin")
        check("jax" not in sys.modules, "the DB process imported jax")

    with rec.phase("byte_parity_tpu_vs_cpu_worker") as ph:
        check("params" in capture,
              f"no job of >= {big_rows} rows came by to capture")
        out_tpu = parity(ph, "job", capture)

    with rec.phase("sequence_span_job") as ph:
        # Zeroed rows beside rows past 2^24 and 2^32 in one file range.
        out_span = parity(ph, "span", span)
    svc.stop()

    if dev["count"] > 1:
        with rec.phase("mesh_all_chips") as ph:
            # One service owns every chip of the host (--chips N).
            svc_n = Service(device, workdir, "n", env, chips=dev["count"])
            cleanup.callback(svc_n.stop)
            svc_n.wait_listening()
            mesh_dir = os.path.join(workdir, "job-mesh")
            write_job(mesh_dir, capture, device)
            res = svc_n.post_job(mesh_dir)
            mem = svc_n.get("/stats")["device_memory"]
            ph.update(mesh_chips=res["stats"]["mesh_chips"],
                      mesh_shards=res["stats"]["mesh_shards"],
                      mesh_fallbacks=res["stats"]["mesh_fallbacks"],
                      device_memory=mem)
            if capture["rows"] >= PIPELINE_FLOOR_ROWS:
                check(res["stats"]["mesh_chips"] == dev["count"],
                      f"mesh used {res['stats']['mesh_chips']} chips of "
                      f"{dev['count']}")
                check(res["stats"]["mesh_fallbacks"] == 0, "mesh fell back")
                # This service ran nothing else: a chip with no allocator
                # peak ran no shard. (XLA:CPU keeps no such counters.)
                check(all(m["peak_bytes_in_use"] > 0 for m in mem),
                      f"a chip stayed idle: {mem}")
            else:  # only a tiny rehearsal gets here
                ph["note"] = "job under the pipeline floor: bytes only"
            check_same_bytes(job_outputs(mesh_dir, res), out_tpu,
                             "all chips vs one chip")
            svc_n.stop()

    with rec.phase("fresh_worker_compiles_nothing") as ph:
        # The chip is free now: a new worker process takes it and must
        # find every program of its job in the persistent compile cache.
        for tag, job, out in (("job", capture, out_tpu),
                              ("span", span, out_span)):
            again_dir = os.path.join(workdir, f"{tag}-tpu-again")
            write_job(again_dir, job, device)
            log = os.path.join(workdir, f"worker-{tag}-tpu.log")
            w = spawn_worker(again_dir, env, log)
            cleanup.callback(kill, w)
            res = worker_result(w, again_dir, log, timeout=1500)
            ph[tag] = dict(
                jit_compiles=res["stats"]["jit_compiles"],
                jit_cache_hits=res["stats"]["jit_cache_hits"],
                jit_load_s=round(res["stats"]["jit_compile_usec"] / 1e6, 2))
            check_same_bytes(job_outputs(again_dir, res), out,
                             f"{tag}: fresh worker vs service")
            check(res["stats"]["jit_cache_hits"] > 0, "no program requested")
            check(res["stats"]["jit_compiles"] == 0,
                  f"the fresh worker compiled {res['stats']['jit_compiles']}"
                  " program(s) the service had compiled")

    with rec.phase("pallas_kernels") as ph:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child",
             "kernels", "--child-device", device],
            cwd=HERE, env=env, capture_output=True, timeout=900)
        check(r.returncode == 0,
              f"kernel check failed: {r.stderr[-1500:].decode()}")
        ph.update(json.loads(r.stdout.decode().strip().splitlines()[-1]))

    check("jax" not in sys.modules, "the DB process imported jax")
    return dev


def run_queries(db, wl: Workload, seed: int, gets: int, mget: int,
                scan: int) -> dict:
    """Point gets (hits and misses), one multi_get, one forward scan from
    a seeded start; every answer against the oracle."""
    rng = np.random.default_rng(seed)
    n = wl.n
    out = {}

    def probe_keys(m):
        k = rng.integers(0, n, m, dtype=np.uint64)
        miss = rng.random(m) < 0.2
        k[miss] += np.uint64(n)  # keys n..2n-1 were never written
        return k

    keys = probe_keys(gets)
    want = wl.expected(keys)
    kb = wl.key_bytes(keys).tobytes()
    t0 = time.time()
    for i, w in enumerate(want):
        got = db.get(kb[8 * i:8 * i + 8])
        if got != w:
            raise AssertionError(
                f"get({int(keys[i])}) = {got!r}, oracle {w!r}")
    out["gets"] = gets
    out["get_misses"] = sum(w is None for w in want)
    out["gets_s"] = round(time.time() - t0, 3)

    keys = probe_keys(mget)
    want = wl.expected(keys)
    kb = wl.key_bytes(keys).tobytes()
    got = db.multi_get([kb[8 * i:8 * i + 8] for i in range(mget)])
    check(len(got) == mget, "multi_get length")
    for i, (g, w) in enumerate(zip(got, want)):
        if g != w:
            raise AssertionError(
                f"multi_get[{int(keys[i])}] = {g!r}, oracle {w!r}")
    out["multi_get_keys"] = mget

    start = int(rng.integers(0, max(1, n - scan)))
    ks = np.arange(start, start + scan, dtype=np.uint64)
    want_v = wl.expected(ks)
    want_k = wl.key_bytes(ks).tobytes()
    it = db.new_iterator()
    it.seek(want_k[0:8])
    t0 = time.time()
    for i in range(scan):
        if not it.valid():
            raise AssertionError(f"scan ended after {i} of {scan} entries")
        if (it.key() != want_k[8 * i:8 * i + 8]
                or it.value() != want_v[i]):
            raise AssertionError(
                f"scan entry {i}: {it.key()!r} -> {it.value()!r}, oracle "
                f"{want_k[8 * i:8 * i + 8]!r} -> {want_v[i]!r}")
        it.next()
    out["scan_entries"] = scan
    out["scan_s"] = round(time.time() - t0, 3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keys", type=int, default=DEFAULT_KEYS,
                    help="distinct keys (the source runs 100M); cut only "
                         "as far as the time limit forces")
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="drive the phases on XLA:CPU at a tiny size; the "
                         "record says platform=cpu and is never a pass")
    ap.add_argument("--child", choices=["kernels"], help=argparse.SUPPRESS)
    ap.add_argument("--child-device", default="tpu", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child == "kernels":
        return child_kernels(args.child_device)

    rec = Record()
    root = "/dev/shm" if os.path.isdir("/dev/shm") else None
    workdir = tempfile.mkdtemp(prefix="chip_smoke_", dir=root)
    t0 = time.time()
    dev = None
    try:
        # Every child and the DB are stopped on every way out.
        with contextlib.ExitStack() as cleanup:
            cleanup.callback(shutil.rmtree, workdir, ignore_errors=True)
            dev = run(args, rec, workdir, cleanup)
    except Exception as e:
        print(f"[smoke] FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)

    phases_ok = dev is not None and rec.all_ok()
    record = dict(rec.facts, phases=rec.phases, phases_ok=phases_ok,
                  total_wall_s=round(time.time() - t0, 2),
                  ok=phases_ok and not args.rehearse_cpu)
    if "device" not in rec.facts:
        # JAX found no accelerator (or nothing came up): no result.
        return 2
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps(record))
    if args.rehearse_cpu:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "rehearsal_ok": phases_ok,
                          "device": rec.facts["device"]}))
        return EXIT_REHEARSAL if phases_ok else 1
    if not phases_ok:
        return 1
    print(json.dumps({"ok": True, "device": rec.facts["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
