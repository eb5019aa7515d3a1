"""The DB side of a cell: options from the configuration's file, the
executor factory with the harness's own clock around each remote job, the
per-job statistics, and the put loop. Pieces copied from chip_smoke.py
(`CapturingFactory`, `JobStatistics`, `write_range`); PERF.md lists the
originals."""

from __future__ import annotations

import os
import time

from toplingdb_tpu.compaction.dcompact_service import (
    HttpCompactionExecutorFactory,
)
from toplingdb_tpu.compaction.resilience import DcompactOptions
from toplingdb_tpu.db import dbformat, filename
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.options import Options
from toplingdb_tpu.table import format as fmt
from toplingdb_tpu.table.builder import TableOptions
from toplingdb_tpu.utils import statistics as st
from toplingdb_tpu.utils.cache import LRUCache

from .workload import KEY_BYTES, VALUE_BYTES

# Above this many input rows a job has >= 2 pipeline shards
# (ops/pipeline.py::_pipeline_shards), so it must run pipelined.
PIPELINE_FLOOR_ROWS = (1 << 19) + 1
COMPRESSION = {"snappy": fmt.SNAPPY_COMPRESSION, "none": fmt.NO_COMPRESSION}


class JobStatistics(st.Statistics):
    """The DB's Statistics, also keeping each job's CompactionStats."""

    def __init__(self):
        super().__init__()
        self.jobs = []

    def record_compaction(self, stats):
        self.jobs.append(stats)
        super().record_compaction(stats)


ONE_ATTEMPT = DcompactOptions(max_attempts=1)


class TimedFactory(HttpCompactionExecutorFactory):
    """No fallback to a local compaction, one attempt (the factory's
    policy here, and `Options.dcompact` in `options`, from which
    `execute_resilient` takes its attempts): a device failure fails the
    run. Keeps the harness-clock interval of every remote job
    (`spans`), and with `capture_dir` the inputs and parameters of each
    (hard links, taken before the DB can delete them)."""

    def __init__(self, url: str, device: str, min_input_bytes: int,
                 dbname: str = "", capture_dir: str = ""):
        super().__init__([url], device=device, allow_fallback=False,
                         min_input_bytes=min_input_bytes,
                         policy=ONE_ATTEMPT)
        self.spans: list[tuple[float, float]] = []
        self.failed = 0                # remote jobs whose execute raised
        self.dbname = dbname
        self.capture_dir = capture_dir
        self.captured: list[dict] = []

    def new_executor(self, compaction):
        ex = super().new_executor(compaction)
        if self.capture_dir:
            self._capture(compaction)
        if ex is not None:
            execute = ex.execute

            def timed_execute(*a, **kw):
                t0 = time.time()
                try:
                    return execute(*a, **kw)
                except BaseException:
                    self.failed += 1   # the DB may swallow it at close
                    raise
                finally:
                    self.spans.append((t0, time.time()))

            ex.execute = timed_execute
        return ex

    def _capture(self, compaction) -> None:
        n = len(self.captured)
        links = []
        for _, f in compaction.all_inputs():
            src = filename.table_file_name(self.dbname, f.number)
            dst = os.path.join(self.capture_dir,
                               f"j{n:03d}-" + os.path.basename(src))
            os.link(src, dst)
            links.append(dst)
        self.captured.append({
            "rows": sum(f.num_entries for _, f in compaction.all_inputs()),
            "params": job_params(
                n + 1, self.dbname, links, compaction.output_level,
                compaction.bottommost, compaction.max_output_file_size)})


def job_params(job_id: int, dbname: str, input_files: list, output_level: int,
               bottommost: bool, max_output_file_size: int) -> dict:
    """CompactionParams of a job of this deployment, less device and
    output directory (the submitter sets those per run)."""
    return dict(
        job_id=job_id, attempt=0, dbname=dbname, output_dir="",
        input_files=input_files, output_level=output_level,
        bottommost=bottommost, max_output_file_size=max_output_file_size,
        snapshots=[], comparator=dbformat.BYTEWISE.name(),
        merge_operator=None, compaction_filter=None,
        compression=fmt.SNAPPY_COMPRESSION, block_size=4096,
        creation_time=1_700_000_000, lease_sec=0.0)


def lsm_sizes(config: dict, scale: float) -> dict:
    """The configuration's LSM sizes; a rehearsal shrinks them with the key
    count so that the same job shapes appear at a tiny size."""
    lsm = config["lsm"]
    f = min(1.0, scale)
    return {
        "keys": max(2000, int(config["keys"] * f)),
        "write_buffer": max(64 << 10, int(lsm["write_buffer_bytes"] * f)),
        "target_file": max(64 << 10, int(lsm["target_file_bytes"] * f)),
        "level_base": max(256 << 10, int(lsm["level_base_bytes"] * f)),
        "min_input": max(16 << 10, int(lsm["min_remote_input_bytes"] * f)),
    }


def options(config: dict, sizes: dict, stats, factory) -> Options:
    lsm = config["lsm"]
    return Options(
        create_if_missing=True,
        compression=COMPRESSION[config["table"]["compression"]],
        table_options=TableOptions(block_size=config["table"]["block_bytes"]),
        write_buffer_size=sizes["write_buffer"],
        target_file_size_base=sizes["target_file"],
        max_bytes_for_level_base=sizes["level_base"],
        max_bytes_for_level_multiplier=lsm["level_multiplier"],
        level0_file_num_compaction_trigger=lsm["l0_compaction_trigger"],
        num_levels=lsm["num_levels"],
        block_cache=LRUCache(config["block_cache_bytes"]),
        dcompact=ONE_ATTEMPT,
        **({"statistics": stats} if stats is not None else {}),
        **({"compaction_executor_factory": factory}
           if factory is not None else {}))


def put_batches(db, kb: bytes, vb: bytes, n: int, per_batch: int) -> None:
    """n pre-encoded puts, per_batch to a WriteBatch (set-up loads)."""
    K, V = KEY_BYTES, VALUE_BYTES
    for b0 in range(0, n, per_batch):
        wb = WriteBatch()
        for j in range(b0, min(b0 + per_batch, n)):
            wb.put(kb[K * j:K * j + K], vb[V * j:V * j + V])
        db.write(wb)


def stream_ran_out(w: int, per_batch: int, end: int, span: float,
                   seconds: float) -> bool:
    """Did a closed-loop window end because the pre-encoded stream did (the
    next batch would pass `end`) before `seconds` were up? Such a run's
    writer outran the mix's ceiling: it is not correct (`stream_ran_out`)."""
    return w + per_batch > end and span < seconds


def off_device(want: str, device: str, rows: int, pipelined: bool,
               host_compute_usec: int) -> bool:
    """Did a remote job run anywhere but on the device path of `want`?"""
    return device != want or (
        rows >= PIPELINE_FLOOR_ROWS
        and (not pipelined or host_compute_usec != 0))


def device_witnesses(jobs, device: str) -> dict:
    """What the per-job statistics say about where remote jobs ran."""
    remote = [s for s in jobs if s.remote]
    return {
        "remote_jobs": len(remote),
        "jobs_off_device": sum(
            off_device(device, s.device, s.input_records, s.pipelined,
                       s.host_compute_usec) for s in remote),
    }
