"""The DB side of the merge deployment's cell: what `dbside.py` has no
field for (universal compaction, the merge operator, 16-byte keys), the
factory that keeps the window's largest remote job for the reference, the
load and the window's write loop. `dbside.py` is used as it is for the
rest (the timed factory, the per-job statistics, the witnesses)."""

from __future__ import annotations

import os
import time

from toplingdb_tpu.db import filename
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.options import Options
from toplingdb_tpu.table.builder import TableOptions
from toplingdb_tpu.utils.cache import LRUCache
from toplingdb_tpu.utils.merge_operator import create_merge_operator

from . import dbside
from .workload_merge import KEY_BYTES, VALUE_BYTES


def program_lacks() -> str:
    """What the checkout's program lacks to run this cell, or "". A
    program without the fold sends every job with operands to the serial
    program, which on the chip compiles for minutes and then fails: such
    a checkout must refuse the cell at once."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats
    from toplingdb_tpu.utils.merge_operator import UInt64AddOperator

    if not hasattr(UInt64AddOperator, "columnar_fold"):
        return "UInt64AddOperator declares no columnar fold"
    if not hasattr(CompactionStats, "merge_operand_rows"):
        return "CompactionStats has no merge_operand_rows"
    return ""


def lsm_sizes(config: dict, scale: float) -> dict:
    """The configuration's sizes; a rehearsal shrinks them with the key
    count so that the same job shapes appear at a tiny size."""
    lsm = config["lsm"]
    f = min(1.0, scale)
    return {
        "keys": max(2000, int(config["keys"] * f)),
        "write_buffer": max(64 << 10, int(lsm["write_buffer_bytes"] * f)),
        "min_input": max(16 << 10, int(lsm["min_remote_input_bytes"] * f)),
    }


def options(config: dict, sizes: dict, stats, factory) -> Options:
    lsm, uni = config["lsm"], config["universal"]
    return Options(
        create_if_missing=True,
        compaction_style=config["compaction_style"],
        merge_operator=create_merge_operator(config["merge_operator"]),
        universal_size_ratio=uni["size_ratio"],
        universal_min_merge_width=uni["min_merge_width"],
        universal_max_merge_width=uni["max_merge_width"],
        universal_max_size_amplification_percent=uni[
            "max_size_amplification_percent"],
        compression=dbside.COMPRESSION[config["table"]["compression"]],
        table_options=TableOptions(block_size=config["table"]["block_bytes"]),
        write_buffer_size=sizes["write_buffer"],
        level0_file_num_compaction_trigger=lsm["l0_compaction_trigger"],
        level0_slowdown_writes_trigger=lsm["l0_slowdown_trigger"],
        level0_stop_writes_trigger=lsm["l0_stop_trigger"],
        num_levels=lsm["num_levels"],
        block_cache=LRUCache(config["block_cache_bytes"]),
        dcompact=dbside.ONE_ATTEMPT,
        **({"statistics": stats} if stats is not None else {}),
        **({"compaction_executor_factory": factory}
           if factory is not None else {}))


class LargestJobFactory(dbside.TimedFactory):
    """The timed factory, also keeping (by hard link, taken before the DB
    can delete them) the inputs and parameters of the largest remote job
    begun while `watch()` is on: the one the reference reads after the
    window."""

    def __init__(self, url, device, min_input_bytes, dbname, keep_dir,
                 merge_operator: str):
        super().__init__(url, device, min_input_bytes, dbname=dbname)
        self.keep_dir = keep_dir
        self.merge_operator = merge_operator
        self.watching = False
        self.largest = None     # {"rows", "links", "params"}
        self.kept = 0

    def watch(self, on: bool = True) -> None:
        """On as the window opens, off as it closes: a job begun later (the
        reopened DB compacts what it recovers) is not the window's, and
        keeping it would unlink the kept inputs under the reference."""
        self.watching = on

    def new_executor(self, compaction):
        ex = super().new_executor(compaction)
        if ex is not None and self.watching:
            self._keep_if_largest(compaction)
        return ex

    def _keep_if_largest(self, compaction) -> None:
        rows = sum(f.num_entries for _, f in compaction.all_inputs())
        if self.largest is not None and rows <= self.largest["rows"]:
            return
        self.kept += 1
        links = []
        for _, f in compaction.all_inputs():
            src = filename.table_file_name(self.dbname, f.number)
            dst = os.path.join(self.keep_dir, f"j{self.kept:03d}-"
                               + os.path.basename(src))
            os.link(src, dst)
            links.append(dst)
        for old in (self.largest or {}).get("links", []):
            os.unlink(old)
        self.largest = {"rows": rows, "links": links, "params": {
            **dbside.job_params(
                self.kept, self.dbname, links, compaction.output_level,
                compaction.bottommost, compaction.max_output_file_size),
            "merge_operator": self.merge_operator}}


def load(db, kb: bytes, vb: bytes, tb: bytes, te: bytes, tomb_at, n: int,
         per_batch: int) -> int:
    """The fill: pre-encoded puts 0..n-1, per_batch to a WriteBatch, and
    every DeleteRange due among them. After each batch the load waits for
    the compactions it may have triggered (a flush happens inside a write,
    and the pick follows it), so the sorted runs it leaves do not depend on
    how long a job took. Returns the next tombstone."""
    K, V = KEY_BYTES, VALUE_BYTES
    t = 0
    for b0 in range(0, n, per_batch):
        wb = WriteBatch()
        b1 = min(b0 + per_batch, n)
        for j in range(b0, b1):
            wb.put(kb[K * j:K * j + K], vb[V * j:V * j + V])
        db.write(wb)
        while t < len(tomb_at) and tomb_at[t] <= b1:
            db.delete_range(tb[K * t:K * t + K], te[K * t:K * t + K])
            t += 1
        db.wait_for_compactions()
    return t


def merge_window(db, kb: bytes, vb: bytes, tb: bytes, te: bytes, tomb_at,
                 t: int, first: int, end: int, per_batch: int,
                 seconds: float, stop=None, clock=time.perf_counter):
    """The window: pre-encoded operands first.. go out per_batch to a
    WriteBatch, closed loop, and each DeleteRange when its turn has come,
    as a write of its own. It closes at the first batch boundary at or
    after `seconds`, or when the stream ends, or (the set-up's use) at
    the first batch boundary where `stop()` holds. Returns (each batch's
    latency, span, next write index, next tombstone)."""
    K, V = KEY_BYTES, VALUE_BYTES
    write, delete_range = db.write, db.delete_range
    n_t = len(tomb_at)
    lat = []
    w = first
    c0 = clock()
    while True:
        wb = WriteBatch()
        for j in range(w, w + per_batch):
            wb.merge(kb[K * j:K * j + K], vb[V * j:V * j + V])
        a = clock()
        write(wb)
        b = clock()
        lat.append(b - a)
        w += per_batch
        if t < n_t and tomb_at[t] <= w:
            while t < n_t and tomb_at[t] <= w:
                delete_range(tb[K * t:K * t + K], te[K * t:K * t + K])
                t += 1
            b = clock()
        if (w + per_batch > end or b - c0 >= seconds
                or (stop is not None and stop())):
            return lat, b - c0, w, t
