"""The DB side of the SingleFastTable deployment's cell: what `dbside.py`
has no field for (the table format of every level), the factory that keeps
the window's largest remote job for the reference, and the witness of what
every flush and every compaction installed. `dbside.py` is used as it is
for the rest (options, the timed factory, the per-job statistics, the
load, the device witnesses)."""

from __future__ import annotations

import dataclasses
import threading
import time

from toplingdb_tpu.db import filename
from toplingdb_tpu.options import Options
from toplingdb_tpu.utils.listener import EventListener

from . import dbside, dbside_merge, sft_plain

COUNTERS = ("sft_input_files", "sft_input_rows", "sft_scan_usec",
            "sft_output_files", "sft_output_rows", "sft_output_bytes",
            "sft_build_usec")


def program_lacks() -> str:
    """What the checkout's program lacks to run this cell, or "". The
    cell's `correct` and its metrics read the format's counters from every
    remote job's reply; a program without them cannot be judged, and one
    whose device plane does not plan a SingleFastTable input walks every
    remote job's millions of rows an entry at a time and compiles a device
    program a job size (minutes each): such a checkout must refuse the
    cell at once."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats

    for name in COUNTERS:
        if not hasattr(CompactionStats, name):
            return f"CompactionStats has no {name}"
    return ""


def options(config: dict, sizes: dict, stats, factory, witness) -> Options:
    opts = dbside.options(config, sizes, stats, factory)
    opts.table_options = dataclasses.replace(
        opts.table_options, format=config["table"]["format"],
        hash_index=config["table"]["hash_index"])
    opts.listeners = [witness]
    return opts


class OutputWitness(EventListener):
    """Every table file the DB installs, from a flush or from a compaction
    that wrote files (a trivial move writes none): whether it is a
    SingleFastTable (the footer's magic, read as the flush or the
    compaction completes), and of a compaction also its levels, its rows
    and where it ran."""

    def __init__(self):
        self.flushes = 0
        self.flushes_not_single_fast = 0
        self.jobs: list[dict] = []

    def on_flush_completed(self, db, info) -> None:
        self.flushes += 1
        self.flushes_not_single_fast += not sft_plain.is_single_fast_table(
            filename.table_file_name(db.dbname, info.file_number))

    def on_compaction_completed(self, db, info) -> None:
        if info.device == "move":
            return
        paths = [filename.table_file_name(db.dbname, n)
                 for n in info.output_files]
        self.jobs.append({
            "from": info.input_level, "level": info.output_level,
            "rows": info.input_records,
            "device": info.device, "ms": info.elapsed_micros // 1000,
            "outputs": len(paths),
            "sft_outputs": sum(sft_plain.is_single_fast_table(p)
                               for p in paths)})

    def outputs_not_single_fast(self) -> int:
        return self.flushes_not_single_fast + sum(
            j["outputs"] - j["sft_outputs"] for j in self.jobs)


class SftJobFactory(dbside_merge.LargestJobFactory):
    """The merge cell's factory (timed; keeps, by hard link, the inputs and
    parameters of the largest remote job begun under `watch()`), its kept
    job's parameters naming the deployment's table format. It also notes
    which compactions stayed in the DB process."""

    def __init__(self, url, device, min_input_bytes, dbname, keep_dir,
                 table_format: str):
        super().__init__(url, device, min_input_bytes, dbname, keep_dir,
                         merge_operator=None)
        self.table_format = table_format
        self.kept_local: list[tuple] = []  # (time, level, to, rows, bytes)
        self._keeping = threading.Lock()   # the DB runs two jobs at a time

    def should_run_local(self, compaction) -> bool:
        local = super().should_run_local(compaction)
        if local:   # under min_remote_input_bytes: it runs in this process
            self.kept_local.append((
                time.time(), compaction.level, compaction.output_level,
                sum(f.num_entries for _, f in compaction.all_inputs()),
                compaction.total_input_bytes()))
        return local

    def _keep_if_largest(self, compaction) -> None:
        with self._keeping:
            super()._keep_if_largest(compaction)
            self.largest["params"]["table_format"] = self.table_format
