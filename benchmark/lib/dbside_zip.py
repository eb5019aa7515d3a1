"""The DB side of the cold-level deployment's cell: what `dbside.py` has no
field for (the cold level's format), the factory that keeps the window's
largest bottommost job with a ZipTable among its inputs for the reference,
and the witness of what every compaction wrote. `dbside.py` is used as it
is for the rest (options, the timed factory, the per-job statistics, the
load, the device witnesses)."""

from __future__ import annotations

import threading
import time

from toplingdb_tpu.db import filename
from toplingdb_tpu.options import Options
from toplingdb_tpu.utils.listener import EventListener

from . import dbside, dbside_merge, zip_plain

COUNTERS = ("zip_input_files", "zip_input_rows", "zip_scan_usec",
            "zip_output_files", "zip_output_bytes", "zip_output_raw_bytes",
            "zip_encode_usec", "zip_dict_train_usec")


def program_lacks() -> str:
    """What the checkout's program lacks to run this cell, or "". The
    cell's `correct` and its metrics read the cold format's counters from
    every remote job's reply; a program without them cannot be judged,
    and one without the entry-range plan fails its write path at the
    second compaction into the cold level (`HTTP Error 500`): such a
    checkout must refuse the cell at once."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats

    for name in COUNTERS:
        if not hasattr(CompactionStats, name):
            return f"CompactionStats has no {name}"
    return ""


def options(config: dict, sizes: dict, stats, factory, witness) -> Options:
    opts = dbside.options(config, sizes, stats, factory)
    opts.bottommost_format = config["table"]["bottommost_format"]
    opts.listeners = [witness]
    return opts


class OutputWitness(EventListener):
    """For every compaction that wrote files (a trivial
    move writes none), the level it wrote to, its rows, where it ran, and
    how many of its outputs are ZipTables (the footer's magic, read as the
    compaction completes). Whether a compaction was bottommost is the
    factory's to say: the DB asks it about every one."""

    def __init__(self, factory):
        self.factory = factory
        self.jobs: list[dict] = []

    def on_compaction_completed(self, db, info) -> None:
        if info.device == "move":
            return
        paths = [filename.table_file_name(db.dbname, n)
                 for n in info.output_files]
        self.jobs.append({
            "from": info.input_level, "level": info.output_level,
            "rows": info.input_records,
            "device": info.device, "ms": info.elapsed_micros // 1000,
            "bottommost": self.factory.bottommost.get(
                tuple(sorted(info.input_files))),
            "outputs": len(paths),
            "zip_outputs": sum(zip_plain.is_zip_table(p) for p in paths)})

    def bottommost_outputs_not_zip(self) -> int:
        return sum(j["outputs"] - j["zip_outputs"] for j in self.jobs
                   if j["bottommost"] is not False)


class ColdJobFactory(dbside_merge.LargestJobFactory):
    """The merge cell's factory (timed; keeps, by hard link, the inputs and
    parameters of the largest remote job begun under `watch()`), here keeping
    only bottommost jobs that have a ZipTable among their inputs: the one
    the reference reads after the window. It also notes of every
    compaction the DB asks it about (each one that is no trivial move)
    whether it is bottommost, and which ones stayed in the DB process."""

    def __init__(self, url, device, min_input_bytes, dbname, keep_dir):
        super().__init__(url, device, min_input_bytes, dbname, keep_dir,
                         merge_operator=None)
        self.bottommost: dict[tuple, bool] = {}
        self.kept_local: list[tuple] = []  # (time, level, to, rows, bytes)
        self._keeping = threading.Lock()   # the DB runs two jobs at a time

    def should_run_local(self, compaction) -> bool:
        self.bottommost[tuple(sorted(
            f.number for _, f in compaction.all_inputs()))] = bool(
                compaction.bottommost)
        local = super().should_run_local(compaction)
        if local:   # under min_remote_input_bytes: it runs in this process
            self.kept_local.append((
                time.time(), compaction.level, compaction.output_level,
                sum(f.num_entries for _, f in compaction.all_inputs()),
                compaction.total_input_bytes()))
        return local

    def _keep_if_largest(self, compaction) -> None:
        if not compaction.bottommost or not any(
                zip_plain.is_zip_table(
                    filename.table_file_name(self.dbname, f.number))
                for _, f in compaction.all_inputs()):
            return
        with self._keeping:
            super()._keep_if_largest(compaction)
            self.largest["params"]["table_format"] = "zip"
