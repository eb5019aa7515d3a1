"""Compaction picking: which files to merge next.

Leveled strategy mirrors the reference's score-driven picker
(db/compaction/compaction_picker_level.cc in /root/reference): L0 scores by
file count against the trigger, L1+ by level bytes against the target; the
highest-scoring level compacts into level+1, expanding inputs to all
overlapping files. Universal and FIFO pickers cover the other two styles
(reference compaction_picker_universal.cc, compaction_picker_fifo.cc).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from toplingdb_tpu.db import dbformat
from toplingdb_tpu.db.version_edit import FileMetaData
from toplingdb_tpu.db.version_set import Version


def _busy(f) -> bool:
    """A file the picker must not touch: already in a running job, or
    quarantined by the IntegrityScrubber (db/integrity.py) — corrupt
    bytes must never be merged into new SSTs."""
    return f.being_compacted or f.quarantined


@dataclass
class Compaction:
    """A picked compaction: inputs at `level` (+ overlapping at output_level),
    producing files at output_level (reference db/compaction/compaction.h)."""

    level: int
    output_level: int
    inputs: list[FileMetaData]          # files at `level`
    output_level_inputs: list[FileMetaData] = field(default_factory=list)
    bottommost: bool = False
    reason: str = ""
    max_output_file_size: int = 8 * 1024 * 1024
    cf_id: int = 0
    # User-defined-timestamp history trim point (reference
    # full_history_ts_low / increase_full_history_ts_low): among versions
    # with ts < this, only the newest survives compaction. 0 = keep all.
    full_history_ts_low: int = 0

    def all_inputs(self) -> list[tuple[int, FileMetaData]]:
        return [(self.level, f) for f in self.inputs] + [
            (self.output_level, f) for f in self.output_level_inputs
        ]

    def total_input_bytes(self) -> int:
        return sum(f.file_size for _, f in self.all_inputs())

    def num_input_files(self) -> int:
        return len(self.inputs) + len(self.output_level_inputs)


class CompactionPicker:
    def __init__(self, options, icmp):
        self.options = options
        self.icmp = icmp

    def compaction_score(self, version: Version) -> list[tuple[float, int]]:
        raise NotImplementedError

    def pick_compaction(self, version: Version) -> Compaction | None:
        raise NotImplementedError

    # -- shared helpers -------------------------------------------------

    def _key_range(self, files) -> tuple[bytes, bytes]:
        smallest = min((f.smallest for f in files), key=self.icmp.sort_key)
        largest = max((f.largest for f in files), key=self.icmp.sort_key)
        return smallest, largest

    def _expand_range_to_level(self, version: Version, level: int,
                               smallest: bytes, largest: bytes) -> list[FileMetaData]:
        """All files at `level` overlapping [smallest, largest] (internal
        keys) — INCLUDING being_compacted ones, so callers can detect a
        conflict with a running job and abort the pick (silently omitting
        them would produce overlapping outputs)."""
        su = dbformat.extract_user_key(smallest)
        lu = dbformat.extract_user_key(largest)
        return version.overlapping_files(level, su, lu)

    def _is_bottommost(self, version: Version, output_level: int,
                       smallest: bytes, largest: bytes) -> bool:
        ucmp = self.icmp.user_comparator
        su = dbformat.extract_user_key(smallest)
        lu = dbformat.extract_user_key(largest)
        for lvl in range(output_level + 1, version.num_levels):
            if version.overlapping_files(lvl, su, lu):
                return False
        return True



class LeveledCompactionPicker(CompactionPicker):
    def compaction_score(self, version: Version) -> list[tuple[float, int]]:
        """(score, level) sorted descending; score >= 1.0 needs compaction
        (reference VersionStorageInfo::ComputeCompactionScore)."""
        scores = []
        l0 = [f for f in version.files[0] if not _busy(f)]
        l0_score = len(l0) / self.options.level0_file_num_compaction_trigger
        if any(f.marked_for_compaction for f in l0):
            l0_score = max(l0_score, 1.0)
        scores.append((l0_score, 0))
        last = version.num_levels - 1
        if any(f.marked_for_compaction and not _busy(f)
               for f in version.files[last]):
            # Bottommost marked files are rewritten in place (reference
            # bottommost_files_marked_for_compaction_).
            scores.append((1.0, last))
        for level in range(1, version.num_levels - 1):
            total = sum(
                f.file_size for f in version.files[level] if not _busy(f)
            )
            score = total / self.options.max_bytes_for_level(level)
            if any(f.marked_for_compaction and not _busy(f)
                   for f in version.files[level]):
                # Collector-flagged files (reference
                # files_marked_for_compaction_) force the level eligible.
                score = max(score, 1.0)
            scores.append((score, level))
        scores.sort(key=lambda s: -s[0])
        return scores

    def pick_compaction(self, version: Version) -> Compaction | None:
        for score, level in self.compaction_score(version):
            if score < 1.0:
                break
            c = self._pick_level(version, level)
            if c is not None:
                return c
        return None

    # Reference kMinFilesForIntraL0Compaction.
    _INTRA_L0_MIN_FILES = 4

    def _try_intra_l0(self, version: Version) -> Compaction | None:
        """L0→L0 merge of the newest CONTIGUOUS run of free files
        (reference TryPickIntraL0Compaction, compaction_picker.cc): L0
        files hold disjoint seqno intervals in newest-first order, so a
        contiguous prefix merges into one file that slots back at its
        position; non-contiguous picks could interleave seqnos."""
        run = []
        total = 0
        cap = self.options.max_compaction_bytes or (1 << 62)
        for f in version.files[0]:  # newest-first
            if _busy(f):
                break
            if total + f.file_size > cap and run:
                break
            run.append(f)
            total += f.file_size
        if len(run) < self._INTRA_L0_MIN_FILES:
            return None
        return Compaction(
            level=0, output_level=0, inputs=run, output_level_inputs=[],
            bottommost=False, reason="intra-L0",
            max_output_file_size=1 << 62,  # one output file
        )

    def _pick_level(self, version: Version, level: int) -> Compaction | None:
        if level == version.num_levels - 1:
            # In-place rewrite of a collector-marked bottommost file.
            marked = [f for f in version.files[level]
                      if f.marked_for_compaction and not _busy(f)]
            if not marked:
                return None
            f0 = marked[0]
            return Compaction(
                level=level, output_level=level, inputs=[f0],
                output_level_inputs=[], bottommost=True,
                reason="bottommost marked",
                max_output_file_size=self.options.target_file_size(level),
            )
        if level == 0:
            inputs = [f for f in version.files[0] if not _busy(f)]
            if (len(inputs) < self.options.level0_file_num_compaction_trigger
                    and not any(f.marked_for_compaction for f in inputs)):
                return None
            if not inputs or any(_busy(f) for f in version.files[0]):
                # L0→L1 must take all L0 files; while some are busy,
                # compact the free newest prefix L0→L0 instead
                # (reference TryPickIntraL0Compaction) so read-amp and
                # the L0 stall triggers keep falling.
                return self._try_intra_l0(version)
            output_level = 1
        else:
            # Pick the largest not-being-compacted file (simple heuristic;
            # the reference uses kByCompensatedSize by default).
            candidates = [f for f in version.files[level] if not _busy(f)]
            if not candidates:
                return None
            marked = [f for f in candidates if f.marked_for_compaction]
            inputs = [max(marked or candidates, key=lambda f: f.file_size)]
            output_level = level + 1
        if output_level >= version.num_levels:
            return None
        smallest, largest = self._key_range(inputs)
        if level > 0:
            # Expand inputs at the same level to cover the user-key range
            # fully; abort on conflict with a running job.
            more = self._expand_range_to_level(version, level, smallest, largest)
            if any(_busy(f) for f in more):
                return None
            merged = {f.number: f for f in inputs + more}
            inputs = sorted(merged.values(), key=lambda f: f.number)
            smallest, largest = self._key_range(inputs)
        outputs = self._expand_range_to_level(version, output_level, smallest, largest)
        if any(_busy(f) for f in outputs):
            return self._try_intra_l0(version) if level == 0 else None
        all_small, all_large = self._key_range(inputs + outputs) if outputs else (smallest, largest)
        return Compaction(
            level=level,
            output_level=output_level,
            inputs=inputs,
            output_level_inputs=outputs,
            bottommost=self._is_bottommost(version, output_level, all_small, all_large),
            reason=f"L{level} score",
            max_output_file_size=self.options.target_file_size(output_level),
        )


class UniversalCompactionPicker(CompactionPicker):
    """Size-tiered universal compaction over sorted runs (reference
    compaction_picker_universal.cc). The runs, newest first: every L0 file,
    then the last level when it holds files (one full-keyspace run). A pick
    merges runs that are neighbours in age, so the output takes their
    place in that order (L0 sorts by largest sequence).

    In the reference's order: (1) size amplification — all the younger runs
    against the oldest; past `universal_max_size_amplification_percent`
    everything merges into the last level; (2) size ratio — from the
    newest run on, a run's older neighbour joins while the candidates' total
    size, plus `universal_size_ratio` percent, reaches the neighbour's
    (PickCompactionToReduceSortedRuns: similar-sized young runs merge, a
    large old run waits until the young ones have grown to it); (3) with
    more runs than the trigger and no such neighbours, the newest runs
    merge whatever their sizes, as many as bring the count back under
    the trigger."""

    def compaction_score(self, version: Version) -> list[tuple[float, int]]:
        n = len(version.files[0])
        return [(n / max(1, self.options.level0_file_num_compaction_trigger), 0)]

    def pick_compaction(self, version: Version) -> Compaction | None:
        opts = self.options
        l0 = list(version.files[0])
        trigger = opts.level0_file_num_compaction_trigger
        if len(l0) < trigger or any(_busy(f) for f in l0):
            return None
        base = list(version.files[version.num_levels - 1])
        if any(_busy(f) for f in base):
            return None
        runs = [([f], f.file_size) for f in l0]
        if base:
            runs.append((base, sum(f.file_size for f in base)))
        # 1. Size amplification: the younger runs against the oldest.
        oldest = runs[-1][1]
        younger = sum(size for _, size in runs[:-1])
        if len(runs) > 1 and oldest > 0 and younger * 100 >= (
                opts.universal_max_size_amplification_percent * oldest):
            return self._merge(version, l0, base, runs, "universal size-amp",
                               into_last_level=True)
        # 2. Size ratio, from the newest run on.
        width = max(2, opts.universal_min_merge_width)
        for first in range(len(runs) - 1):
            n, size = 1, runs[first][1]
            while (first + n < len(runs)
                   and n < opts.universal_max_merge_width
                   and size * (100 + opts.universal_size_ratio)
                   >= runs[first + n][1] * 100):
                size += runs[first + n][1]
                n += 1
            if n >= width:
                return self._merge(version, l0, base, runs[first:first + n],
                                   "universal size-ratio")
        # 3. Too many runs: the newest merge, whatever their sizes.
        n = min(len(l0) - trigger + 1, opts.universal_max_merge_width)
        if n >= 2:
            return self._merge(version, l0, base, runs[:n],
                               "universal run-count")
        return None

    def _merge(self, version, l0, base, picked, reason,
               into_last_level=False) -> Compaction:
        """The compaction of `picked`, neighbours in age. With the last
        level's run among them (or for size amplification) the output goes
        to the last level and nothing older exists; else it stays in L0,
        bottommost only when it holds the oldest L0 run and no level
        beneath overlaps it."""
        files = [f for run, _ in picked for f in run]
        with_base = bool(base) and files[-1] is base[-1]
        inputs = files[:len(files) - len(base)] if with_base else files
        if with_base or into_last_level:
            return Compaction(
                level=0, output_level=version.num_levels - 1, inputs=inputs,
                output_level_inputs=base if with_base else [],
                bottommost=with_base or not base, reason=reason,
                max_output_file_size=2**62,
            )
        bottom = inputs[-1] is l0[-1] and self._is_bottommost(
            version, 0, *self._key_range(inputs))
        return Compaction(
            level=0, output_level=0, inputs=inputs, bottommost=bottom,
            reason=reason, max_output_file_size=2**62,
        )


class FIFOCompactionPicker(CompactionPicker):
    """Drop oldest files when total size exceeds the budget, or when older
    than fifo_ttl_seconds (reference compaction_picker_fifo.cc incl.
    CompactionOptionsFIFO.ttl). Deletion-only: output nothing.
    `creation_time_fn` (set by the scheduler) reads a file's creation time
    from its cached table properties."""

    creation_time_fn = None  # f -> unix time | None

    def compaction_score(self, version: Version) -> list[tuple[float, int]]:
        total = sum(f.file_size for f in version.files[0])
        score = total / max(1, self.options.fifo_max_table_files_size)
        if self._ttl_expired(version):
            score = max(score, 1.0)
        return [(score, 0)]

    def _ttl_expired(self, version: Version) -> list:
        ttl = self.options.fifo_ttl_seconds
        if not ttl or self.creation_time_fn is None:
            return []
        import time as _t

        cutoff = int(_t.time()) - ttl
        out = []
        for f in version.files[0]:
            if _busy(f):
                continue
            ct = self.creation_time_fn(f)
            if ct and ct <= cutoff:
                out.append(f)
        return out

    def pick_compaction(self, version: Version) -> Compaction | None:
        expired = self._ttl_expired(version)
        if expired:
            return Compaction(
                level=0, output_level=0, inputs=expired, reason="fifo ttl",
            )
        total = sum(f.file_size for f in version.files[0])
        if total <= self.options.fifo_max_table_files_size:
            return None
        # files[0] is newest-first; drop from the tail (oldest).
        drop = []
        for f in reversed(version.files[0]):
            if _busy(f):
                break
            drop.append(f)
            total -= f.file_size
            if total <= self.options.fifo_max_table_files_size:
                break
        if not drop:
            return None
        return Compaction(
            level=0, output_level=0, inputs=drop, reason="fifo ttl/size",
        )


def create_picker(options, icmp) -> CompactionPicker:
    style = options.compaction_style
    if style == "leveled":
        return LeveledCompactionPicker(options, icmp)
    if style == "universal":
        return UniversalCompactionPicker(options, icmp)
    if style == "fifo":
        return FIFOCompactionPicker(options, icmp)
    from toplingdb_tpu.utils.status import InvalidArgument

    raise InvalidArgument(f"unknown compaction style {style!r}")
