"""UniversalCompactionPicker against the reference's three rules
(compaction_picker_universal.cc): size amplification, size ratio from the
newest run, and the run-count fallback."""

import pytest

from toplingdb_tpu.compaction.picker import UniversalCompactionPicker
from toplingdb_tpu.db.dbformat import (
    InternalKeyComparator, ValueType, make_internal_key,
)
from toplingdb_tpu.db.version_edit import FileMetaData
from toplingdb_tpu.options import Options


class FakeVersion:
    num_levels = 7

    def __init__(self, l0_sizes, base_size=0):
        # L0 newest first: the newest holds the largest sequences.
        n = len(l0_sizes)
        self.files = [[] for _ in range(self.num_levels)]
        for i, size in enumerate(l0_sizes):
            self.files[0].append(self._meta(100 - i, size, (n - i) * 1000))
        if base_size:
            self.files[6].append(self._meta(7, base_size, 0))

    @staticmethod
    def _meta(number, size, seq):
        return FileMetaData(
            number=number, file_size=size,
            smallest=make_internal_key(b"a", seq + 1, ValueType.VALUE),
            largest=make_internal_key(b"z", seq + 1, ValueType.VALUE),
            smallest_seqno=seq, largest_seqno=seq + 1)

    def overlapping_files(self, level, lo, hi):
        return list(self.files[level])


def pick(l0_sizes, base_size=0, **kw):
    opts = Options(compaction_style="universal",
                   level0_file_num_compaction_trigger=4, **kw)
    v = FakeVersion(l0_sizes, base_size)
    c = UniversalCompactionPicker(opts, InternalKeyComparator()) \
        .pick_compaction(v)
    return v, c


CASES = [
    # L0 sizes newest first, base size -> (reason, picked L0 indexes,
    # with the base, output level, bottommost)
    ("below_trigger", [10, 10, 10], 0, None),
    ("equal_runs_amplify", [10, 10, 10, 10], 0,
     ("universal size-amp", [0, 1, 2, 3], False, 6, True)),
    ("large_old_run_waits", [10, 10, 10, 45], 0,
     ("universal size-ratio", [0, 1, 2], False, 0, False)),
    ("tiers_hold_at_the_trigger", [10, 21, 45, 100], 0, None),
    ("run_count_merges_the_newest", [10, 21, 45, 100, 220], 0,
     ("universal run-count", [0, 1], False, 0, False)),
    ("older_neighbours_merge", [10, 50, 50, 120], 0,
     ("universal size-ratio", [1, 2], False, 0, False)),
    ("the_oldest_run_joins", [10, 50, 50, 100], 0,
     ("universal size-ratio", [1, 2, 3], False, 0, True)),
    ("size_amp_merges_everything", [10, 10, 10, 10], 20,
     ("universal size-amp", [0, 1, 2, 3], True, 6, True)),
    ("size_amp_without_a_last_level_run", [30, 30, 30, 40], 0,
     ("universal size-amp", [0, 1, 2, 3], False, 6, True)),
    ("ratio_reaches_the_last_level_run", [10, 10, 10, 10], 38,
     ("universal size-ratio", [0, 1, 2, 3], True, 6, True)),
    ("last_level_run_waits", [10, 10, 10, 10], 45,
     ("universal size-ratio", [0, 1, 2, 3], False, 0, False)),
]


@pytest.mark.parametrize("name,l0,base,want", CASES,
                         ids=[c[0] for c in CASES])
def test_universal_pick(name, l0, base, want):
    v, c = pick(l0, base)
    if want is None:
        assert c is None
        return
    reason, idx, with_base, out_level, bottommost = want
    assert c is not None and c.reason == reason
    assert [f.number for f in c.inputs] == [v.files[0][i].number
                                            for i in idx]
    assert bool(c.output_level_inputs) == with_base
    assert c.output_level == out_level
    assert c.bottommost == bottommost


def test_a_busy_run_holds_every_pick():
    opts = Options(compaction_style="universal",
                   level0_file_num_compaction_trigger=4)
    v = FakeVersion([10, 10, 10, 10], 20)
    v.files[6][0].being_compacted = True
    assert UniversalCompactionPicker(
        opts, InternalKeyComparator()).pick_compaction(v) is None
