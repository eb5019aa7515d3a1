"""Range-deletion tombstones: fragmenting and aggregation.

Roles match the reference's FragmentedRangeTombstoneIterator /
RangeDelAggregator (db/range_tombstone_fragmenter.h:135,
db/range_del_aggregator.h:284-407 in /root/reference). A tombstone is
(seq, begin_user_key inclusive, end_user_key exclusive). The aggregator
answers "is this (key, seqno) shadowed by a newer tombstone?" for reads and
compaction, and yields fragments for writing tombstones into output SSTs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

from toplingdb_tpu.db import dbformat
from toplingdb_tpu.db.dbformat import ValueType


@dataclass(frozen=True)
class RangeTombstone:
    seq: int
    begin: bytes  # user key, inclusive
    end: bytes    # user key, exclusive

    def to_table_entry(self) -> tuple[bytes, bytes]:
        """(internal begin key, end user key) as stored in SST meta blocks."""
        return (
            dbformat.make_internal_key(self.begin, self.seq, ValueType.RANGE_DELETION),
            self.end,
        )

    @staticmethod
    def from_table_entry(begin_ikey: bytes, end_user_key: bytes) -> "RangeTombstone":
        uk, seq, t = dbformat.split_internal_key(begin_ikey)
        assert t == ValueType.RANGE_DELETION, t
        return RangeTombstone(seq, uk, end_user_key)


def fragment_tombstones(tombstones: list[RangeTombstone], ucmp) -> list[RangeTombstone]:
    """Split overlapping tombstones into non-overlapping fragments, keeping
    for each fragment every distinct seqno whose original tombstone covers it
    (reference range_tombstone_fragmenter.cc). Output sorted by (begin, -seq);
    only fragments are emitted (empty input → empty output).

    One sweep over the boundary points in the comparator's order: a
    tombstone's seqno joins the live set at its begin and leaves it at its
    end, so n tombstones cost n log n comparisons (a deployment that
    deletes a range every few thousand writes brings thousands to one
    compaction)."""
    if not tombstones:
        return []
    # Bytewise keys sort as bytes do; any other order goes by compare().
    points = sorted(
        {t.begin for t in tombstones} | {t.end for t in tombstones},
        key=None if type(ucmp) is dbformat.Comparator
        else functools.cmp_to_key(ucmp.compare),
    )
    index = {p: i for i, p in enumerate(points)}
    opens: list[list[int]] = [[] for _ in points]
    closes: list[list[int]] = [[] for _ in points]
    for t in tombstones:
        if index[t.begin] < index[t.end]:
            opens[index[t.begin]].append(t.seq)
            closes[index[t.end]].append(t.seq)
    live: dict[int, int] = {}  # seqno -> tombstones holding it open
    out: list[RangeTombstone] = []
    for i in range(len(points) - 1):
        for s in closes[i]:
            if live[s] == 1:
                del live[s]
            else:
                live[s] -= 1
        for s in opens[i]:
            live[s] = live.get(s, 0) + 1
        for s in sorted(live, reverse=True):
            out.append(RangeTombstone(s, points[i], points[i + 1]))
    return out


class RangeDelAggregator:
    """Collects tombstones from all sources for one read/compaction."""

    def __init__(self, ucmp):
        self._ucmp = ucmp
        self._tombstones: list[RangeTombstone] = []

    def add(self, t: RangeTombstone) -> None:
        self._tombstones.append(t)

    def add_many(self, ts) -> None:
        for t in ts:
            self.add(t)

    def empty(self) -> bool:
        return not self._tombstones

    def max_covering_seq(self, user_key: bytes, snapshot_seq: int) -> int:
        """Max tombstone seqno <= snapshot covering user_key (0 = none)."""
        best = 0
        for t in self._tombstones:
            if (t.seq <= snapshot_seq and t.seq > best
                    and self._ucmp.compare(t.begin, user_key) <= 0
                    and self._ucmp.compare(user_key, t.end) < 0):
                best = t.seq
        return best

    def should_delete(self, ikey: bytes, snapshot_seq: int = dbformat.MAX_SEQUENCE_NUMBER) -> bool:
        """True if the point entry is shadowed by a strictly newer tombstone."""
        uk, seq, _ = dbformat.split_internal_key(ikey)
        return self.max_covering_seq(uk, snapshot_seq) > seq

    def fragments(self) -> list[RangeTombstone]:
        return fragment_tombstones(self._tombstones, self._ucmp)

    def tombstones(self) -> list[RangeTombstone]:
        return list(self._tombstones)
