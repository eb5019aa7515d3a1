"""The data of the merge deployment, and the order of its writes: numpy and
the seed only. Nothing here imports the package under test.

Write w (0-based) touches key_of[w]. Writes 0..n-1 are a seeded permutation
of the n distinct keys, each a `Put` of an 8-byte little-endian counter
(db_bench fillrandom); writes n.. are seeded draws with replacement, each a
`Merge` of an 8-byte little-endian operand (db_bench mergerandom with
merge_keys = num, --merge_operator=uint64add). A key is db_bench's at its
default key_size of 16: the key number as 8 big-endian bytes, then eight
'0' bytes. The counter or operand of write w is mix(seed, key, w): a sum
that lacks one operand is off by a number that names the write.

After every `every`-th write, of the fill and of the merge stream alike, one
`DeleteRange(k, k + width)` at a seeded key number k goes out as a write of
its own (db_bench's writes_per_range_tombstone and range_tombstone_width).
Tombstone i is written when exactly `tomb_at[i]` writes have been made.
"""

from __future__ import annotations

import numpy as np

KEY_BYTES = 16
VALUE_BYTES = 8
KEY_TAIL = b"0" * 8

_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xC2B2AE3D27D4EB4F)


def key_bytes(keys: np.ndarray) -> np.ndarray:
    """[m] key numbers -> [m, 16] uint8."""
    out = np.full((len(keys), KEY_BYTES), ord("0"), dtype=np.uint8)
    out[:, :8] = np.asarray(keys).astype(">u8").view(np.uint8).reshape(-1, 8)
    return out


class MergeWorkload:
    def __init__(self, n_keys: int, n_operands: int, seed: int, every: int,
                 width: int):
        self.n = n_keys
        self.width = width
        self.every = every
        self.seed = np.uint64(seed % (1 << 63))
        rng = np.random.default_rng(seed)
        self.key_of = np.concatenate([
            rng.permutation(n_keys).astype(np.uint64),
            rng.integers(0, n_keys, n_operands, dtype=np.uint64),
        ])
        fill = np.arange(every, n_keys + 1, every, dtype=np.int64)
        stream = n_keys + np.arange(every, n_operands + 1, every,
                                    dtype=np.int64)
        self.tomb_at = np.concatenate([fill, stream])
        self.tomb_lo = np.random.default_rng([seed, 7]).integers(
            0, max(1, n_keys - width), len(self.tomb_at)).astype(np.uint64)

    def numbers(self, lo: int, hi: int) -> np.ndarray:
        """The counters / operands of writes lo..hi-1, uint64."""
        w = np.arange(lo, hi, dtype=np.uint64)
        return ((self.key_of[lo:hi] * _M1) ^ (w * _M2)) + self.seed

    def encode(self, lo: int, hi: int) -> tuple[bytes, bytes]:
        """Writes lo..hi-1 as two contiguous byte strings (keys, values)."""
        return (key_bytes(self.key_of[lo:hi]).tobytes(),
                self.numbers(lo, hi).astype("<u8").tobytes())

    def tombstones(self, n_writes: int):
        """(begin keys, end keys, count) of every DeleteRange due once
        n_writes writes are made, as contiguous bytes in issue order."""
        m = int(np.searchsorted(self.tomb_at, n_writes, side="right"))
        lo = self.tomb_lo[:m]
        return (key_bytes(lo).tobytes(),
                key_bytes(lo + np.uint64(self.width)).tobytes(), m)
