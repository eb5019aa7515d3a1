"""The data every cell writes, and its oracle: numpy and the seed only.

Copied from chip_smoke.py's `Workload` (PERF.md lists the original for a
later PR to delete). Nothing here imports the package under test.

Write w (0-based) puts key_of[w]. Writes 0..n-1 are a seeded permutation of
the n distinct keys (db_bench fillrandom); writes n.. are seeded draws with
replacement (db_bench overwrite). A key is db_bench's: the key number as 8
big-endian bytes. A value is 20 bytes made from the seed, the key number and
the write index, so the oracle names the exact bytes the last writer of every
key left, and any row found in an SST can be traced back to its write.
"""

from __future__ import annotations

import numpy as np

KEY_BYTES = 8
VALUE_BYTES = 20
RAW_KV_BYTES = KEY_BYTES + VALUE_BYTES  # BASELINE.json's unit: raw user KV

_M1 = np.uint64(0x9E3779B97F4A7C15)
_M2 = np.uint64(0xC2B2AE3D27D4EB4F)


class Workload:
    def __init__(self, n_keys: int, n_draws: int, seed: int):
        self.n = n_keys
        self.seed = np.uint64(seed % (1 << 63))
        rng = np.random.default_rng(seed)
        self.key_of = np.concatenate([
            rng.permutation(n_keys).astype(np.uint64),
            rng.integers(0, n_keys, n_draws, dtype=np.uint64),
        ])

    @staticmethod
    def key_bytes(keys: np.ndarray) -> np.ndarray:
        """[m] key numbers -> [m, 8] uint8, big-endian."""
        return keys.astype(">u8").view(np.uint8).reshape(len(keys), KEY_BYTES)

    def value_bytes(self, keys: np.ndarray, writes: np.ndarray) -> np.ndarray:
        """[m, 20] uint8: LE64(write) | LE64(mix(seed, key, write)) | vvvv."""
        m = len(keys)
        out = np.full((m, VALUE_BYTES), ord("v"), dtype=np.uint8)
        mix = ((keys.astype(np.uint64) * _M1)
               ^ (writes.astype(np.uint64) * _M2)) + self.seed
        out[:, 0:8] = writes.astype("<u8").view(np.uint8).reshape(m, 8)
        out[:, 8:16] = mix.astype("<u8").view(np.uint8).reshape(m, 8)
        return out

    def encode(self, lo: int, hi: int) -> tuple[bytes, bytes]:
        """Writes lo..hi-1 as two contiguous byte strings (keys, values)."""
        keys = self.key_of[lo:hi]
        return (self.key_bytes(keys).tobytes(),
                self.value_bytes(
                    keys, np.arange(lo, hi, dtype=np.uint64)).tobytes())

    def last_write(self, n_writes: int) -> np.ndarray:
        """The oracle after writes 0..n_writes-1: the last write index of
        each key. Write indexes only grow, so it is the maximum."""
        last = np.zeros(self.n, dtype=np.uint64)
        np.maximum.at(last, self.key_of[:n_writes].astype(np.int64),
                      np.arange(n_writes, dtype=np.uint64))
        return last

    def expected(self, keys: np.ndarray, last: np.ndarray) -> list:
        """Oracle answers for key numbers: value bytes, None past n."""
        keys = np.asarray(keys, dtype=np.uint64)
        live = keys < np.uint64(self.n)
        blob = self.value_bytes(
            keys[live], last[keys[live].astype(np.int64)]).tobytes()
        it = (blob[i:i + VALUE_BYTES]
              for i in range(0, len(blob), VALUE_BYTES))
        return [next(it) if ok else None for ok in live]

    def rows_not_from_seed(self, ukeys: np.ndarray, values: np.ndarray) -> int:
        """How many (key number, [m, 20] value) rows no write of this seed
        made: the value names its write, and that write must be of this
        key with these bytes."""
        w = values[:, 0:8].copy().view("<u8").reshape(-1).astype(np.uint64)
        ok = w < np.uint64(len(self.key_of))
        wi = np.where(ok, w, 0).astype(np.int64)
        ok &= self.key_of[wi] == ukeys
        ok &= (self.value_bytes(ukeys, w) == values).all(axis=1)
        return int((~ok).sum())
