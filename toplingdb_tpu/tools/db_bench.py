"""db_bench: the canonical benchmark driver.

Workload set mirrors the reference's db_bench dispatch
(tools/db_bench_tool.cc:3784-3893 in /root/reference): comma-separated
benchmarks run in order against one DB. `--json` loads a SidePlugin-style
config document (the Topling -json flag analogue).

Usage:
  python -m toplingdb_tpu.tools.db_bench --benchmarks=fillseq,readrandom \
      --num=100000 --db=/tmp/bench_db [--json=config.json] [--value-size=100]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import time

from toplingdb_tpu.utils import concurrency as ccy
from toplingdb_tpu.db.db import DB
from toplingdb_tpu.options import Options, ReadOptions, WriteOptions
from toplingdb_tpu.db.write_batch import WriteBatch


class Bench:
    def __init__(self, args):
        self.args = args
        self.rng = random.Random(args.seed)
        if args.json:
            from toplingdb_tpu.utils.config import options_from_config

            with open(args.json) as f:
                cfg = json.load(f)
            self.options = options_from_config(cfg.get("options", cfg))
        else:
            self.options = Options()
        if args.statistics and self.options.statistics is None:
            from toplingdb_tpu.utils.statistics import Statistics

            self.options.statistics = Statistics()
        self.db: DB | None = None
        if ("mergerandom" in args.benchmarks
                or "readwhilemerging" in args.benchmarks):
            # merge workloads write uint64 operands; reads after them would
            # fail with MergeInProgress without an operator.
            self._ensure_merge_operator()

    def _ensure_merge_operator(self) -> None:
        if self.options.merge_operator is None:
            from toplingdb_tpu.utils.merge_operator import UInt64AddOperator

            self.options.merge_operator = UInt64AddOperator()
            if self.db is not None:
                self.open_db(fresh=False)

    def key(self, i: int) -> bytes:
        return b"%016d" % i

    def value(self, i: int) -> bytes:
        data = (b"%d" % i) * (self.args.value_size // max(1, len(b"%d" % i)) + 1)
        return data[: self.args.value_size]

    def open_db(self, fresh: bool) -> None:
        if self.db is not None:
            self.db.close()
            self.db = None
        if fresh and not self.args.use_existing_db and os.path.exists(self.args.db):
            shutil.rmtree(self.args.db)
        self.db = DB.open(self.args.db, self.options)

    def run(self) -> None:
        for name in self.args.benchmarks.split(","):
            name = name.strip()
            fn = getattr(self, "bench_" + name, None)
            if fn is None:
                print(f"unknown benchmark: {name}")
                continue
            fresh = name.startswith("fill")
            if self.db is None or fresh:
                self.open_db(fresh)
            n = self.args.num
            t0 = time.time()
            ops = fn(n)
            dt = time.time() - t0
            ops = ops or n
            print(
                f"{name:<20} : {dt * 1e6 / ops:10.3f} micros/op "
                f"{ops / dt:12.0f} ops/sec; {dt:8.2f} s"
            )
        if self.db is not None:
            if self.args.print_stats and self.db.stats is not None:
                print(self.db.stats.to_string())
            self.db.close()

    # -- workloads ------------------------------------------------------

    def bench_fillseq(self, n):
        wo = WriteOptions(disable_wal=self.args.disable_wal)
        batch = self.args.batch_size
        i = 0
        while i < n:
            b = WriteBatch()
            for _ in range(min(batch, n - i)):
                b.put(self.key(i), self.value(i))
                i += 1
            self.db.write(b, wo)
        return n

    def bench_fillrandom(self, n):
        wo = WriteOptions(disable_wal=self.args.disable_wal)
        batch = self.args.batch_size
        i = 0
        while i < n:
            b = WriteBatch()
            for _ in range(min(batch, n - i)):
                b.put(self.key(self.rng.randrange(n)), self.value(i))
                i += 1
            self.db.write(b, wo)
        return n

    def bench_overwrite(self, n):
        return self.bench_fillrandom(n)

    def bench_readseq(self, n):
        it = self.db.new_iterator()
        it.seek_to_first()
        count = 0
        while it.valid() and count < n:
            it.key(), it.value()
            it.next()
            count += 1
        return count

    def bench_readrandom(self, n):
        ro = ReadOptions()
        hits = 0
        for _ in range(n):
            if self.db.get(self.key(self.rng.randrange(self.args.num)), ro) is not None:
                hits += 1
        return n

    def bench_fillrandomblob(self, n):
        """fillrandom with blob separation on: every value >= min_blob_size
        lands in .blob files (reference db_bench --enable_blob_files)."""
        self.options.enable_blob_files = True
        if self.args.value_size < self.options.min_blob_size:
            self.options.min_blob_size = max(1, self.args.value_size // 2)
        if self.options.blob_cache is None and self.args.blob_cache_size:
            self.options.blob_cache = self.args.blob_cache_size
        self.open_db(fresh=True)
        return self.bench_fillrandom(n)

    def bench_readrandomblob(self, n):
        """readrandom against blob-separated values — exercises the
        BlobSource value cache + file-reader LRU (reference
        db/blob/blob_source.h tier)."""
        self.db.flush()
        self.db.wait_for_compactions()
        return self.bench_readrandom(n)

    def bench_seekrandom(self, n):
        ro = ReadOptions()
        it = self.db.new_iterator(ro)
        for _ in range(n):
            it.seek(self.key(self.rng.randrange(self.args.num)))
            if it.valid():
                it.key(), it.value()
        return n

    def bench_mergerandom(self, n):
        import struct

        wo = WriteOptions(disable_wal=self.args.disable_wal)
        batch = self.args.batch_size
        one = struct.pack("<Q", 1)
        i = 0
        while i < n:
            b = WriteBatch()
            for _ in range(min(batch, n - i)):
                b.merge(self.key(self.rng.randrange(self.args.num)), one)
                i += 1
            self.db.write(b, wo)
        return n

    def bench_fillrandombatch(self, n):
        saved = self.args.batch_size
        self.args.batch_size = max(saved, 100)
        try:
            return self.bench_fillrandom(n)
        finally:
            self.args.batch_size = saved

    def bench_multireadrandom(self, n):
        ro = ReadOptions()
        done = 0
        while done < n:
            ks = [self.key(self.rng.randrange(self.args.num))
                  for _ in range(min(16, n - done))]
            self.db.multi_get(ks, ro)
            done += len(ks)
        return n

    def _with_background(self, bg_op, fg_bench, n):
        """Run fg_bench(n) while a daemon thread loops bg_op(i) — the
        shared scaffold of the *while-writing / *while-merging mixes."""
        import threading

        stop = threading.Event()

        def loop():
            i = 0
            while not stop.is_set():
                bg_op(i)
                i += 1

        t = ccy.spawn("db-bench-background", loop)
        try:
            return fg_bench(n)
        finally:
            stop.set()
            t.join()

    def bench_readwhilewriting(self, n):
        return self._with_background(
            lambda i: self.db.put(
                self.key(self.rng.randrange(self.args.num)), self.value(i)
            ),
            self.bench_readrandom, n,
        )

    def bench_deleteseq(self, n):
        for i in range(n):
            self.db.delete(self.key(i))
        return n

    def bench_deleterandom(self, n):
        for _ in range(n):
            self.db.delete(self.key(self.rng.randrange(self.args.num)))
        return n

    def bench_fillsync(self, n):
        wo = WriteOptions(sync=True)
        m = min(n, max(1, n // 100))  # reference runs num/100 synced writes
        for i in range(m):
            self.db.put(self.key(self.rng.randrange(n)), self.value(i), wo)
        return m

    def bench_fill100K(self, n):
        wo = WriteOptions(disable_wal=self.args.disable_wal)
        m = min(n, max(1, n // 1000))
        big = b"x" * 100_000
        for i in range(m):
            self.db.put(self.key(i), big, wo)
        return m

    def bench_readmissing(self, n):
        ro = ReadOptions()
        for _ in range(n):
            # '.' suffix never collides with written keys.
            self.db.get(self.key(self.rng.randrange(self.args.num)) + b".",
                        ro)
        return n

    def bench_readhot(self, n):
        ro = ReadOptions()
        span = max(1, self.args.num // 100)  # hottest 1% of the key space
        for _ in range(n):
            self.db.get(self.key(self.rng.randrange(span)), ro)
        return n

    def bench_readreverse(self, n):
        it = self.db.new_iterator()
        it.seek_to_last()
        count = 0
        while it.valid() and count < n:
            it.key(), it.value()
            it.prev()
            count += 1
        return count

    def bench_updaterandom(self, n):
        # read-modify-write (reference updaterandom)
        ro = ReadOptions()
        wo = WriteOptions(disable_wal=self.args.disable_wal)
        for i in range(n):
            k = self.key(self.rng.randrange(self.args.num))
            self.db.get(k, ro)
            self.db.put(k, self.value(i), wo)
        return n

    def bench_appendrandom(self, n):
        ro = ReadOptions()
        wo = WriteOptions(disable_wal=self.args.disable_wal)
        for i in range(n):
            k = self.key(self.rng.randrange(self.args.num))
            old = self.db.get(k, ro) or b""
            self.db.put(k, (old + self.value(i))[:1024], wo)
        return n

    def bench_readrandomwriterandom(self, n):
        ro = ReadOptions()
        wo = WriteOptions(disable_wal=self.args.disable_wal)
        for i in range(n):
            k = self.key(self.rng.randrange(self.args.num))
            if i % 10 < 9:  # reference readwritepercent default: 90% reads
                self.db.get(k, ro)
            else:
                self.db.put(k, self.value(i), wo)
        return n

    def bench_readwhilemerging(self, n):
        import struct

        self._ensure_merge_operator()
        return self._with_background(
            lambda i: self.db.merge(
                self.key(self.rng.randrange(self.args.num)),
                struct.pack("<Q", 1),
            ),
            self.bench_readrandom, n,
        )

    def bench_seekrandomwhilewriting(self, n):
        return self._with_background(
            lambda i: self.db.put(
                self.key(self.rng.randrange(self.args.num)), self.value(i)
            ),
            self.bench_seekrandom, n,
        )

    def bench_fillseekseq(self, n):
        # Sequential writes interleaved with a seek to every 16th
        # just-written key (the reference's fillseekseq write+seek mix).
        wo = WriteOptions(disable_wal=self.args.disable_wal)
        for i in range(n):
            self.db.put(self.key(i), self.value(i), wo)
            if i % 16 == 0:
                it = self.db.new_iterator()
                it.seek(self.key(i))
                assert it.valid() and it.key() == self.key(i)
        return n

    def bench_randomtransaction(self, n):
        from toplingdb_tpu.utilities.transactions import TransactionDB

        # Each txn moves "value" between 4 random accounts atomically
        # (reference randomtransaction's bank workload shape).
        self.db.close()
        tdb = TransactionDB.open(self.args.db, self.options)
        try:
            m = max(1, n // 10)
            for _ in range(m):
                t = tdb.begin_transaction()
                for _ in range(4):
                    k = self.key(self.rng.randrange(self.args.num))
                    v = t.get(k) or b"0"
                    t.put(k, v[:64] + b"+")
                t.commit()
            return m * 4
        finally:
            tdb.close()
            self.db = DB.open(self.args.db, self.options)

    def bench_compact(self, n):
        self.db.compact_range()
        return 1

    def bench_compactall(self, n):
        return self.bench_compact(n)

    def bench_waitforcompaction(self, n):
        self.db.wait_for_compactions()
        return 1

    def bench_flush(self, n):
        self.db.flush()
        return 1

    def bench_verifychecksum(self, n):
        # The engine's own checksum sweep (reference DB::VerifyChecksum) —
        # it pins/locks correctly and closes its readers.
        self.db.verify_checksum()
        return 1

    def bench_crc32c(self, n):
        from toplingdb_tpu.utils import crc32c

        block = b"x" * 4096
        for _ in range(n):
            crc32c.value(block)
        return n

    def bench_xxhash(self, n):
        from toplingdb_tpu.utils import crc32c

        block = b"x" * 4096
        for _ in range(n):
            crc32c.xxh64(block)
        return n

    def bench_stats(self, n):
        print(self.db.get_property("tpulsm.stats"))
        return 1

    def bench_levelstats(self, n):
        print(self.db.get_property("tpulsm.levelstats"))
        return 1

    def bench_sstables(self, n):
        from toplingdb_tpu.db.dbformat import extract_user_key

        for cf_id in self.db.versions.column_families:
            v = self.db.versions.cf_current(cf_id)
            for level, level_files in enumerate(v.files):
                for f in level_files:
                    print(f"cf{cf_id} L{level} #{f.number} "
                          f"{f.file_size}B "
                          f"[{extract_user_key(f.smallest)!r} .. "
                          f"{extract_user_key(f.largest)!r}]")
        return 1

    def bench_memstats(self, n):
        for cf_id, cfd in self.db._cfs.items():
            print(f"cf{cf_id} mem_entries={cfd.mem.num_entries} "
                  f"imm={len(cfd.imm)}")
        stats = self.db.stats
        if stats is not None:
            from toplingdb_tpu.utils import statistics as st

            seal = stats.get_histogram(st.MEMTABLE_SEAL_MICROS)
            print(f"flush units handed_over="
                  f"{stats.get_ticker_count(st.FLUSH_UNITS_HANDED_OVER)} "
                  f"installed="
                  f"{stats.get_ticker_count(st.FLUSH_UNITS_INSTALLED)} "
                  f"memtable_limit_wait_us="
                  f"{stats.get_ticker_count(st.STALL_MEMTABLE_LIMIT_MICROS)}"
                  f" seal_us_p50={seal.percentile(50):.0f} "
                  f"seal_us_max={seal.max:.0f}")
        return 1


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--benchmarks", default="fillseq,readrandom")
    ap.add_argument("--num", type=int, default=100000)
    ap.add_argument("--db", default="/tmp/tpulsm_bench")
    ap.add_argument("--value-size", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=1)
    ap.add_argument("--seed", type=int, default=301)
    ap.add_argument("--json", default=None, help="SidePlugin-style config")
    ap.add_argument("--disable-wal", action="store_true")
    ap.add_argument("--use-existing-db", action="store_true")
    ap.add_argument("--statistics", action="store_true")
    ap.add_argument("--print-stats", action="store_true")
    ap.add_argument("--blob-cache-size", type=int, default=32 << 20,
                    help="BlobSource value cache bytes for *blob workloads")
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    Bench(args).run()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
