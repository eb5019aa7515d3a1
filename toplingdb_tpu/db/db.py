"""DB: the central engine object.

Analogue of the reference's DBImpl (db/db_impl/db_impl.cc in /root/reference):
open/recover, the write path (WAL + memtable), point reads through
memtable → immutables → versioned SST levels, flush, iterators, snapshots,
and obsolete-file GC. Background compaction is driven by the scheduler in
toplingdb_tpu/compaction (installed via `_maybe_schedule_compaction`).
"""

from __future__ import annotations

import threading

from toplingdb_tpu.utils import concurrency as ccy
from toplingdb_tpu.utils import errors as _errors
import time
import uuid
import warnings

from toplingdb_tpu.db import dbformat, filename
from toplingdb_tpu.db.db_iter import DBIter
from toplingdb_tpu.db.dbformat import InternalKeyComparator, ValueType
from toplingdb_tpu.db.flush_job import flush_memtable_to_table
from toplingdb_tpu.db.get_context import GetContext
from toplingdb_tpu.db.level_iterator import LevelIterator
from toplingdb_tpu.db.log import LogReader, LogWriter
from toplingdb_tpu.db.memtable import MemTable
from toplingdb_tpu.db.range_del import RangeDelAggregator, RangeTombstone
from toplingdb_tpu.db.snapshot import SnapshotList
from toplingdb_tpu.db.table_cache import TableCache
from toplingdb_tpu.db.version_edit import VersionEdit
from toplingdb_tpu.db.version_set import VersionSet
from toplingdb_tpu.utils import kill_point as _kill_point
from toplingdb_tpu.utils.kill_point import test_kill_random
from toplingdb_tpu.utils.listener import FlushJobInfo, notify
from toplingdb_tpu.utils.sync_point import sync_point
from toplingdb_tpu.utils.thread_status import thread_operation
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.env import Env, default_env
from toplingdb_tpu.env.env import _PosixWritable
from toplingdb_tpu.options import FlushOptions, Options, ReadOptions, WriteOptions
from toplingdb_tpu.utils import statistics as _st
from toplingdb_tpu.utils import telemetry as _tm
from toplingdb_tpu.table.merging_iterator import MergingIterator
from toplingdb_tpu.utils.status import (
    Busy, Corruption, InvalidArgument, IOError_, NoSpace, NotFound,
)

_DEFAULT_READ = ReadOptions()
_DEFAULT_WRITE = WriteOptions()


# Cap on bytes merged into one commit group (reference
# max_write_batch_group_size_bytes, db/db_impl/db_impl_write.cc).
_MAX_WRITE_GROUP_BYTES = 1 << 20

# Cached ctypes array types for the native write plane's per-group
# marshalling (n_batches -> (c_char_p*n, c_int64*n)); building fresh array
# TYPES per group dominates small-group dispatch cost.
_GC_ARR_TYPES: dict = {}


def _group_arrays(reps: list):
    """The plane's (reps, lens) ctypes arrays of a group's wire images."""
    n = len(reps)
    at = _GC_ARR_TYPES.get(n)
    if at is None:
        import ctypes

        if len(_GC_ARR_TYPES) > 512:
            _GC_ARR_TYPES.clear()
        at = _GC_ARR_TYPES[n] = (ctypes.c_char_p * n, ctypes.c_int64 * n)
    return at[0](*reps), at[1](*[len(r) for r in reps])


def _framed_cap(merged_len: int) -> int:
    """Tight bound on a merged record's framed bytes: one 7/11B header a
    fragment + <=10B of block-tail padding (a fragment spans at most
    BLOCK-hdr bytes)."""
    return merged_len + 11 * (merged_len // 32757 + 2) + 16

# The kill point between a group's WAL write and its memtable insert; the
# one-call commit has no such moment, so it stands aside while it is armed.
_AFTER_WAL = "DBImpl::WriteImpl:AfterWAL"


class _PlaneBuffers:
    """The one-call commit's ctypes arguments, made once a DB (the commit
    runs under _mutex to its end, so one set serves every group): the out
    array, the arrays of a group of one, and the framed-record scratch,
    grown to the largest group seen."""

    __slots__ = ("out", "rep1", "len1", "buf", "cap")

    def __init__(self):
        import ctypes

        self.out = (ctypes.c_int64 * 12)()
        self.rep1 = (ctypes.c_char_p * 1)()
        self.len1 = (ctypes.c_int64 * 1)()
        self.buf = None
        self.cap = 0

    def scratch(self, cap: int):
        if cap > self.cap:
            import ctypes

            self.cap = max(cap, 2 * self.cap, 64 << 10)
            self.buf = (ctypes.c_ubyte * self.cap)()
        return self.buf


def _adopt_filled(group: list, prot_ptr, n: int) -> None:
    """Hand the protection vectors a fill-mode plane call computed back to
    the batches (the same zero-copy shape _native_protect produces), so the
    memtable carry and any later verify see them."""
    import numpy as np

    vec = np.frombuffer(prot_ptr, dtype=np.uint64)
    off = 0
    for w in group:
        c = w.batch._count
        w.batch._prot = vec if n == 1 else vec[off:off + c]
        w.batch._prot_n = c
        off += c


class _WriterClocks(threading.local):
    """A DB's per-thread running sums (ns) over the thread's native
    write-plane calls: inside the call (out[9] - out[8]), and from its exit
    to Python's time.monotonic_ns() on return (the wait to get the GIL
    back). DB.write books its own call's deltas as write.native.micros and
    write.gil.wait.micros."""

    native_ns = 0
    gil_ns = 0


class _Writer:
    """One queued write (reference WriteThread::Writer, db/write_thread.h:32).

    Lifecycle: enqueued → either becomes the group leader (front of queue) or
    blocks on its event until a leader commits it (done=True), promotes it
    to lead the next group (done=False), or drafts it into a parallel
    memtable phase (parallel=True — the reference's
    STATE_PARALLEL_MEMTABLE_WRITER)."""

    __slots__ = ("batch", "opts", "done", "error", "event", "on_sequenced",
                 "parallel", "pg", "pg_mems")

    def __init__(self, batch: WriteBatch, opts: WriteOptions,
                 on_sequenced=None):
        self.batch = batch
        self.opts = opts
        self.done = False
        self.error: BaseException | None = None
        self.event = threading.Event()
        # Optional callable(first_seq, last_seq) fired INSIDE the commit
        # critical section, before the group's last_sequence publishes —
        # the WritePrepared policy registers its undecided seqno range here
        # so no reader can ever observe the data unexcluded.
        self.on_sequenced = on_sequenced
        self.parallel = False          # drafted into parallel memtable phase
        self.pg = None                 # _InsertBarrier of the phase
        self.pg_mems = None            # {cf_id: MemTable} snapshot to insert


class _InsertBarrier:
    """Completion barrier for one group's parallel memtable phase
    (reference WriteThread::LaunchParallelMemTableWriters /
    CompleteParallelMemTableWriter)."""

    __slots__ = ("remaining", "all_done", "error", "lock")

    def __init__(self, n: int):
        self.remaining = n
        self.all_done = threading.Event()
        self.error: BaseException | None = None
        self.lock = ccy.Lock("db._InsertBarrier.lock")

    def member_done(self, err: BaseException | None = None) -> None:
        with self.lock:
            if err is not None and self.error is None:
                self.error = err
            self.remaining -= 1
            if self.remaining == 0:
                self.all_done.set()


class ColumnFamilyHandle:
    """Opaque per-CF handle (reference include/rocksdb/db.h
    ColumnFamilyHandle)."""

    __slots__ = ("id", "name")

    def __init__(self, cf_id: int, name: str):
        self.id = cf_id
        self.name = name

    def __repr__(self):
        return f"ColumnFamilyHandle({self.id}, {self.name!r})"


class _CFData:
    """Mutable per-CF state (the reference's ColumnFamilyData memtable side)."""

    __slots__ = ("handle", "mem", "imm")

    def __init__(self, handle: ColumnFamilyHandle, icmp, rep_name: str = "vector",
                 protection_bytes: int = 0, stats=None):
        from toplingdb_tpu.db.memtable import create_memtable_rep

        self.handle = handle
        self.mem = MemTable(icmp, create_memtable_rep(rep_name),
                            protection_bytes=protection_bytes, stats=stats)
        self.imm: list[MemTable] = []


class _FlushUnit:
    """What one memtable switch sealed: the memtable of every column family
    that had rows, and the number of the WAL opened at that seal. The flush
    thread takes units in seal order; once a unit and every older one are in
    the MANIFEST no WAL below `wal_number` is needed, and `log_number` moves
    there."""

    __slots__ = ("mems", "wal_number")

    def __init__(self, mems: dict, wal_number: int):
        self.mems = mems            # cf_id -> MemTable
        self.wal_number = wal_number


class _SeqSnapshot:
    """Sequence-pinning shim for internal reads: quacks like a Snapshot
    (.sequence, no excluded ranges) without registering in the snapshot
    list."""

    __slots__ = ("sequence",)
    excluded_ranges = ()

    def __init__(self, seq: int):
        self.sequence = seq


class _NGetState:
    """Per-thread bound state for the native point-read fast path: the
    native ctx (owns out/value buffers), mapped views, and strong refs to
    the memtables/version whose handles the ctx embeds (identity-compared
    by the caller to detect memtable switches / version installs)."""

    __slots__ = ("mem", "imm", "version", "ctx", "fn", "out",
                 "val_ptr", "val_cap", "_lib", "mg", "mg_arena", "fast",
                 "fast_mg")

    def __del__(self):
        lib = getattr(self, "_lib", None)
        ctx = getattr(self, "ctx", None)
        if lib is not None and ctx:
            try:
                lib.tpulsm_getctx_free(ctx)
            except Exception as e:
                _errors.swallow(reason="getctx-free-at-gc", exc=e)

    def remap(self, lib, vlen: int) -> None:
        # The C side grew its buffer to >= vlen; record vlen as the known
        # capacity so any LARGER future value triggers another remap (the
        # vector may reallocate again, moving the pointer).
        self.val_ptr = lib.tpulsm_getctx_val(self.ctx)
        self.val_cap = vlen

    @classmethod
    def build(cls, lib, mem, imm, version, table_cache):
        import ctypes

        handles = []
        kinds = []
        for m in [mem] + imm:
            h = getattr(m._rep, "_h", None)
            kind = getattr(m._rep, "_nget_mem_kind", None)
            if h is None or kind is None:
                return None  # rep layout the native probe can't walk
            handles.append(h)
            kinds.append(kind)
        vh = version.native_read_chain(table_cache)
        if vh is None and any(version.files):
            return None
        marr = (ctypes.c_void_p * len(handles))(*handles)
        ctx = lib.tpulsm_getctx_new(marr, len(handles), vh, 4096)
        if not ctx:
            return None
        for i, kind in enumerate(kinds):
            if kind:
                lib.tpulsm_getctx_set_mem_kind(ctx, i, kind)
        s = cls.__new__(cls)
        s.mem = mem
        s.imm = list(imm)
        s.version = version
        s.ctx = ctx
        s.fn = lib.tpulsm_getctx_get
        s.out = (ctypes.c_int64 * 8).from_address(
            lib.tpulsm_getctx_out(ctx))
        s.val_ptr = lib.tpulsm_getctx_val(ctx)
        s.val_cap = 4096
        s._lib = lib
        # C-extension fast calls (ctypes marshaling was ~30% of a warm
        # Get); None → the ctypes paths stay in charge.
        from toplingdb_tpu import native as _nat

        s.fast = _nat.fastget()
        s.fast_mg = _nat.fastmultiget()
        return s


class DB:
    """LSM engine instance (multi column family). Use DB.open()."""

    def __init__(self, dbname: str, options: Options, env: Env):
        self.dbname = dbname
        self.options = options
        self.env = env
        self.icmp = InternalKeyComparator(options.comparator)
        self._nget_tl = threading.local()  # native-get per-thread state
        self._op_tracer = None             # DB::StartTrace recorder
        # Integrity plane: per-entry protection + whole-file checksums +
        # scrubber state (utils/protection.py, utils/file_checksum.py,
        # db/integrity.py).
        from toplingdb_tpu.utils.file_checksum import factory_for
        from toplingdb_tpu.utils.protection import check_protection_bytes

        pb = getattr(options, "protection_bytes_per_key", 0)
        check_protection_bytes(pb)
        self._protection = pb
        self._file_checksum_factory = factory_for(options)
        self._quarantined: set[int] = set()
        self._integrity_scrubber = None
        if pb and getattr(options.table_options,
                          "protection_bytes_per_key", 0) != pb:
            # Propagate into the table layer (like prefix_extractor below)
            # so the flush/compaction/scan data planes see the knob without
            # signature plumbing; copy — never mutate the caller's object.
            import dataclasses as _dcs_p

            options.table_options = _dcs_p.replace(
                options.table_options, protection_bytes_per_key=pb,
            )
        if (options.prefix_extractor is not None
                and options.table_options.prefix_extractor is None):
            # CF-level extractor feeds the table layer (prefix blooms, plain
            # format), like reference CFOptions.prefix_extractor does. Copy:
            # the caller's TableOptions object must not be mutated.
            import dataclasses as _dcs

            options.table_options = _dcs.replace(
                options.table_options,
                prefix_extractor=options.prefix_extractor,
            )
        if options.bottommost_format is not None:
            from toplingdb_tpu.table.factory import FORMATS
            from toplingdb_tpu.utils.status import InvalidArgument

            if options.bottommost_format not in FORMATS:
                # Fail at open — a typo must not surface hours later as a
                # repeatedly failing background compaction.
                raise InvalidArgument(
                    f"bottommost_format {options.bottommost_format!r} is "
                    f"not one of {FORMATS}"
                )
        if (getattr(options.table_options, "partition_filters", False)
                and options.table_options.prefix_extractor is not None):
            from toplingdb_tpu.utils.status import InvalidArgument

            # Fail at open, not in the first background flush.
            raise InvalidArgument(
                "partition_filters supports whole-key filtering only "
                "(prefix probes could span filter partitions)"
            )
        if getattr(options.table_options, "format", "block") == "plain":
            # Fail at open, not in a background flush/compaction job.
            from toplingdb_tpu.utils.slice_transform import (
                slice_transform_from_name,
            )
            from toplingdb_tpu.utils.status import InvalidArgument

            pe = options.table_options.prefix_extractor
            if pe is None:
                raise InvalidArgument(
                    "plain table format requires Options.prefix_extractor"
                )
            if (options.compaction_executor_factory is not None
                    and slice_transform_from_name(pe.name()) is None):
                raise InvalidArgument(
                    "plain format with a remote compaction executor needs a "
                    "stock prefix_extractor (fixed/capped/noop) — custom "
                    "extractors can't be reconstructed by workers"
                )
        self.versions = VersionSet(env, dbname, self.icmp, options.num_levels)
        self.table_cache = TableCache(env, dbname, self.icmp,
                                      options.table_options,
                                      block_cache=options.block_cache)
        self.table_cache.stats = options.statistics
        self.default_cf = ColumnFamilyHandle(0, "default")
        self._cfs: dict[int, _CFData] = {
            0: _CFData(self.default_cf, self.icmp, options.memtable_rep,
                       protection_bytes=self._protection,
                       stats=options.statistics)
        }
        from toplingdb_tpu.db.blob import BlobSource

        self.blob_source = BlobSource(
            env, dbname, blob_cache=getattr(options, "blob_cache", None),
            open_limit=getattr(options, "blob_file_open_limit", 256),
            statistics=options.statistics)
        self.snapshots = SnapshotList()
        self._mutex = ccy.RLock("db.DB._mutex")
        self._writers: list[_Writer] = []  # FIFO write queue (leader = [0])
        self._wq_lock = ccy.Lock("db.DB._wq_lock")
        # Staged write modes (pipelined/unordered): seqno ALLOCATION runs
        # ahead of PUBLICATION. _alloc_ranges is a deque of [first, last,
        # done] entries in allocation order (indexed by _alloc_entry for
        # O(1) completion marking); last_sequence advances as an in-order
        # low watermark over the done prefix — no front-of-list pops or
        # set scans on the hot path. _mt_cv (on _mutex) signals completion
        # to memtable-switch / snapshot / close waiters.
        from collections import deque as _deque

        self._mt_cv = ccy.Condition(lock=self._mutex)
        self._mt_inflight = 0
        # Background flush: the writer seals (_switch_memtable) and hands
        # the sealed unit to ONE flush thread, started at the first seal.
        # _flush_cv (on _mutex) wakes the thread (a unit queued, resume(),
        # close) and whoever waits for it: a writer at
        # max_write_buffer_number, flush(), wait_for_compactions().
        # _flush_failed is the error the queue's head unit met, with its
        # traceback: the thread parks on it until resume().
        self._flush_cv = ccy.Condition(lock=self._mutex)
        self._flush_queue: "_deque[_FlushUnit]" = _deque()
        self._flush_thread: threading.Thread | None = None
        self._flush_failed: tuple | None = None
        self._flush_stop = False
        self._memtable_limit_waiters = 0
        # Explicit flush() calls in flight: write groups wait meanwhile.
        self._flush_fence = 0
        self._seq_alloc = 0
        self._alloc_ranges: "_deque[list]" = _deque()
        self._alloc_entry: dict[int, list] = {}  # first -> its deque entry
        # Fused native write plane (ISSUE 7 tentpole): TPULSM_WRITE_PLANE=0
        # disables; unset/1 enables when the native symbol + a native
        # memtable rep are available and the comparator carries no
        # timestamp. Resolved lazily (None) to the ctypes fn or False.
        import os as _os

        self._write_plane_knob = (
            _os.environ.get("TPULSM_WRITE_PLANE", "1") != "0")
        self._write_plane = None
        self._plane_bufs: _PlaneBuffers | None = None
        # threading ident of the thread that last led a write group (one
        # store a group); get_property("tpulsm.thread-times") resolves it
        # to the kernel tid it marks.
        self._led_ident = None
        self._wclk = _WriterClocks()
        # Async WAL writer ring (Options.enable_async_wal): WAL durability
        # leaves the commit critical section and concurrent leaders' syncs
        # coalesce into shared fsyncs. Shared Env primitive — the
        # IntegrityScrubber and FilePrefetchBuffer submit through the same
        # AsyncIORing facility.
        self._wal_ring = None
        if (options.enable_async_wal and options.wal_enabled
                and not options.read_only):
            from toplingdb_tpu.env.env import AsyncIORing

            stats_ = options.statistics
            self._wal_ring = AsyncIORing(
                capacity=options.async_wal_ring_size,
                coalesce_cb=(
                    (lambda n, s=stats_: s.record_tick(
                        _st.WRITE_GROUP_FSYNCS_COALESCED, n))
                    if stats_ is not None else None),
                fault_hook=getattr(env, "wal_writer_fault", None),
                name="tpulsm-wal-writer")
        self._wal: LogWriter | None = None
        self._wal_number = 0
        self._recycle_wals: list[int] = []  # obsolete WALs kept for reuse
        # Only logs THIS process wrote in recyclable format may enter the
        # pool — a legacy-format WAL's stale records carry no log-number
        # stamp and could silently replay after reuse (reference
        # alive_log_files scoping).
        self._recyclable_written: set[int] = set()
        self._closed = False
        # Wakes sleeping auto-recover threads so close() can join them
        # promptly instead of waiting out their backoff.
        self._recover_stop = threading.Event()
        # Write-stall accounting surfaced by write_stall_state() (the
        # sharding router's backpressure signal): cumulative counters are
        # folded in by _maybe_stall_writes; the live state is derived from
        # L0 vs the triggers at query time.
        self._stall_totals = {"stalls": 0, "stall_micros": 0,
                              "last_stall_micros": 0, "last_state": "none"}
        self._compaction_scheduler = None  # set by compaction module
        self._pending_outputs: set[int] = set()  # files being written by jobs
        self._bg_error: BaseException | None = None
        from toplingdb_tpu.utils.status import Severity as _Sev
        self._bg_error_severity = _Sev.NO_ERROR
        self._bg_error_reason = ""
        self._store_gc_inflight = False  # one reclaim GC sweep at a time
        self._mem_id_counter = 0
        # WritePrepared policy hook (reference SnapshotChecker): a callable
        # returning the seqno ranges of prepared-but-undecided transactions,
        # which every read must treat as invisible. Set by
        # utilities.transactions.TransactionDB under write_prepared /
        # write_unprepared write policies; None = plain visibility.
        self._undecided_provider = None
        self.identity = ""
        self.stats = options.statistics  # may be None
        # Storage-pressure plane: an SstFileManager tracking this DB's
        # live SST+WAL+blob bytes. Caller-shared via
        # Options.sst_file_manager, else built privately when any disk
        # budget/poller knob is set (the common no-knob path carries None
        # and pays nothing).
        from toplingdb_tpu.utils.rate_limiter import SstFileManager
        sfm = options.sst_file_manager
        self._sfm_owned = False
        if sfm is None and (options.max_allowed_space_usage > 0
                            or options.free_space_poll_period_sec > 0):
            headroom = options.flush_headroom_bytes
            if headroom <= 0 and options.max_allowed_space_usage > 0:
                headroom = 2 * options.write_buffer_size
            sfm = SstFileManager(
                env=env, path=dbname,
                max_allowed_space_usage=options.max_allowed_space_usage,
                compaction_buffer_size=options.compaction_buffer_size,
                flush_headroom_bytes=headroom,
                free_space_poll_period_sec=(
                    options.free_space_poll_period_sec),
                amber_free_ratio=options.disk_amber_free_ratio,
                red_free_ratio=options.disk_red_free_ratio,
                pressure_hysteresis=options.disk_pressure_hysteresis,
                statistics=self.stats)
            self._sfm_owned = True
        elif sfm is not None:
            # Shared manager: adopt this DB's env/root/stats only if the
            # owner didn't already bind them.
            if sfm._env is None:
                sfm._env = env
            if sfm._path is None:
                sfm._path = dbname
            if sfm._stats is None:
                sfm._stats = self.stats
        self._sfm = sfm
        if sfm is not None:
            sfm.add_pressure_callback(self._on_disk_pressure_change)
        from toplingdb_tpu.utils.seqno_to_time import SeqnoToTimeMapping
        from toplingdb_tpu.utils.stats_history import (
            StatsDumpScheduler, StatsHistory,
        )

        if (self.stats is not None
                and getattr(options, "histogram_window_sec", None) is not None
                and options.histogram_window_sec != self.stats._window_sec):
            # Re-key the windowed-histogram ring to the DB's knob (only
            # empty histograms are rebuilt; a shared Statistics keeps
            # its populated series).
            self.stats.set_histogram_window(options.histogram_window_sec)
        self.stats_history = StatsHistory(self.stats)
        # SLO engine (utils/slo.py): declarative burn-rate objectives
        # over the stats; /slo/<name> + /metrics serve its verdicts and
        # ShardRouter folds them into per-shard health scores.
        self.slo_engine = None
        if self.stats is not None and getattr(options, "slo_specs", ()):
            from toplingdb_tpu.utils.slo import SLOEngine

            self.slo_engine = SLOEngine(
                self.stats, options.slo_specs, db=self,
                db_name=dbname, listeners=options.listeners,
                default_window_sec=getattr(options, "slo_window_sec", 60.0)
                or 60.0)
            if getattr(options, "slo_eval_period_sec", 0) > 0:
                self.slo_engine.start(options.slo_eval_period_sec)
        self._stats_dumper = (
            StatsDumpScheduler(self.stats_history,
                               options.stats_persist_period_sec)
            if self.stats is not None and options.stats_persist_period_sec > 0
            else None
        )
        # stats_dump_period_sec: periodic snapshot + a compact `stats_dump`
        # event-log line (the reference's stats-dump thread); started after
        # event_logger exists, below.
        self._stats_dump_thread = None
        # Request-scoped span tracer (utils/telemetry.py): None unless a
        # trace_* knob turns it on — the hot paths check `is not None`
        # before paying anything. The get path's 1-in-N decision is a
        # precomputed cycle iterator (`_trace_sched` yields 1 on the Nth
        # op, 2 for slow-watch rounds, 0 otherwise): the unsampled cost
        # is one attribute load + one C-level next + one branch.
        self.tracer = _tm.tracer_from_options(options)
        self._trace_sched = None
        _tr = self.tracer
        if _tr is not None:
            import itertools as _it

            se, slow = _tr.sample_every, _tr.slow_usec
            if se:
                pat = [2 if slow else 0] * (se - 1) + [1]
            else:
                pat = [2]  # slow-watch only
            self._trace_sched = _it.cycle(pat).__next__
        self.seqno_to_time = SeqnoToTimeMapping()
        # The mapping must survive reopens (reference persists it through
        # MANIFEST/SST properties) or every restart would treat ALL data
        # as young for preclude_last_level_data_seconds; a JSON sidecar
        # is our persistence (loaded in DB.open, saved on sample/close).
        self._seqno_time_path = None
        self._seqno_time_dirty = False
        self._last_seqno_time_sample = 0.0
        self._wbm_charged = 0  # bytes charged to options.write_buffer_manager
        self._options_file_number = 0  # latest persisted OPTIONS file
        self._mget_pool = None  # lazy long-lived async multi_get executor
        # Async read plane (env/async_reads.py, TPULSM_ASYNC_READS=1):
        # lazy AsyncReadBatcher fanning batched block fetches across
        # Options.async_read_rings reader rings; closed by DB.close.
        self._read_batcher = None
        self._async_pool = None  # lazy get_async/multi_get_async executor
        # Test seam: set before the first async-routed read to plug a
        # ReadFaultInjector into every reader ring (fault_hook).
        self.read_fault_hook = None
        self._file_deletions_disabled = 0  # DisableFileDeletions pin count
        # Replication plane hook: LogShipper / FollowerDB / ReplicaRouter
        # register a status callable here; the SidePlugin HTTP layer serves
        # it at /replication/<name> (utils/config.py).
        self._repl_status_provider = None
        from toplingdb_tpu.utils.listener import EventLogger

        self._log_file = None
        if not options.read_only:
            try:
                # Through the Env (fault injection / MemEnv see it too); the
                # previous LOG is rolled aside like the reference's
                # auto_roll_logger.
                if env.file_exists(f"{dbname}/LOG"):
                    env.rename_file(f"{dbname}/LOG", f"{dbname}/LOG.old")
                self._log_file = env.new_writable_file(f"{dbname}/LOG")
            except Exception as e:
                _errors.swallow(reason="info-log-roll-best-effort", exc=e)
        self.event_logger = EventLogger(
            (lambda line: self._log_file.append(line.encode() + b"\n"))
            if self._log_file is not None else None
        )
        if (self.stats is not None
                and getattr(options, "stats_dump_period_sec", 0) > 0):
            from toplingdb_tpu.utils.stats_history import StatsDumpScheduler

            self._stats_dump_thread = StatsDumpScheduler(
                self.stats_history, options.stats_dump_period_sec,
                on_snapshot=self._log_stats_dump)

    def _log_stats_dump(self) -> None:
        """One compact stats line per dump period (the reference's periodic
        stats dump into the info LOG), fed from the history ring's latest
        delta sample so the dump and /stats_history always agree."""
        sample = self.stats_history.last_sample()
        if sample is None:
            return
        ts, delta = sample
        top = sorted(delta.items(), key=lambda kv: -abs(kv[1]))[:12]
        self.event_logger.log(
            "stats_dump", sample_ts=ts,
            tickers={k: v for k, v in top},
            last_sequence=self.versions.last_sequence,
        )

    # -- default-CF views (most callers are single-CF) ------------------

    @property
    def mem(self) -> MemTable:
        return self._cfs[0].mem

    @mem.setter
    def mem(self, m: MemTable) -> None:
        self._cfs[0].mem = m

    @property
    def imm(self) -> list:
        return self._cfs[0].imm

    @imm.setter
    def imm(self, v: list) -> None:
        self._cfs[0].imm = v

    def _cf_id(self, cf) -> int:
        if cf is None:
            return 0
        if isinstance(cf, ColumnFamilyHandle):
            return cf.id
        return int(cf)

    def _cf_data(self, cf) -> _CFData:
        cfd = self._cfs.get(self._cf_id(cf))
        if cfd is None:
            raise InvalidArgument(f"unknown column family {cf!r}")
        return cfd

    # -- column family management ---------------------------------------

    def create_column_family(self, name: str) -> ColumnFamilyHandle:
        with self._mutex:
            cf_id = self.versions.create_column_family(name)
            h = ColumnFamilyHandle(cf_id, name)
            self._cfs[cf_id] = _CFData(h, self.icmp, self.options.memtable_rep,
                                       protection_bytes=self._protection,
                                       stats=self.stats)
            return h

    def drop_column_family(self, handle: ColumnFamilyHandle) -> None:
        with self._mutex:
            self.versions.drop_column_family(handle.id)
            self._cfs.pop(handle.id, None)
            self._delete_obsolete_files()

    def create_column_family_with_import(
        self, name: str, source_dir: str, metadata=None,
        move_files: bool = False,
    ) -> ColumnFamilyHandle:
        """Create a CF populated from a Checkpoint export_column_family dir
        (reference DB::CreateColumnFamilyWithImport /
        ImportColumnFamilyJob, db/import_column_family_job.cc)."""
        from toplingdb_tpu.db.import_column_family_job import (
            import_column_family,
        )

        return import_column_family(self, name, source_dir, metadata,
                                    move_files=move_files)

    def list_column_families(self) -> list[ColumnFamilyHandle]:
        with self._mutex:
            return [cfd.handle for cfd in self._cfs.values()]

    def get_column_family(self, name: str) -> ColumnFamilyHandle | None:
        for cfd in self._cfs.values():
            if cfd.handle.name == name:
                return cfd.handle
        return None

    def cf_name(self, cf_id: int) -> str:
        cfd = self._cfs.get(cf_id)
        if cfd is not None:
            return cfd.handle.name
        st = self.versions.column_families.get(cf_id)
        return st.name if st is not None else f"cf{cf_id}"

    # ==================================================================
    # Open / close
    # ==================================================================

    @staticmethod
    def open(dbname: str, options: Options | None = None, env: Env | None = None) -> "DB":
        """Reference DBImpl::Open (db/db_impl/db_impl_open.cc:1906)."""
        options = options or Options()
        env = env or default_env()
        # Disaggregated SST storage (toplingdb_tpu/storage/): when the
        # shared-store knob is on, wrap the env so installed tables
        # publish to the content-addressed store and live as references.
        # The env var wins over Options so the parity harness can flip
        # modes without touching code.
        import os as _os_knob
        spec = _os_knob.environ.get("TPULSM_SHARED_STORE")
        if spec is None:
            spec = options.shared_store
        owns_shared_env = False
        from toplingdb_tpu.storage import store_spec_enabled
        if store_spec_enabled(spec) and not hasattr(env, "publish_sst"):
            from toplingdb_tpu.storage import SharedSstEnv, open_store

            cache_dir = None
            if isinstance(spec, str) and not spec.startswith(
                    ("http://", "https://")):
                cache_dir = _os_knob.path.join(spec, "cache")
            env = SharedSstEnv(env, open_store(spec, env=env),
                               cache_dir=cache_dir,
                               stats=options.statistics)
            owns_shared_env = True
        elif hasattr(env, "publish_sst") and hasattr(env, "retain"):
            # Reopening on a caller-supplied shared env (migration dest,
            # checkpoint restore): co-own it — the LAST close tears down
            # the cache/prefetch threads.
            owns_shared_env = True
        env.create_dir(dbname)
        db = DB(dbname, options, env)
        db._owns_shared_env = owns_shared_env
        if db.stats is not None:
            # The process's collector pauses of generation >= 1, for
            # get_property("tpulsm.thread-times").
            _tm.watch_gc()
        if owns_shared_env:
            env.retain()
        current = filename.current_file_name(dbname)
        if env.file_exists(current):
            if options.error_if_exists:
                raise InvalidArgument(f"{dbname} exists (error_if_exists)")
            db._recover()
        else:
            if not options.create_if_missing:
                raise InvalidArgument(f"{dbname} does not exist (create_if_missing=False)")
            db.versions.create_new()
            env.write_file(
                filename.identity_file_name(dbname), uuid.uuid4().hex.encode()
            )
        try:
            db.identity = env.read_file(filename.identity_file_name(dbname)).decode()
        except NotFound:
            db.identity = uuid.uuid4().hex
            env.write_file(filename.identity_file_name(dbname), db.identity.encode())
        db._new_wal()
        import os as _os

        db._seqno_time_path = _os.path.join(dbname, "SEQNO_TIME.json")
        try:
            import json as _json

            raw = env.read_file(db._seqno_time_path)
            db.seqno_to_time.load(_json.loads(raw.decode()))
        except Exception as e:
            # Absent/corrupt sidecar: start fresh (best effort).
            _errors.swallow(reason="seqno-time-sidecar-load", exc=e)
        try:
            from toplingdb_tpu.utils.config import (
                load_latest_options, persist_options,
            )

            if db.icmp.user_comparator.timestamp_size:
                # full_history_ts_low is monotonic ACROSS reopens (the
                # reference persists it in the MANIFEST): take the max of
                # the caller's value and the persisted one — already-trimmed
                # history must never become readable again.
                prev = load_latest_options(dbname, env=env)
                if prev is not None:
                    options.full_history_ts_low = max(
                        options.full_history_ts_low,
                        prev.full_history_ts_low,
                    )
            persist_options(db)  # reference PersistRocksDBOptions on open
        except Exception as e:
            # OPTIONS persistence is best-effort, like the reference.
            _errors.swallow(reason="options-persist-on-open", exc=e,
                            stats=options.statistics)
        db._delete_obsolete_files()
        try:
            # A kill -9'd dcompact worker leaves its job dir (params,
            # partial outputs, stale heartbeat) behind; detect expiry by
            # lease and sweep before background work starts. The job's
            # inputs are still live in the version, so the picker simply
            # re-runs it (compaction/resilience.py).
            from toplingdb_tpu.compaction.resilience import (
                DcompactOptions, sweep_orphan_jobs,
            )

            policy = options.dcompact or DcompactOptions()
            roots = {_os.path.join(dbname, "dcompact")}
            factory = options.compaction_executor_factory
            if factory is not None and getattr(factory, "job_root", None):
                roots.add(factory.job_root)
            for root in roots:
                sweep_orphan_jobs(root, policy.lease_sec,
                                  statistics=options.statistics,
                                  event_logger=db.event_logger)
        except Exception as e:
            # Sweeping is best-effort; never blocks open.
            _errors.swallow(reason="orphan-job-sweep-on-open", exc=e,
                            stats=options.statistics)
        if db._sfm is not None:
            # Seed the manager with the surviving tree (recovered SSTs,
            # blobs, the fresh WAL) so budget math starts from reality,
            # then start the free-space poller.
            for child in env.get_children(dbname):
                ftype, _num = filename.parse_file_name(child)
                if ftype in (filename.FileType.TABLE,
                             filename.FileType.BLOB,
                             filename.FileType.WAL):
                    db._sfm.on_add_file(f"{dbname}/{child}")
            db._sfm.poll()
            db._sfm.start_poller()
        from toplingdb_tpu.compaction.scheduler import CompactionScheduler

        db._compaction_scheduler = CompactionScheduler(db)
        db._maybe_schedule_compaction()
        if (not options.read_only
                and getattr(options, "integrity_scrub_period_sec", 0) > 0):
            from toplingdb_tpu.db.integrity import IntegrityScrubber

            db._integrity_scrubber = IntegrityScrubber(db)
            db._integrity_scrubber.start()
        return db

    def _recover(self) -> None:
        self.versions.recover()
        self._materialize_cfs()
        # Replay WALs >= versions.log_number in file-number order
        # (reference DBImpl::Recover → RecoverLogFiles).
        wal_numbers = []
        for child in self.env.get_children(self.dbname):
            ftype, num = filename.parse_file_name(child)
            if ftype == filename.FileType.WAL and num >= self.versions.log_number:
                wal_numbers.append(num)
            if ftype in (filename.FileType.WAL, filename.FileType.TABLE,
                         filename.FileType.MANIFEST, filename.FileType.BLOB):
                self.versions.mark_file_number_used(num)
        max_seq = self.versions.last_sequence
        mems = {cf_id: cfd.mem for cf_id, cfd in self._cfs.items()}
        for num in sorted(wal_numbers):
            path = filename.log_file_name(self.dbname, num)
            reader = LogReader(self.env.new_sequential_file(path),
                               log_number=num)
            for rec in reader.records():
                # The WAL record's own CRC vouched for `rec`; protection
                # computed here covers the replayed entries from decode
                # through memtable and flush.
                batch = WriteBatch(
                    rec, protection_bytes_per_key=self._protection)
                batch.insert_into(mems)
                end_seq = batch.sequence() + batch.count() - 1
                max_seq = max(max_seq, end_seq)
        self.versions.last_sequence = max_seq
        any_flushed = False
        for cf_id, cfd in self._cfs.items():
            if not cfd.mem.empty():
                # Inline: nobody writes beside recovery.
                with self._flush_root_span(1):
                    built = [self._build_flush_table(cfd.mem, cf_id)]
                    try:
                        self._install_flush_tables(built, log_number=None)
                    finally:
                        self._release_flush_outputs(built)
                cfd.mem = self._fresh_memtable()
                any_flushed = True
        if any_flushed:
            # Single atomic log_number advance once every CF is durable.
            self.versions.log_and_apply(
                VersionEdit(log_number=self.versions.next_file_number)
            )

    def _materialize_cfs(self) -> None:
        """Build per-CF memtable state from the recovered VersionSet."""
        for cf_id, st in self.versions.column_families.items():
            if cf_id not in self._cfs:
                h = ColumnFamilyHandle(cf_id, st.name)
                self._cfs[cf_id] = _CFData(h, self.icmp,
                                           self.options.memtable_rep,
                                           protection_bytes=self._protection,
                                           stats=self.stats)

    def _fresh_memtable(self) -> MemTable:
        from toplingdb_tpu.db.memtable import create_memtable_rep

        m = MemTable(self.icmp, create_memtable_rep(self.options.memtable_rep),
                     protection_bytes=self._protection, stats=self.stats)
        self._mem_id_counter += 1
        m.mem_id = self._mem_id_counter
        return m

    def _new_wal(self) -> None:
        self._wal_number = self.versions.new_file_number()
        path = filename.log_file_name(self.dbname, self._wal_number)
        recycle_on = self.options.recycle_log_file_num > 0
        if recycle_on and self._recycle_wals:
            old_num = self._recycle_wals.pop(0)
            old_path = filename.log_file_name(self.dbname, old_num)
            w = self.env.reuse_writable_file(old_path, path)
            if self._sfm is not None:
                self._sfm.on_delete_file(old_path)  # renamed onto `path`
        else:
            w = self.env.new_writable_file(path)
        if self._wal_ring is not None:
            from toplingdb_tpu.env.env import AsyncWritableFile

            w = AsyncWritableFile(w, self._wal_ring)
        # recycle_log_file_num > 0 => ALWAYS the recyclable record format,
        # so any WAL written from now on is safe to reuse later.
        self._wal = LogWriter(w, log_number=self._wal_number,
                              recycled=recycle_on)
        if recycle_on:
            self._recyclable_written.add(self._wal_number)
        if self._sfm is not None:
            self._sfm.on_add_file(path, 0)  # grows; resized at switch/close

    def close(self) -> None:
        from toplingdb_tpu.utils.status import Severity as _Sev

        self._recover_stop.set()
        if self._integrity_scrubber is not None:
            self._integrity_scrubber.stop()
        if self._stats_dumper is not None:
            self._stats_dumper.stop()
        if self._stats_dump_thread is not None:
            self._stats_dump_thread.stop()
        if self.slo_engine is not None:
            self.slo_engine.stop()
        if self._mget_pool is not None:
            self._mget_pool.shutdown(wait=True)
            self._mget_pool = None
        if self._async_pool is not None:
            self._async_pool.shutdown(wait=True)
            self._async_pool = None
        if self._read_batcher is not None:
            # Joins every reader-ring thread (zero leaked ring threads
            # after close — the no_thread_leaks guarantee).
            self._read_batcher.close()
            self._read_batcher = None
        if self._compaction_scheduler is not None:
            self._compaction_scheduler.shutdown()
        if self._sfm is not None and self._sfm_owned:
            # Private manager: join its poller + trash deleters. A shared
            # manager (Options.sst_file_manager) outlives this DB and is
            # closed by whoever built it.
            self._sfm.close()
        with self._mutex:
            if self._closed:
                return
            # Drain staged (pipelined/unordered) memtable phases before
            # flushing — their entries are WAL-durable but must land in the
            # memtables for the final flush to carry them.
            while self._mt_inflight > 0:
                self._mt_cv.wait(timeout=10.0)
            if (any(not c.mem.empty() or c.imm for c in self._cfs.values())
                    and self._bg_error_severity < _Sev.FATAL_ERROR):
                # A unit whose flush failed is tried once more: whatever
                # stays unflushed is in its WAL and is replayed at open.
                # Not after a failed MANIFEST write: what that file holds
                # is not known before it is read again.
                self._flush_failed = None
                self._flush_cv.notify_all()
                self.flush(FlushOptions())
            # The flush thread ends here; join_all below waits for it.
            self._flush_stop = True
            self._flush_cv.notify_all()
            if self._wal is not None:
                self._wal.sync()
                self._wal.close()
            wbm = self.options.write_buffer_manager
            if wbm is not None and self._wbm_charged:
                wbm.free(self._wbm_charged)
                self._wbm_charged = 0
            self.seqno_to_time.append(self.versions.last_sequence,
                                      int(time.time()))
            self._save_seqno_time()
            self.versions.close()
            self.table_cache.close()
            self.blob_source.close()
            if self._wal_ring is not None:
                self._wal_ring.close()
            if self._log_file is not None:
                self._log_file.close()
            self._closed = True
        # Shared-store env: DB.open retained it (knob-built or reopened
        # on a caller-supplied one); the last release closes the
        # warm-ring thread + persistent cache.
        if getattr(self, "_owns_shared_env", False):
            self.env.release()
        # Thread-lifecycle check: everything spawned with owner=self must
        # be gone by now. A leak here is a bug in a stop() path above.
        ccy.registry.join_all(owner=self, timeout=5.0)
        leaked = ccy.registry.check_leaks(owner=self)
        if leaked:
            warnings.warn(
                f"DB.close() leaked threads: {leaked}", RuntimeWarning,
                stacklevel=2)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ==================================================================
    # Write path
    # ==================================================================

    def _validate_ts_batch(self, batch: WriteBatch) -> None:
        """Every key entering a ts-comparator DB must be encode_ts_key-form;
        a single raw key would poison iteration forever (strip_ts raises on
        it). Write paths that can't carry a timestamp (transactions,
        DeleteRange) are rejected here rather than corrupting the DB."""
        if getattr(batch, "_ts_checked", False):
            return
        for _cf, t, key, _val in batch.entries_cf():
            if t == ValueType.RANGE_DELETION:
                raise InvalidArgument(
                    "DeleteRange is not supported with user-defined "
                    "timestamps"
                )
            if t == ValueType.LOG_DATA:
                continue
            try:
                dbformat.strip_ts(key)
            except ValueError as e:
                raise InvalidArgument(
                    f"key {key!r} lacks a timestamp suffix; this DB's "
                    f"comparator requires ts= on every write (transactions "
                    f"do not support user-defined timestamps)"
                ) from e
        batch._ts_checked = True

    def _ts_key(self, key: bytes, ts: int | None) -> bytes:
        """Suffix the user timestamp when the comparator carries one
        (reference user-defined-timestamp write paths: Put(cf, key, ts, v))."""
        sz = self.icmp.user_comparator.timestamp_size
        if sz == 0:
            if ts is not None:
                raise InvalidArgument(
                    "timestamp given but the comparator has none "
                    "(use Options(comparator=U64_TS_BYTEWISE))"
                )
            return key
        if ts is None:
            raise InvalidArgument(
                "this DB's comparator requires a timestamp on every write"
            )
        return dbformat.encode_ts_key(key, ts)

    def put(self, key: bytes, value: bytes, opts: WriteOptions = _DEFAULT_WRITE,
            cf=None, ts: int | None = None) -> int:
        b = WriteBatch(protection_bytes_per_key=self._protection)
        b.put(self._ts_key(key, ts), value, cf=self._cf_id(cf))
        return self.write(b, opts)

    def delete(self, key: bytes, opts: WriteOptions = _DEFAULT_WRITE,
               cf=None, ts: int | None = None) -> int:
        b = WriteBatch(protection_bytes_per_key=self._protection)
        b.delete(self._ts_key(key, ts), cf=self._cf_id(cf))
        return self.write(b, opts)

    def single_delete(self, key: bytes, opts: WriteOptions = _DEFAULT_WRITE,
                      cf=None, ts: int | None = None) -> int:
        b = WriteBatch(protection_bytes_per_key=self._protection)
        b.single_delete(self._ts_key(key, ts), cf=self._cf_id(cf))
        return self.write(b, opts)

    def merge(self, key: bytes, value: bytes, opts: WriteOptions = _DEFAULT_WRITE,
              cf=None) -> int:
        if self.icmp.user_comparator.timestamp_size:
            raise InvalidArgument(
                "Merge is not supported with user-defined timestamps"
            )
        b = WriteBatch(protection_bytes_per_key=self._protection)
        b.merge(key, value, cf=self._cf_id(cf))
        return self.write(b, opts)

    def delete_range(self, begin: bytes, end: bytes,
                     opts: WriteOptions = _DEFAULT_WRITE, cf=None) -> int:
        if self.icmp.user_comparator.timestamp_size:
            raise InvalidArgument(
                "DeleteRange is not supported with user-defined timestamps"
            )
        b = WriteBatch(protection_bytes_per_key=self._protection)
        b.delete_range(begin, end, cf=self._cf_id(cf))
        return self.write(b, opts)

    def latest_sequence_number(self) -> int:
        """The newest PUBLISHED sequence — a valid staleness token for
        replication/router.py reads (reference GetLatestSequenceNumber)."""
        return self.versions.last_sequence

    def write(self, batch: WriteBatch, opts: WriteOptions = _DEFAULT_WRITE,
              on_sequenced=None) -> int:
        """Group-commit write path (reference DBImpl::WriteImpl +
        WriteThread::JoinBatchGroup, db/db_impl/db_impl_write.cc:169,311):
        concurrent writers queue up; the front writer leads, merging the
        queue into one WAL append + one fsync, then applies every batch to
        the memtables and publishes the group's last sequence at once.

        Returns this batch's LAST sequence number — the staleness token of
        the replication plane: a token-carrying read served by any replica
        whose applied sequence >= token observes this write
        (replication/router.py)."""
        if batch.is_empty():
            return self.versions.last_sequence  # trivially-satisfied token
        self._check_open()  # fail fast before any stall sleep
        if batch._pend:
            batch._settle()  # the wire image, in the writer's own thread
        stats = self.stats
        if batch._n_deferred and stats is not None:
            stats.record_tick(_st.WRITE_BATCH_DEFERRED_RECORDS,
                              batch._n_deferred)
        if self._protection:
            wp = self._write_plane
            if wp is None:
                wp = self._resolve_write_plane()
            if not wp or batch._pb != self._protection \
                    or batch._prot is None:
                # Materialize (caller-constructed batches / records added
                # since the last compute): one native pass BEFORE the WAL
                # append and group merge — the memtable-insert
                # re-verification then spans the whole commit path.
                batch.ensure_protection(self._protection)
            # else: defer — the plane VERIFIES a current vector or
            # COMPUTES a stale one fused into the WAL frame walk (each
            # record hashed once, not twice); fallback paths attach at
            # the insert handoff exactly like direct insert_into callers.
        tr = self._op_tracer
        if tr is not None:
            tr.record_write(batch.data())
        tracer = self.tracer
        root = None
        if tracer is not None and tracer.sample_every \
                and next(tracer.counter) % tracer.sample_every == 0:
            # Sampled: full span tree for this write (the inline check is
            # the whole unsampled cost — one count + one mod).
            root = tracer.start("db.write", records=batch.count(),
                                bytes=batch.data_size(),
                                sync=bool(opts.sync))
        if stats is None and tracer is None:
            return self._write_impl(batch, opts, on_sequenced)
        # time/_st are module-level imports: no per-call import
        # machinery on the write hot path. The native clocks are read
        # inside the wall's, so native + gil <= wall. No clock here costs
        # a system call (monotonic_ns is served by the vDSO); the writer's
        # CPU over a window is tpulsm.thread-times' delta.
        t0 = time.monotonic_ns()
        clk = self._wclk
        n0, g0 = clk.native_ns, clk.gil_ns
        try:
            seq = self._write_impl(batch, opts, on_sequenced)
            if root is not None:
                # Replication propagation: WAL shipping forwards this
                # write's context to followers by sequence range.
                tracer.note_seq(seq, root)
            return seq
        finally:
            micros = (time.monotonic_ns() - t0) / 1000
            if stats is not None:
                stats.record_in_histogram(_st.DB_WRITE_MICROS, micros)
                dn, dg = clk.native_ns - n0, clk.gil_ns - g0
                if dn or dg:
                    stats.record_ticks(
                        ((_st.WRITE_NATIVE_MICROS, dn // 1000),
                         (_st.WRITE_GIL_WAIT_MICROS, dg // 1000)))
            if root is not None:
                root.finish()
            elif tracer is not None and tracer.slow_usec \
                    and micros >= tracer.slow_usec:
                tracer.note_slow("db.write", micros,
                                 records=batch.count())

    @staticmethod
    def _write_token(w: _Writer) -> int:
        """The completed writer's staleness token (its last sequence)."""
        return w.batch.sequence() + w.batch.count() - 1

    def _write_impl(self, batch: WriteBatch, opts: WriteOptions,
                    on_sequenced) -> int:
        if self.icmp.user_comparator.timestamp_size:
            self._validate_ts_batch(batch)
        self._maybe_stall_writes()
        w = _Writer(batch, opts, on_sequenced)
        with self._wq_lock:
            self._writers.append(w)
            is_leader = self._writers[0] is w
        if not is_leader:
            interrupted: BaseException | None = None
            # Time spent queued behind the current leader (a sampled
            # follower's dominant latency component).
            _wsp = _tm.span("write.leader_wait")
            while True:
                try:
                    w.event.wait()
                    break
                except BaseException as e:  # noqa: BLE001
                    # Async interrupt (KeyboardInterrupt) mid-wait: the queue
                    # slot MUST still resolve — abandoning it would deadlock
                    # every later writer behind a never-driven leader.
                    interrupted = e
            _wsp.finish()
            if w.parallel:
                # Drafted into the group's parallel memtable phase: insert
                # our own batch (GIL-free native path), then wait for the
                # leader to publish (reference parallel memtable writers).
                interrupted = self._parallel_member(w) or interrupted
                if interrupted is not None:
                    raise interrupted
                if w.error is not None:
                    raise w.error
                return self._write_token(w)
            if w.done:
                if interrupted is not None:
                    raise interrupted
                if w.error is not None:
                    raise w.error
                return self._write_token(w)
            # Woken with done=False: promoted to lead the next group.
            self._lead_write_group(w)
            if interrupted is not None:
                raise interrupted
            return self._write_token(w)
        self._lead_write_group(w)
        return self._write_token(w)

    def _parallel_member(self, w: _Writer) -> BaseException | None:
        """Follower half of a parallel memtable phase: insert own batch,
        report to the barrier, block until the leader completes the group.
        Returns an async interrupt caught mid-wait (re-raised by write())."""
        w.event.clear()
        err: BaseException | None = None
        try:
            w.batch.insert_into(w.pg_mems)
        except BaseException as e:  # noqa: BLE001
            err = e
        w.pg.member_done(err)
        interrupted: BaseException | None = None
        while True:
            try:
                w.event.wait()
                return interrupted
            except BaseException as e:  # noqa: BLE001
                interrupted = e  # leader WILL complete us; keep the slot

    def _snapshot_group(self, leader: _Writer) -> list[_Writer]:
        # Leader + queued followers with the same WAL disposition, capped in
        # bytes so a giant group can't starve later writers' latency
        # (reference WriteThread::EnterAsBatchGroupLeader).
        with self._wq_lock:
            group = [leader]
            size = leader.batch.data_size()
            for w in self._writers[1:]:
                if w.opts.disable_wal != leader.opts.disable_wal:
                    break
                size += w.batch.data_size()
                if size > _MAX_WRITE_GROUP_BYTES:
                    break
                group.append(w)
        return group

    def _lead_write_group(self, leader: _Writer) -> None:
        self._led_ident = threading.get_ident()  # pthread_self: no syscall
        group = self._snapshot_group(leader)
        if self.options.unordered_write or self.options.enable_pipelined_write:
            self._lead_write_group_staged(leader, group)
            return
        err: BaseException | None = None
        try:
            self._commit_write_group(group)
        except BaseException as e:  # propagate to the whole group
            err = e
        with self._wq_lock:
            del self._writers[: len(group)]
            nxt = self._writers[0] if self._writers else None
        for w in group:
            w.done = True
            w.error = err
            if w is not leader:
                w.event.set()
        if nxt is not None:
            nxt.event.set()  # done=False → it takes over as leader
        if err is not None:
            raise err

    def _lead_write_group_staged(self, leader: _Writer,
                                 group: list[_Writer]) -> None:
        """Pipelined / unordered write path (reference PipelinedWriteImpl
        db_impl_write.cc:657 and WriteImplWALOnly :267-301): the WAL stage
        runs under _mutex, then the NEXT group's leader is woken — its WAL
        append overlaps this group's memtable inserts. Publication advances
        as an in-order low watermark over completed groups."""
        err: BaseException | None = None
        first = last = 0
        mems: dict | None = None
        wal_wait = None
        plane = None
        wal_on = (self.options.wal_enabled
                  and not group[0].opts.disable_wal)
        try:
            with self._mutex:
                self._check_writable()
                first = max(self._seq_alloc,
                            self.versions.last_sequence) + 1
                seq = first
                for w in group:
                    w.batch.set_sequence(seq)
                    seq += w.batch.count()
                last = seq - 1
                mems = {cf_id: cfd.mem for cf_id, cfd in self._cfs.items()}
                if wal_on:
                    # Native plane frames+appends the merged record here;
                    # its insert half runs OUTSIDE _mutex below, exactly
                    # like the Python interiors it replaces.
                    with _tm.span("write.wal_frame", group=len(group),
                                  staged=True):
                        plane = self._native_group_commit(group, first,
                                                          mems, frame=True)
                        wal_wait = (plane[0] if plane is not None
                                    else self._append_group_wal(group,
                                                                first))
                self._seq_alloc = last
                entry = [first, last, False]
                self._alloc_ranges.append(entry)
                self._alloc_entry[first] = entry
                self._mt_inflight += 1
        except BaseException as e:  # noqa: BLE001
            err = e
        # Hand the queue to the next leader NOW (the overlap window).
        with self._wq_lock:
            del self._writers[: len(group)]
            nxt = self._writers[0] if self._writers else None
        if nxt is not None:
            nxt.event.set()
        if err is not None:
            for w in group:
                w.done = True
                w.error = err
                if w is not leader:
                    w.event.set()
            raise err
        # Memtable phase. The native plane applies the WHOLE group in one
        # GIL-released call; otherwise unordered mode always fans out
        # (each writer inserts its own batch, truly parallel via the
        # GIL-free native inserts) and pipelined-only mode fans out when
        # allowed.
        native_used = False
        _msp = _tm.span("write.memtable_apply", group=len(group),
                        staged=True)
        if plane is not None:
            try:
                plane[1]()
                native_used = True
            except BaseException as e:  # noqa: BLE001
                err = e
                native_used = True  # nothing inserted, but don't re-run
        elif not wal_on:
            try:
                native_used = self._native_group_commit(
                    group, first, mems, frame=False) is not None
            except BaseException as e:  # noqa: BLE001
                err = e
                native_used = True  # nothing inserted, but don't re-run
        if not native_used and err is None:
            fan_out = len(group) > 1 and (
                self.options.unordered_write
                or self.options.allow_concurrent_memtable_write
            )
            if fan_out:
                pg = _InsertBarrier(len(group))
                for w in group[1:]:
                    w.pg = pg
                    w.pg_mems = mems
                    w.parallel = True
                    w.event.set()
                try:
                    leader.batch.insert_into(mems)
                    pg.member_done()
                except BaseException as e:  # noqa: BLE001
                    pg.member_done(e)
                pg.all_done.wait()
                err = pg.error
            else:
                try:
                    for w in group:
                        w.batch.insert_into(mems)
                except BaseException as e:  # noqa: BLE001
                    err = e
        _msp.finish()
        if wal_wait is not None:
            # Async WAL: the durability barrier overlapped the memtable
            # phase; settle it before completion so a failed group never
            # acknowledges.
            sync_point("DBImpl::GroupCommit:BeforeWALBarrier")
            _fsp = _tm.span("write.fsync_barrier", staged=True)
            try:
                wal_wait()
            except BaseException as e:  # noqa: BLE001
                if err is None:
                    err = e
            finally:
                _fsp.finish()
        self._tick_write_group(group, native_used and err is None)
        self._complete_staged_group(group, first, last, err)
        if err is not None:
            raise err

    def _append_group_wal(self, group: list[_Writer], first_seq: int):
        """WAL append for one group through the Python encoder (caller
        holds _mutex). Returns the durability barrier from
        _group_wal_durability: None when durability settled inline, else a
        zero-arg callable the leader invokes AFTER the memtable phase."""
        if not (self.options.wal_enabled and not group[0].opts.disable_wal):
            return None
        if len(group) == 1:
            rec = group[0].batch.data()
        else:
            merged = WriteBatch()
            merged.set_sequence(first_seq)
            for w in group:
                merged.append_from(w.batch)
            rec = merged.data()
        self._wal.add_record(rec)
        return self._group_wal_durability(group, len(rec))

    def _group_wal_durability(self, group: list[_Writer], rec_len: int):
        """Shared durability tail of both WAL encoders (Python merge and
        the native plane): stats ticks plus the sync/flush barrier. With
        the async WAL writer, returns a callable that waits the ring
        barrier — WAL durability leaves the _mutex critical section and
        overlaps the memtable phase; concurrent leaders' sync barriers
        coalesce into shared fsyncs on the writer thread. Without it,
        settles inline (the seed ordering: durability before insert) and
        returns None."""
        from toplingdb_tpu.utils.kill_point import test_kill_random

        stats = self.stats
        if stats is not None:
            stats.record_tick(_st.WAL_BYTES, rec_len)
            stats.record_tick(_st.WRITE_WITH_WAL, len(group))
        if _st.perf_level:
            # PerfContext write-plane feed (reference wal_write_bytes):
            # the leader's thread accounts the whole group's WAL record.
            _st.perf_context().wal_write_bytes += rec_len
        want_sync = any(w.opts.sync for w in group)
        wfile = self._wal._f
        if self._wal_ring is not None and hasattr(wfile, "sync_async"):
            _sp = _tm.current_span()
            if _sp is not None:
                # Ring depth AT ENQUEUE: how backed up the async WAL
                # writer was when this group's barrier was submitted.
                _sp.tag(wal_ring_depth=len(self._wal_ring._q),
                        want_sync=want_sync)
            tok = wfile.sync_async() if want_sync else wfile.append_barrier()

            def wait(tok=tok, want_sync=want_sync, stats=stats):
                t0 = time.perf_counter() if (want_sync
                                             and stats is not None) else 0
                try:
                    tok.wait()
                except BaseException as e:  # noqa: BLE001
                    # The memtable phase already ran: latch a HARD error so
                    # writes stall until resume() (reference ErrorHandler
                    # on a WAL write failure).
                    self._set_background_error(e, reason="wal")
                    raise
                if want_sync and stats is not None:
                    stats.record_tick(_st.WAL_SYNCS)
                    stats.record_in_histogram(
                        _st.WAL_FILE_SYNC_MICROS,
                        (time.perf_counter() - t0) * 1e6)
                test_kill_random("DBImpl::WriteImpl:AfterWAL")

            return wait
        if want_sync:
            t_sync = time.perf_counter() if stats is not None else 0
            self._wal.sync()
            if stats is not None:
                stats.record_tick(_st.WAL_SYNCS)
                stats.record_in_histogram(
                    _st.WAL_FILE_SYNC_MICROS,
                    (time.perf_counter() - t_sync) * 1e6)
        else:
            self._wal.flush()
        test_kill_random("DBImpl::WriteImpl:AfterWAL")
        return None

    # -- fused native write plane (ISSUE 7 tentpole) --------------------

    def _resolve_write_plane(self):
        """tpulsm_wb_group_commit, or False when the plane is unavailable
        for this DB (knob off, no native lib, ts comparator)."""
        wp = self._write_plane
        if wp is not None:
            return wp
        fn = False
        if (self._write_plane_knob
                and self.icmp.user_comparator.timestamp_size == 0):
            from toplingdb_tpu import native

            l = native.lib()
            f = getattr(l, "tpulsm_wb_group_commit", None) \
                if l is not None else None
            if f is not None:
                fn = f
        self._write_plane = fn
        return fn

    def _native_group_commit(self, group: list[_Writer], first_seq: int,
                             mems, frame: bool, one_call: bool = False):
        """The fused native write plane for one group
        (tpulsm_wb_group_commit): the frame call re-sequences the merged
        header, frames the WAL record gather-style (no Python append_from
        copy, no Python crc framing) and re-hashes carried protection in
        the same validation pass; the insert half applies every record to
        the memtable rep with consecutive seqnos in one GIL-released call.

        frame=True (caller holds _mutex, WAL on): frames + appends +
        starts durability, returning (wal_wait_or_None, insert_fn) — the
        caller runs insert_fn() as the memtable phase (outside _mutex in
        the staged modes; the insert call skips re-validation because the
        frame call just proved these exact buffers).
        one_call=True as well (the caller holds _mutex to the end of the
        memtable phase): where the WAL is a plain POSIX file with nothing
        in its Python buffer, no member asks for sync and no kill point is
        armed, ONE call validates, frames, writes the record to the WAL's
        fd and only then inserts; it returns (None, book_fn), book_fn the
        memtable phase's bookkeeping. A failed write raises IOError_ with
        nothing inserted.
        frame=False (WAL off for this group): validates + inserts in ONE
        call and returns (None, None).
        Returns None on fallback — the Python interiors stay the oracle:
        CF-prefixed records, range deletes, wide-column entities,
        merge-heavy groups, ts comparators, non-native reps, stale
        protection. Raises Corruption — with NOTHING framed or inserted —
        on a protection mismatch."""
        fn = self._resolve_write_plane()
        if not fn:
            return None
        mem0 = mems.get(0)
        gh = mem0.group_handle() if mem0 is not None else None
        if gh is None:
            return None
        pb = self._protection
        reps = []
        prot_vecs = [] if pb else None
        total = 0
        n_stale = 0
        for w in group:
            b = w.batch
            if (not b._simple or b._has_wide
                    or (b._n_merge and b._n_merge * 2 > b._count)):
                return None  # fallback matrix: Python path is the oracle
            if pb:
                if b._prot is None or b._pb != pb:
                    return None
                if b._prot_n != b._count:
                    n_stale += 1
                else:
                    prot_vecs.append(b._prot)
            reps.append(b.data())
            total += b._count
        if total == 0:
            return None
        # Protection: every member current -> VERIFY the carried vectors;
        # every member stale (the DB.write deferral) -> FILL them fused
        # with the frame walk; a mixed group falls back (rare — each
        # member must keep its own verification point).
        fill = n_stale == len(group) if pb and n_stale else False
        if pb and n_stale and not fill:
            return None
        import ctypes

        n = len(reps)
        prot_ptr = None
        n_prots = 0
        pv = None
        if pb:
            if fill:
                prot_ptr = (ctypes.c_uint64 * total)()
                n_prots = total
            else:
                base = getattr(prot_vecs[0], "base", None) if n == 1 \
                    else None
                if isinstance(base, ctypes.Array) and len(base) == total:
                    # _native_protect's buffer: no data_as crossing.
                    prot_ptr = base
                    n_prots = total
                else:
                    import numpy as np

                    pv = (np.ascontiguousarray(prot_vecs[0],
                                               dtype=np.uint64)
                          if n == 1 else np.concatenate(
                              [np.asarray(p, dtype=np.uint64)
                               for p in prot_vecs]))
                    prot_ptr = pv.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_uint64))
                    n_prots = len(pv)
        if frame and one_call:
            wfile = self._wal._f
            if (type(wfile) is _PosixWritable
                    and not any(w.opts.sync for w in group)
                    and "add_record" not in self._wal.__dict__
                    and not _kill_point.armed(_AFTER_WAL)):
                fd = wfile.raw_fd()
                if fd is not None:
                    return self._commit_in_one_call(
                        fn, group, first_seq, mem0, gh, reps, total, pb,
                        fill, prot_ptr, n_prots, wfile, fd)
        rep_arr, len_arr = _group_arrays(reps)
        # out[0..4]: framed bytes / new block offset / mem byte delta /
        # delete count / merged record length. out[5..7]: native interior
        # timings in ns (validate / WAL frame / memtable insert) — the
        # telemetry plane's window into the GIL-released interior without
        # any per-record Python overhead. out[8..9]: the call's entry and
        # exit on time.monotonic_ns()'s clock (older .so builds leave all
        # five 0, and then nothing is booked). out[10]: the records the
        # native apply pool's worker inserted beside this thread.
        out = (ctypes.c_int64 * 11)()

        def run(mode, block_off=0, log_no=-1, wal_ptr=None, cap=0):
            return self._plane_returned(
                fn(gh[0], gh[1], rep_arr, len_arr, n, first_seq, prot_ptr,
                   n_prots, pb, mode, block_off, log_no, wal_ptr, cap, -1,
                   out), out)

        def insert(validated=True):
            rc = run(2 | (4 if validated else 8 if fill else 0))
            if rc < 0:  # only reachable from the unvalidated single call
                return None
            if fill and not validated:
                _adopt_filled(group, prot_ptr, n)
            self._note_plane_applied(mem0, group, reps, first_seq, rc, out)
            return rc

        if not frame:
            return (None, None) if insert(validated=False) is not None \
                else None
        if "add_record" in self._wal.__dict__:
            # Instance-hooked writer (tests / sync points interpose on
            # add_record): the hook must see every record — Python path.
            return None
        block_off, log_no = self._wal.framing_state()
        cap = _framed_cap(12 + sum(len(r) - 12 for r in reps))
        wal_buf = bytearray(cap)
        wal_ptr = (ctypes.c_ubyte * cap).from_buffer(wal_buf)
        rc = run(1 | (8 if fill else 0), block_off, log_no, wal_ptr, cap)
        del wal_ptr  # release the bytearray's buffer export
        if rc < 0:
            return None  # -2/-4: the Python path decides (and names) it
        if out[5]:
            _tm.span_event("native.wal_validate", out[5] // 1000,
                           records=rc)
        if out[6]:
            _tm.span_event("native.wal_frame", out[6] // 1000,
                           bytes=int(out[0]))
        if fill:
            _adopt_filled(group, prot_ptr, n)
        self._wal.append_preframed(memoryview(wal_buf)[:int(out[0])],
                                   int(out[1]))
        return (self._group_wal_durability(group, int(out[4])), insert)

    def _commit_in_one_call(self, fn, group: list[_Writer], first_seq: int,
                            mem0, gh, reps: list, total: int, pb: int,
                            fill: bool, prot_ptr, n_prots: int, wfile,
                            fd: int):
        """_native_group_commit's one-call commit (mode 1|2|16): validate,
        frame, write(2) to the WAL's fd, insert, in one GIL-released call
        with the DB's own ctypes arguments; then adopt the framer's block
        offset and the file's size, and tick what _group_wal_durability
        ticks (there is nothing to flush: the bytes went past the file's
        Python buffer, which was empty)."""
        bufs = self._plane_bufs
        if bufs is None:
            bufs = self._plane_bufs = _PlaneBuffers()
        n = len(reps)
        if n == 1:
            rep_arr, len_arr = bufs.rep1, bufs.len1
            rep_arr[0] = reps[0]
            merged_len = len_arr[0] = len(reps[0])
        else:
            rep_arr, len_arr = _group_arrays(reps)
            merged_len = 12 + sum(len(r) - 12 for r in reps)
        cap = _framed_cap(merged_len)
        out = bufs.out
        block_off, log_no = self._wal.framing_state()
        rc = self._plane_returned(
            fn(gh[0], gh[1], rep_arr, len_arr, n, first_seq, prot_ptr,
               n_prots, pb, 19 | (8 if fill else 0), block_off, log_no,
               bufs.scratch(cap), cap, fd, out), out)
        if rc < 0:
            if rc == -1:
                self._one_call_wal_failed(wfile, int(out[11]), int(out[0]))
            return None  # -2/-4, nothing written: the Python path decides
        if fill:
            _adopt_filled(group, prot_ptr, n)
        framed = out[0]
        self._wal.adopt_block_offset(out[1])
        wfile.wrote_raw(framed, out[11])
        rec_len = out[4]
        if _st.perf_level:
            _st.perf_context().wal_write_bytes += rec_len
        stats = self.stats
        if stats is not None:
            stats.record_ticks(((_st.WAL_BYTES, rec_len),
                                (_st.WRITE_WITH_WAL, len(group)),
                                (_st.WRITE_GROUP_ONE_CALL, 1)))
        if _tm.current_span() is not None:
            if out[5]:
                _tm.span_event("native.wal_validate", out[5] // 1000,
                               records=rc)
            if out[6]:
                _tm.span_event("native.wal_frame", out[6] // 1000,
                               bytes=framed, write_us=out[11] // 1000)
        return (None, lambda: self._note_plane_applied(
            mem0, group, reps, first_seq, rc, out))

    def _plane_returned(self, rc: int, out) -> int:
        """Book a plane call's native and GIL-wait ns (out[8..9]; 0 from
        an older .so books nothing) and raise its protection mismatch."""
        t_back = time.monotonic_ns()
        t_exit = out[9]
        if t_exit:
            clk = self._wclk
            clk.native_ns += t_exit - out[8]
            clk.gil_ns += t_back - t_exit
        if rc <= -5:
            raise Corruption(
                f"write batch protection mismatch at record "
                f"{-(rc + 5)} during group commit"
            )
        return rc

    def _one_call_wal_failed(self, wfile, err: int, torn: int) -> None:
        """Raise the one-call commit's failed WAL write (nothing was
        inserted, nothing publishes). Where the call could not cut a torn
        record off the file again, no later record may follow it (a reader
        would meet the torn one mid-log, not at the tail): a FATAL
        background error stops writes until the DB is reopened."""
        import errno as _errno
        import os as _os

        msg = f"WAL write: {_os.strerror(err)}"
        e = NoSpace(msg) if err == _errno.ENOSPC else IOError_(msg)
        e.__cause__ = OSError(err, _os.strerror(err))
        if torn:
            wfile.wrote_raw(torn, 0)
            self._set_background_error(
                Corruption(f"the WAL ends in a torn record: {msg}"),
                reason="wal")
        raise e

    def _note_plane_applied(self, mem0, group: list[_Writer], reps: list,
                            first_seq: int, total: int, out) -> None:
        """The memtable phase's bookkeeping after the plane inserted a
        group: out[7] the insert's ns, out[2]/out[3] its byte delta and
        deletes, out[10] the records the pool's worker took."""
        parallel = int(out[10])
        if out[7]:
            _tm.span_event("native.memtable_insert", out[7] // 1000,
                           records=total,
                           threads=2 if parallel else 1)
        pb = self._protection
        seq = first_seq
        meta = []
        for w, rep in zip(group, reps):
            meta.append((seq, rep, w.batch._prot if pb else None))
            seq += w.batch._count
        mem0.note_group_applied(meta, int(out[2]), int(out[3]), total,
                                insert_ns=int(out[7]),
                                runs=[w.batch._count for w in group],
                                parallel=parallel)

    def _tick_write_group(self, group: list[_Writer], native: bool) -> None:
        """WRITE_GROUP_* observability for one committed group."""
        stats = self.stats
        if stats is None:
            return
        stats.record_ticks((
            (_st.WRITE_GROUP_LED, 1),
            (_st.WRITE_GROUP_FOLLOWERS, len(group) - 1),
            (_st.WRITE_GROUP_NATIVE_COMMITS if native
             else _st.WRITE_GROUP_FALLBACKS, 1),
        ))
        stats.record_in_histogram(
            _st.WRITE_GROUP_BYTES,
            sum(w.batch.data_size() for w in group))

    def _complete_staged_group(self, group: list[_Writer], first: int,
                               last: int, err: BaseException | None) -> None:
        """Mark one staged group's memtable phase complete, advance the
        publish watermark in allocation order, and run the post-commit work
        (stats, flush trigger) when the watermark moved. The group is marked
        complete even on error — its records are durable in the WAL, and
        stalling the watermark would deadlock every later write."""
        with self._mutex:
            self._mt_inflight -= 1
            if err is None:
                for w in group:
                    if w.on_sequenced is not None:
                        s0 = w.batch.sequence()
                        w.on_sequenced(s0, s0 + w.batch.count() - 1)
            entry = self._alloc_entry.pop(first, None)
            if entry is not None:
                entry[2] = True
            ranges = self._alloc_ranges
            while ranges and ranges[0][2]:
                # In-order publish watermark: O(1) per completed group
                # (deque popleft + dict mark), no front-of-list pops or
                # per-completion set scans.
                self.versions.last_sequence = ranges.popleft()[1]
            if not self._closed:
                self._post_publish_work(group)
            self._mt_cv.notify_all()
        for w in group:
            w.done = True
            w.error = err
            w.parallel = False
            if w is not group[0]:
                w.event.set()

    def _maybe_sample_seqno_time(self, seq: int) -> None:
        """Record (seq, now) when the sampling period elapsed (period 0 =
        manual only); shared by both publish paths. Persistence happens
        off the write hot path (bg flush / explicit flush / close)."""
        period = self.options.seqno_time_sample_period_sec
        if period <= 0:
            return
        now = time.time()
        if now - self._last_seqno_time_sample >= period:
            self._last_seqno_time_sample = now
            self.seqno_to_time.append(seq, int(now))
            self._seqno_time_dirty = True

    def _save_seqno_time(self) -> None:
        """Best-effort sidecar persistence of the seqno<->time mapping
        (the reference rides MANIFEST/SST properties): without it a
        reopen would treat ALL existing data as young for
        preclude_last_level_data_seconds. Called OUTSIDE the write hot
        path — samples mark dirty; flush/close persist."""
        if self._seqno_time_path is None:
            return
        self._seqno_time_dirty = False
        try:
            import json as _json

            self.env.write_file(
                self._seqno_time_path,
                _json.dumps(self.seqno_to_time.to_list()).encode())
        except Exception as e:
            _errors.swallow(reason="seqno-time-sidecar-save", exc=e,
                            stats=self.stats)

    def _post_publish_work(self, group: list[_Writer]) -> None:
        """Stats + seqno/time sampling + flush trigger after a publish
        (caller holds _mutex)."""
        seq_top = self.versions.last_sequence + 1
        self._maybe_sample_seqno_time(seq_top - 1)
        if self.stats is not None:
            self.stats.record_tick(
                _st.NUMBER_KEYS_WRITTEN, sum(w.batch.count() for w in group)
            )
            self.stats.record_tick(
                _st.BYTES_WRITTEN, sum(w.batch.data_size() for w in group)
            )
        self._sync_wbm()
        if self._memtable_full():
            self._seal_and_hand_over()

    def _check_writable(self) -> None:
        """Top of a write group (caller holds _mutex): the DB is open, no
        hard background error is latched, and no failed flush has left a
        full memtable with nowhere to go (the group would be written and
        then have to wait for a flush that does not run). While an explicit
        flush() runs, the group waits here."""
        while self._flush_fence:
            self._flush_cv.wait(timeout=10.0)
        self._check_open()
        if self._bg_error is not None:
            from toplingdb_tpu.utils.status import Severity as _Sev

            if self._bg_error_severity >= _Sev.HARD_ERROR:
                raise IOError_(
                    f"background error pending (call resume()): "
                    f"{self._bg_error!r}"
                )
        if (self._flush_failed is not None and self._memtable_full()
                and not self._memtable_room()):
            raise self._flush_failure()

    def _commit_write_group(self, group: list[_Writer]) -> None:
        with self._mutex:
            self._check_writable()
            first_seq = max(self._seq_alloc, self.versions.last_sequence) + 1
            seq = first_seq
            for w in group:
                w.batch.set_sequence(seq)
                seq += w.batch.count()
            self._seq_alloc = seq - 1
            mems = {cf_id: cfd.mem for cf_id, cfd in self._cfs.items()}
            # Fused native plane: frame+append the merged WAL record first
            # (mode 1 — durability ordering matches the Python path: a WAL
            # failure inserts NOTHING), then apply the whole group to the
            # memtable rep in one GIL-released call (mode 2). On a plain
            # POSIX WAL with no sync asked, both halves and the WAL's
            # write(2) between them are ONE call (mode 1|2|16).
            wal_on = (self.options.wal_enabled
                      and not group[0].opts.disable_wal)
            wal_wait = None
            with _tm.span("write.wal_frame", group=len(group),
                          wal=wal_on):
                plane = self._native_group_commit(group, first_seq, mems,
                                                  frame=wal_on, one_call=True)
                if plane is None and wal_on:
                    wal_wait = self._append_group_wal(group, first_seq)
            _mt0 = time.perf_counter() if _st.perf_level >= 2 else 0.0
            if plane is not None:
                p_wait, insert_fn = plane
                if p_wait is not None:
                    wal_wait = p_wait
                if insert_fn is not None:
                    with _tm.span("write.memtable_apply",
                                  group=len(group), native=True):
                        insert_fn()
            if plane is not None:
                self._tick_write_group(group, native=True)
            else:
                with _tm.span("write.memtable_apply", group=len(group),
                              native=False):
                    if (self.options.allow_concurrent_memtable_write
                            and len(group) > 1):
                        # Parallel memtable phase (reference
                        # LaunchParallelMemTableWriters): followers insert
                        # their own batches concurrently — the native
                        # skiplist insert is lock-free and GIL-releasing, so
                        # this scales with threads. The leader holds _mutex
                        # throughout, so no memtable switch can race the
                        # phase.
                        pg = _InsertBarrier(len(group))
                        for w in group[1:]:
                            w.pg = pg
                            w.pg_mems = mems
                            w.parallel = True
                            w.event.set()
                        try:
                            group[0].batch.insert_into(mems)
                            pg.member_done()
                        except BaseException as e:  # noqa: BLE001
                            pg.member_done(e)
                        pg.all_done.wait()
                        for w in group[1:]:
                            w.parallel = False
                        if pg.error is not None:
                            raise pg.error
                    else:
                        for w in group:
                            w.batch.insert_into(mems)
                self._tick_write_group(group, native=False)
            if _mt0:
                # PerfContext timed tier (reference write_memtable_time).
                _st.perf_context().write_memtable_time += int(
                    (time.perf_counter() - _mt0) * 1e9)
            if wal_wait is not None:
                # async WAL: durability overlapped the inserts
                sync_point("DBImpl::GroupCommit:BeforeWALBarrier")
                with _tm.span("write.fsync_barrier"):
                    wal_wait()
            # on_sequenced fires only after the WAL append + memtable insert
            # succeeded (a failed group must not leak registrations), but
            # BEFORE the group's sequence publishes: entries stay invisible
            # (seq > last_sequence) until the registration exists.
            for w in group:
                if w.on_sequenced is not None:
                    s0 = w.batch.sequence()
                    w.on_sequenced(s0, s0 + w.batch.count() - 1)
            self.versions.last_sequence = seq - 1
            self._maybe_sample_seqno_time(seq - 1)
            if self.stats is not None:
                self.stats.record_tick(
                    _st.NUMBER_KEYS_WRITTEN,
                    sum(w.batch.count() for w in group))
                bw = sum(w.batch.data_size() for w in group)
                self.stats.record_tick(_st.BYTES_WRITTEN, bw)
                self.stats.record_in_histogram(_st.BYTES_PER_WRITE, bw)
                self.stats.record_tick(_st.WRITE_DONE_BY_SELF)
                if len(group) > 1:
                    self.stats.record_tick(_st.WRITE_DONE_BY_OTHER,
                                           len(group) - 1)
            self._sync_wbm()
            if self._memtable_full():
                self._seal_and_hand_over()

    def _sync_wbm(self) -> None:
        """Reconcile this DB's memtable memory with the shared
        WriteBufferManager (reference WriteBufferManager charging) — called
        wherever memtable memory changes (writes AND flushes)."""
        wbm = self.options.write_buffer_manager
        if wbm is None:
            return
        total = sum(
            c.mem.approximate_memory_usage()
            + sum(m.approximate_memory_usage() for m in c.imm)
            for c in self._cfs.values()
        )
        delta = total - self._wbm_charged
        if delta > 0:
            wbm.reserve(delta)
        elif delta < 0:
            wbm.free(-delta)
        self._wbm_charged = total

    def _memtable_full(self) -> bool:
        """Is it time to seal the active memtables (caller holds _mutex)?"""
        total_mem = sum(
            c.mem.approximate_memory_usage() for c in self._cfs.values()
        )
        if total_mem >= self.options.write_buffer_size:
            return True
        wbm = self.options.write_buffer_manager
        return (wbm is not None and wbm.should_flush()
                and total_mem >= 4096)  # floor: don't thrash tiny DBs

    def _memtable_room(self) -> bool:
        """May the active memtables be sealed with no column family holding
        more than max_write_buffer_number memtables, the new active one
        counted (caller holds _mutex)?"""
        limit = max(1, self.options.max_write_buffer_number - 1)
        return all(len(c.imm) < limit or c.mem.empty()
                   for c in self._cfs.values())

    def _flush_failure(self) -> BaseException:
        """What a caller raises who needs the flush thread while it is
        parked on a failed unit: the unit's own error, as when the flush
        ran inline. Every raise starts from the traceback of the failure,
        or the frames of all the callers so far would pile up on it."""
        err, tb = self._flush_failed
        return err.with_traceback(tb)

    def _seal_and_hand_over(self) -> None:
        """The writer's share of a flush (caller holds _mutex): wait for room
        under max_write_buffer_number, seal, and leave the sealed unit to
        the flush thread."""
        t0 = time.perf_counter()
        waited = 0.0 if self._memtable_room() \
            else self._wait_for_memtable_room()
        if self._closed or all(c.mem.empty() for c in self._cfs.values()):
            return  # nothing to seal (any more: the wait released _mutex)
        self._switch_memtable()
        if self.stats is not None:
            self.stats.record_in_histogram(
                _st.MEMTABLE_SEAL_MICROS,
                (time.perf_counter() - t0 - waited) * 1e6)

    def _wait_for_memtable_room(self) -> float:
        """The one wait of a put: max_write_buffer_number memtables are
        unflushed, so the writer waits (the _mutex released meanwhile) for
        the flush thread to install a unit. A failed flush ends the wait
        with its error. Returns the seconds waited, which are a stall of
        state "memtable_limit"."""
        t0 = time.perf_counter()
        self._memtable_limit_waiters += 1
        try:
            while not self._memtable_room() and not self._closed:
                if self._flush_failed is not None:
                    raise self._flush_failure()
                self._flush_cv.wait(timeout=10.0)
        finally:
            self._memtable_limit_waiters -= 1
            waited = time.perf_counter() - t0
            self._account_stall("memtable_limit", waited)
            if self.stats is not None:
                self.stats.record_tick(_st.STALL_MEMTABLE_LIMIT_MICROS,
                                       int(waited * 1e6))
        return waited

    def _switch_memtable(self) -> None:
        """Seal every CF's non-empty active memtable, start a new WAL and
        queue the sealed unit for the flush thread (reference
        DBImpl::SwitchMemtable + MaybeScheduleFlushOrCompaction; all-CF
        switching = atomic-flush behavior so log_number can advance
        safely). Caller holds _mutex."""
        # Staged groups insert into the active memtables OUTSIDE _mutex
        # (pipelined/unordered modes): sealing a memtable mid-insert could
        # let the flush miss an already-published entry. Drain them first
        # (reference WriteThread::WaitForMemTableWriters).
        while self._mt_inflight > 0:
            self._mt_cv.wait(timeout=10.0)
        test_kill_random("DBImpl::SwitchMemtable:Start")
        # Interleaving seam (tests/test_concurrency_interleavings.py):
        # the switch closes the current WAL, so its ordering against a
        # staged group's async durability barrier is the drain protocol
        # above — this point lets tests pin that order.
        sync_point("DBImpl::SwitchMemtable:Start")
        if self._wal is not None:
            self._wal.sync()
            self._wal.close()
            if self._sfm is not None:
                # Final size of the sealed WAL (tracked as 0 at creation).
                self._sfm.on_add_file(filename.log_file_name(
                    self.dbname, self._wal_number))
        sealed = {}
        for cf_id, cfd in self._cfs.items():
            if not cfd.mem.empty():
                sealed[cf_id] = cfd.mem
                # A new list, never an insert: readers walk imm unlocked.
                cfd.imm = [cfd.mem] + cfd.imm
                cfd.mem = self._fresh_memtable()
        try:
            self._new_wal()
        finally:
            if sealed:
                self._flush_queue.append(
                    _FlushUnit(sealed, self._wal_number))
                if self.stats is not None:
                    self.stats.record_tick(_st.FLUSH_UNITS_HANDED_OVER)
                if self._flush_thread is None:
                    self._flush_thread = ccy.spawn(
                        "db-flush", self._flush_loop, owner=self)
                self._flush_cv.notify_all()

    def _flush_loop(self) -> None:
        """The DB's one flush thread: sealed units in seal order, so L0
        files appear oldest first and log_number never passes a WAL that
        an unflushed unit needs. A unit that fails stays at the head with
        its memtables in imm (and its rows in their WAL); the thread parks
        on it until resume() or close() clears _flush_failed."""
        while True:
            with self._mutex:
                while not self._flush_stop and (
                        not self._flush_queue
                        or self._flush_failed is not None):
                    self._flush_cv.wait()
                if self._flush_stop:
                    return
                unit = self._flush_queue[0]
            try:
                self._flush_unit(unit)
            except BaseException as e:  # noqa: BLE001 — latched below
                with self._mutex:
                    self._flush_failed = (e, e.__traceback__)
                    self._flush_cv.notify_all()
                # A MANIFEST failure in the install carries its own reason
                # (version_set.log_and_apply) and latches FATAL.
                self._set_background_error(
                    e, reason=getattr(e, "_bg_reason", "flush"))

    def _flush_root_span(self, memtables: int):
        """Flushes are rare and high-value: always traced while a tracer
        exists (sampling applies to the per-op read/write roots only). The
        root stays open over the install, so that its flush_finished
        events carry the trace's id."""
        return (self.tracer.start("flush", memtables=memtables)
                if self.tracer is not None else _tm.NOOP_SPAN)

    def _flush_unit(self, unit: _FlushUnit) -> None:
        """Build the unit's tables with no DB lock held; then, in ONE hold
        of _mutex, put them into the version, move log_number, and only
        then drop the memtables from imm: a reader walks [mem] + imm and
        then the version, unlocked, and must find a row in one of them."""
        built = []
        try:
            with self._flush_root_span(len(unit.mems)):
                for cf_id, mem in unit.mems.items():
                    if cf_id in self._cfs:  # else dropped since the seal
                        built.append(self._build_flush_table(mem, cf_id))
                with self._mutex:
                    self._install_flush_tables(built, unit.wal_number)
                    sync_point("FlushJob::BeforeImmDrop")
                    for cf_id, mem in unit.mems.items():
                        cfd = self._cfs.get(cf_id)
                        if cfd is not None:
                            cfd.imm = [m for m in cfd.imm if m is not mem]
                    self._flush_queue.popleft()
                    if self.stats is not None:
                        self.stats.record_tick(_st.FLUSH_UNITS_INSTALLED)
                    self._sync_wbm()
                    self._flush_cv.notify_all()
                    self._delete_obsolete_files()
                    self._maybe_schedule_compaction()
        except BaseException as e:
            if getattr(e, "_bg_reason", "") == "manifest":
                # The MANIFEST may hold the record that names these tables
                # (appended, its sync failed): they stay guarded from the
                # obsolete-file sweep until the next open decides by the
                # MANIFEST it recovers.
                built = []
            raise
        finally:
            self._release_flush_outputs(built)

    def _release_flush_outputs(self, built: list) -> None:
        """The tables are in the version, or will never be: either way the
        guard against the obsolete-file sweep has done its work."""
        with self._mutex:
            for _cf_id, _meta, numbers, _t0 in built:
                self._pending_outputs.difference_update(numbers)

    def _build_flush_table(self, mem: MemTable, cf_id: int):
        """One column family's memtable into one L0 table file; _mutex is
        taken for the file numbers only. Returns (cf_id, meta or None, the
        file numbers, guarded in _pending_outputs until
        _release_flush_outputs, and the start time)."""
        sync_point("FlushJob::Start")
        if self._sfm is not None:
            # Preflight: refuse to START a flush only when even the
            # reserved flush/WAL headroom can't absorb it (flushes may
            # spend the headroom compactions must leave alone, so a
            # red-pressure DB still drains its memtables). The refusal
            # latches SOFT no_space (_set_background_error re-reasons a
            # NoSpace) — ingest resumes when space frees.
            est = mem.approximate_memory_usage()
            if not self._sfm.check_flush(est):
                if self.stats is not None:
                    self.stats.record_tick(_st.NO_SPACE_PREFLIGHT_BLOCKS, 1)
                raise NoSpace(
                    f"flush of ~{est} bytes would breach the disk budget")
        t0 = time.time()
        if self._seqno_time_dirty:
            # Every flush funnels here, off the write hot path: persist
            # pending seqno-time samples so a crash doesn't lose them and
            # make all existing data look young after reopen.
            self._save_seqno_time()
        with self._mutex:
            fnum = self.versions.new_file_number()
            blob_num = (
                self.versions.new_file_number()
                if self.options.enable_blob_files else None
            )
            # Guard in-flight outputs (incl. the blob sibling) from
            # obsolete-file GC until the version edit lands.
            numbers = (fnum,) if blob_num is None else (fnum, blob_num)
            self._pending_outputs.update(numbers)
        try:
            with thread_operation("flush", f"cf{cf_id}", self.dbname), \
                    _tm.span("flush.build_table", file_number=fnum,
                             cf_id=cf_id):
                meta = flush_memtable_to_table(
                    self.env, self.dbname, fnum, self.icmp, [mem],
                    self.options.table_options_for_level(0),
                    creation_time=int(t0),
                    blob_file_number=blob_num,
                    min_blob_size=self.options.min_blob_size,
                    column_family=(cf_id, self.cf_name(cf_id)),
                )
            test_kill_random("FlushJob::AfterTableWrite")
            if meta is not None:
                self._stamp_file_checksums([meta])
        except BaseException:
            with self._mutex:
                self._pending_outputs.difference_update(numbers)
            raise
        return cf_id, meta, numbers, t0

    def _install_flush_tables(self, built: list,
                              log_number: int | None) -> None:
        """Put built flush tables into the MANIFEST and the version (caller
        holds _mutex). `log_number` rides on the last edit, so it moves
        only when every column family's table is in: a crash before that
        replays the unit's WALs."""
        edits = []
        for cf_id, meta, _numbers, _t0 in built:
            # An edit for a family dropped since the build would be
            # discarded whole, the log_number on it too.
            if meta is not None and cf_id in self.versions.column_families:
                edit = VersionEdit(column_family=cf_id)
                edit.add_file(0, meta)
                edits.append(edit)
        if log_number is not None:
            if not edits:
                edits.append(VersionEdit())
            edits[-1].log_number = log_number
        for edit in edits:
            self.versions.log_and_apply(edit)
        for _cf_id, meta, numbers, t0 in built:
            if meta is None:
                continue
            if self._sfm is not None:
                self._sfm.on_add_file(
                    filename.table_file_name(self.dbname, meta.number),
                    meta.file_size)
                if len(numbers) > 1:
                    from toplingdb_tpu.db.blob import blob_file_name

                    bpath = blob_file_name(self.dbname, numbers[1])
                    if self.env.file_exists(bpath):
                        self._sfm.on_add_file(bpath)
            if self.stats is not None:
                self.stats.record_tick(_st.FLUSH_WRITE_BYTES, meta.file_size)
                self.stats.record_in_histogram(
                    _st.FLUSH_TIME_MICROS, (time.time() - t0) * 1e6
                )
            self.event_logger.log(
                "flush_finished", file_number=meta.number,
                file_size=meta.file_size, num_entries=meta.num_entries,
            )
            notify(self.options.listeners, "on_flush_completed", self,
                   FlushJobInfo(
                       db_name=self.dbname, file_number=meta.number,
                       file_size=meta.file_size, num_entries=meta.num_entries,
                       smallest_seqno=meta.smallest_seqno,
                       largest_seqno=meta.largest_seqno,
                   ))

    def _wait_for_flushes(self) -> None:
        """Return when the flush thread has installed every sealed unit, so
        imm is empty (caller holds _mutex, released while waiting); raise
        if the thread is parked on a failed one."""
        if threading.current_thread() is self._flush_thread:
            return  # a listener on the flush thread: it would wait for itself
        while self._flush_queue:
            if self._flush_failed is not None:
                raise self._flush_failure()
            self._flush_cv.wait(timeout=10.0)

    def flush(self, fopts: FlushOptions = FlushOptions()) -> None:
        """Seal what the memtables hold; with fopts.wait (the default),
        return when every sealed memtable is an L0 file in the version.
        Write groups are held out meanwhile, as when the flush ran under
        _mutex: a caller that holds _mutex around flush() (checkpoint,
        export, ingest) finds the version and last_sequence of one moment,
        though the wait for the flush thread releases the lock."""
        with self._mutex:
            self._check_open()
            self._seal_and_hand_over()
            if fopts.wait:
                self._flush_fence += 1
                try:
                    self._wait_for_flushes()
                finally:
                    self._flush_fence -= 1
                    self._flush_cv.notify_all()
        if self._seqno_time_dirty:
            self._save_seqno_time()  # outside _mutex: best-effort IO

    # ==================================================================
    # Read path
    # ==================================================================

    # -- workload tracing (reference DB::StartTrace / EndTrace) ----------

    def start_trace(self, trace_path: str, options=None) -> None:
        """Record every subsequent Get/MultiGet/Write/Iterator-seek to
        `trace_path` until end_trace (reference DB::StartTrace,
        trace_replay/trace_replay.cc). Replay with utils.trace.Replayer."""
        from toplingdb_tpu.utils.trace import OpTracer

        self._check_open()
        if self._op_tracer is not None:
            from toplingdb_tpu.utils.status import InvalidArgument

            raise InvalidArgument("a trace is already being recorded")
        self._op_tracer = OpTracer(self.env, trace_path, options)

    def end_trace(self) -> None:
        tr = self._op_tracer
        self._op_tracer = None
        if tr is not None:
            tr.close()

    def _nget_state(self, cfd, opts):
        """Shared eligibility gate + per-thread call state for the native
        read fast paths. Returns (lib, state) with state None when the
        Python chain must run. State is PER-THREAD (the ctx's out/value
        buffers are written inside a GIL-released call — sharing them
        across threads would race), keyed by object IDENTITY of (active
        mem, imm list, version); the state holds refs so ids can't recycle
        while cached."""
        lib = getattr(self, "_nget_lib", False)
        if lib is False:
            from toplingdb_tpu import native

            lib = native.lib()
            if lib is None or not hasattr(lib, "tpulsm_getctx_get"):
                lib = None
            if getattr(self.options, "block_cache", None) is not None:
                # A user-configured block cache is a contract (capacity
                # budget, secondary tier, tracer, stats) the native
                # engine's internal LRU would silently bypass.
                lib = None
            self._nget_lib = lib
        if (lib is None or opts.just_check_key_exists
                or self._excluded_for(opts)):
            return lib, None
        mem = cfd.mem
        if mem._range_dels:
            # The ACTIVE memtable mutates under a cached state — this
            # check must run per call; immutables are frozen and are
            # vetted once at state-build time below.
            return lib, None
        imm = cfd.imm
        version = self.versions.cf_current(cfd.handle.id)
        tl = self._nget_tl
        try:
            states = tl.states
        except AttributeError:
            states = tl.states = {}
        cc = states.get(cfd.handle.id)
        if cc is not None and cc.mem is mem and cc.version is version \
                and cc.imm == imm:
            return lib, cc
        if any(m._range_dels for m in imm):
            return lib, None
        cc = _NGetState.build(lib, mem, imm, version, self.table_cache)
        if cc is None:
            return lib, None
        states[cfd.handle.id] = cc
        return lib, cc

    def _native_get(self, cfd, key: bytes, snap_seq: int, opts):
        """One-call native point lookup (reference GetImpl's chain in one
        GIL-released call, db_impl.cc:2079 → version_set.cc:2606 →
        block_based_table_reader.cc:2095). Returns (handled, value, src):
        handled=False → run the Python chain (ineligible, or the native
        walk hit something only the Python state machine handles). The
        hot call carries 4 args against a persistent native context; the
        value and counters are read from ctx-owned memory mapped once."""
        # Inlined steady-state check (one cached-state hit per Get is the
        # common case; _nget_state handles every slow/ineligible path).
        mem = cfd.mem
        cc = None
        if (opts is _DEFAULT_READ and not mem._range_dels
                and self._undecided_provider is None):
            states = getattr(self._nget_tl, "states", None)
            if states is not None:
                cc = states.get(cfd.handle.id)
                if cc is not None and (
                        cc.mem is not mem
                        or cc.version is not self.versions.cf_current(
                            cfd.handle.id)
                        or cc.imm != cfd.imm):
                    cc = None
        if cc is None:
            lib, cc = self._nget_state(cfd, opts)
            if cc is None:
                return False, None, None
        fast = cc.fast
        if fast is not None:
            r = fast(cc.ctx, key, snap_seq)
            if r is False:
                return False, None, None
            rc = 0 if r is None else 1
        else:
            rc = cc.fn(cc.ctx, key, len(key), snap_seq)
            if rc == 2 or rc < 0:
                return False, None, None
        out = cc.out
        st = _st
        if st.perf_level:
            pctx = st.perf_context()
            pctx.get_from_memtable_count += out[2]
            pctx.bloom_sst_miss_count += out[3]
            pctx.bloom_sst_hit_count += out[4]
            pctx.block_cache_hit_count += out[5]
            pctx.block_read_count += out[6]
            pctx.block_read_byte += out[7]
        if self.stats is not None and (out[3] or out[5] or out[6]):
            self.stats.record_ticks(
                (t, c) for t, c in ((st.BLOOM_USEFUL, out[3]),
                                    (st.BLOCK_CACHE_HIT, out[5]),
                                    (st.BLOCK_CACHE_MISS, out[6])) if c)
        src = out[1]
        src = "mem" if src == 0 else (src - 1 if src >= 1 else None)
        if rc == 1:
            if fast is not None:
                return True, r, src  # the extension already built bytes
            vlen = out[0]
            if vlen > cc.val_cap:  # ctx grew its buffer: re-map
                cc.remap(cc._lib, vlen)
            import ctypes

            return True, ctypes.string_at(cc.val_ptr, vlen), src
        return True, None, src

    def _probe_memtable(self, mem, key: bytes, snap_seq: int,
                        ctx: GetContext) -> bool:
        """One memtable source; returns False when the lookup is complete."""
        from toplingdb_tpu.utils import statistics as st

        if st.perf_level:
            st.perf_context().get_from_memtable_count += 1
        ctx.add_tombstone_seq(mem.covering_tombstone_seq(key, snap_seq))
        for seq, t, val in mem.entries_for_key(key, snap_seq):
            if not ctx.save_value(seq, t, val):
                return False
        return True

    def _probe_file(self, reader, key: bytes, snap_seq: int, ctx: GetContext,
                    tombs, it=None, preread=None) -> tuple[bool, object]:
        """One SST source; `tombs` is the file's parsed RangeTombstone list;
        `it` is a reusable iterator for this reader (created on demand).
        `preread`: async read plane overlay (block-table PrereadSpans or
        zip value-group preload) — only ever non-None for readers whose
        new_iterator accepts it. Returns (continue?, iterator)."""
        from toplingdb_tpu.utils import statistics as st

        ucmp = self.icmp.user_comparator
        for t in tombs:
            if ucmp.compare(t.begin, key) <= 0 and ucmp.compare(key, t.end) < 0:
                ctx.add_tombstone_seq(t.seq)
        has_filter = (getattr(reader, "_filter_data", None) is not None
                      or getattr(reader, "_filter_top", None) is not None)
        if not reader.key_may_match(key):
            if self.stats is not None:
                self.stats.record_tick(st.BLOOM_USEFUL)
            if st.perf_level:
                st.perf_context().bloom_sst_miss_count += 1
            return True, it
        if has_filter and st.perf_level:
            # Only a CONSULTED filter counts (fail-open paths don't).
            st.perf_context().bloom_sst_hit_count += 1
        if getattr(reader, "has_hash_index", False):
            # O(1) bucket probe (single_fast hash index): lands on the
            # newest version; the loop below skips seqs above the snapshot.
            ordinal = reader.hash_probe(key)
            if ordinal is None:
                return True, it  # definitively absent from this file
            if it is None:
                it = reader.new_iterator()
            it.seek_ordinal(ordinal)
        else:
            if it is None:
                it = (reader.new_iterator(preread=preread)
                      if preread is not None else reader.new_iterator())
            it.seek(dbformat.make_internal_key(
                key, snap_seq, dbformat.VALUE_TYPE_FOR_SEEK
            ))
        while it.valid():
            uk, seq, t = dbformat.split_internal_key(it.key())
            if ucmp.compare(uk, key) != 0:
                break
            if seq <= snap_seq:
                if not ctx.save_value(seq, t, it.value()):
                    return False, it
            it.next()
        return True, it

    def _parsed_tombstones(self, reader):
        return [RangeTombstone.from_table_entry(b, e)
                for b, e in reader.range_del_entries()]

    def get(self, key: bytes, opts: ReadOptions = _DEFAULT_READ,
            cf=None) -> bytes | None:
        """Point lookup (reference DBImpl::GetImpl, db_impl.cc:2079).
        Returns None if not found. A wide-column entity presents as its
        anonymous default column (reference Get-on-entity semantics,
        db/wide/wide_columns_helper) — use get_entity for every column.
        Entity detection is by the DEDICATED kTypeWideColumnEntity-style
        value type, so plain binary values are never reinterpreted;
        Options.legacy_wide_column_unwrap re-enables the old magic-prefix
        sniff for databases written before the dedicated type existed."""
        sched = self._trace_sched
        if sched is not None:
            m = sched()
            if m:
                return self._get_traced(key, opts, cf, m == 1)
        v, is_entity = self._get_impl_entry(key, opts, cf)
        if v is not None:
            if is_entity:
                from toplingdb_tpu.db.wide_columns import default_column_of

                return default_column_of(v)
            if (v[:1] == b"\x00"
                    and getattr(self.options, "legacy_wide_column_unwrap",
                                False)):
                from toplingdb_tpu.db.wide_columns import default_column_of

                return default_column_of(v)
        return v

    def _get_traced(self, key: bytes, opts, cf, sampled: bool):
        """The rare half of get(): sampled root span, or the slow-watch
        backstop when trace_slow_usec is set (every get pays one
        perf_counter pair in that mode)."""
        tracer = self.tracer
        root = tracer.start("db.get") if sampled else None
        t0 = 0.0 if sampled else time.perf_counter()
        try:
            v, is_entity = self._get_impl_entry(key, opts, cf)
        finally:
            if root is not None:
                root.finish()
            else:
                _us = (time.perf_counter() - t0) * 1e6
                if _us >= tracer.slow_usec:
                    tracer.note_slow("db.get", _us)
        if v is not None:
            if is_entity:
                from toplingdb_tpu.db.wide_columns import default_column_of

                return default_column_of(v)
            if (v[:1] == b"\x00"
                    and getattr(self.options, "legacy_wide_column_unwrap",
                                False)):
                from toplingdb_tpu.db.wide_columns import default_column_of

                return default_column_of(v)
        return v

    def _get_impl_entry(self, key: bytes, opts: ReadOptions = _DEFAULT_READ,
                        cf=None, record_trace: bool = True):
        """Returns (value_or_None, is_wide_column_entity)."""
        self._check_open()
        if record_trace:
            tr = self._op_tracer
            if tr is not None:
                tr.record_get(key)
        if self.icmp.user_comparator.timestamp_size:
            return self._get_with_ts(key, opts, cf), False
        self._check_read_ts(opts)
        cfd = self._cf_data(cf)
        snap_seq = (
            opts.snapshot.sequence if opts.snapshot is not None
            else self.versions.last_sequence
        )
        st_on = self.stats is not None
        t0 = time.perf_counter() if st_on else 0.0
        # Native fast chain: memtable skiplists + SST walk in ONE
        # GIL-released C call (reference GetImpl -> Version::Get ->
        # BlockBasedTable::Get). Anything the Python state machine must
        # see (merge operands, single-delete in SSTs, blob indexes, range
        # tombstones, wide-column entities, perf-context accounting)
        # falls through below. TPULSM_ASYNC_READS=1 routes around it:
        # the async read plane lives in the Python walk, whose block
        # fetches batch-submit through the reader rings.
        async_on = self._async_reads_on()
        if not async_on:
            handled, val, src = self._native_get(cfd, key, snap_seq, opts)
            if handled:
                if st_on:
                    self._record_get_stats(t0, val, src)
                return val, False
        ctx = GetContext(
            key, snap_seq, self.options.merge_operator,
            blob_resolver=self.blob_source.get,
            excluded_ranges=self._excluded_for(opts),
        )
        # 1. Active memtable, then immutables (newest first).
        for mem in [cfd.mem] + cfd.imm:
            if not self._probe_memtable(mem, key, snap_seq, ctx):
                val = ctx.result()
                if st_on:
                    self._record_get_stats(t0, val, "mem")
                return val, ctx.result_is_entity
        # 2. SST files, newest data first. Async plane: every candidate
        # file's cache-missing blocks are submitted as ONE batch before
        # the walk, so a multi-level chain overlaps its preads (deeper
        # candidates are speculative — wasted only when an upper level
        # terminates the lookup first).
        version = self.versions.cf_current(cfd.handle.id)
        preread_map = None
        if async_on:
            file_order = [f for _lvl, f in version.files_for_get(key)]
            preread_map = self._plan_async_preread(
                file_order, {f.number: [key] for f in file_order},
                {key}, snap_seq)
        hit_level = self._walk_sst_chain(version, key, snap_seq, ctx,
                                         preread_map=preread_map)
        val = ctx.result()
        if st_on:
            self._record_get_stats(t0, val, hit_level)
        return val, ctx.result_is_entity

    def _record_get_stats(self, t0: float, val, src) -> None:
        """Read-path ticker family (reference MEMTABLE_HIT/GET_HIT_L*,
        statistics.h) — one lock acquisition via Statistics.record_get."""
        self.stats.record_get(
            (time.perf_counter() - t0) * 1e6,
            len(val) if val is not None else None, src)

    def _walk_sst_chain(self, version, key: bytes, snap_seq: int, ctx,
                        tombs_for=None, preread_map=None):
        """Probe the key's SST candidates newest-first until the lookup
        completes (shared by get, async multi_get, get_merge_operands).
        `preread_map`: async read plane overlays keyed by file number —
        the chain's block fetches were batch-submitted up front, so a
        deep walk consumes already-overlapped reads instead of paying
        one serial pread per level. Returns the level that completed
        the lookup, or None."""
        for level, f in version.files_for_get(key):
            reader = self.table_cache.get_reader(f.number)
            tombs = (tombs_for(f) if tombs_for is not None
                     else self._parsed_tombstones(reader))
            more, _ = self._probe_file(
                reader, key, snap_seq, ctx, tombs,
                preread=(preread_map.get(f.number)
                         if preread_map is not None else None))
            if not more:
                return level
        ctx.finish()
        return None

    def _max_l0_files(self) -> int:
        return max(
            (len(self.versions.cf_current(cf_id).files[0])
             for cf_id in self.versions.column_families), default=0,
        )

    def _maybe_stall_writes(self, timeout: float = 10.0) -> None:
        """L0 back-pressure (reference WriteController + the
        level0_slowdown/stop triggers, db_impl_write.cc DelayWrite): past the
        slowdown trigger writes are delayed; past the stop trigger they block
        until compaction drains L0 (the worst CF counts — a pileup in any CF
        throttles). No-op when nothing can drain L0 (auto compaction off /
        no scheduler): stalling a bulk load forever helps no one."""
        import time as _time

        opts = self.options
        if (opts.disable_auto_compactions
                or self._compaction_scheduler is None
                or self._compaction_scheduler._paused):
            return  # nothing can drain L0; stalling would only block
        n_l0 = self._max_l0_files()
        if n_l0 >= opts.level0_stop_writes_trigger:
            t0 = _time.monotonic()
            while (self._max_l0_files() >= opts.level0_stop_writes_trigger
                   and _time.monotonic() - t0 < timeout
                   and not self._closed):
                self._maybe_schedule_compaction()
                _time.sleep(0.01)
            stalled = _time.monotonic() - t0
            self._account_stall("stopped", stalled)
            if stalled >= timeout:
                self.event_logger.log(
                    "write_stall_timeout", l0_files=self._max_l0_files(),
                    stalled_s=round(stalled, 2),
                )
        elif n_l0 >= opts.level0_slowdown_writes_trigger:
            # Proportional delay ramp toward the stop trigger.
            span = max(1, opts.level0_stop_writes_trigger
                       - opts.level0_slowdown_writes_trigger)
            frac = (n_l0 - opts.level0_slowdown_writes_trigger + 1) / span
            delay = min(0.05 * frac, 0.05)
            _time.sleep(delay)
            self._account_stall("delayed", delay)

    def _account_stall(self, state: str, stalled_s: float) -> None:
        """Fold one stall episode into the cumulative totals + the
        STALL_MICROS/WRITE_STALL_COUNT tickers and the write.stall.micros
        histogram (previously only the stop path ticked, and only
        STALL_MICROS — the delay ramp was invisible)."""
        micros = int(stalled_s * 1e6)
        tot = self._stall_totals
        tot["stalls"] += 1
        tot["stall_micros"] += micros
        tot["last_stall_micros"] = micros
        tot["last_state"] = state
        if self.stats is not None:
            from toplingdb_tpu.utils import statistics as st

            self.stats.record_tick(st.STALL_MICROS, micros)
            self.stats.record_tick(st.WRITE_STALL_COUNT)
            self.stats.record_in_histogram(st.WRITE_STALL_MICROS_HIST,
                                           micros)

    def write_stall_state(self) -> dict:
        """Queryable write-stall state (the sharding router's backpressure
        signal, also exposed as /metrics gauges): the LIVE state derived
        from L0 file counts vs the slowdown/stop triggers — "none",
        "delayed", or "stopped" — or "memtable_limit" while a writer waits
        for the flush thread with max_write_buffer_number memtables
        unflushed; plus cumulative stall totals. `drainable` is False when
        nothing can reduce L0 (auto compaction off / scheduler paused), in
        which case writes are never stalled by L0 either."""
        opts = self.options
        n_l0 = self._max_l0_files()
        drainable = not (opts.disable_auto_compactions
                         or self._compaction_scheduler is None
                         or self._compaction_scheduler._paused)
        if drainable and n_l0 >= opts.level0_stop_writes_trigger:
            state = "stopped"
        elif drainable and n_l0 >= opts.level0_slowdown_writes_trigger:
            state = "delayed"
        elif self._memtable_limit_waiters:
            state = "memtable_limit"
        else:
            state = "none"
        out = dict(self._stall_totals)
        out.update(
            state=state,
            immutable_memtables=max(
                (len(c.imm) for c in self._cfs.values()), default=0),
            memtable_limit=max(1, opts.max_write_buffer_number - 1),
            l0_files=n_l0,
            drainable=drainable,
            slowdown_trigger=opts.level0_slowdown_writes_trigger,
            stop_trigger=opts.level0_stop_writes_trigger,
        )
        return out

    def _check_read_ts(self, opts: ReadOptions) -> None:
        """Validate ReadOptions.timestamp against this DB (reference: reads
        need a ts comparator, and reading below full_history_ts_low is
        InvalidArgument — that history may already be collapsed, so the
        answer would depend on compaction timing)."""
        if opts.timestamp is None:
            return
        if self.icmp.user_comparator.timestamp_size == 0:
            raise InvalidArgument(
                "ReadOptions.timestamp requires a timestamp-carrying "
                "comparator (U64_TS_BYTEWISE)"
            )
        if opts.timestamp < self.options.full_history_ts_low:
            raise InvalidArgument(
                f"cannot read at ts={opts.timestamp}: history below "
                f"full_history_ts_low={self.options.full_history_ts_low} "
                f"may be collapsed"
            )

    def _ts_lookup(self, it, key: bytes) -> tuple[bytes, int] | None:
        """Shared ts-DB point lookup over an existing ts-aware iterator:
        seek lands directly on the newest visible version of the key."""
        it.seek(key)
        if it.valid() and it.key() == key:
            # raw: the caller layer does the wide-column unwrap exactly once
            raw = getattr(it, "raw_value", it.value)()
            return raw, it.timestamp()
        return None

    _TS_SLOW = object()  # fast-path bail sentinel

    def _ts_fast_lookup(self, key: bytes, opts: ReadOptions, cf):
        """Layered memtable-first point lookup on a timestamped DB — the
        per-Get full-iterator build was this path's flagged perf debt.
        Each source (memtable, immutables, overlapping files per level) is
        seeked independently for its newest visible version; candidates
        combine by (ts desc, seq desc), matching DBIter's dedup order.
        Returns (value, ts) | None | _TS_SLOW when the workload needs the
        iterator path (merge operator, range tombstones, undecided-seqno
        exclusions)."""
        if self.options.merge_operator is not None:
            return self._TS_SLOW  # operand chains need full resolution
        if self._excluded_for(opts):
            return self._TS_SLOW  # WritePrepared visibility exclusions
        cfd = self._cf_data(cf)
        read_ts = (opts.timestamp if opts.timestamp is not None
                   else dbformat.MAX_TIMESTAMP)
        snap_seq = (
            opts.snapshot.sequence if opts.snapshot is not None
            else self.versions.last_sequence
        )
        enc_hi = dbformat.encode_ts_key(key, read_ts)   # newest visible
        enc_lo = dbformat.encode_ts_key(key, 0)         # oldest possible
        seek_ikey = dbformat.make_internal_key(
            enc_hi, snap_seq, dbformat.VALUE_TYPE_FOR_SEEK)
        best = None  # (ts, seq, vtype, value)

        esc = enc_lo[:-8]  # escaped base key + terminator (ts-independent)

        def probe(it):
            """Source's best visible version into `best`; False = bail."""
            nonlocal best
            it.seek(seek_ikey)
            while it.valid():
                uk, seq, t = dbformat.split_internal_key(it.key())
                if len(uk) != len(esc) + 8 or not uk.startswith(esc):
                    break  # past this base key's versions
                if t in (dbformat.ValueType.MERGE,
                         dbformat.ValueType.SINGLE_DELETION):
                    return False
                if seq <= snap_seq:
                    ts = dbformat.decode_ts(uk[-8:])
                    cand = (ts, seq, t, it.value())
                    if best is None or cand[:2] > best[:2]:
                        best = cand
                    break  # ordered (ts desc, seq desc): first wins here
                it.next()
            return True


        for mem in [cfd.mem] + cfd.imm:
            if mem._range_dels:
                return self._TS_SLOW
            if not probe(mem.new_iterator()):
                return self._TS_SLOW
        version = self.versions.cf_current(cfd.handle.id)
        for level in range(version.num_levels):
            for f in version.overlapping_files(level, enc_hi, enc_lo):
                reader = self.table_cache.get_reader(f.number)
                if reader.range_del_entries():
                    return self._TS_SLOW
                if not probe(reader.new_iterator()):
                    return self._TS_SLOW
        if best is None:
            return None
        if best[2] == dbformat.ValueType.BLOB_INDEX:
            # Resolve through the blob source like GetContext does.
            return self.blob_source.get(best[3]), best[0]
        if best[2] != dbformat.ValueType.VALUE:
            return None
        return best[3], best[0]

    def _ts_point_lookup(self, key: bytes, opts: ReadOptions,
                         cf) -> tuple[bytes, int] | None:
        self._check_read_ts(opts)  # the iterator path checks in new_iterator
        hit = self._ts_fast_lookup(key, opts, cf)
        if hit is not self._TS_SLOW:
            return hit
        return self._ts_lookup(self.new_iterator(opts, cf=cf), key)

    def _get_with_ts(self, key: bytes, opts: ReadOptions, cf) -> bytes | None:
        """Point lookup on a timestamped DB (reference GetImpl with
        ReadOptions.timestamp)."""
        hit = self._ts_point_lookup(key, opts, cf)
        if hit is None:
            return None
        return b"" if opts.just_check_key_exists else hit[0]

    def get_with_ts(self, key: bytes, opts: ReadOptions = _DEFAULT_READ,
                    cf=None) -> tuple[bytes, int] | None:
        """Get returning (value, version timestamp) — the reference's
        Get(..., std::string* timestamp) overload."""
        self._check_open()
        return self._ts_point_lookup(key, opts, cf)

    def _native_multi_get(self, cfd, keys, snap_seq: int, opts, cf=None):
        """Whole-batch native MultiGet: one GIL-released call walks every
        key's chain; only keys the native engine can't decide (merge
        chains, blob indexes, range-tombstoned tables) re-resolve through
        the Python path. Returns (handled, results)."""
        if not keys:
            return False, None
        lib, cc = self._nget_state(cfd, opts)
        if cc is None or not hasattr(lib, "tpulsm_getctx_multiget"):
            return False, None
        if cc.fast_mg is not None and isinstance(keys, list) \
                and all(type(k) is bytes for k in keys):
            # Whole batch + result materialization in the C extension.
            fm = cc.fast_mg(cc.ctx, keys, snap_seq)
            if fm is not None:
                res, ctr = fm
                self._mg_record_stats(ctr)
                return True, self._mg_resolve_fallbacks(
                    res, keys, snap_seq, opts, cf)
        import ctypes

        import numpy as np

        n = len(keys)
        key_lens = np.fromiter((len(k) for k in keys), np.int32, n)
        key_offs = np.zeros(n, np.int64)
        np.cumsum(key_lens[:-1], out=key_offs[1:])
        keybuf = np.frombuffer(b"".join(keys), np.uint8)
        from toplingdb_tpu import native as _nat

        # Per-batch scratch is PERSISTENT on the thread-local get state —
        # a fresh 1MiB arena per 128-key batch dominated the multiget
        # wall at bench scale.
        mg = getattr(cc, "mg", None)
        if mg is None or len(mg[0]) < n:
            cap = max(n, 256)
            mg = cc.mg = (np.zeros(cap, np.int8), np.zeros(cap, np.int64),
                          np.zeros(cap, np.int64))
        status, voffs, vlens = mg
        arena = getattr(cc, "mg_arena", None)
        if arena is None:
            arena = cc.mg_arena = np.empty(1 << 20, np.uint8)
        ctr = (ctypes.c_int64 * 6)()
        used = (ctypes.c_int64 * 1)()
        while True:
            rc = lib.tpulsm_getctx_multiget(
                cc.ctx, _nat.np_u8p(keybuf), _nat.np_i64p(key_offs),
                _nat.np_i32p(key_lens), n, snap_seq,
                status.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
                _nat.np_i64p(voffs), _nat.np_i64p(vlens),
                _nat.np_u8p(arena), len(arena), used, ctr,
            )
            if rc == -2:
                arena = cc.mg_arena = np.empty(len(arena) * 4, np.uint8)
                continue
            if rc != 0:
                return False, None
            break
        self._mg_record_stats(ctr)
        mv = memoryview(arena)
        out: list = [None] * n
        for i in range(n):
            s = status[i]
            if s == 1:
                o = voffs[i]
                out[i] = bytes(mv[o: o + vlens[i]])
            elif s == 2:
                out[i] = False  # undecidable natively: resolve below
        return True, self._mg_resolve_fallbacks(out, keys, snap_seq, opts,
                                                cf)

    def _mg_record_stats(self, ctr) -> None:
        """Batch-level perf/ticker accounting from the native MultiGet's
        six counters (shared by the ctypes and C-extension paths)."""
        st = _st
        if st.perf_level:
            pctx = st.perf_context()
            pctx.get_from_memtable_count += ctr[0]
            pctx.bloom_sst_miss_count += ctr[1]
            pctx.bloom_sst_hit_count += ctr[2]
            pctx.block_cache_hit_count += ctr[3]
            pctx.block_read_count += ctr[4]
            pctx.block_read_byte += ctr[5]
        if self.stats is not None:
            for tick, cnt in ((st.BLOOM_USEFUL, ctr[1]),
                              (st.BLOCK_CACHE_HIT, ctr[3]),
                              (st.BLOCK_CACHE_MISS, ctr[4])):
                if cnt:
                    self.stats.record_tick(tick, cnt)

    def _mg_resolve_fallbacks(self, out, keys, snap_seq, opts, cf):
        """Replace False markers (keys the native walk could not decide:
        merge chains, blob indexes, entities, range-tombstoned tables)
        with full per-key Python resolutions, PINNED to the batch's
        snapshot seqno — re-reading at a fresh last_sequence would mix
        sequence points within one MultiGet. No tracer record: the
        OP_MULTIGET record already covers these keys."""
        if not any(v is False for v in out):
            return out
        pinned_opts = opts
        if opts.snapshot is None:
            import dataclasses as _dcs

            pinned_opts = _dcs.replace(opts,
                                       snapshot=_SeqSnapshot(snap_seq))
        for i, v in enumerate(out):
            if v is not False:
                continue
            r, is_entity = self._get_impl_entry(keys[i], pinned_opts, cf,
                                                record_trace=False)
            if r is not None and is_entity:
                from toplingdb_tpu.db.wide_columns import default_column_of

                r = default_column_of(r)
            out[i] = r
        return out

    # -- async read plane (env/async_reads.py; ROADMAP item 4b) --------

    @staticmethod
    def _async_reads_on() -> bool:
        """TPULSM_ASYNC_READS=1 routes multi_get/get block fetches
        through the AsyncReadBatcher; default 0 keeps the synchronous
        path — the byte-parity oracle (write/scan/zip plane pattern)."""
        import os as _os

        return _os.environ.get("TPULSM_ASYNC_READS", "0") == "1"

    def _reader_batcher(self):
        """Lazy per-DB AsyncReadBatcher (first async-routed read)."""
        b = self._read_batcher
        if b is None:
            from toplingdb_tpu.env.async_reads import AsyncReadBatcher

            with self._mutex:
                b = self._read_batcher
                if b is None and not self._closed:
                    opts = self.options
                    b = self._read_batcher = AsyncReadBatcher(
                        rings=max(1, getattr(opts, "async_read_rings", 4)),
                        task_capacity=getattr(
                            opts, "async_read_task_capacity", 256),
                        stats=self.stats,
                        fault_hook=self.read_fault_hook,
                        name="tpulsm-read")
        return b

    def _plan_async_preread(self, file_order, per_file, live, snap_seq):
        """Plan + submit one batch of block fetches for a (multi_)get:
        per candidate file, seek the resident index for each live key's
        data-block handle, drop cache-resident blocks, and fan the rest
        through the reader rings in ONE submit_batch (coalescing merges
        neighbours). Returns {file_number: overlay} where the overlay is
        a PrereadSpans (block tables) or a {vg: token} value-group
        preload (zip tables); files the plane cannot serve (hash-index /
        plain formats) get no entry and probe synchronously —
        READ_ASYNC_FALLBACKS counts them."""
        batcher = self._reader_batcher()
        if batcher is None:
            return None
        mk = dbformat.make_internal_key
        flat: list[tuple] = []       # (rfile, offset, length)
        flat_file: list[int] = []    # aligned file numbers
        zip_plans: dict[int, tuple] = {}
        planned: set[int] = set()
        fallbacks = 0
        for f in file_order:
            if f.number in planned:
                continue
            planned.add(f.number)
            todo = sorted(k for k in per_file[f.number] if k in live)
            if not todo:
                continue
            reader = self.table_cache.get_reader(f.number)
            ikeys = [mk(k, snap_seq, dbformat.VALUE_TYPE_FOR_SEEK)
                     for k in todo if reader.key_may_match(k)]
            if not ikeys:
                continue
            if hasattr(reader, "plan_block_reads") \
                    and not getattr(reader, "has_hash_index", False):
                for off, n in reader.plan_block_reads(ikeys):
                    flat.append((reader._f, off, n))
                    flat_file.append(f.number)
            elif hasattr(reader, "plan_value_groups"):
                vgs = reader.plan_value_groups(ikeys)
                if vgs:
                    zip_plans[f.number] = (reader, vgs)
            else:
                fallbacks += 1
        overlays: dict[int, object] = {}
        if flat:
            from toplingdb_tpu.env.async_reads import PrereadSpans

            toks = batcher.submit_batch(flat)
            spans: dict[int, list] = {}
            for (rf, off, n), fnum, tok in zip(flat, flat_file, toks):
                spans.setdefault(fnum, []).append((off, off + n, tok))
            for fnum, sp in spans.items():
                overlays[fnum] = PrereadSpans(
                    self.table_cache.get_reader(fnum)._f, sp)
        for fnum, (reader, vgs) in zip_plans.items():
            overlays[fnum] = {
                vg: batcher.submit_task(
                    lambda r=reader, v=vg: r._value_group(v))
                for vg in vgs
            }
        if zip_plans and self.stats is not None:
            # A value-group preload is one planned batch too: keep the
            # ticker meaningful for zip-format tables.
            self.stats.record_tick(_st.READ_ASYNC_BATCHES, len(zip_plans))
        if fallbacks and self.stats is not None:
            self.stats.record_tick(_st.READ_ASYNC_FALLBACKS, fallbacks)
        return overlays

    def _submit_async(self, fn):
        """Run `fn` on the lazy async-read executor; returns a
        concurrent.futures.Future."""
        self._check_open()
        pool = self._async_pool
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            with self._mutex:
                pool = self._async_pool
                if pool is None:
                    pool = self._async_pool = ThreadPoolExecutor(
                        max_workers=max(
                            2, getattr(self.options, "async_read_rings", 4)),
                        thread_name_prefix="tpulsm-get-async")
        return pool.submit(fn)

    def get_async(self, key: bytes, opts: ReadOptions = _DEFAULT_READ,
                  cf=None):
        """Future-returning point lookup: `.result()` is exactly what
        `get(key, opts, cf)` returns. The batched async surface the
        shard/fleet routers fan requests across shards with."""
        return self._submit_async(lambda: self.get(key, opts, cf))

    def multi_get_async(self, keys: list[bytes],
                        opts: ReadOptions = _DEFAULT_READ, cf=None):
        """Future-returning batched lookup: `.result()` is exactly what
        `multi_get(keys, opts, cf)` returns."""
        keys = list(keys)
        return self._submit_async(lambda: self.multi_get(keys, opts, cf))

    def multi_get(self, keys: list[bytes], opts: ReadOptions = _DEFAULT_READ,
                  cf=None) -> list[bytes | None]:
        """Batched point lookups (reference DBImpl::MultiGet, including the
        Topling fiber variant db_impl.cc:3026-3227 — our batching analogue
        groups all keys per source so each memtable/file is visited once,
        instead of per-key)."""
        self._check_open()
        tr = self._op_tracer
        if tr is not None:
            tr.record_multiget(keys)
        self._check_read_ts(opts)
        tracer = self.tracer
        root = None
        if tracer is not None and tracer.sample_every \
                and next(tracer.counter) % tracer.sample_every == 0:
            root = tracer.start("db.multiget", keys=len(keys))
        t_mg = time.perf_counter() \
            if (self.stats is not None or tracer is not None) else 0.0
        try:
            res = self._multi_get_impl(keys, opts, cf)
        finally:
            if root is not None:
                root.finish()
            elif tracer is not None and tracer.slow_usec:
                _us = (time.perf_counter() - t_mg) * 1e6
                if _us >= tracer.slow_usec:
                    tracer.note_slow("db.multiget", _us, keys=len(keys))
        # Entities were already unwrapped per key by their typed fallback
        # resolution; the magic sniff survives only behind the legacy gate.
        if getattr(self.options, "legacy_wide_column_unwrap", False) \
                and any(v is not None and v[:1] == b"\x00" for v in res):
            from toplingdb_tpu.db.wide_columns import default_column_of

            res = [v if v is None else default_column_of(v) for v in res]
        if self.stats is not None:
            from toplingdb_tpu.utils import statistics as st

            self.stats.record_tick(st.NUMBER_MULTIGET_CALLS)
            self.stats.record_tick(st.NUMBER_MULTIGET_KEYS_READ, len(keys))
            self.stats.record_tick(
                st.NUMBER_MULTIGET_BYTES_READ,
                sum(len(v) for v in res if v is not None))
            self.stats.record_in_histogram(
                st.DB_MULTIGET_MICROS, (time.perf_counter() - t_mg) * 1e6)
        return res

    def _multi_get_impl(self, keys, opts, cf):
        if self.icmp.user_comparator.timestamp_size:
            # ONE iterator for the whole batch (single view/mutex), seeked
            # across the keys in sorted order.
            it = self.new_iterator(opts, cf=cf)
            hits = {}
            for k in sorted(set(keys)):
                hit = self._ts_lookup(it, k)
                hits[k] = None if hit is None else hit[0]
            return [hits[k] for k in keys]
        cfd = self._cf_data(cf)
        snap_seq = (
            opts.snapshot.sequence if opts.snapshot is not None
            else self.versions.last_sequence
        )
        # TPULSM_ASYNC_READS=1: the batch runs the Python per-file walk
        # with its block fetches fanned through the reader rings; the
        # native whole-batch path serializes its preads in-call and is
        # bypassed (knob off = the sync oracle, default).
        async_on = self._async_reads_on()
        if not async_on:
            handled, native_res = self._native_multi_get(cfd, keys, snap_seq,
                                                         opts, cf)
            if handled:
                return native_res
        resolver = self.blob_source.get
        excluded = self._excluded_for(opts)
        ctxs = {
            k: GetContext(k, snap_seq, self.options.merge_operator,
                          blob_resolver=resolver, excluded_ranges=excluded)
            for k in keys
        }
        live = dict(ctxs)
        # 1. Memtables: one pass per source for ALL live keys.
        for mem in [cfd.mem] + cfd.imm:
            for k in list(live):
                if not self._probe_memtable(mem, k, snap_seq, live[k]):
                    del live[k]
        # 2. SSTs: group keys by candidate file so each reader/iterator is
        # reused across the batch (the fiber MultiGet's IO-batching effect).
        version = self.versions.cf_current(cfd.handle.id)
        # Per-file tombstone parses are memoized ONCE per batch and shared
        # by both the fiber path and the sync per-file loop below. The
        # probe runs under the lock: a bare dict.get racing the insert
        # relies on CPython's GIL atomicity; one uncontended acquire on
        # the hit path buys correctness on any runtime, and the parse
        # stays inside the lock so a file is never parsed twice.
        tombs_cache: dict[int, list] = {}
        cache_mu = ccy.Lock("db.DB.cache_mu")

        def tombs_for(f):
            with cache_mu:
                t = tombs_cache.get(f.number)
                if t is None:
                    t = self._parsed_tombstones(
                        self.table_cache.get_reader(f.number))
                    tombs_cache[f.number] = t
            return t

        if live and opts.async_io and len(live) > 1 and not async_on:
            # Fiber-MultiGet analogue: each missing key walks its own file
            # chain on a worker thread (one "fiber" per key; file pread
            # releases the GIL, so misses overlap their IO).
            pool = self._mget_pool
            if pool is None:
                from concurrent.futures import ThreadPoolExecutor

                pool = self._mget_pool = ThreadPoolExecutor(
                    max_workers=max(1, opts.async_queue_depth),
                    thread_name_prefix="mget",
                )
            list(pool.map(
                lambda k: self._walk_sst_chain(
                    version, k, snap_seq, ctxs[k], tombs_for),
                list(live),
            ))
            return [self._ctx_plain_result(ctxs[k]) for k in keys]
        if live:
            per_file: dict[int, list[bytes]] = {}
            for k in live:
                for level, f in version.files_for_get(k):
                    per_file.setdefault(f.number, []).append(k)
            # Visit files in global level order — L0 newest-first, then each
            # deeper level — which preserves EVERY key's newest-first source
            # order (per-key candidates are a subsequence of this walk).
            file_order = [
                f for lvl in range(version.num_levels)
                for f in version.files[lvl] if f.number in per_file
            ]
            # Async read plane: submit EVERY file's cache-missing blocks
            # as one batch before any probe — the fiber-MultiGet overlap
            # (PAPER.md item 4) with the rings doing the preads while
            # this thread decodes whatever completed first.
            overlays = None
            if async_on and file_order:
                overlays = self._plan_async_preread(
                    file_order, per_file, live, snap_seq)
            import contextlib as _ctxlib
            span_cm = (_tm.span("read.async.wait", files=len(file_order))
                       if overlays else _ctxlib.nullcontext())
            with span_cm:
                for f in file_order:
                    todo = [k for k in per_file[f.number] if k in live]
                    if not todo:
                        continue
                    reader = self.table_cache.get_reader(f.number)
                    tombs = tombs_for(f)  # once per file per batch
                    it = None
                    preread = (overlays.get(f.number)
                               if overlays is not None else None)
                    for k in sorted(todo):
                        ctx = live.get(k)
                        if ctx is None:
                            continue
                        more, it = self._probe_file(
                            reader, k, snap_seq, ctx, tombs, it,
                            preread=preread
                        )
                        if not more:
                            del live[k]
        for ctx in live.values():
            ctx.finish()
        return [self._ctx_plain_result(ctxs[k]) for k in keys]

    @staticmethod
    def _ctx_plain_result(ctx):
        """GetContext result for a PLAIN Get: entities present as their
        default column (the typed unwrap; reference Get-on-entity)."""
        v = ctx.result()
        if v is not None and ctx.result_is_entity:
            from toplingdb_tpu.db.wide_columns import default_column_of

            return default_column_of(v)
        return v

    def key_exists(self, key: bytes, opts: ReadOptions = _DEFAULT_READ) -> bool:
        return self.get(key, opts) is not None

    def put_entity(self, key: bytes, columns: dict[bytes, bytes],
                   opts: WriteOptions = _DEFAULT_WRITE, cf=None) -> None:
        """Wide-column write under the DEDICATED entity value type
        (reference DB::PutEntity → kTypeWideColumnEntity)."""
        from toplingdb_tpu.db.wide_columns import encode_entity

        b = WriteBatch()
        b.put_entity(self._ts_key(key, None), encode_entity(columns),
                     cf=self._cf_id(cf))
        self.write(b, opts)

    def get_entity(self, key: bytes, opts: ReadOptions = _DEFAULT_READ,
                   cf=None) -> dict[bytes, bytes] | None:
        """Wide-column read (reference DB::GetEntity); plain values present
        as the anonymous default column."""
        from toplingdb_tpu.db.wide_columns import decode_entity

        v = self._get_raw(key, opts, cf=cf)
        return None if v is None else decode_entity(v)

    def _get_raw(self, key: bytes, opts: ReadOptions = _DEFAULT_READ,
                 cf=None):
        """Point lookup WITHOUT wide-column default-column unwrapping
        (get_entity needs the full encoding)."""
        return self._get_impl_entry(key, opts, cf)[0]

    def get_merge_operands(self, key: bytes,
                           opts: ReadOptions = _DEFAULT_READ,
                           cf=None) -> list[bytes]:
        """The UNMERGED chain for a key (reference DB::GetMergeOperands):
        the base value (if any) first, then merge operands oldest→newest.
        A plain key returns [value]; a missing/deleted key returns [].
        Reuses GetContext's visibility/tombstone state machine in
        collect-only mode."""
        self._check_open()
        cfd = self._cf_data(cf)
        snap_seq = (
            opts.snapshot.sequence if opts.snapshot is not None
            else self.versions.last_sequence
        )
        ctx = GetContext(
            key, snap_seq, None, blob_resolver=self.blob_source.get,
            collect_operands=True, excluded_ranges=self._excluded_for(opts),
        )
        more = True
        for mem in [cfd.mem] + cfd.imm:
            if not self._probe_memtable(mem, key, snap_seq, ctx):
                more = False
                break
        if more:
            version = self.versions.cf_current(cfd.handle.id)
            self._walk_sst_chain(version, key, snap_seq, ctx)
        return ctx.merge_operand_list()

    # ==================================================================
    # Iterators & snapshots
    # ==================================================================

    def new_iterator(self, opts: ReadOptions = _DEFAULT_READ, cf=None) -> DBIter:
        """MVCC iterator over the whole keyspace (reference
        DBImpl::NewIterator → DBIter over a MergingIterator)."""
        self._check_open()
        self._check_read_ts(opts)
        if opts.tailing:
            import dataclasses as _dcs

            from toplingdb_tpu.db.forward_iterator import ForwardIterator

            fwd = ForwardIterator(
                self, _dcs.replace(opts, tailing=False), cf=cf
            )
            tr = self._op_tracer
            if tr is not None:
                from toplingdb_tpu.utils.trace import TracingIterator

                return TracingIterator(fwd, tr)
            return fwd
        cfd = self._cf_data(cf)
        # Async read plane: iterator readahead windows become reader-ring
        # tasks (FilePrefetchBuffer(aio_ring=)); each child pins one ring
        # so its windows stay ordered while children overlap. The batcher
        # is resolved BEFORE taking the DB mutex (its creation takes it).
        batcher = self._reader_batcher() if self._async_reads_on() else None
        with self._mutex:
            snap_seq = (
                opts.snapshot.sequence if opts.snapshot is not None
                else self.versions.last_sequence
            )
            version = self.versions.cf_current(cfd.handle.id)
            children = []
            rd = RangeDelAggregator(self.icmp.user_comparator)
            ra = opts.readahead_size
            for mem in [cfd.mem] + cfd.imm:
                children.append(mem.new_iterator())
                for seq, begin, end in mem.range_del_entries():
                    rd.add(RangeTombstone(seq, begin, end))
            for i, f in enumerate(version.files[0]):
                reader = self.table_cache.get_reader(f.number)
                if (ra or batcher is not None) \
                        and hasattr(reader, "new_index_iterator"):
                    children.append(reader.new_iterator(
                        readahead_size=ra,
                        aio_ring=(batcher.ring_for(i)
                                  if batcher is not None else None)))
                else:
                    children.append(reader.new_iterator())
                for b, e in reader.range_del_entries():
                    rd.add(RangeTombstone.from_table_entry(b, e))
            for level in range(1, version.num_levels):
                if version.files[level]:
                    children.append(
                        LevelIterator(self.table_cache, version.files[level],
                                      self.icmp, readahead_size=ra,
                                      aio_ring=(batcher.ring_for(level)
                                                if batcher is not None
                                                else None))
                    )
                    # Only files that actually hold tombstones are opened here
                    # (num_range_deletions travels in the MANIFEST metadata);
                    # data blocks are still opened lazily by LevelIterator.
                    for f in version.files[level]:
                        if f.num_range_deletions == 0:
                            continue
                        reader = self.table_cache.get_reader(f.number)
                        for b, e in reader.range_del_entries():
                            rd.add(RangeTombstone.from_table_entry(b, e))
            internal = MergingIterator(self.icmp.compare, children)
            it = DBIter(
                internal, self.icmp, snap_seq,
                range_del_agg=None if rd.empty() else rd,
                merge_operator=self.options.merge_operator,
                lower_bound=opts.iterate_lower_bound,
                upper_bound=opts.iterate_upper_bound,
                pinned=version,
                blob_resolver=self.blob_source.get,
                prefix_extractor=self.options.prefix_extractor,
                prefix_same_as_start=(
                    opts.prefix_same_as_start and not opts.total_order_seek
                ),
                excluded_ranges=self._excluded_for(opts),
                read_ts=opts.timestamp,
                legacy_wce=bool(getattr(
                    self.options, "legacy_wide_column_unwrap", False)),
            )
            # Chunked scan plane (ops/scan_plane.py): native block decode
            # + k-way merge for forward scans; None when the iterator
            # shape is ineligible (the per-entry path runs unchanged).
            from toplingdb_tpu.ops.scan_plane import make_scan_plane

            plane = make_scan_plane(
                mems=[cfd.mem] + list(cfd.imm),
                l0_files=list(version.files[0]),
                level_runs=[version.files[lv]
                            for lv in range(1, version.num_levels)
                            if version.files[lv]],
                table_cache=self.table_cache,
                icmp=self.icmp,
                snap_seq=snap_seq,
                rd=None if rd.empty() else rd,
                lower=opts.iterate_lower_bound,
                upper=opts.iterate_upper_bound,
                blob_resolver=self.blob_source.get,
                merge_operator=self.options.merge_operator,
                prefix_mode=(opts.prefix_same_as_start
                             and not opts.total_order_seek
                             and self.options.prefix_extractor is not None),
                excluded=self._excluded_for(opts),
                read_ts=opts.timestamp,
                stats=self.stats,
                readahead_size=ra,
                protection_bytes=self._protection,
                aio_rings=batcher,
            )
            if plane is not None:
                it.attach_scan_plane(plane)
            if opts.snapshot is None:
                # Refresh re-reads at the LATEST sequence; snapshot-pinned
                # iterators can't refresh (reference Iterator::Refresh
                # returns NotSupported for them).
                it._refresh_fn = lambda: self.new_iterator(opts, cf)
            if self.stats is not None:
                from toplingdb_tpu.utils import statistics as st

                it.stats = self.stats
                self.stats.record_tick(st.NO_ITERATOR_CREATED)
            tr = self._op_tracer
            if tr is not None:
                from toplingdb_tpu.utils.trace import TracingIterator

                return TracingIterator(it, tr)
            return it

    def _excluded_for(self, opts) -> tuple:
        """Seqno ranges invisible to this read (undecided WritePrepared
        transactions): a snapshot carries the set captured at its creation;
        snapshot-less reads use the live set."""
        if opts.snapshot is not None:
            return getattr(opts.snapshot, "excluded_ranges", ())
        fn = self._undecided_provider
        return fn() if fn is not None else ()

    def increase_full_history_ts_low(self, ts_low: int) -> None:
        """Raise the UDT history trim point (reference
        DB::IncreaseFullHistoryTsLow): future compactions collapse versions
        below it. Monotonic; requires a ts comparator."""
        if self.icmp.user_comparator.timestamp_size == 0:
            raise InvalidArgument("DB has no user-defined timestamps")
        if ts_low < self.options.full_history_ts_low:
            raise InvalidArgument(
                f"full_history_ts_low can only increase "
                f"({ts_low} < {self.options.full_history_ts_low})"
            )
        old = self.options.full_history_ts_low
        self.options.full_history_ts_low = ts_low
        from toplingdb_tpu.utils.config import persist_options

        try:
            # The bump must be durable BEFORE any compaction trims under it
            # — otherwise a reopen resets the floor and already-collapsed
            # history becomes silently readable. Persist or roll back.
            persist_options(self)
        except Exception:
            self.options.full_history_ts_low = old
            raise

    def get_snapshot(self):
        if self.options.unordered_write:
            # Unordered writes publish out of allocation order: drain the
            # in-flight memtable phases that were allocated before now, so
            # the snapshot sees a prefix-consistent sequence history
            # (reference DBImpl::GetSnapshotImpl -> WaitForPendingWrites).
            with self._mutex:
                target = self._seq_alloc
                while self.versions.last_sequence < target:
                    self._mt_cv.wait(timeout=10.0)
        fn = self._undecided_provider
        return self.snapshots.new_snapshot(
            self.versions.last_sequence,
            excluded_ranges=fn() if fn is not None else (),
        )

    def release_snapshot(self, snap) -> None:
        snap.release()

    # ==================================================================
    # Maintenance
    # ==================================================================

    def compact_range(self, begin: bytes | None = None, end: bytes | None = None) -> None:
        """Manual compaction; wired up by the compaction module."""
        self.flush()
        if self._compaction_scheduler is not None:
            self._compaction_scheduler.compact_range(begin, end)

    def compact_files(self, file_numbers: list[int], output_level: int,
                      cf=None) -> None:
        """Compact a caller-chosen set of files into output_level (reference
        DB::CompactFiles, db.h): files must live at one source level and/or
        at output_level itself."""
        cfd = self._cf_data(cf)
        from toplingdb_tpu.compaction.picker import Compaction

        if not 0 <= output_level < self.options.num_levels:
            raise InvalidArgument(
                f"output_level {output_level} out of range "
                f"[0, {self.options.num_levels})"
            )
        want = set(file_numbers)
        with self._mutex:
            version = self.versions.cf_current(cfd.handle.id)
            by_level: dict[int, list] = {}
            for lvl, f in version.all_files():
                if f.number in want:
                    by_level.setdefault(lvl, []).append(f)
                    want.discard(f.number)
            if want:
                raise InvalidArgument(f"files not live: {sorted(want)}")
            src_levels = [lvl for lvl in by_level if lvl != output_level]
            if len(src_levels) > 1:
                raise InvalidArgument(
                    f"input files span levels {sorted(by_level)}; at most "
                    f"one source level plus output_level {output_level}"
                )
            src = src_levels[0] if src_levels else output_level
            if src > output_level:
                raise InvalidArgument(
                    f"source level {src} is below output level {output_level}"
                )
            inputs = by_level.get(src, [])
            out_inputs = (
                by_level.get(output_level, []) if src != output_level else []
            )
            # Reference CompactFiles sanitization EXPANDS the caller's set
            # rather than rejecting it
            # (compaction_picker.cc:908 SanitizeCompactionInputFilesForAllLevels):
            # at L0 every file OLDER than the newest listed file comes along
            # (newer unlisted runs stay on top, so reads never see stale data
            # below newer data); at sorted levels the listed run is widened
            # across same-user-key boundaries; at the output level all
            # overlapping files are included to keep it non-overlapping.
            listed = {f.number for f in inputs + out_inputs}
            ucmp = self.icmp.user_comparator

            def _widen(lvl_files, lo, hi):
                # Same-user-key boundary widening (reference while-loops at
                # compaction_picker.cc:959-975): a neighbor sharing a
                # boundary user key must come along, else seqno zeroing can
                # reorder that key across the excluded file.
                while lo > 0 and ucmp.compare(
                        dbformat.extract_user_key(lvl_files[lo - 1].largest),
                        dbformat.extract_user_key(
                            lvl_files[lo].smallest)) >= 0:
                    lo -= 1
                while hi + 1 < len(lvl_files) and ucmp.compare(
                        dbformat.extract_user_key(lvl_files[hi + 1].smallest),
                        dbformat.extract_user_key(
                            lvl_files[hi].largest)) <= 0:
                    hi += 1
                return lo, hi

            if inputs and src == 0:
                # L0 is time-ordered, not key-ordered: every file OLDER than
                # the newest listed file comes along (for intra-L0 jobs too —
                # a non-contiguous subset compacted past an unlisted middle
                # file would re-sort newer data below it).
                l0 = version.files[0]  # newest-first
                first = min(i for i, f in enumerate(l0)
                            if f.number in listed)
                inputs = list(l0[first:])
            elif inputs and src >= 1:
                lvl_files = version.files[src]  # sorted by smallest key
                idxs = [i for i, f in enumerate(lvl_files)
                        if f.number in listed]
                lo, hi = _widen(lvl_files, min(idxs), max(idxs))
                inputs = list(lvl_files[lo:hi + 1])
            all_in = inputs + out_inputs
            if all_in:
                su = dbformat.extract_user_key(
                    min((f.smallest for f in all_in), key=self.icmp.sort_key))
                lu = dbformat.extract_user_key(
                    max((f.largest for f in all_in), key=self.icmp.sort_key))
                if src != output_level and output_level > 0:
                    out_files = version.files[output_level]
                    ov = {f.number for f in version.overlapping_files(
                        output_level, su, lu)}
                    oidxs = [i for i, f in enumerate(out_files)
                             if f.number in ov]
                    if oidxs:
                        lo, hi = _widen(out_files, min(oidxs), max(oidxs))
                        out_inputs = list(out_files[lo:hi + 1])
                    else:
                        out_inputs = []
                # Intermediate levels can't be represented by a two-level
                # Compaction: anything overlapping there keeps its newer
                # data ABOVE the moved output, which is unsafe — reject.
                for lvl in range(src + 1, output_level):
                    for f in version.overlapping_files(lvl, su, lu):
                        raise InvalidArgument(
                            f"file #{f.number} at intermediate L{lvl} "
                            f"overlaps the compaction range; compact it "
                            f"first or choose output_level {lvl}"
                        )
            if any(f.being_compacted for f in inputs + out_inputs):
                raise Busy("some input files are already being compacted")
            c = Compaction(
                level=src, output_level=output_level, inputs=inputs,
                output_level_inputs=out_inputs,
                bottommost=self._compaction_scheduler.picker._is_bottommost(
                    version, output_level,
                    min((f.smallest for f in inputs + out_inputs),
                        key=self.icmp.sort_key),
                    max((f.largest for f in inputs + out_inputs),
                        key=self.icmp.sort_key),
                ) if inputs + out_inputs else False,
                reason="compact_files",
                max_output_file_size=self.options.target_file_size(output_level),
                cf_id=cfd.handle.id,
                full_history_ts_low=self.options.full_history_ts_low,
            )
            for _, f in c.all_inputs():
                f.being_compacted = True
        try:
            self._compaction_scheduler._run_compaction(c)
        finally:
            with self._mutex:
                for _, f in c.all_inputs():
                    f.being_compacted = False

    def suggest_compact_range(self, begin: bytes | None = None,
                              end: bytes | None = None, cf=None) -> int:
        """Mark files overlapping [begin, end) for compaction (reference
        DB::SuggestCompactRange): the picker prioritizes marked files on its
        next pass. Returns the number of files marked."""
        cfd = self._cf_data(cf)
        ucmp = self.icmp.user_comparator
        marked = 0
        with self._mutex:
            version = self.versions.cf_current(cfd.handle.id)
            for _lvl, f in version.all_files():
                fs = dbformat.extract_user_key(f.smallest)
                fl = dbformat.extract_user_key(f.largest)
                if begin is not None and ucmp.compare(fl, begin) < 0:
                    continue
                if end is not None and ucmp.compare(fs, end) >= 0:
                    continue
                if not f.marked_for_compaction:
                    f.marked_for_compaction = True
                    marked += 1
        if marked:
            self._maybe_schedule_compaction()
        return marked

    def promote_l0(self, target_level: int = 1, cf=None) -> None:
        """Metadata-only move of ALL L0 files to target_level (reference
        DB::PromoteL0): requires pairwise non-overlapping L0 files and
        empty levels 1..target_level."""
        if not 1 <= target_level < self.options.num_levels:
            raise InvalidArgument(
                f"target_level {target_level} out of range "
                f"[1, {self.options.num_levels})"
            )
        cfd = self._cf_data(cf)
        ucmp = self.icmp.user_comparator
        with self._mutex:
            version = self.versions.cf_current(cfd.handle.id)
            l0 = list(version.files[0])
            if not l0:
                return
            for lvl in range(1, target_level + 1):
                if version.files[lvl]:
                    raise InvalidArgument(
                        f"level {lvl} is not empty; cannot promote L0 over it"
                    )
            ordered = sorted(
                l0, key=lambda f: self.icmp.sort_key(f.smallest)
            )
            for a, b in zip(ordered, ordered[1:]):
                if ucmp.compare(dbformat.extract_user_key(a.largest),
                                dbformat.extract_user_key(b.smallest)) >= 0:
                    raise InvalidArgument(
                        "L0 files overlap; compact instead of promoting"
                    )
            if any(f.being_compacted for f in l0):
                raise Busy("L0 files are being compacted")
            edit = VersionEdit(column_family=cfd.handle.id)
            for f in l0:
                edit.delete_file(0, f.number)
                edit.add_file(target_level, f)
            self.versions.log_and_apply(edit)

    def wait_for_compactions(self) -> None:
        """Return when no sealed memtable waits for the flush thread and
        no compaction is running or pending."""
        with self._mutex:
            self._wait_for_flushes()
        if self._compaction_scheduler is not None:
            self._compaction_scheduler.wait_idle()
        if self._bg_error is not None:
            raise IOError_(f"background error: {self._bg_error!r}")

    def _classify_bg_error(self, e: BaseException, reason: str):
        """Map (error, background reason) → Severity, mirroring the
        reference's ErrorHandler severity tables (db/error_handler.cc:
        kSoft for retryable/no-space flush+compaction IO errors, kFatal for
        MANIFEST failures and corruption, kUnrecoverable for corruption
        found BY compaction — it would be baked into new SSTs). The
        integrity scrubber's kCorruption latch (reason="scrub") is HARD,
        not FATAL: the corrupt file is quarantined before the latch, so
        nothing wrong was served or propagated — after the operator
        restores/repairs the file and a clean re-scrub, resume() is
        legitimate (db/integrity.py)."""
        from toplingdb_tpu.utils.status import Corruption as _Corr
        from toplingdb_tpu.utils.status import Severity

        if isinstance(e, _Corr):
            if reason == "scrub":
                return Severity.HARD_ERROR
            return (Severity.UNRECOVERABLE if reason == "compaction"
                    else Severity.FATAL_ERROR)
        if reason == "manifest":
            return Severity.FATAL_ERROR
        if reason == "no_space":
            # kNoSpace: space comes back (trash drain, store GC, operator
            # freeing the disk) — SOFT, so the auto-recover loop clears
            # the latch once the free-space poller sees headroom again.
            return Severity.SOFT_ERROR
        if getattr(e, "retryable", False) and reason in (
                "flush", "compaction"):
            return Severity.SOFT_ERROR
        return Severity.HARD_ERROR

    def _set_background_error(self, e: BaseException,
                              reason: str = "compaction") -> None:
        """Reference ErrorHandler::SetBGError. Severity decides behavior:
        SOFT (retryable flush/compaction IO) — foreground writes continue,
        background work pauses, auto-recovery retries; HARD — writes raise
        until resume(); FATAL/UNRECOVERABLE (corruption, MANIFEST loss) —
        resume() refuses, the DB must be reopened."""
        from toplingdb_tpu.utils.status import Severity, is_no_space

        if reason != "no_space" and is_no_space(e):
            # Re-reason a raw ENOSPC surfacing through any background
            # path (flush, compaction, WAL sync) so it classifies SOFT
            # and auto-recovers, mirroring the reference's kNoSpace
            # subcode extraction in ErrorHandler::SetBGError.
            reason = "no_space"
        if reason == "no_space":
            try:
                e.retryable = True  # the recover loop's keep-retrying gate
                e._bg_reason = "no_space"
            except Exception as attr_err:  # __slots__-style exceptions
                _errors.swallow(reason="bg-error-annotate", exc=attr_err)
            if self.stats is not None:
                self.stats.record_tick(_st.NO_SPACE_ERRORS, 1)
        sev = self._classify_bg_error(e, reason)
        with self._mutex:
            if self._bg_error is not None:
                # Only ever escalate (reference keeps the max severity).
                if sev <= self._bg_error_severity:
                    return
                self._bg_error = e
                self._bg_error_severity = sev
                self._bg_error_reason = reason
            else:
                self._bg_error = e
                self._bg_error_severity = sev
                self._bg_error_reason = reason
        # Listener + auto-recovery apply to escalations too: monitoring must
        # learn the DB got WORSE, and a retryable error that replaced the
        # one a recovery thread was chasing needs a fresh thread (the old
        # one exits at its `is not target` identity check).
        from toplingdb_tpu.utils.listener import notify

        notify(self.options.listeners, "on_background_error", self, e)
        if sev == Severity.SOFT_ERROR or (
                getattr(e, "retryable", False)
                and sev < Severity.FATAL_ERROR):
            ccy.spawn("db-auto-recover", self._auto_recover_loop,
                      args=(e,), owner=self)

    def _auto_recover_loop(self, target: BaseException,
                           max_attempts: int = 10,
                           base_delay: float = 0.05) -> None:
        """Only ever clears THE error it was started for (or retryable ones
        it re-latched itself) — a concurrently latched non-retryable error,
        or a manual resume(), ends the loop untouched (reference checks the
        recovery error identity the same way)."""
        no_space = getattr(target, "_bg_reason", "") == "no_space" or (
            self._bg_error is target and self._bg_error_reason == "no_space")
        attempt = 0
        backoff = 0  # grows on every pass, attempted or not
        while attempt < max_attempts:
            if self._recover_stop.wait(
                    min(base_delay * (2 ** min(backoff, 8)), 2.0)):
                return  # DB is closing; abandon recovery
            backoff += 1
            with self._mutex:
                if self._closed or self._bg_error is not target:
                    return
            if (no_space and self._sfm is not None
                    and not self._sfm.has_headroom()):
                # Space hasn't come back yet (trash still draining, store
                # GC pending, disk still full). Waiting here doesn't
                # consume an attempt: a no_space latch clears exactly when
                # the poller sees headroom, however long that takes.
                continue
            attempt += 1
            try:
                self.resume(_auto=True)
                self.wait_for_compactions()
                self.event_logger.log("auto_recovery_succeeded",
                                      attempts=attempt)
                return
            except Exception as err:  # still failing
                # ONE thread per latched error: chase only `target`. A new
                # error latched through _set_background_error spawns its
                # own successor thread, so any identity mismatch means
                # this thread's watch is over — re-targeting here would
                # leave two loops calling resume() concurrently.
                with self._mutex:
                    latched = self._bg_error
                if latched is target and getattr(
                        target, "retryable", False):
                    continue  # still our transient error; keep retrying
                if latched is None:
                    # Our retry cleared the old latch but then failed with
                    # a fresh error nothing latched yet: go through the
                    # front door (classification + successor thread) and
                    # bow out.
                    self._set_background_error(
                        err, getattr(err, "_bg_reason", "flush")
                    )
                return
        self.event_logger.log("auto_recovery_gave_up", attempts=max_attempts)

    def resume(self, *, _auto: bool = False) -> None:
        """Clear a background error and restart background work (reference
        DB::Resume / ErrorHandler::RecoverFromBGError). FATAL and
        UNRECOVERABLE errors (corruption, MANIFEST loss) refuse: the DB
        must be reopened to rebuild consistent state. Clearing a live
        latch notifies on_error_recovery_completed on BOTH the manual and
        auto paths (previously only the auto-recover loop notified) and
        ticks BG_ERROR_RESUMES."""
        from toplingdb_tpu.utils.status import Severity as _Sev

        with self._mutex:
            if (self._bg_error is not None
                    and self._bg_error_severity >= _Sev.FATAL_ERROR):
                raise IOError_(
                    f"background error is not resumable "
                    f"({self._bg_error_severity.name}); reopen the DB: "
                    f"{self._bg_error!r}"
                )
            had = self._bg_error
            reason = self._bg_error_reason
            self._bg_error = None
            self._bg_error_severity = _Sev.NO_ERROR
            self._bg_error_reason = ""
            # The flush thread tries its failed unit again.
            self._flush_failed = None
            self._flush_cv.notify_all()
        if had is not None:
            if self.stats is not None:
                self.stats.record_tick(_st.BG_ERROR_RESUMES, 1)
            from toplingdb_tpu.utils.listener import (
                ErrorRecoveryInfo, notify,
            )

            notify(self.options.listeners, "on_error_recovery_completed",
                   self, ErrorRecoveryInfo(db_name=self.dbname,
                                           reason=reason, auto=_auto))
        self._maybe_schedule_compaction()

    def _maybe_schedule_compaction(self) -> None:
        if self._compaction_scheduler is not None and not self.options.disable_auto_compactions:
            self._compaction_scheduler.maybe_schedule()

    def disk_pressure(self) -> str:
        """Current storage-pressure level ("ok" / "amber" / "red") from the
        SstFileManager's poller; "ok" when no manager is attached. The
        sharding admission controller and fleet write front door consult
        this to shed writes BEFORE the disk actually fills."""
        return self._sfm.pressure() if self._sfm is not None else "ok"

    def _on_disk_pressure_change(self, level: str, prev: str,
                                 info: dict) -> None:
        """SstFileManager pressure-transition callback (fires outside the
        manager's locks, on the poller thread). Escalations climb the
        reclaim ladder; a recovery to ok restarts paused compactions."""
        from toplingdb_tpu.utils.listener import DiskPressureInfo, notify

        notify(self.options.listeners, "on_disk_pressure", self,
               DiskPressureInfo(
                   db_name=self.dbname, path=self.dbname, level=level,
                   prev_level=prev,
                   free_fraction=info.get("free_fraction", 0.0),
                   tracked_bytes=info.get("tracked_bytes", 0),
                   trash_bytes=info.get("trash_bytes", 0),
                   budget_bytes=info.get("budget_bytes", 0)))
        self.event_logger.log(
            "disk_pressure", level=level, prev=prev,
            free_fraction=round(info.get("free_fraction", 0.0), 4))
        order = {"ok": 0, "amber": 1, "red": 2}
        if order.get(level, 0) > order.get(prev, 0):
            self._run_reclaim_ladder(level)
        elif level == "ok":
            self._maybe_schedule_compaction()

    def _run_reclaim_ladder(self, level: str) -> None:
        """Free bytes in escalating cost order: (1) unpace trash deletion
        — bytes already condemned drain immediately; at red additionally
        (2) drop the clean shared-store cache tier and (3) kick a
        mark-sweep GC of the shared object store (own thread — the sweep
        walks manifests and may contend on the store-gc lease)."""
        if self._sfm is None:
            return
        if self.stats is not None:
            self.stats.record_tick(_st.DISK_RECLAIM_RUNS, 1)
        self._sfm.accelerate_deletes()
        if level != "red":
            return
        tier = getattr(self.env, "tier", None)
        if tier is not None and hasattr(tier, "prune"):
            try:
                tier.prune()
            except Exception as e:
                _errors.swallow(reason="disk-reclaim-cache-prune", exc=e,
                                stats=self.stats)
        store = getattr(self.env, "store", None)
        if store is not None and not self._store_gc_inflight:
            self._store_gc_inflight = True

            def run_gc():
                try:
                    from toplingdb_tpu.storage.gc import mark_sweep

                    # Roots: this DB plus every sibling directory that
                    # looks like a DB (has a CURRENT) — fleet shards
                    # share one store, and a sweep rooted only at *this*
                    # shard would reap its neighbors' live objects. The
                    # grace window additionally shields anything a root
                    # scan can't see yet.
                    import os as _os_gc

                    roots = {self.dbname}
                    parent = _os_gc.path.dirname(self.dbname)
                    try:
                        for child in self.env.get_children(parent or "."):
                            d = f"{parent}/{child}" if parent else child
                            if self.env.file_exists(
                                    filename.current_file_name(d)):
                                roots.add(d)
                    except Exception as probe_err:
                        _errors.swallow(reason="reclaim-gc-root-scan",
                                        exc=probe_err)
                    mark_sweep(store, sorted(roots), env=self.env,
                               grace_sec=60.0, statistics=self.stats)
                except Exception as e:
                    # Busy (another sweeper holds the lease) or a mid-
                    # sweep IO error: reclaim is best-effort by design.
                    _errors.swallow(reason="disk-reclaim-store-gc", exc=e,
                                    stats=self.stats)
                finally:
                    self._store_gc_inflight = False

            ccy.spawn("disk-reclaim-store-gc", run_gc, owner=self)

    def disable_file_deletions(self) -> None:
        """Reference DB::DisableFileDeletions (used by backup/checkpoint
        tools to pin the file set while copying). Counted: each disable
        needs a matching enable."""
        with self._mutex:
            self._file_deletions_disabled += 1

    def enable_file_deletions(self, force: bool = False) -> None:
        with self._mutex:
            n = self._file_deletions_disabled
            self._file_deletions_disabled = 0 if force else max(0, n - 1)
            if n > 0 and self._file_deletions_disabled == 0:
                self._delete_obsolete_files()  # final unpin purges

    def flush_wal(self, sync: bool = False) -> None:
        """Reference DB::FlushWAL/SyncWAL."""
        with self._mutex:
            if self._wal is not None:
                if sync:
                    self._wal.sync()
                else:
                    self._wal.flush()

    def _delete_obsolete_files(self) -> None:
        """GC: remove WALs below the manifest log number, non-live SSTs, and
        stale MANIFESTs (reference DBImpl::DeleteObsoleteFiles)."""
        if self._file_deletions_disabled:
            return  # a backup/checkpoint is pinning the file set
        live, live_blobs = self.versions.live_file_sets()
        for child in self.env.get_children(self.dbname):
            ftype, num = filename.parse_file_name(child)
            keep = True
            if ftype == filename.FileType.WAL:
                keep = (num >= self.versions.log_number
                        or num == self._wal_number
                        or num in self._recycle_wals)
                if not keep and (len(self._recycle_wals)
                                 < self.options.recycle_log_file_num
                                 and num in self._recyclable_written):
                    self._recycle_wals.append(num)
                    keep = True
                if not keep and self.options.wal_ttl_seconds > 0:
                    self._archive_wal(child)
                    continue
            elif ftype == filename.FileType.TABLE:
                keep = num in live or num in self._pending_outputs
            elif ftype == filename.FileType.BLOB:
                keep = num in live_blobs or num in self._pending_outputs
            elif ftype == filename.FileType.MANIFEST:
                keep = num == self.versions.manifest_file_number
            elif ftype == filename.FileType.OPTIONS:
                keep = (num == self._options_file_number
                        or self._options_file_number == 0)
            elif ftype == filename.FileType.TEMP:
                keep = False
            if not keep:
                path = f"{self.dbname}/{child}"
                if ftype == filename.FileType.TABLE:
                    self.table_cache.evict(num)
                elif ftype == filename.FileType.BLOB:
                    self.blob_source.evict(num)
                if (self._sfm is not None
                        and ftype in (filename.FileType.TABLE,
                                      filename.FileType.BLOB)):
                    # Obsolete SSTs/blobs (and store-materialized refs —
                    # the SharedSstEnv rename/delete passthroughs keep the
                    # local tree authoritative) go through the manager:
                    # paced trash deletion + live-byte accounting.
                    self._sfm.schedule_delete(path)
                    continue
                if self._sfm is not None:
                    self._sfm.on_delete_file(path)
                try:
                    self.env.delete_file(path)
                except NotFound:
                    pass

    def _archive_wal(self, child: str) -> None:
        """Move an obsolete WAL to <db>/archive/ and purge entries older
        than wal_ttl_seconds (reference WalManager::ArchiveWALFile /
        PurgeObsoleteWALFiles)."""
        arch = f"{self.dbname}/archive"
        self.env.create_dir(arch)
        try:
            self.env.rename_file(f"{self.dbname}/{child}", f"{arch}/{child}")
        except (OSError, NotFound):
            return
        if self._sfm is not None:
            # Archived WALs leave the tracked tree (TTL purge owns them).
            self._sfm.on_delete_file(f"{self.dbname}/{child}")
        now = time.time()
        try:
            names = self.env.get_children(arch)
        except NotFound:
            return
        for name in names:
            p = f"{arch}/{name}"
            try:
                mtime = self.env.get_file_mtime(p)
                if mtime is not None and \
                        now - mtime > self.options.wal_ttl_seconds:
                    self.env.delete_file(p)
            except (OSError, NotFound):
                continue

    def get_wal_files(self) -> list[tuple[int, str, bool]]:
        """(log_number, path, archived) for every retained WAL — live AND
        archived — oldest first (the reference WalFile metadata shape;
        get_sorted_wal_files keeps its names-only live-file contract for
        the backup tooling)."""
        out = []
        for child in self.env.get_children(self.dbname):
            ftype, num = filename.parse_file_name(child)
            if ftype == filename.FileType.WAL:
                out.append((num, f"{self.dbname}/{child}", False))
        arch = f"{self.dbname}/archive"
        try:
            for child in self.env.get_children(arch):
                ftype, num = filename.parse_file_name(child)
                if ftype == filename.FileType.WAL:
                    out.append((num, f"{arch}/{child}", True))
        except NotFound:
            pass
        return sorted(out)

    def verify_checksum(self) -> None:
        """Full checksum scan of every live SST (reference
        DB::VerifyChecksum): every data block is read FROM DISK and
        CRC-verified — cached readers/blocks are bypassed, as the reference
        scans with fill_cache=false; raises Corruption on the first bad
        block. Opening with verify_checksums=True also CRC-verifies the
        index, metaindex, properties, filter, and range-del meta blocks at
        construction, and every BLOB_INDEX entry's referenced blob record
        is probed with its record CRC — the meta/blob coverage the plain
        data-block walk used to miss. Holding the Version objects pins the
        files against concurrent obsolete-file GC."""
        import dataclasses as _dc

        from toplingdb_tpu.table.factory import open_table
        from toplingdb_tpu.utils import statistics as _st

        with self._mutex:
            versions = [
                self.versions.cf_current(cf_id)
                for cf_id in self.versions.column_families
            ]
        topts = _dc.replace(self.options.table_options, verify_checksums=True)
        bytes_verified = 0
        for version in versions:
            for _, f in version.all_files():
                path = filename.table_file_name(self.dbname, f.number)
                reader = open_table(
                    self.env.new_random_access_file(path), self.icmp, topts
                )
                try:
                    it = reader.new_iterator()
                    it.seek_to_first()
                    for ik, v in it.entries():  # decoding verifies block CRCs
                        if ik[-8] == dbformat.ValueType.BLOB_INDEX:
                            # Sweep the referenced blob record (its value
                            # CRC rides in the blob file, db/blob.py).
                            self.blob_source.get(v, verify=True)
                finally:
                    reader.close()
                bytes_verified += f.file_size
        if self.stats is not None and bytes_verified:
            self.stats.record_tick(_st.INTEGRITY_BYTES_VERIFIED,
                                   bytes_verified)

    def verify_file_checksums(self) -> dict:
        """Recompute every live SST's whole-file checksum and compare with
        the MANIFEST-recorded value (reference DB::VerifyFileChecksums);
        raises Corruption on the first mismatch. Returns
        {'files_verified', 'bytes_verified', 'files_skipped'} — skipped
        files predate checksum recording (or it is disabled)."""
        from toplingdb_tpu.utils import statistics as _st
        from toplingdb_tpu.utils.file_checksum import (
            verify_recorded_checksum,
        )

        with self._mutex:
            versions = [
                self.versions.cf_current(cf_id)
                for cf_id in self.versions.column_families
            ]
        verified = bytes_v = skipped = 0
        seen: set[int] = set()
        for version in versions:
            for _, f in version.all_files():
                if f.number in seen:
                    continue
                seen.add(f.number)
                path = filename.table_file_name(self.dbname, f.number)
                n = verify_recorded_checksum(self.env, path, f)
                if n:
                    verified += 1
                    bytes_v += n
                else:
                    skipped += 1
        if self.stats is not None and bytes_v:
            self.stats.record_tick(_st.INTEGRITY_BYTES_VERIFIED, bytes_v)
        return {"files_verified": verified, "bytes_verified": bytes_v,
                "files_skipped": skipped}

    def scrub(self, deep: bool = False) -> dict:
        """Run one IntegrityScrubber pass synchronously (db/integrity.py)
        and return its report. Detected corruption quarantines the file,
        fires on_corruption_detected, and latches the background-error
        machinery (resume() after repair)."""
        self._check_open()
        if self._integrity_scrubber is None:
            from toplingdb_tpu.db.integrity import IntegrityScrubber

            self._integrity_scrubber = IntegrityScrubber(self)
        return self._integrity_scrubber.run_pass(deep=deep)

    def scrub_status(self) -> dict:
        """The /integrity HTTP view's payload (utils/config.py)."""
        if self._integrity_scrubber is None:
            return {"running": False, "passes": 0,
                    "quarantined_files": sorted(self._quarantined)}
        return self._integrity_scrubber.status()

    def _stamp_file_checksums(self, metas) -> None:
        """Compute + record whole-file checksums on freshly produced SST
        metadata before it reaches the MANIFEST (flush, compaction
        install, ingest, import). No-op when disabled."""
        factory = self._file_checksum_factory
        if factory is None:
            return
        from toplingdb_tpu.utils.file_checksum import stamp_file_checksum

        publish = getattr(self.env, "publish_sst", None)
        for meta in metas:
            path = filename.table_file_name(self.dbname, meta.number)
            stamp_file_checksum(self.env, path, meta, factory)
            # Shared-store mode: every install (flush, compaction,
            # ingest, import) also publishes the table to the
            # content-addressed store. Idempotent — an already-published
            # address (dcompact adoption) is a contains() probe.
            if publish is not None:
                try:
                    publish(path, meta)
                except Exception as e:  # noqa: BLE001 — store outage
                    # The install stays valid on local bytes; a later
                    # checkpoint/dcompact re-publishes (idempotent).
                    from toplingdb_tpu.utils import errors as _errors
                    _errors.swallow(reason="install-publish-sst", exc=e)

    def get_approximate_sizes(self, ranges: list[tuple[bytes, bytes]],
                              cf=None) -> list[int]:
        """Approximate on-disk bytes per [begin, end) user-key range
        (reference DB::GetApproximateSizes via ApproximateOffsetOf)."""
        cfd = self._cf_data(cf)
        ucmp = self.icmp.user_comparator
        version = self.versions.cf_current(cfd.handle.id)
        out = []
        for begin, end in ranges:
            bk = dbformat.make_internal_key(
                begin, dbformat.MAX_SEQUENCE_NUMBER,
                dbformat.VALUE_TYPE_FOR_SEEK)
            ek = dbformat.make_internal_key(
                end, dbformat.MAX_SEQUENCE_NUMBER,
                dbformat.VALUE_TYPE_FOR_SEEK)
            total = 0
            for level in range(version.num_levels):
                for f in version.files[level]:
                    # Metadata-only overlap check before touching a reader.
                    if (ucmp.compare(dbformat.extract_user_key(f.largest),
                                     begin) < 0
                            or ucmp.compare(end, dbformat.extract_user_key(
                                f.smallest)) < 0):
                        continue
                    reader = self.table_cache.get_reader(f.number)
                    lo = reader.approximate_offset_of(bk)
                    hi = reader.approximate_offset_of(ek)
                    if hi > lo:
                        total += hi - lo
            out.append(total)
        return out

    def delete_files_in_range(self, begin: bytes, end: bytes, cf=None) -> int:
        """Drop whole SSTs fully contained in [begin, end) (reference
        DeleteFilesInRange — the bulk-wipe fast path; boundary files keep
        their data, which a DeleteRange + compaction then clears). Returns
        the number of files dropped."""
        cfd = self._cf_data(cf)
        ucmp = self.icmp.user_comparator
        with self._mutex:
            version = self.versions.cf_current(cfd.handle.id)
            doomed: list[tuple[int, int]] = []
            for level in range(1, version.num_levels):  # L0 ranges overlap
                for f in version.files[level]:
                    if f.being_compacted:
                        continue
                    fs = dbformat.extract_user_key(f.smallest)
                    fl = dbformat.extract_user_key(f.largest)
                    if ucmp.compare(begin, fs) <= 0 and ucmp.compare(fl, end) < 0:
                        doomed.append((level, f.number))
            if not doomed:
                return 0
            edit = VersionEdit(column_family=cfd.handle.id)
            for level, num in doomed:
                edit.delete_file(level, num)
            self.versions.log_and_apply(edit)
            self._delete_obsolete_files()
            return len(doomed)

    def get_live_files(self, flush_memtable: bool = True
                       ) -> tuple[list[str], int]:
        """(relative file names, manifest_file_size) — everything a
        consistent copy needs (reference DB::GetLiveFiles): SSTs + blobs +
        CURRENT/MANIFEST/OPTIONS. The live MANIFEST keeps growing, so the
        caller must TRUNCATE its copy at manifest_file_size or the copy
        references files newer than the snapshot. Hold
        disable_file_deletions() while copying."""
        from toplingdb_tpu.db.blob import blob_file_name

        self._check_open()
        if flush_memtable:
            self.flush()
        with self._mutex:
            # CURRENT versions only — files pinned solely by in-flight
            # readers are not part of a consistent copy (reference
            # GetLiveFiles semantics).
            ssts: set[int] = set()
            blobs: set[int] = set()
            for cf_id in self.versions.column_families:
                for _, f in self.versions.cf_current(cf_id).all_files():
                    ssts.add(f.number)
                    blobs.update(f.blob_refs)
            # filename helpers with dbname="" yield bare basenames.
            out = [filename.table_file_name("", n) for n in sorted(ssts)]
            out += [blob_file_name("", n) for n in sorted(blobs)]
            out.append(filename.current_file_name(""))
            out.append(filename.manifest_file_name(
                "", self.versions.manifest_file_number))
            if self._options_file_number:
                out.append(filename.options_file_name(
                    "", self._options_file_number))
            return out, self.versions.manifest_size()

    def get_sorted_wal_files(self) -> list[str]:
        """Live WAL file names, oldest first (reference
        DB::GetSortedWalFiles). While file deletions are disabled, EVERY
        on-disk WAL is returned — a concurrent flush may have advanced
        log_number, but the pinned older WALs can still carry data absent
        from a get_live_files snapshot taken earlier."""
        self._check_open()
        with self._mutex:
            pinned = self._file_deletions_disabled > 0
            nums = sorted(
                num for child in self.env.get_children(self.dbname)
                for t, num in [filename.parse_file_name(child)]
                if t == filename.FileType.WAL
                and (pinned or num >= self.versions.log_number
                     or num == self._wal_number)
            )
            return [filename.log_file_name("", n) for n in nums]

    def pause_background_work(self) -> None:
        """Sealed memtables are flushed first; flushes go on while paused
        (L0 piles up), compactions do not."""
        with self._mutex:
            self._wait_for_flushes()
        if self._compaction_scheduler is not None:
            self._compaction_scheduler.pause()

    def continue_background_work(self) -> None:
        if self._compaction_scheduler is not None:
            self._compaction_scheduler.resume_background()

    _MUTABLE_OPTIONS = frozenset({
        "write_buffer_size", "level0_file_num_compaction_trigger",
        "level0_slowdown_writes_trigger", "level0_stop_writes_trigger",
        "disable_auto_compactions", "max_bytes_for_level_base",
        "max_bytes_for_level_multiplier", "target_file_size_base",
        "target_file_size_multiplier", "max_compaction_bytes",
        "max_subcompactions", "max_background_jobs",
        "enable_blob_garbage_collection",
        "blob_garbage_collection_age_cutoff", "min_blob_size",
        "seqno_time_sample_period_sec", "fifo_ttl_seconds",
        "periodic_compaction_seconds",
    })

    def set_options(self, changes: dict) -> None:
        """Online option changes for the mutable subset (reference
        DB::SetOptions; the SidePlugin online-config mechanism). Unknown or
        immutable names — and values of the wrong type — raise
        InvalidArgument; the new values persist to a fresh OPTIONS file
        (persistence failures propagate). Serialized under the DB mutex so
        concurrent callers (the threaded HTTP server) can't interleave the
        OPTIONS-file roll."""
        base = Options()
        for k, v in changes.items():
            if k not in self._MUTABLE_OPTIONS:
                raise InvalidArgument(f"option {k!r} is not dynamically "
                                      f"changeable")
            want = type(getattr(base, k))
            if want is bool:
                ok = isinstance(v, bool)
            elif want is int:
                ok = isinstance(v, int) and not isinstance(v, bool)
            elif want is float:
                ok = isinstance(v, (int, float)) and not isinstance(v, bool)
            else:
                ok = isinstance(v, want)
            if not ok:
                raise InvalidArgument(
                    f"option {k!r} expects {want.__name__}, "
                    f"got {type(v).__name__}"
                )
        from toplingdb_tpu.utils.config import persist_options

        with self._mutex:
            for k, v in changes.items():
                setattr(self.options, k, v)
            old = self._options_file_number
            persist_options(self)
            if old:
                try:
                    self.env.delete_file(
                        filename.options_file_name(self.dbname, old))
                except NotFound:
                    pass
        self._maybe_schedule_compaction()

    _STATS_CF = "__tpulsm_stats__"

    def get_stats_history(self, start_time: int = 0, end_time: int = 2 ** 62,
                          include_persisted: bool = False):
        """Time-series ticker deltas (reference DBImpl::GetStatsHistory,
        db/db_impl/db_impl.cc:1102). Samples are taken every
        stats_persist_period_sec, or manually via persist_stats(). With
        include_persisted, samples stored in the hidden stats CF by
        persist_stats(to_db=True) are merged in (the reference's
        persist_stats_to_disk / ___rocksdb_stats_history___ CF)."""
        out = self.stats_history.get(start_time, end_time)
        if include_persisted:
            import json as _json

            in_memory = {ts for ts, _ in out}
            cf = self.get_column_family(self._STATS_CF)
            if cf is not None:
                it = self.new_iterator(cf=cf)
                it.seek(b"%020d" % start_time)
                while it.valid():
                    try:
                        ts = int(it.key().split(b".")[0].decode())
                        delta = {
                            k: int(v) for k, v in
                            _json.loads(it.value().decode()).items()
                        }
                    except (ValueError, UnicodeDecodeError):
                        it.next()
                        continue  # foreign/corrupt entry: skip, don't crash
                    if ts >= end_time:
                        break
                    if ts not in in_memory:  # avoid double-counting samples
                        out.append((ts, delta))
                    it.next()
                out.sort(key=lambda s: s[0])
        return out

    def persist_stats(self, to_db: bool = False) -> None:
        self.stats_history.snapshot()
        if not to_db:
            return
        sample = self.stats_history.last_sample()
        if sample is None:
            return
        import json as _json

        with self._mutex:
            cf = self.get_column_family(self._STATS_CF)
            if cf is None:
                cf = self.create_column_family(self._STATS_CF)
            self._stats_persist_seq = getattr(
                self, "_stats_persist_seq", 0) + 1
            seq = self._stats_persist_seq
        ts, delta = sample
        # Counter suffix: two persists in the same second must not collide.
        self.put(b"%020d.%06d" % (ts, seq), _json.dumps(delta).encode(),
                 cf=cf)

    def get_property(self, name: str) -> str | None:
        v = self.versions.current
        if name == "tpulsm.stats" or name == "tpulsm.levelstats":
            lines = [f"last_seq={self.versions.last_sequence} "
                     f"mem_entries={self.mem.num_entries} imm={len(self.imm)}"]
            for level in range(v.num_levels):
                n = len(v.files[level])
                if n:
                    lines.append(f"L{level}: {n} files {v.total_bytes(level)} bytes")
            return "\n".join(lines)
        if name == "tpulsm.num-files":
            return str(v.num_files())
        if name == "tpulsm.background-errors":
            return str(int(self._bg_error is not None))
        if name == "tpulsm.bg-error-severity":
            return self._bg_error_severity.name
        if name == "tpulsm.estimate-num-keys":
            # Reference rocksdb.estimate-num-keys: live table entries minus
            # deletions plus memtable entries (overcounts overwrites).
            n = sum(
                max(0, m.num_entries - 2 * m.num_deletes)
                for c in self._cfs.values() for m in [c.mem] + c.imm
            )
            for cf_id in self.versions.column_families:
                for _, f in self.versions.cf_current(cf_id).all_files():
                    n += max(0, f.num_entries - 2 * f.num_deletions)
            return str(n)
        if name == "tpulsm.cur-size-all-mem-tables":
            return str(sum(
                c.mem.approximate_memory_usage()
                + sum(m.approximate_memory_usage() for m in c.imm)
                for c in self._cfs.values()
            ))
        if name == "tpulsm.num-immutable-mem-table":
            return str(sum(len(c.imm) for c in self._cfs.values()))
        if name == "tpulsm.num-snapshots":
            return str(self.snapshots.num_live())
        if name == "tpulsm.estimate-live-data-size":
            return str(sum(
                f.file_size
                for cf_id in self.versions.column_families
                for _, f in self.versions.cf_current(cf_id).all_files()
            ))
        if name == "tpulsm.background-errors":
            return "1" if self._bg_error is not None else "0"
        if name == "tpulsm.num-running-compactions":
            s = self._compaction_scheduler
            return str(s._running if s is not None else 0)
        if name == "tpulsm.thread-times":
            import json as _json

            # A led writer that has exited resolves to None.
            tids = {t.ident: t.native_id for t in threading.enumerate()}
            return _json.dumps(_tm.thread_times(tids.get(self._led_ident)))
        if name == "tpulsm.threads":
            import json as _json

            from toplingdb_tpu.utils.thread_status import get_thread_list

            return _json.dumps(get_thread_list())
        if name.startswith("tpulsm.num-files-at-level"):
            try:
                lvl = int(name[len("tpulsm.num-files-at-level"):])
            except ValueError:
                return None
            return str(len(v.files[lvl])) if 0 <= lvl < v.num_levels else None
        return None

    def _check_open(self) -> None:
        if self._closed:
            from toplingdb_tpu.utils.status import ShutdownInProgress

            raise ShutdownInProgress("DB is closed")
