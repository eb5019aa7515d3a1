"""Background compaction scheduling.

Role of the reference's MaybeScheduleFlushOrCompaction → BGWorkCompaction
chain (db/db_impl/db_impl_compaction_flush.cc:2662-3279 in /root/reference):
after every flush/compaction the scores are re-evaluated and jobs run on a
bounded worker pool. Jobs route through the CompactionExecutor boundary when
one is configured (device=cpu|tpu|remote), with fallback to local
(reference compaction_job.cc:648-655).
"""

from __future__ import annotations

import threading

from toplingdb_tpu.utils import concurrency as ccy
import traceback

from toplingdb_tpu.db import dbformat

from toplingdb_tpu.compaction.compaction_job import (
    make_version_edit,
    run_compaction_to_tables,
)
from toplingdb_tpu.compaction.picker import Compaction, create_picker


from toplingdb_tpu.compaction.compaction_job import (  # noqa: E402
    emit_phase_spans as _emit_phase_spans,
)


class CompactionScheduler:
    def __init__(self, db, background: bool = True):
        self.db = db
        self.picker = create_picker(db.options, db.icmp)
        # Age policies need table properties (creation_time lives there).
        self.picker.creation_time_fn = self._file_creation_time
        self.background = background
        self._pending = 0
        self._running = 0
        self._lock = ccy.Lock("scheduler.CompactionScheduler._lock")
        self._cv = ccy.Condition(lock=self._lock)
        self._shutdown = False
        self._manual_active = False
        self._paused = 0
        self.last_error: BaseException | None = None
        self.num_completed = 0
        self.num_trivial_moves = 0
        # Graceful-degradation gate for remote compaction: after N
        # consecutive remote JOB failures, jobs pin local for a cooldown
        # (compaction/resilience.py). Lazily built from options.dcompact.
        self._pin_gate = None
        # (retry_ts, FileMetaData) of marked-rewrite jobs postponed by
        # preclude_last_level_data_seconds; re-marked once aged.
        self._preclude_remark: list = []
        # Consecutive space-preflight refusals since the last job that ran
        # (log the FIRST refusal of a streak, tick all of them).
        self._space_blocks = 0

    # ------------------------------------------------------------------

    def pause(self) -> None:
        """Reference DB::PauseBackgroundWork: block until running jobs
        drain, then hold new ones."""
        with self._lock:
            self._paused += 1
        self.wait_idle()

    def resume_background(self) -> None:
        with self._lock:
            self._paused = max(0, self._paused - 1)
        self.maybe_schedule()

    def maybe_schedule(self) -> None:
        if self.db.options.disable_auto_compactions:
            return
        if self.background:
            with self._lock:
                # _paused must be checked under the lock, or a racing
                # schedule could slip in after pause() returned.
                if self._shutdown or self._manual_active or self._paused:
                    return
                if self._running + self._pending >= self.db.options.max_background_jobs:
                    return
                self._pending += 1
            ccy.spawn("compaction-bg", self._bg_work, owner=self,
                      stop=self.shutdown)
        else:
            with self._lock:
                if self._paused:
                    return
            while self._run_one():
                pass

    def _bg_work(self) -> None:
        # Keep running jobs in THIS thread until no work remains: _running
        # stays nonzero for the whole drain, so wait_idle() can never observe
        # a false idle gap between one job finishing and its follow-up being
        # scheduled.
        with self._lock:
            self._pending -= 1
            self._running += 1
        try:
            while True:
                with self._lock:
                    if self._shutdown or self._manual_active:
                        break
                if not self._run_one():
                    break
        except BaseException as e:
            # Surface to the DB's error handler: writes fail until resume()
            # (reference ErrorHandler, db/error_handler.h:28).
            self.last_error = e
            self.db._set_background_error(
                e, getattr(e, "_bg_reason", "compaction")
            )
            traceback.print_exc()
        finally:
            with self._lock:
                self._running -= 1
                self._cv.notify_all()

    def wait_idle(self) -> None:
        """Block until no compaction is running or pending (test/bench aid)."""
        while True:
            with self._lock:
                if self._running == 0 and self._pending == 0:
                    return
                self._cv.wait(timeout=0.1)

    def shutdown(self) -> None:
        with self._lock:
            self._shutdown = True
        self.wait_idle()

    # ------------------------------------------------------------------

    def _file_creation_time(self, f):
        """Creation time from table properties, memoized on the meta so the
        age sweeps never re-open files (a sweep across a big DB would
        otherwise thrash the table-cache LRU on every cycle)."""
        ct = getattr(f, "_creation_time_cache", None)
        if ct is not None:
            return ct or None  # 0 sentinel = previously failed / absent
        try:
            ct = self.db.table_cache.get_reader(f.number).properties \
                .creation_time
        except Exception as e:
            self.db.event_logger.log(
                "creation_time_unreadable", file_number=f.number,
                error=repr(e),
            )
            ct = 0
        f._creation_time_cache = ct
        return ct or None

    def _apply_periodic_marking(self) -> None:
        """Reference periodic_compaction_seconds: files past the age get
        marked so the picker rewrites them (the rewrite refreshes
        creation_time; 'bottommost marked' outputs suppress re-marks).
        Leveled style only — the universal/FIFO pickers don't consult
        marked_for_compaction (FIFO ages out via fifo_ttl_seconds)."""
        db = self.db
        per = db.options.periodic_compaction_seconds
        if not per or db.options.compaction_style != "leveled":
            return
        import time as _t

        cutoff = int(_t.time()) - per
        with db._mutex:
            for cf_id in list(db.versions.column_families):
                v = db.versions.cf_current(cf_id)
                for lvl in range(v.num_levels):
                    for f in v.files[lvl]:
                        if f.marked_for_compaction or f.being_compacted:
                            continue
                        ct = self._file_creation_time(f)
                        if ct and ct <= cutoff:
                            f.marked_for_compaction = True

    def _run_one(self) -> bool:
        db = self.db
        self._apply_periodic_marking()
        if self._preclude_remark:
            import time as _t

            now = _t.time()
            with self._lock:  # concurrent bg workers append + sweep
                pending = self._preclude_remark
                still = []
                expired = []
                for retry, f in pending:
                    (expired if retry <= now else still).append((retry, f))
                self._preclude_remark = still
            for _retry, f in expired:
                f.marked_for_compaction = True
        with db._mutex:
            # Visit CFs by descending top compaction score — fixed id order
            # would starve later CFs under sustained load on an earlier one.
            scored = []
            for cf_id in db.versions.column_families:
                version = db.versions.cf_current(cf_id)
                scores = self.picker.compaction_score(version)
                top = scores[0][0] if scores else 0.0
                scored.append((top, cf_id, version))
            scored.sort(key=lambda s: -s[0])
            c = None
            for top, cf_id, version in scored:
                if top < 1.0:
                    break
                c = self.picker.pick_compaction(version)
                if c is not None:
                    c.cf_id = cf_id
                    c.full_history_ts_low = self.db.options.full_history_ts_low
                    break
            if c is None:
                return False
            if self._space_refused(c):
                # Nothing is marked being_compacted yet, so the exact same
                # job stays pickable. Returning False stops the drain loop
                # (a True here would re-pick this compaction in a hot
                # loop); the pressure callback's _maybe_schedule_compaction
                # re-enters once the poller sees headroom again.
                return False
            for _, f in c.all_inputs():
                f.being_compacted = True
        try:
            self._run_compaction(c)
        finally:
            with db._mutex:
                for _, f in c.all_inputs():
                    f.being_compacted = False
        with self._lock:
            self.num_completed += 1
            self._space_blocks = 0
        return True

    def _space_refused(self, c: Compaction) -> bool:
        """Storage-pressure preflight (reference
        SstFileManagerImpl::EnoughRoomForCompaction): refuse to START a
        rewriting compaction while pressure is amber/red — degradation is
        amber-first, compactions pause before anything errors — or when
        the estimated output (~= input bytes) would eat into the reserved
        flush headroom / compaction buffer. FIFO deletion jobs are exempt:
        they only free space. Manual compact_range does not route through
        _run_one and stays operator-controlled."""
        db = self.db
        sfm = db._sfm
        if sfm is None or c.reason.startswith("fifo"):
            return False
        est = sum(f.file_size for _, f in c.all_inputs())
        if sfm.pressure() == "ok" and sfm.check_compaction(est):
            return False
        if db.stats is not None:
            from toplingdb_tpu.utils import statistics as _st

            db.stats.record_tick(_st.NO_SPACE_PREFLIGHT_BLOCKS, 1)
        with self._lock:
            first = self._space_blocks == 0
            self._space_blocks += 1
        if first:
            db.event_logger.log(
                "compaction_space_blocked", reason=c.reason,
                estimated_bytes=est, pressure=sfm.pressure(),
            )
        return True

    def _maybe_preclude_last_level(self, c: Compaction) -> None:
        """preclude_last_level_data_seconds (reference options.h +
        seqno_to_time_mapping consumer): a bottommost-targeting job whose
        inputs hold data YOUNGER than the cutoff keeps full MVCC
        semantics — no seqno zeroing, no tombstone dropping — until a
        later compaction finds it aged. Placement is NOT changed (the
        reference splits outputs to the penultimate level per key; a
        job-granularity retarget would install overlapping files into
        sorted-disjoint levels, so we defer the last-level TREATMENT
        instead — the documented design difference)."""
        import time as _time

        db = self.db
        secs = getattr(db.options, "preclude_last_level_data_seconds", 0)
        if not secs or not c.bottommost:
            # Same-level bottommost rewrites (marked-file rewrites,
            # universal L0 self-compactions) are last-level-treatment jobs
            # too — c.bottommost alone decides eligibility.
            return False
        cutoff_seq = db.seqno_to_time.get_proximal_seqno(
            int(_time.time()) - secs)
        if cutoff_seq is None:
            # The cutoff time predates every recorded sample: nothing can
            # be PROVEN old, so everything is treated as young.
            cutoff_seq = 0
        newest = max((f.largest_seqno for _, f in c.all_inputs()),
                     default=0)
        if newest > cutoff_seq:
            if c.reason == "bottommost marked":
                # A marked-file rewrite exists ONLY to drop garbage; run
                # precluded it would drop nothing and then suppress the
                # re-mark — cancelling the collector's request forever.
                # SKIP instead: unmark now, re-mark after a backoff so
                # the picker doesn't spin on the same young file.
                import time as _t2

                retry = _t2.time() + min(60.0, float(secs))
                with self._lock:
                    for f in c.inputs:
                        f.marked_for_compaction = False
                        self._preclude_remark.append((retry, f))
                return True
            c.bottommost = False
        return False

    def _run_compaction(self, c: Compaction) -> None:
        db = self.db
        if not c.output_level_inputs and not c.inputs:
            return
        if self._maybe_preclude_last_level(c):
            return  # postponed (young marked rewrite); re-marks later
        if c.reason.startswith("fifo"):
            # Deletion-only compaction.
            edit = make_version_edit(c, [])
            with db._mutex:
                db.versions.log_and_apply(edit)
                db._delete_obsolete_files()
            return
        def _bottom_move_ok(f) -> bool:
            # A bottommost rewrite exists to GC tombstones / fold merges;
            # a file with neither loses nothing by moving.
            if f.num_deletions or f.num_range_deletions:
                return False
            props = db.table_cache.get_reader(f.number).properties
            return props.num_merge_operands == 0

        if (len(c.inputs) == 1 and not c.output_level_inputs
                and c.level > 0 and c.output_level > c.level
                and db.options.compaction_filter is None
                and (not c.bottommost or _bottom_move_ok(c.inputs[0]))
                and not (db.options.enable_blob_garbage_collection
                         and c.inputs[0].blob_refs)):
            # Trivial move (reference Compaction::IsTrivialMove /
            # db_impl_compaction_flush.cc): nothing overlaps below — just
            # relocate the file's metadata, no rewrite, no IO.
            meta = c.inputs[0]
            from toplingdb_tpu.db.version_edit import VersionEdit

            edit = VersionEdit(column_family=c.cf_id)
            edit.delete_file(c.level, meta.number)
            edit.add_file(c.output_level, meta)
            with db._mutex:
                db.versions.log_and_apply(edit)
            with self._lock:
                self.num_trivial_moves += 1
            db.event_logger.log(
                "trivial_move", file_number=meta.number,
                from_level=c.level, to_level=c.output_level,
            )
            from toplingdb_tpu.utils.listener import CompactionJobInfo, notify

            notify(db.options.listeners, "on_compaction_completed", db,
                   CompactionJobInfo(
                       db_name=db.dbname, input_level=c.level,
                       output_level=c.output_level,
                       input_files=[meta.number], output_files=[meta.number],
                       input_records=meta.num_entries,
                       output_records=meta.num_entries,
                       elapsed_micros=0, device="move",
                       reason="trivial move",
                   ))
            return
        from toplingdb_tpu.utils.thread_status import thread_operation

        with thread_operation("compaction",
                              f"L{c.level}->L{c.output_level}", db.dbname):
            self._run_compaction_inner(c)

    def _run_compaction_inner(self, c: Compaction) -> None:
        from toplingdb_tpu.utils import telemetry as _tm

        db = self.db
        # Compactions are always traced while a tracer exists — they are
        # the ops RESYSTANCE-style stage visibility pays off on most.
        _root = (db.tracer.start(
            "compaction", level=c.level, output_level=c.output_level,
            reason=c.reason, cf_id=c.cf_id)
            if getattr(db, "tracer", None) is not None else _tm.NOOP_SPAN)
        try:
            self._run_compaction_traced(c, _root)
        finally:
            _root.finish()

    def _run_compaction_traced(self, c: Compaction, _root) -> None:
        from toplingdb_tpu.utils import telemetry as _tm

        db = self.db
        snapshots = db.snapshots.sequences()
        pending: list[int] = []

        def alloc() -> int:
            # Protect in-flight outputs from obsolete-file GC until the
            # version edit lands (reference DBImpl pending_outputs_).
            n = db.versions.new_file_number()
            with db._mutex:
                db._pending_outputs.add(n)
            pending.append(n)
            return n

        try:
            factory = db.options.compaction_executor_factory
            if factory is not None and not factory.should_run_local(c):
                # The resilient path: per-attempt retry with backoff, a
                # per-job deadline, breaker-aware worker picks, and the
                # graceful-degradation local pin — with DCOMPACTION_*
                # stats and listener events for every decision
                # (compaction/resilience.py).
                from toplingdb_tpu.compaction.resilience import (
                    execute_resilient,
                )

                outputs, stats = execute_resilient(
                    db, factory, c, snapshots, alloc,
                    run_local=lambda: self._run_local(c, snapshots, alloc),
                    gate=self._degradation_gate(),
                )
            else:
                outputs, stats = self._run_local(c, snapshots, alloc)
            _root.tag(mode=self._compaction_mode(stats),
                      input_records=stats.input_records,
                      output_records=stats.output_records)
            _emit_phase_spans(stats)
            if db.options.statistics is not None:
                db.options.statistics.record_compaction(stats)
            from toplingdb_tpu.utils.sync_point import sync_point_callback

            sync_point_callback("CompactionJob::BeforeInstall", c)
            if c.reason == "bottommost marked":
                # The rewrite already dropped everything droppable; keeping a
                # collector re-mark would rewrite the same file forever while
                # snapshots pin its remaining tombstones.
                for m in outputs:
                    m.marked_for_compaction = False
            # Whole-file checksums ride into the MANIFEST with the install
            # (covers local, device, and remote-worker outputs uniformly).
            db._stamp_file_checksums(outputs)
            edit = make_version_edit(c, outputs)
            with db._mutex:
                db.versions.log_and_apply(edit)
                db._delete_obsolete_files()
            if db._sfm is not None:
                from toplingdb_tpu.db import filename as _fn

                for m in outputs:
                    db._sfm.on_add_file(
                        _fn.table_file_name(db.dbname, m.number),
                        m.file_size)
            from toplingdb_tpu.utils.listener import CompactionJobInfo, notify

            db.event_logger.log(
                "compaction_finished", input_level=c.level,
                output_level=c.output_level, device=stats.device,
                input_records=stats.input_records,
                output_records=stats.output_records,
                input_bytes=stats.input_bytes, output_bytes=stats.output_bytes,
                micros=stats.work_time_usec, reason=c.reason,
            )
            notify(db.options.listeners, "on_compaction_completed", db,
                   CompactionJobInfo(
                       db_name=db.dbname, input_level=c.level,
                       output_level=c.output_level,
                       input_files=[f.number for _, f in c.all_inputs()],
                       output_files=[m.number for m in outputs],
                       input_records=stats.input_records,
                       output_records=stats.output_records,
                       elapsed_micros=stats.work_time_usec,
                       device=stats.device, reason=c.reason,
                   ))
        except BaseException as e:
            if getattr(e, "_bg_reason", "") == "manifest":
                # As after a flush (db.py::_flush_unit): the MANIFEST may
                # name the outputs, so they stay guarded until the next open.
                pending.clear()
            raise
        finally:
            with db._mutex:
                db._pending_outputs.difference_update(pending)

    @staticmethod
    def _compaction_mode(stats) -> str:
        """serial / columnar / device / pipelined / remote / mesh — the
        trace tag the ISSUE's per-mode waterfalls key on."""
        if getattr(stats, "remote", False):
            return "remote"
        if getattr(stats, "mesh_chips", 0) > 1:
            return "mesh"
        if getattr(stats, "pipelined", False):
            return "pipelined"
        if stats.device not in ("cpu",):
            return "device"
        if getattr(stats, "host_compute_usec", 0) \
                or getattr(stats, "encode_write_usec", 0):
            return "columnar"
        return "serial"

    def _degradation_gate(self):
        if self._pin_gate is None:
            from toplingdb_tpu.compaction.resilience import (
                DcompactOptions, LocalPinGate,
            )

            policy = getattr(self.db.options, "dcompact", None) \
                or DcompactOptions()
            self._pin_gate = LocalPinGate(policy)
        return self._pin_gate

    def _run_local(self, c: Compaction, snapshots, alloc):
        from toplingdb_tpu.db.blob import maybe_new_blob_gc

        db = self.db
        return run_compaction_to_tables(
            db.env, db.dbname, db.icmp, c, db.table_cache,
            db.options.table_options_for_level(c.output_level, c.bottommost),
            snapshots,
            merge_operator=db.options.merge_operator,
            compaction_filter=db.options.compaction_filter,
            new_file_number=alloc,
            blob_resolver=db.blob_source.get,
            blob_gc=maybe_new_blob_gc(db, c, alloc),
            column_family=(c.cf_id, db.cf_name(c.cf_id)),
            max_subcompactions=db.options.max_subcompactions,
        )

    # ------------------------------------------------------------------

    def compact_range(self, begin: bytes | None, end: bytes | None) -> None:
        """Manual compaction: push overlapping files down level by level
        (reference DBImpl::CompactRange). Pauses auto scheduling while
        running so picks cannot race."""
        with self._lock:
            self._manual_active = True
        try:
            self.wait_idle()
            self._compact_range_impl(begin, end)
        finally:
            with self._lock:
                self._manual_active = False
        # The per-level loop's frame pinned the previous Version (weak-ref
        # lifetime) during the last install; sweep again now it's released.
        with self.db._mutex:
            self.db._delete_obsolete_files()
        self.maybe_schedule()

    def _compact_range_impl(self, begin: bytes | None, end: bytes | None) -> None:
        for cf_id in sorted(self.db.versions.column_families):
            self._compact_range_cf(begin, end, cf_id)

    def _compact_range_cf(self, begin: bytes | None, end: bytes | None,
                          cf_id: int) -> None:
        db = self.db
        if cf_id not in db.versions.column_families:
            return  # dropped concurrently
        version = db.versions.cf_current(cf_id)
        if db.options.compaction_style == "universal":
            self._manual_universal(cf_id)
            return
        for level in range(0, version.num_levels - 1):
            with db._mutex:
                version = db.versions.cf_current(cf_id)
                if level == 0:
                    inputs = [f for f in version.files[0]
                              if not f.quarantined]
                else:
                    inputs = [
                        f for f in version.overlapping_files(level, begin, end)
                        if not f.quarantined
                    ]
                if not inputs:
                    continue
                smallest = min((f.smallest for f in inputs), key=db.icmp.sort_key)
                largest = max((f.largest for f in inputs), key=db.icmp.sort_key)
                su = dbformat.extract_user_key(smallest)
                lu = dbformat.extract_user_key(largest)
                outputs = version.overlapping_files(level + 1, su, lu)
                c = Compaction(
                    level=level, output_level=level + 1, inputs=inputs,
                    output_level_inputs=outputs,
                    bottommost=self.picker._is_bottommost(
                        version, level + 1, smallest, largest
                    ),
                    reason="manual",
                    max_output_file_size=db.options.target_file_size(level + 1),
                    cf_id=cf_id,
                    full_history_ts_low=db.options.full_history_ts_low,
                )
                for _, f in c.all_inputs():
                    f.being_compacted = True
            try:
                self._run_compaction(c)
            finally:
                with db._mutex:
                    for _, f in c.all_inputs():
                        f.being_compacted = False

    def _manual_universal(self, cf_id: int = 0) -> None:
        db = self.db
        with db._mutex:
            version = db.versions.cf_current(cf_id)
            runs = [f for f in version.files[0] if not f.quarantined]
            last = version.num_levels - 1
            base = [f for f in version.files[last] if not f.quarantined]
            if not runs and not base:
                return
            c = Compaction(
                level=0, output_level=last, inputs=runs,
                output_level_inputs=base, bottommost=True,
                reason="manual universal", max_output_file_size=2**62,
                cf_id=cf_id,
                full_history_ts_low=db.options.full_history_ts_low,
            )
            for _, f in c.all_inputs():
                f.being_compacted = True
        try:
            self._run_compaction(c)
        finally:
            with db._mutex:
                for _, f in c.all_inputs():
                    f.being_compacted = False

