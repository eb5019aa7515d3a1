"""Compaction worker process: the dcompact worker analogue.

Runs one serialized compaction job from a job dir (params.json → SST outputs
+ results.json). This is the process that owns the TPU in a disaggregated
deployment: the DB process never touches JAX; the worker reads input SSTs
from shared storage, runs the device data plane, and writes outputs back
(reference: the absent topling-dcompact worker binary, whose DB-side
contract is db/compaction/compaction_executor.h in /root/reference).

Usage: python -m toplingdb_tpu.compaction.worker --job-dir DIR
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
import traceback


def run_job(job_dir: str) -> int:
    from toplingdb_tpu.compaction.executor import CompactionParams

    t_enter = time.time()
    pjson = os.path.join(job_dir, "params.json")
    try:
        # Queue wait: params were written when the DB submitted the job
        # (reference CompactionResults::waiting_time_usec).
        waiting_usec = max(0, int((t_enter - os.path.getmtime(pjson)) * 1e6))
    except OSError:
        waiting_usec = 0
    with open(pjson) as f:
        params = CompactionParams.from_json(f.read())
    # Job lease: heartbeat the job dir while we run so the DB side (and a
    # later DB open) can tell a live job from an orphan left by a crashed
    # worker (compaction/resilience.py).
    heartbeat = None
    lease_sec = float(getattr(params, "lease_sec", 0.0) or 0.0)
    if lease_sec > 0:
        from toplingdb_tpu.compaction.resilience import HeartbeatWriter

        heartbeat = HeartbeatWriter(job_dir, lease_sec).start()
    # The worker always records its job: one `dcompact.worker` span and
    # the spans of every stage below it (ARCHITECTURE.md §2.9.1). Under a
    # service the handler's `dcompact.request` is open on this thread and
    # its tracer keeps the trace; a worker process of its own keeps one
    # itself. The spans go back in results.json only when the submitter
    # sampled this compaction (`params.trace.sampled`).
    from toplingdb_tpu.utils import telemetry as _tm

    ctx = getattr(params, "trace", None)
    tags = dict(job_id=params.job_id, attempt=params.attempt,
                device=params.device)
    above = _tm.current_span()
    if above is not None and (not ctx or not ctx.get("trace_id")
                              or ctx["trace_id"] == above.trace_id):
        root = _tm.span("dcompact.worker", **tags)
    else:
        root = _tm.Tracer(proc="dcompact-worker").start_from(
            ctx, "dcompact.worker", **tags)
    try:
        with root:
            if waiting_usec:
                # Measured from params.json's mtime, before this process
                # saw the job: the one back-dated span of a job.
                _tm.span_event("compaction.queue_wait", waiting_usec)
            results = _run_job_inner(job_dir, params, t_enter, waiting_usec)
            root.tag(input_records=results.stats.get("input_records", 0),
                     pipelined=bool(results.stats.get("pipelined")))
            with _tm.span("dcompact.results"):
                if ctx and ctx.get("sampled"):
                    # The spans still open (this one, the job's, the
                    # request's) carry their time so far.
                    results.spans = root._tracer.export_trace(root.trace_id)
                with open(os.path.join(job_dir, "results.json"), "w") as f:
                    f.write(results.to_json())
        return 0
    finally:
        if heartbeat is not None:
            heartbeat.stop()


def _run_job_inner(job_dir: str, params, t_enter: float,
                   waiting_usec: int):
    store_mode = _StoreJobMode.maybe(params)
    try:
        return _run_job_body(job_dir, params, t_enter, waiting_usec,
                             store_mode)
    finally:
        if store_mode is not None:
            store_mode.cleanup()


def _run_job_body(job_dir: str, params, t_enter: float,
                  waiting_usec: int, store_mode):
    """Runs the job; returns its CompactionResults (run_job writes them)."""
    from toplingdb_tpu.compaction.compaction_job import (
        CompactionStats, build_outputs, surviving_tombstone_fragments,
    )
    from toplingdb_tpu.compaction.executor import CompactionResults
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db import dbformat
    from toplingdb_tpu.db.range_del import RangeDelAggregator, RangeTombstone
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableOptions
    from toplingdb_tpu.table.factory import open_table
    from toplingdb_tpu.utils.compaction_filter import create_compaction_filter

    if os.environ.get("TPULSM_TEST_WORKER_CRASH") == "mid_job":
        # Chaos hook (resilience.DcompactFaultInjector "kill" plan): die
        # the way kill -9 does — partial output on disk, heartbeats
        # stopped, no results.json, no cleanup.
        with open(os.path.join(params.output_dir, "partial.sst"),
                  "wb") as f:
            f.write(b"\x00" * 4096)
        os._exit(137)
    from toplingdb_tpu.utils import telemetry as _tm

    # Params, options, opening every input, first/last seeks for the
    # metas: everything before the data plane starts.
    prepare = _tm.span("compaction.prepare", files=len(params.input_files))
    env = default_env()
    if store_mode is not None:
        # Disaggregated mode: inputs resolve from the shared store by
        # content address into a process-local scratch dir, outputs are
        # written there and published back — the job dir (the transport)
        # carries only params/results metadata, zero SST bytes.
        env = store_mode.attach(env)
    if params.comparator == dbformat.BYTEWISE.name():
        ucmp = dbformat.BYTEWISE
    elif params.comparator == dbformat.REVERSE_BYTEWISE.name():
        ucmp = dbformat.REVERSE_BYTEWISE
    elif params.comparator == dbformat.U64_TS_BYTEWISE.name():
        # Raw ordering is plain bytewise (inverted-ts suffix encoding), so
        # the worker's merge/GC path is unchanged; the UDT history-trim
        # optimization is local-only (keeping all versions is always safe).
        ucmp = dbformat.U64_TS_BYTEWISE
    else:
        raise ValueError(f"unknown comparator {params.comparator!r}")
    icmp = dbformat.InternalKeyComparator(ucmp)
    merge_op = (
        _merge_operator_by_name(params.merge_operator)
        if params.merge_operator else None
    )
    cfilter = (
        create_compaction_filter(params.compaction_filter)
        if params.compaction_filter else None
    )
    from toplingdb_tpu.table.filter import filter_policy_from_name
    from toplingdb_tpu.utils.slice_transform import slice_transform_from_name
    from toplingdb_tpu.utils.table_properties_collector import (
        create_collector_factory)
    topts = TableOptions(
        block_size=params.block_size, compression=params.compression,
        format=params.table_format, hash_index=params.hash_index,
        **({} if params.filter_policy is None else {
            "filter_policy": filter_policy_from_name(params.filter_policy)}),
        prefix_extractor=(
            slice_transform_from_name(params.prefix_extractor)
            if getattr(params, "prefix_extractor", None) else None
        ),
        properties_collector_factories=[
            create_collector_factory(d)
            for d in getattr(params, "collectors", [])
        ],
    )

    from toplingdb_tpu.db.blob import BlobSource
    from toplingdb_tpu.db.version_edit import FileMetaData

    blob_source = BlobSource(env, params.dbname)
    counter = [0]

    def alloc():
        counter[0] += 1
        return counter[0]

    device_job = params.device in ("tpu", "cpu-jax")
    if device_job and ucmp.name() == dbformat.BYTEWISE.name():
        # Full data plane — the same columnar/pipelined path the in-process
        # device executor takes (ops/device_compaction.py), so the worker
        # overlaps scan/compute/encode and reports the per-phase shape
        # (input_scan/host_compute/device_wait/encode_write/stall) in
        # results.json instead of one opaque work_time.
        from toplingdb_tpu.ops.device_compaction import run_device_compaction

        readers = {}
        metas = []
        for i, path in enumerate(params.input_files, 1):
            r = open_table(env.new_random_access_file(path), icmp, topts)
            readers[i] = r
            # Real key bounds + entry counts: the columnar/pipelined plane
            # shards by them (metas built bare broke every device job into
            # the error-fallback path before this).
            it = r.new_iterator()
            it.seek_to_first()
            smallest = it.key() if it.valid() else b""
            it.seek_to_last()
            largest = it.key() if it.valid() else smallest
            metas.append(FileMetaData(
                number=i, file_size=env.get_file_size(path),
                smallest=smallest, largest=largest,
                num_entries=r.properties.num_entries,
                num_deletions=r.properties.num_deletions,
            ))
        fake_compaction = Compaction(
            level=0, output_level=params.output_level, inputs=metas,
            bottommost=params.bottommost,
            max_output_file_size=params.max_output_file_size,
        )
        prepare.finish()
        outputs, stats = run_device_compaction(
            env, params.output_dir, icmp, fake_compaction,
            _PathTableCache(readers), topts, params.snapshots,
            merge_operator=merge_op, compaction_filter=cfilter,
            new_file_number=alloc, creation_time=params.creation_time,
            device_name=params.device, blob_resolver=blob_source.get,
            column_family=(getattr(params, "cf_id", 0),
                           getattr(params, "cf_name", "default")),
        )
        stats.input_files = len(params.input_files)
        stats.input_bytes = sum(
            env.get_file_size(p) for p in params.input_files)
        stats.prepare_time_usec = max(
            0, int((time.time() - t_enter) * 1e6) - stats.work_time_usec)
        stats.waiting_time_usec = waiting_usec
        with _tm.span("compaction.finish"):  # output metas, for the reply
            return CompactionResults(
                status="ok",
                output_files=_encode_outputs(outputs, env, params,
                                             store_mode),
                stats=dataclasses.asdict(stats),
                work_time_usec=stats.work_time_usec,
            )

    # Per-entry path (CPU jobs and exotic comparators): read inputs raw —
    # unsorted for the device stream, host-sorted for the CPU reference.
    prepare.finish()
    entries = []
    rd = RangeDelAggregator(ucmp)
    readers_l = []
    with _tm.span("compaction.input_scan", files=len(params.input_files)):
        for path in params.input_files:
            r = open_table(env.new_random_access_file(path), icmp, topts)
            readers_l.append(r)
            it = r.new_iterator()
            it.seek_to_first()
            for k, v in it.entries():
                entries.append((k, v))
            for b, e in r.range_del_entries():
                rd.add(RangeTombstone.from_table_entry(b, e))

    stats = CompactionStats(device=params.device)
    stats.input_records = len(entries)
    stats.input_files = len(params.input_files)
    stats.input_bytes = sum(env.get_file_size(p) for p in params.input_files)
    # Setup + input scan before the merge/GC work starts (the reference's
    # prepare_time_usec, compaction_executor.h:146-150).
    stats.prepare_time_usec = int((time.time() - t_enter) * 1e6)
    stats.waiting_time_usec = waiting_usec

    fake_compaction = Compaction(
        level=0, output_level=params.output_level, inputs=[],
        bottommost=params.bottommost,
        max_output_file_size=params.max_output_file_size,
    )

    if device_job:
        from toplingdb_tpu.ops.device_compaction import device_gc_entries

        stream = device_gc_entries(
            entries, icmp, params.snapshots, params.bottommost,
            merge_operator=merge_op, compaction_filter=cfilter,
            compaction_filter_level=params.output_level,
            rd=None if rd.empty() else rd,
            blob_resolver=blob_source.get,
        )
    else:
        # CPU reference path over a host-sorted stream.
        from toplingdb_tpu.compaction.compaction_iterator import CompactionIterator

        entries.sort(key=lambda kv: icmp.sort_key(kv[0]))
        stream = CompactionIterator(
            _ListIter(entries), icmp, params.snapshots,
            bottommost_level=params.bottommost, merge_operator=merge_op,
            compaction_filter=cfilter,
            compaction_filter_level=params.output_level,
            range_del_agg=None if rd.empty() else rd,
            blob_resolver=blob_source.get,
        ).entries()

    tombs = surviving_tombstone_fragments(
        rd, params.snapshots, params.bottommost, ucmp
    )
    with _tm.span("compaction.encode_write"):
        outputs = build_outputs(
            env, params.output_dir, icmp, fake_compaction, stream, tombs,
            alloc, topts, stats, params.creation_time,
            column_family=(getattr(params, "cf_id", 0),
                           getattr(params, "cf_name", "default")),
        )
    return CompactionResults(
        status="ok",
        output_files=_encode_outputs(outputs, env, params, store_mode),
        stats=dataclasses.asdict(stats),
        # Disjoint from prepare: waiting + prepare + work partition the
        # worker's wall clock (reference CompactionResults fields).
        work_time_usec=max(
            0, int((time.time() - t_enter) * 1e6) - stats.prepare_time_usec),
    )


class _StoreJobMode:
    """Disaggregated-storage job context (storage/): resolve inputs from
    the shared store by content address, publish outputs back, pin them
    until the DB side adopts. All SST bytes live in a process-local
    scratch dir torn down when the job ends — never in the job dir."""

    @staticmethod
    def maybe(params):
        return (_StoreJobMode(params) if getattr(params, "store_spec", None)
                else None)

    def __init__(self, params):
        import tempfile

        self.params = params
        self.holder = f"dcompact-job-{params.job_id}"
        self.scratch = tempfile.mkdtemp(
            prefix=f"dcompact-store-{params.job_id}-")
        self.env = None
        self.store = None

    def attach(self, base_env):
        from toplingdb_tpu.storage import SharedSstEnv, open_store

        self.store = open_store(self.params.store_spec)
        self.env = SharedSstEnv(base_env, self.store)
        out_dir = os.path.join(self.scratch, "out")
        os.makedirs(out_dir, exist_ok=True)
        local_inputs = []
        for path, addr in zip(self.params.input_files,
                              self.params.input_addrs):
            lp = os.path.join(self.scratch, os.path.basename(path))
            self.env.adopt(lp, addr)  # materializes on first open
            local_inputs.append(lp)
        self.params.input_files = local_inputs
        self.params.output_dir = out_dir
        return self.env

    def publish_output(self, env, path: str, meta) -> dict:
        """Checksum-stamp + publish one output; returns the extra keys
        the DB side needs to adopt it (address + pre-computed digest)."""
        from toplingdb_tpu.storage.object_store import address_of_meta
        from toplingdb_tpu.utils.file_checksum import (
            FileChecksumGenFactory, stamp_file_checksum,
        )

        factory = FileChecksumGenFactory(
            getattr(self.params, "checksum_func", None) or "crc32c")
        stamp_file_checksum(env, path, meta, factory)
        addr = address_of_meta(meta)
        self.store.publish_file(path, addr, src_env=env.base)
        # Pin until the DB side's adopt makes a refs-table entry (the GC
        # mark phase sees that); the TTL bounds a crashed primary.
        self.store.pin(addr, self.holder)
        return {"store_addr": addr,
                "file_checksum": meta.file_checksum.hex(),
                "file_checksum_func_name": meta.file_checksum_func_name}

    def cleanup(self):
        import shutil

        shutil.rmtree(self.scratch, ignore_errors=True)
        if self.env is not None:
            self.env.close()


def _encode_outputs(outputs, env, params, store_mode) -> list[dict]:
    from toplingdb_tpu.compaction.executor import encode_file_meta

    docs = []
    for m in outputs:
        name = f"{m.number:06d}.sst"
        d = encode_file_meta(m, name)
        if store_mode is not None:
            d.update(store_mode.publish_output(
                env, os.path.join(params.output_dir, name), m))
        docs.append(d)
    return docs


def _merge_operator_by_name(name: str):
    from toplingdb_tpu.utils.merge_operator import create_merge_operator

    return create_merge_operator(name)


class _PathTableCache:
    """TableCache-shaped view over the job's already-open input readers
    (the worker addresses inputs by path, not by live version state)."""

    def __init__(self, readers: dict):
        self._readers = readers

    def get_reader(self, number: int):
        return self._readers[number]


class _ListIter:
    def __init__(self, items):
        self._items = items
        self._i = 0

    def valid(self):
        return self._i < len(self._items)

    def key(self):
        return self._items[self._i][0]

    def value(self):
        return self._items[self._i][1]

    def next(self):
        self._i += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--job-dir", required=True)
    args = ap.parse_args(argv)
    try:
        return run_job(args.job_dir)
    except Exception as e:
        traceback.print_exc()
        try:
            from toplingdb_tpu.compaction.executor import CompactionResults

            with open(os.path.join(args.job_dir, "results.json"), "w") as f:
                f.write(CompactionResults(
                    status=f"{type(e).__name__}: {e}", output_files=[],
                    stats={},
                ).to_json())
        except OSError:
            pass
        return 1


if __name__ == "__main__":
    sys.exit(main())
