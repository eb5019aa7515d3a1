"""ZipTables on the device data plane (ISSUE 32): a job that reads block
files and ZipTables side by side — pipelined (two shards or more) and
serial (one shard), to zip and to block outputs — is byte-identical to the
CPU path's per-entry build; a remote job builds the output level's format;
the per-entry route iterates a zip input; the benchmark's plain reader
(benchmark/lib/zip_plain.py) reads every ZipTable these jobs write to the
rows ZipTableReader reads; the cold format's counters add up."""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))

from lib import dbside, zip_plain  # noqa: E402
from lib.workload import Workload  # noqa: E402

import toplingdb_tpu.db.filename as fn  # noqa: E402
from test_compaction_pipeline import (  # noqa: E402
    ICMP, _build_runs, _mk_alloc, _sst_bytes,
)
from toplingdb_tpu.ops import compaction_kernels as ck  # noqa: E402
from toplingdb_tpu.table import format as fmt  # noqa: E402
from toplingdb_tpu.table.builder import TableOptions  # noqa: E402

ZIP_COUNTERS = ("zip_input_files", "zip_input_rows", "zip_scan_usec",
                "zip_output_files", "zip_output_bytes",
                "zip_output_raw_bytes", "zip_encode_usec",
                "zip_dict_train_usec")
BLOCK = TableOptions(block_size=4096, compression=fmt.SNAPPY_COMPRESSION,
                     filter_policy=None)
ZIP = dataclasses.replace(BLOCK, format="zip")


def _job(env, dbdir, metas, out_topts, alloc_base, snapshots, bottommost,
         device, compaction_filter=None):
    from toplingdb_tpu.compaction.compaction_job import (
        run_compaction_to_tables,
    )
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.ops.device_compaction import run_device_compaction

    tc = TableCache(env, dbdir, ICMP, BLOCK)
    # Level 0: the inputs overlap, so every file is a run of its own.
    c = Compaction(level=0, output_level=2, inputs=list(metas),
                   bottommost=bottommost, max_output_file_size=256 << 10)
    run = run_device_compaction if device else run_compaction_to_tables
    return run(env, dbdir, ICMP, c, tc, out_topts, snapshots,
               new_file_number=_mk_alloc(alloc_base), creation_time=7,
               compaction_filter=compaction_filter,
               **({"device_name": "cpu-jax"} if device else {}))


@pytest.fixture(scope="module")
def mixed_inputs(tmp_path_factory):
    """Two block runs, and ZipTables made of two more runs and a file
    with a range tombstone by two non-bottommost compactions: they hold
    deletions and the tombstone (a job with a range tombstone writes one
    file; the other is cut into several)."""
    from toplingdb_tpu.env import default_env

    env = default_env()
    dbdir = str(tmp_path_factory.mktemp("zipplane"))
    metas = _build_runs(env, dbdir, 40000, BLOCK, runs=4,
                        tombstone_file=True)
    cold, st = _job(env, dbdir, metas[:1] + metas[-1:], ZIP, 100, [],
                    bottommost=False, device=False)
    more, st2 = _job(env, dbdir, metas[1:2], ZIP, 150, [],
                     bottommost=False, device=False)
    assert len(cold) == 1 and len(more) >= 2
    assert st.zip_output_files + st2.zip_output_files == len(cold + more)
    cold = cold + more
    assert sum(m.num_deletions for m in cold) > 0
    assert sum(m.num_range_deletions for m in cold) == 1
    return env, dbdir, [metas[2], metas[3]] + cold, cold


def _rows_by_reader(env, path):
    from toplingdb_tpu.table.factory import open_table

    r = open_table(env.new_random_access_file(path), ICMP, BLOCK)
    it = r.new_iterator()
    it.seek_to_first()
    return list(it.entries()), r.range_del_entries()


def _assert_plain_reader_agrees(env, path):
    t = zip_plain.read_table(path)
    rows, tombs = _rows_by_reader(env, path)
    offs = np.concatenate([[0], np.cumsum(t["val_lens"])])
    got = [(t["keys"][i].tobytes(),
            t["val_buf"][offs[i]:offs[i + 1]].tobytes())
           for i in range(len(t["keys"]))]
    assert got == rows
    assert [(b, seq, e) for b, seq, e in t["tombstones"]] == [
        (b[:-8], int.from_bytes(b[-8:], "little") >> 8, e) for b, e in tombs]


@pytest.mark.parametrize("bottommost", [True, False])
@pytest.mark.parametrize("out", ["zip", "block"])
@pytest.mark.parametrize("shards", [4, 1])
def test_block_and_zip_inputs_equal_the_cpu_path(mixed_inputs, monkeypatch,
                                                 shards, out, bottommost):
    env, dbdir, metas, cold = mixed_inputs
    monkeypatch.setattr(ck, "shard_count", lambda n: shards)
    out_topts = ZIP if out == "zip" else BLOCK
    base = 1000 + 100 * (shards + 10 * (out == "zip") + 20 * bottommost)
    # Bottommost with no snapshot drops the tombstone, so the output is
    # cut into files; the other half keeps it under a snapshot.
    snaps = [] if bottommost else [5]
    dev, sd = _job(env, dbdir, metas, out_topts, base, snaps, bottommost,
                   device=True)
    cpu, sc = _job(env, dbdir, metas, out_topts, base + 50, snaps,
                   bottommost, device=False)
    assert sd.pipelined == (shards > 1), sd.pipeline_exit
    assert _sst_bytes(env, dbdir, dev) == _sst_bytes(env, dbdir, cpu)
    assert len(dev) >= (2 if bottommost and out == "zip" else 1)
    # The counters of the cold format, on both ends of the job.
    assert sd.zip_input_files == len(cold)
    assert sd.zip_input_rows == sum(m.num_entries for m in cold)
    assert 0 < sd.zip_input_rows <= sd.input_records
    assert sd.zip_scan_usec > 0
    if out == "zip":
        assert sd.zip_output_files == sd.output_files == len(dev)
        assert 0 < sd.zip_output_bytes <= sd.output_bytes
        assert sd.zip_output_bytes < sd.zip_output_raw_bytes
        assert 0 < sd.zip_dict_train_usec <= sd.zip_encode_usec
        assert (sc.zip_output_files, sc.zip_output_bytes,
                sc.zip_output_raw_bytes) == (
            sd.zip_output_files, sd.zip_output_bytes,
            sd.zip_output_raw_bytes)
        for m in dev:
            _assert_plain_reader_agrees(
                env, fn.table_file_name(dbdir, m.number))
    else:
        assert (sd.zip_output_files, sd.zip_output_bytes,
                sd.zip_output_raw_bytes, sd.zip_encode_usec,
                sd.zip_dict_train_usec) == (0, 0, 0, 0, 0)


def test_plain_reader_reads_the_cold_inputs(mixed_inputs):
    env, dbdir, _metas, cold = mixed_inputs
    for m in cold:
        _assert_plain_reader_agrees(env, fn.table_file_name(dbdir, m.number))


@pytest.mark.parametrize("shards", [4, 1])
def test_a_block_only_job_counts_nothing_of_the_cold_format(
        mixed_inputs, monkeypatch, shards):
    env, dbdir, metas, _cold = mixed_inputs
    monkeypatch.setattr(ck, "shard_count", lambda n: shards)
    _outs, st = _job(env, dbdir, metas[:2], BLOCK, 5000 + shards, [], True,
                     device=True)
    assert st.input_records > 0
    assert [getattr(st, k) for k in ZIP_COUNTERS] == [0] * 8


def test_the_per_entry_route_reads_a_zip_input(mixed_inputs):
    """A compaction filter forces the per-entry route
    (`collect_raw_entries`): over a zip input it returns the CPU path's
    rows (the parent died on `ZipTableIterator.prefetch_counts`)."""
    from toplingdb_tpu.utils.compaction_filter import RemoveEmptyValueCompactionFilter

    env, dbdir, metas, _cold = mixed_inputs
    filt = RemoveEmptyValueCompactionFilter()
    dev, sd = _job(env, dbdir, metas, ZIP, 6000, [], True, device=True,
                   compaction_filter=filt)
    cpu, _ = _job(env, dbdir, metas, ZIP, 6100, [], True, device=False,
                  compaction_filter=filt)
    assert not sd.pipelined and sd.input_records > 0
    assert _sst_bytes(env, dbdir, dev) == _sst_bytes(env, dbdir, cpu)


def test_zip_candidates_weigh_as_a_block_files_separators(mixed_inputs):
    """A ZipTable's splitter candidates: one group head for about a
    block's worth of raw bytes, ascending, each a user key of the file."""
    from toplingdb_tpu.table.factory import open_table

    env, dbdir, _metas, cold = mixed_inputs
    for m in cold:
        r = open_table(env.new_random_access_file(
            fn.table_file_name(dbdir, m.number)), ICMP, BLOCK)
        p = r.properties
        cands = r.split_candidates(4096)
        assert cands == sorted(cands) and len(cands) > 4
        per = (p.raw_key_size + p.raw_value_size) / len(cands)
        assert 2048 <= per <= 8192
        # The entry range of a splitter starts at its user key's first row.
        for uk in cands[:: max(1, len(cands) // 8)]:
            e = r.entry_lower_bound(uk + b"\xff" * 8)
            assert r.key_at(e)[:-8] == uk
            assert e == 0 or r.key_at(e - 1)[:-8] < uk


class _Bottommost(dbside.TimedFactory):
    """Which compactions the DB called bottommost, by their inputs."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.bottommost = {}

    def should_run_local(self, compaction):
        self.bottommost[tuple(sorted(
            f.number for _, f in compaction.all_inputs()))] = \
            compaction.bottommost
        return super().should_run_local(compaction)


@pytest.mark.parametrize("min_remote", [0, 1 << 40])
def test_db_with_zip_cold_level_behind_a_service(tmp_path, min_remote):
    """`bottommost_format="zip"` behind an in-process dcompact service
    (and, with a threshold no job reaches, with every compaction in the DB
    process): every bottommost job's outputs are ZipTables, later jobs
    read them beside block files, and every read equals the oracle, after
    reopen too. On the parent the remote job wrote block tables."""
    from toplingdb_tpu.compaction.dcompact_service import (
        DcompactWorkerService,
    )
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils.listener import EventListener

    n, draws = 30000, 90000
    wl = Workload(n, draws, seed=32)
    kb, vb = wl.encode(0, n + draws)
    svc = DcompactWorkerService(device="cpu-jax")
    port = svc.start()
    stats = dbside.JobStatistics()
    factory = _Bottommost(f"http://127.0.0.1:{port}", "cpu-jax", min_remote)
    seen = []

    class Witness(EventListener):
        def on_compaction_completed(self, db, info):
            if info.device != "move":
                seen.append((
                    factory.bottommost[tuple(sorted(info.input_files))],
                    [zip_plain.is_zip_table(
                        fn.table_file_name(db.dbname, x))
                     for x in info.output_files]))

    opts = Options(
        create_if_missing=True, write_buffer_size=256 << 10,
        target_file_size_base=256 << 10,
        max_bytes_for_level_base=512 << 10,
        level0_file_num_compaction_trigger=4,
        table_options=TableOptions(block_size=1024),
        bottommost_format="zip", statistics=stats,
        compaction_executor_factory=factory, listeners=[Witness()],
        dcompact=dbside.ONE_ATTEMPT)
    dbdir = str(tmp_path / "db")
    db = DB.open(dbdir, opts)
    try:
        for w in range(0, n + draws, 1000):
            dbside.put_batches(db, kb[8 * w:8 * (w + 1000)],
                               vb[20 * w:20 * (w + 1000)], 1000, 500)
            db.wait_for_compactions()  # the tree is the put count's
        bottom = [zips for b, zips in seen if b]
        assert len(bottom) >= 2 and all(all(z) and z for z in bottom)
        assert any(not b for b, _ in seen)
        jobs = stats.jobs
        assert all(s.remote == (min_remote == 0) for s in jobs)
        assert sum(s.zip_input_rows for s in jobs) > 0
        assert all(s.zip_input_rows <= s.input_records
                   and s.zip_output_bytes <= s.output_bytes for s in jobs)
        if min_remote == 0:
            assert svc.job_sums["zip_input_rows"] == sum(
                s.zip_input_rows for s in jobs)
            assert svc.job_sums["zip_output_files"] == sum(
                s.zip_output_files for s in jobs) > 0
            assert svc.jobs_failed == 0
        last = wl.last_write(n + draws)
        keys = np.random.default_rng(5).integers(0, n + 300, 2000).astype(
            np.uint64)
        want = wl.expected(keys, last)
        kk = wl.key_bytes(keys).tobytes()
        klist = [kk[8 * i:8 * i + 8] for i in range(len(keys))]
        for reopened in (False, True):
            assert db.multi_get(klist) == want
            assert [db.get(k) for k in klist[:300]] == want[:300]
            span = np.arange(1000, 1600, dtype=np.uint64)
            it = db.new_iterator()
            it.seek(wl.key_bytes(span[:1]).tobytes())
            got = []
            while it.valid() and len(got) < len(span):
                got.append(it.value())
                it.next()
            assert got == wl.expected(span, last)
            if not reopened:
                db.close()
                db = DB.open(dbdir, opts)
    finally:
        db.close()
        svc.stop()


def test_a_remote_jobs_format_is_the_output_levels(tmp_path):
    """`CompactionParams.table_format` is
    `table_options_for_level(output_level, bottommost).format`."""
    import json

    from toplingdb_tpu.compaction.executor import SubprocessCompactionExecutor
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    sent = []

    def spawn(job_dir, device):
        with open(os.path.join(job_dir, "params.json")) as f:
            sent.append(json.load(f))
        raise OSError("not run: the parameters are what is asked")

    db = DB.open(str(tmp_path / "db"), Options(
        create_if_missing=True, bottommost_format="zip"))
    try:
        for bottommost in (True, False):
            ex = SubprocessCompactionExecutor("cpu", None, spawn=spawn)
            c = Compaction(level=1, output_level=2, inputs=[],
                           bottommost=bottommost,
                           max_output_file_size=1 << 20)
            with pytest.raises(Exception):
                ex.execute(db, c, [], lambda: 99)
        assert [p["table_format"] for p in sent] == ["zip", "block"]
    finally:
        db.close()


def test_shards_of_a_mixed_job_are_even_in_rows(mixed_inputs, monkeypatch):
    """The plan's splitters put about the same number of ROWS in every
    shard though a ZipTable's candidates and a block file's stand for
    different numbers of rows (on the chip a job of 3,985,213 rows in 8
    shards put over 2^19 rows in one and met a second program: PERF.md)."""
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.ops import pipeline as pl

    env, dbdir, metas, _cold = mixed_inputs
    monkeypatch.setattr(ck, "shard_count", lambda n: 4)
    tc = TableCache(env, dbdir, ICMP, BLOCK)
    readers = [tc.get_reader(m.number) for m in metas]
    _kv, _files, splitters, _slack = pl._build_plan(readers)
    assert len(splitters) == 3
    uks = []
    for r in readers:
        it = r.new_iterator()
        it.seek_to_first()
        uks += [k[:-8] for k, _ in it.entries()]
    uks = np.array(sorted(uks))
    cuts = np.searchsorted(uks, np.array(splitters))
    rows = np.diff(np.concatenate([[0], cuts, [len(uks)]]))
    assert rows.sum() == len(uks)
    assert rows.max() <= 1.02 * len(uks) / 4, rows


@pytest.mark.parametrize("shards", [4, 1])
def test_a_traced_cold_job_names_its_spans_and_counters(
        mixed_inputs, tmp_path, monkeypatch, shards):
    """A worker's job of block + zip inputs to zip outputs, run as the
    service's handler runs it: the trace holds `pipeline.zip_scan`,
    `zip.index_build`, `zip.dict_train` and `zip.encode`, each inside its
    parent, and the reply's stats the eight counters."""
    import json

    from toplingdb_tpu.compaction import worker
    from toplingdb_tpu.compaction.executor import CompactionParams
    from toplingdb_tpu.utils import telemetry as tm

    env, dbdir, metas, _cold = mixed_inputs
    monkeypatch.setattr(ck, "shard_count", lambda n: shards)
    job_dir = str(tmp_path / "job")
    os.makedirs(os.path.join(job_dir, "out"))
    params = CompactionParams(**{
        **dbside.job_params(
            1, dbdir, [fn.table_file_name(dbdir, m.number) for m in metas],
            2, True, 256 << 10),
        "device": "cpu-jax", "table_format": "zip",
        "output_dir": os.path.join(job_dir, "out")})
    with open(os.path.join(job_dir, "params.json"), "w") as f:
        f.write(params.to_json())
    tracer = tm.Tracer(proc="dcompact-worker")
    with tracer.start_from(None, "dcompact.request"):
        assert worker.run_job(job_dir) == 0
    (trace,) = tracer.finished()
    names = {s.name for s in trace.spans}
    assert {"pipeline.zip_scan", "zip.group_decode", "zip.index_build",
            "zip.dict_train", "zip.encode"} <= names, names
    by_id = {s.span_id: s for s in trace.spans}
    for s in trace.spans:
        if s.name.startswith("zip.") or s.name == "pipeline.zip_scan":
            parent = by_id[s.parent_id]
            if s.name != "pipeline.zip_scan":  # the stage's span holds them
                assert parent.name == {
                    "zip.group_decode": "pipeline.zip_scan"}.get(
                        s.name, "pipeline.encode_write")
            assert parent.start_us <= s.start_us + 2
            assert s.start_us + s.dur_us <= parent.start_us + parent.dur_us + 2
    with open(os.path.join(job_dir, "results.json")) as f:
        stats = json.load(f)["stats"]
    assert stats["pipelined"] == (shards > 1)
    assert all(stats[k] > 0 for k in ZIP_COUNTERS), stats
