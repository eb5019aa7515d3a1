"""Pipelined compaction data plane (ops/pipeline.py): byte parity with the
serial path across codecs and compute modes, clean cancellation, prefetch
ticker export, and a seeded pipeline soak."""

import os
import random
import shutil
import tempfile

import numpy as np
import pytest

from toplingdb_tpu.db.dbformat import (
    InternalKeyComparator,
    ValueType,
    make_internal_key,
)
from toplingdb_tpu.ops import pipeline as pl

ICMP = InternalKeyComparator()
_PIPELINE_ENABLED = pl.pipeline_enabled


def _build_runs(env, dbdir, n_total, topts, seed=1, runs=4, first_fnum=21,
                with_dels=True, tombstone_file=False):
    """Vectorized multi-run input builder: ~2x overwrite factor, optional
    deletions; optionally one per-entry file carrying a range tombstone."""
    import toplingdb_tpu.db.filename as fn
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.ops.columnar_io import ColumnarKV, write_tables_columnar
    from toplingdb_tpu.table.builder import TableBuilder

    rng = np.random.default_rng(seed)
    per_run = n_total // runs
    metas = []
    counter = [first_fnum - 1]

    def alloc():
        counter[0] += 1
        return counter[0]

    for run in range(runs):
        n = per_run
        draws = rng.integers(0, max(1, n_total // 2), n, dtype=np.int64)
        seqs = np.arange(run * per_run + 1, run * per_run + n + 1,
                         dtype=np.uint64)
        vts = np.full(n, int(ValueType.VALUE), dtype=np.uint64)
        if with_dels:
            vts[np.asarray(rng.random(n) < 0.15)] = int(ValueType.DELETION)
        ik = np.empty((n, 16), dtype=np.uint8)
        for j in range(8):
            ik[:, 7 - j] = (draws // 10 ** j) % 10 + ord("0")
        packed = (seqs << np.uint64(8)) | vts
        ik[:, 8:] = packed[:, None] >> (np.arange(8) * 8).astype(
            np.uint64)[None, :] & np.uint64(0xFF)
        vlens = np.where(vts == int(ValueType.VALUE), 20, 0).astype(np.int32)
        vals = np.full(int(vlens.sum()), ord("v"), dtype=np.uint8)
        s = np.lexsort((np.iinfo(np.int64).max - seqs.view(np.int64), draws))
        voffs = (np.cumsum(vlens[s]) - vlens[s]).astype(np.int32)
        kv = ColumnarKV(
            np.ascontiguousarray(ik[s]).reshape(-1),
            np.arange(n, dtype=np.int32) * 16,
            np.full(n, 16, dtype=np.int32),
            vals, voffs, vlens[s],
        )
        files = write_tables_columnar(
            env, dbdir, alloc, ICMP, topts, kv,
            np.arange(n, dtype=np.int32), np.full(n, -1, dtype=np.int64),
            vts.astype(np.int32)[s], seqs[s], [], creation_time=1,
        )
        for fnum, path, props, smallest, largest, _sel in files:
            metas.append(FileMetaData(
                number=fnum, file_size=env.get_file_size(path),
                smallest=smallest, largest=largest,
                smallest_seqno=props.smallest_seqno,
                largest_seqno=props.largest_seqno,
            ))
    if tombstone_file:
        fnum = alloc()
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, ICMP, topts)
        base = n_total * 2
        for i in range(50):
            b.add(make_internal_key(b"%08d" % (i * 37), base + i,
                                    ValueType.VALUE), b"t%05d" % i)
        lo = b"%08d" % (n_total // 8)
        hi = b"%08d" % (n_total // 4)
        b.add_tombstone(make_internal_key(lo, base + 99,
                                          ValueType.RANGE_DELETION), hi)
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum,
            file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno,
        ))
    return metas


def _mk_alloc(base):
    s = [base]

    def alloc():
        s[0] += 1
        return s[0]

    return alloc


def _run_job(env, dbdir, metas, topts, out_topts, alloc_base, snapshots,
             device=True):
    from toplingdb_tpu.compaction.compaction_job import run_compaction_to_tables
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.ops.device_compaction import run_device_compaction

    tc = TableCache(env, dbdir, ICMP, topts)
    c = Compaction(level=0, output_level=2, inputs=list(metas),
                   bottommost=True, max_output_file_size=1 << 62)
    if device:
        return run_device_compaction(
            env, dbdir, ICMP, c, tc, out_topts, snapshots,
            new_file_number=_mk_alloc(alloc_base), creation_time=7,
            device_name="cpu-jax",
        )
    return run_compaction_to_tables(
        env, dbdir, ICMP, c, tc, out_topts, snapshots,
        new_file_number=_mk_alloc(alloc_base), creation_time=7,
    )


def _sst_bytes(env, dbdir, outs):
    import toplingdb_tpu.db.filename as fn

    return [open(fn.table_file_name(dbdir, m.number), "rb").read()
            for m in outs]


def _enable_small_pipeline(monkeypatch, shards=4):
    """A test-sized job is cut into `shards` (the rule would leave it one
    shard, which the pipeline leaves to the serial path)."""
    from toplingdb_tpu.ops import compaction_kernels as ck

    monkeypatch.setattr(ck, "shard_count", lambda total_rows: shards)


def _pipeline(monkeypatch, on: bool):
    """Send jobs down the serial path (on=False), or give the choice back
    to the program."""
    monkeypatch.setattr(pl, "pipeline_enabled",
                        _PIPELINE_ENABLED if on else (lambda *_a: False))


def _spy_pipeline(monkeypatch):
    """Count successful run_pipelined invocations (parity tests must not
    silently degrade to the serial path)."""
    from toplingdb_tpu.ops import pipeline as pl

    calls = []
    orig = pl.run_pipelined

    def spy(*a, **k):
        r = orig(*a, **k)
        calls.append(1)
        return r

    monkeypatch.setattr(pl, "run_pipelined", spy)
    return calls


@pytest.mark.parametrize("codec", ["none", "snappy", "zstd"])
@pytest.mark.parametrize("mode", ["host", "device"])
def test_pipeline_byte_parity(tmp_path, monkeypatch, codec, mode):
    """Pipelined outputs are byte-identical to the serial path across
    codecs, compute modes, snapshots and a surviving range tombstone."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableOptions
    from toplingdb_tpu.utils import codecs

    if mode == "device" and codec == "zstd":
        pytest.skip("device mode covered by none/snappy; zstd adds compile")
    comp = {"none": fmt.NO_COMPRESSION, "snappy": fmt.SNAPPY_COMPRESSION,
            "zstd": fmt.ZSTD_COMPRESSION}[codec]
    if codec != "none" and not codecs.available(codec):
        pytest.skip(f"{codec} unavailable")
    if mode == "host":
        monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    else:
        monkeypatch.delenv("TPULSM_HOST_SORT", raising=False)
    _enable_small_pipeline(monkeypatch)
    calls = _spy_pipeline(monkeypatch)

    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512, compression=comp)
    n = 24_000
    metas = _build_runs(env, dbdir, n, topts, seed=3, tombstone_file=True)
    snapshots = [n // 3, 2 * n // 3]

    _pipeline(monkeypatch, False)
    out_serial, _ = _run_job(env, dbdir, metas, topts, topts, 1000, snapshots)
    assert not calls
    _pipeline(monkeypatch, True)
    out_pipe, stats = _run_job(env, dbdir, metas, topts, topts, 2000,
                               snapshots)
    assert calls, "pipeline did not engage"
    assert stats.prefetch_misses > 0

    assert len(out_serial) == len(out_pipe) >= 1
    for a, b in zip(_sst_bytes(env, dbdir, out_serial),
                    _sst_bytes(env, dbdir, out_pipe)):
        assert a == b, "pipelined SST bytes differ from serial"
    for a, b in zip(out_serial, out_pipe):
        assert (a.smallest, a.largest, a.num_entries) == \
            (b.smallest, b.largest, b.num_entries)


def test_pipeline_multi_output_cut_parity(tmp_path, monkeypatch):
    """Output cutting at max_output_file_size interacts with the chunked
    writer (withheld final blocks): bytes must still match serially."""
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops.device_compaction import run_device_compaction
    from toplingdb_tpu.table.builder import TableOptions

    monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    _enable_small_pipeline(monkeypatch, shards=5)
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    metas = _build_runs(env, dbdir, 20_000, topts, seed=5)
    outs = {}
    for knob in ("0", "1"):
        _pipeline(monkeypatch, knob == "1")
        tc = TableCache(env, dbdir, ICMP, topts)
        c = Compaction(level=0, output_level=2, inputs=list(metas),
                       bottommost=True, max_output_file_size=64 * 1024)
        outs[knob], _ = run_device_compaction(
            env, dbdir, ICMP, c, tc, topts, [],
            new_file_number=_mk_alloc(3000 if knob == "0" else 4000),
            creation_time=7, device_name="cpu-jax",
        )
    assert len(outs["0"]) == len(outs["1"]) > 1, "want a multi-output job"
    for a, b in zip(_sst_bytes(env, dbdir, outs["0"]),
                    _sst_bytes(env, dbdir, outs["1"])):
        assert a == b


def test_pipeline_complex_groups_fall_back_byte_identical(tmp_path,
                                                          monkeypatch):
    """MERGE operands abort the pipeline mid-flight; the serial fallback
    must still produce the CPU path's exact bytes and leave no stray
    files from the aborted attempt."""
    import struct

    import toplingdb_tpu.db.filename as fn
    from toplingdb_tpu.compaction.compaction_job import run_compaction_to_tables
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops.device_compaction import run_device_compaction
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions
    from toplingdb_tpu.utils.merge_operator import UInt64AddOperator

    monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    _enable_small_pipeline(monkeypatch)
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    rng = random.Random(11)
    metas = []
    seq = 1
    for fnum in (61, 62, 63):
        entries = []
        for _ in range(600):
            k = b"key%05d" % rng.randrange(700)
            r = rng.random()
            if r < 0.7:
                entries.append((make_internal_key(k, seq, ValueType.VALUE),
                                b"val%06d" % seq))
            else:
                entries.append((make_internal_key(k, seq, ValueType.MERGE),
                                struct.pack("<Q", seq % 97)))
            seq += 1
        entries.sort(key=lambda kv: ICMP.sort_key(kv[0]))
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, ICMP, topts)
        for k, v in entries:
            b.add(k, v)
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum,
            file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno,
        ))
    op = UInt64AddOperator()

    def run(device, base):
        tc = TableCache(env, dbdir, ICMP, topts)
        c = Compaction(level=0, output_level=2, inputs=list(metas),
                       bottommost=True, max_output_file_size=1 << 62)
        if device:
            return run_device_compaction(
                env, dbdir, ICMP, c, tc, topts, [], merge_operator=op,
                new_file_number=_mk_alloc(base), creation_time=7,
                device_name="cpu-jax")
        return run_compaction_to_tables(
            env, dbdir, ICMP, c, tc, topts, [], merge_operator=op,
            new_file_number=_mk_alloc(base), creation_time=7)

    before = set(os.listdir(dbdir))
    out_cpu, _ = run(False, 5000)
    out_dev, _ = run(True, 6000)
    for a, b in zip(_sst_bytes(env, dbdir, out_cpu),
                    _sst_bytes(env, dbdir, out_dev)):
        assert a == b
    after = set(os.listdir(dbdir))
    expect = before | {f"{m.number:06d}.sst" for m in out_cpu + out_dev}
    assert after == expect, f"stray files: {sorted(after - expect)}"


def test_pipeline_zip_byte_parity(tmp_path, monkeypatch):
    """Zip-format outputs ride the pipeline: pipelined vs serial zip
    compaction produce byte-identical SSTs (snapshots + a surviving range
    tombstone included), and TPULSM_ZIP_PLANE=0 restores the serial
    fallback gate with the Python builder emitting the same bytes."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableOptions
    from toplingdb_tpu.utils import codecs

    monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    _enable_small_pipeline(monkeypatch)
    calls = _spy_pipeline(monkeypatch)

    env = default_env()
    dbdir = str(tmp_path)
    comp = fmt.ZSTD_COMPRESSION if codecs.available("zstd") \
        else fmt.NO_COMPRESSION
    topts = TableOptions(block_size=512)
    zip_topts = TableOptions(format="zip", compression=comp)
    n = 24_000
    metas = _build_runs(env, dbdir, n, topts, seed=3, tombstone_file=True)
    snapshots = [n // 3, 2 * n // 3]

    _pipeline(monkeypatch, False)
    out_serial, _ = _run_job(env, dbdir, metas, topts, zip_topts, 1000,
                             snapshots)
    assert not calls
    _pipeline(monkeypatch, True)
    out_pipe, _ = _run_job(env, dbdir, metas, topts, zip_topts, 2000,
                           snapshots)
    assert calls, "zip job did not ride the pipeline"

    assert len(out_serial) == len(out_pipe) >= 1
    for a, b in zip(_sst_bytes(env, dbdir, out_serial),
                    _sst_bytes(env, dbdir, out_pipe)):
        assert a == b, "pipelined zip SST bytes differ from serial"
    for a, b in zip(out_serial, out_pipe):
        assert (a.smallest, a.largest, a.num_entries) == \
            (b.smallest, b.largest, b.num_entries)

    # Knob off: the pipeline gate is back AND the pure-Python builder
    # reproduces the native kernels' bytes (the PR's writer oracle).
    calls.clear()
    monkeypatch.setenv("TPULSM_ZIP_PLANE", "0")
    out_off, _ = _run_job(env, dbdir, metas, topts, zip_topts, 3000,
                          snapshots)
    assert not calls, "TPULSM_ZIP_PLANE=0 must gate the pipeline"
    for a, b in zip(_sst_bytes(env, dbdir, out_serial),
                    _sst_bytes(env, dbdir, out_off)):
        assert a == b, "python zip builder bytes differ from native"


class _Cancel(BaseException):
    """Out-of-band cancellation (BaseException so no fallback retries)."""


def test_cancel_mid_pipeline_leaves_no_orphans(tmp_path, monkeypatch):
    """A cancellation landing in the compute stage mid-pipeline must tear
    down all stages and delete every partial output file."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import compaction_kernels as ck
    from toplingdb_tpu.ops import pipeline as pl
    from toplingdb_tpu.table.builder import TableOptions

    monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    _enable_small_pipeline(monkeypatch)
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    metas = _build_runs(env, dbdir, 20_000, topts, seed=9)
    before = set(os.listdir(dbdir))

    orig = ck.host_fused_full
    hits = []

    def cancel_on_second(*a, **k):
        hits.append(1)
        if len(hits) >= 2:
            raise _Cancel("injected cancel")
        return orig(*a, **k)

    monkeypatch.setattr(ck, "host_fused_full", cancel_on_second)
    with pytest.raises(_Cancel):
        _run_job(env, dbdir, metas, topts, topts, 7000, [])
    monkeypatch.setattr(ck, "host_fused_full", orig)
    assert set(os.listdir(dbdir)) == before, "orphan outputs left behind"
    # The job still completes once the cancellation is gone.
    outs, _ = _run_job(env, dbdir, metas, topts, topts, 7100, [])
    assert outs and pl.pipeline_enabled()


def test_pipeline_prefetch_tickers(tmp_path, monkeypatch):
    """The compaction input scan exports FilePrefetchBuffer counters as
    PREFETCH_HITS / PREFETCH_MISSES tickers on the DB's statistics."""
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.table.builder import TableOptions
    from toplingdb_tpu.utils import statistics as st

    stats = st.Statistics()
    with DB.open(str(tmp_path / "db"),
                 Options(write_buffer_size=16 * 1024,
                         table_options=TableOptions(block_size=256),
                         statistics=stats)) as db:
        for i in range(4000):
            db.put(b"key%05d" % (i % 1200), b"val%06d" % i)
        db.flush()
        db.compact_range()
        db.wait_for_compactions()
    assert stats.get_ticker_count(st.PREFETCH_MISSES) > 0
    # Sequential block loads during the scan escalate into readahead
    # windows, so at least some reads must have been served from them.
    assert stats.get_ticker_count(st.PREFETCH_HITS) > 0


def test_phase_dict_overlap_reporting():
    """other_s clamps at 0; over-counted (overlapping) phases report an
    explicit pipeline_overlap_s instead of a free-text note."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats

    s = CompactionStats(work_time_usec=1_000_000, input_scan_usec=300_000,
                        host_compute_usec=500_000)
    d = s.phase_dict()
    assert d["other_s"] == pytest.approx(0.2)
    assert "pipeline_overlap_s" not in d

    s = CompactionStats(work_time_usec=1_000_000, input_scan_usec=800_000,
                        host_compute_usec=900_000,
                        encode_write_usec=700_000)
    d = s.phase_dict()
    assert d["other_s"] == 0.0
    assert d["pipeline_overlap_s"] == pytest.approx(1.4)
    assert all(not isinstance(v, str) for v in d.values())


def test_prefetch_buffer_pre_armed_window():
    """arm_immediately + initial_readahead fetch a full window on the very
    first read; sequential successors hit, a random read resets cleanly."""
    from toplingdb_tpu.env import MemEnv
    from toplingdb_tpu.table.prefetch import FilePrefetchBuffer

    env = MemEnv()
    w = env.new_writable_file("/pf")
    w.append(bytes(range(256)) * 1024)  # 256 KiB
    w.close()
    f = env.new_random_access_file("/pf")
    pf = FilePrefetchBuffer(f, max_readahead=64 * 1024,
                            initial_readahead=64 * 1024,
                            arm_immediately=True)
    assert pf.read(0, 4096) == bytes(range(256)) * 16
    assert (pf.hits, pf.misses) == (0, 1)
    for i in range(1, 16):
        pf.read(i * 4096, 4096)
    assert pf.hits == 15  # the rest of the 64 KiB window
    h, m = pf.hits, pf.misses
    pf.read(200 * 1024, 4096)  # random access: miss, state reset
    assert (pf.hits, pf.misses) == (h, m + 1)


@pytest.mark.parametrize("seed", [2])
def test_pipeline_soak_acknowledged_writes_survive(monkeypatch, seed):
    """Seeded soak with the pipeline forced on for every compaction
    (tests/test_fault_soak.py's model-checked shape): every acknowledged
    write survives flush+compaction cycles and a clean reopen."""
    from toplingdb_tpu.compaction.executor import (
        DeviceCompactionExecutorFactory,
    )
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    _enable_small_pipeline(monkeypatch, shards=3)
    calls = _spy_pipeline(monkeypatch)
    monkeypatch.setenv("TPULSM_HOST_SORT", "1")
    rng = random.Random(seed)
    root = tempfile.mkdtemp(prefix=f"pipesoak{seed}_")
    d = root + "/db"
    model = {}
    try:
        db = DB.open(d, Options(
            write_buffer_size=8 * 1024, level0_file_num_compaction_trigger=3,
            compaction_executor_factory=DeviceCompactionExecutorFactory(
                device="cpu-jax", allow_fallback=False)))
        for cycle in range(5):
            for _ in range(rng.randrange(150, 400)):
                k = b"k%04d" % rng.randrange(600)
                if rng.random() < 0.12:
                    db.delete(k)
                    model.pop(k, None)
                else:
                    v = b"v%06d" % rng.randrange(10 ** 6)
                    db.put(k, v)
                    model[k] = v
            db.flush()
            if cycle % 2:
                db.compact_range()
            db.wait_for_compactions()
            bad = [k for k, v in model.items() if db.get(k) != v]
            assert not bad, (cycle, bad[:3])
            gone = [k for k in (b"k%04d" % i for i in range(600))
                    if k not in model and db.get(k) is not None]
            assert not gone, (cycle, gone[:3])
        db.close()
        assert calls, "no compaction ran pipelined"
        with DB.open(d, Options()) as db2:
            bad = [k for k, v in model.items() if db2.get(k) != v]
            assert not bad, bad[:3]
    finally:
        shutil.rmtree(root, ignore_errors=True)
