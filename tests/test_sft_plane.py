"""SingleFastTables on the device data plane (ISSUE 35): a job that reads
SingleFastTables — alone, beside block files, beside ZipTables — pipelined
(two shards or more) and serial (one shard), to single_fast, block and zip
outputs, is byte-identical to the CPU path's per-entry build; the columnar
writer equals `SingleFastTableBuilder` through `build_outputs`, the
columnar flush the iterator flush; a reader bounds and scans entry ranges;
a remote job builds what the DB would build; the benchmark's plain reader
(benchmark/lib/sft_plain.py) reads every file to the rows
`SingleFastTableReader` reads; the format's counters add up."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmark"))

from lib import dbside, sft_plain  # noqa: E402
from lib.workload import Workload  # noqa: E402

import toplingdb_tpu.db.filename as fn  # noqa: E402
from test_compaction_pipeline import (  # noqa: E402
    ICMP, _build_runs, _mk_alloc, _sst_bytes,
)
from toplingdb_tpu.db.dbformat import ValueType, make_internal_key  # noqa: E402
from toplingdb_tpu.ops import compaction_kernels as ck  # noqa: E402
from toplingdb_tpu.table import format as fmt  # noqa: E402
from toplingdb_tpu.table.builder import TableOptions  # noqa: E402
from toplingdb_tpu.table.factory import new_table_builder, open_table  # noqa: E402

SFT_COUNTERS = ("sft_input_files", "sft_input_rows", "sft_scan_usec",
                "sft_output_files", "sft_output_rows", "sft_output_bytes",
                "sft_build_usec")
SFT = TableOptions(format="single_fast")          # the deployment's
BLOCK = TableOptions(block_size=4096, compression=fmt.SNAPPY_COMPRESSION)
ZIP = dataclasses.replace(BLOCK, format="zip")
OUT = {"single_fast": SFT, "block": BLOCK, "zip": ZIP}


def _job(env, dbdir, metas, out_topts, alloc_base, snapshots, bottommost,
         device, compaction_filter=None, max_file=256 << 10):
    from toplingdb_tpu.compaction.compaction_job import (
        run_compaction_to_tables,
    )
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.ops.device_compaction import run_device_compaction

    tc = TableCache(env, dbdir, ICMP, SFT)
    # Level 0: the inputs overlap, so every file is a run of its own.
    c = Compaction(level=0, output_level=2, inputs=list(metas),
                   bottommost=bottommost, max_output_file_size=max_file)
    run = run_device_compaction if device else run_compaction_to_tables
    return run(env, dbdir, ICMP, c, tc, out_topts, snapshots,
               new_file_number=_mk_alloc(alloc_base), creation_time=7,
               compaction_filter=compaction_filter,
               **({"device_name": "cpu-jax"} if device else {}))


@pytest.fixture(scope="module")
def inputs(tmp_path_factory):
    """Four SingleFastTable runs with deletions (written by the columnar
    writer), a block file with a range tombstone, two block runs, and
    ZipTables made of one more run by a CPU compaction."""
    from toplingdb_tpu.env import default_env

    env = default_env()
    dbdir = str(tmp_path_factory.mktemp("sftplane"))
    sft = _build_runs(env, dbdir, 40000, SFT, runs=4, tombstone_file=True)
    tomb, sft = sft[-1], sft[:-1]
    block = _build_runs(env, dbdir, 20000, BLOCK, seed=2, runs=2,
                        first_fnum=41)
    cold, st = _job(env, dbdir, block[:1], ZIP, 60, [], bottommost=False,
                    device=False)
    assert st.zip_output_files == len(cold) >= 1
    for m in sft:
        assert sft_plain.is_single_fast_table(
            fn.table_file_name(dbdir, m.number))
    return env, dbdir, {"sft": sft, "tomb": [tomb], "block": block[1:],
                        "zip": cold}


def _rows_by_reader(env, path):
    r = open_table(env.new_random_access_file(path), ICMP, SFT)
    it = r.new_iterator()
    it.seek_to_first()
    return list(it.entries()), r.range_del_entries()


def _assert_plain_reader_agrees(env, path):
    t = sft_plain.read_table(path, verify=True)
    rows, tombs = _rows_by_reader(env, path)
    ko = np.concatenate([[0], np.cumsum(t["key_lens"])])
    vo = np.concatenate([[0], np.cumsum(t["val_lens"])])
    got = [(t["key_buf"][ko[i]:ko[i + 1]].tobytes(),
            t["val_buf"][vo[i]:vo[i + 1]].tobytes())
           for i in range(len(t["key_lens"]))]
    assert got == rows
    assert [(b, seq, e) for b, seq, e in t["tombstones"]] == [
        (b[:-8], int.from_bytes(b[-8:], "little") >> 8, e) for b, e in tombs]


MIXES = {"sft": ("sft",), "sft+block": ("sft", "tomb", "block"),
         "sft+zip": ("sft", "zip"),
         "sft+block+zip": ("sft", "tomb", "block", "zip")}


@pytest.mark.parametrize("bottommost", [True, False])
@pytest.mark.parametrize("out", ["single_fast", "block", "zip"])
@pytest.mark.parametrize("shards", [4, 1])
@pytest.mark.parametrize("mix", ["sft", "sft+block+zip"])
def test_sft_inputs_equal_the_cpu_path(inputs, monkeypatch, mix, shards,
                                       out, bottommost):
    env, dbdir, files = inputs
    metas = [m for k in MIXES[mix] for m in files[k]]
    monkeypatch.setattr(ck, "shard_count", lambda n: shards)
    base = 1000 + 100 * (shards + 10 * list(OUT).index(out)
                         + 40 * bottommost + 80 * (mix != "sft"))
    # Bottommost with no snapshot drops the tombstone, so the output is
    # cut into files; the other half keeps it under a held snapshot.
    snaps = [] if bottommost else [5, 20000]
    dev, sd = _job(env, dbdir, metas, OUT[out], base, snaps, bottommost,
                   device=True)
    cpu, sc = _job(env, dbdir, metas, OUT[out], base + 50, snaps,
                   bottommost, device=False)
    assert sd.pipelined == (shards > 1), sd.pipeline_exit
    assert not sd.pipelined or sd.host_compute_usec == 0
    assert _sst_bytes(env, dbdir, dev) == _sst_bytes(env, dbdir, cpu)
    if bottommost and out != "block":
        assert len(dev) >= 2              # the cut rule was met
    # The format's counters, on both ends of the job and on both routes.
    n_sft = len(files["sft"])
    assert sd.sft_input_files == sc.sft_input_files == n_sft
    assert sd.sft_input_rows == sc.sft_input_rows == 40000
    assert sd.sft_input_rows <= sd.input_records
    assert sd.sft_scan_usec > 0 and sc.sft_scan_usec == 0
    if out == "single_fast":
        assert sd.sft_output_files == sd.output_files == len(dev)
        assert sd.sft_output_bytes == sd.output_bytes
        assert sd.sft_output_rows == sd.output_records > 0
        assert sd.sft_build_usec > 0
        assert (sc.sft_output_files, sc.sft_output_rows,
                sc.sft_output_bytes) == (
            sd.sft_output_files, sd.sft_output_rows, sd.sft_output_bytes)
        for m in dev:
            _assert_plain_reader_agrees(
                env, fn.table_file_name(dbdir, m.number))
    else:
        assert (sd.sft_output_files, sd.sft_output_rows,
                sd.sft_output_bytes, sd.sft_build_usec) == (0, 0, 0, 0)


@pytest.mark.parametrize("shards", [4, 1])
@pytest.mark.parametrize("mix", ["sft+block", "sft+zip"])
def test_two_formats_in_one_job(inputs, monkeypatch, mix, shards):
    env, dbdir, files = inputs
    metas = [m for k in MIXES[mix] for m in files[k]]
    monkeypatch.setattr(ck, "shard_count", lambda n: shards)
    base = 9000 + 100 * (shards + 10 * (mix == "sft+zip"))
    dev, sd = _job(env, dbdir, metas, SFT, base, [], True, device=True)
    cpu, _ = _job(env, dbdir, metas, SFT, base + 50, [], True, device=False)
    assert sd.pipelined == (shards > 1), sd.pipeline_exit
    assert _sst_bytes(env, dbdir, dev) == _sst_bytes(env, dbdir, cpu)
    assert 0 < sd.sft_input_rows < sd.input_records
    assert (sd.zip_input_rows > 0) == (mix == "sft+zip")


def test_plain_reader_reads_the_inputs(inputs):
    env, dbdir, files = inputs
    for m in files["sft"]:
        _assert_plain_reader_agrees(env, fn.table_file_name(dbdir, m.number))


@pytest.mark.parametrize("shards", [4, 1])
def test_a_block_only_job_counts_nothing_of_the_format(
        inputs, monkeypatch, shards):
    env, dbdir, files = inputs
    monkeypatch.setattr(ck, "shard_count", lambda n: shards)
    _outs, st = _job(env, dbdir, files["block"] + files["tomb"], BLOCK,
                     12000 + shards, [], True, device=True)
    assert st.input_records > 0
    assert [getattr(st, k) for k in SFT_COUNTERS] == [0] * 7


def test_the_per_entry_route_reads_and_counts_sft_inputs(inputs):
    """A compaction filter forces the per-entry route
    (`collect_raw_entries`): the CPU path's bytes, the counters of files,
    rows and bytes (no columnar wall there)."""
    from toplingdb_tpu.utils.compaction_filter import (
        RemoveEmptyValueCompactionFilter,
    )

    env, dbdir, files = inputs
    filt = RemoveEmptyValueCompactionFilter()
    dev, sd = _job(env, dbdir, files["sft"], SFT, 13000, [], True,
                   device=True, compaction_filter=filt)
    cpu, _ = _job(env, dbdir, files["sft"], SFT, 13100, [], True,
                  device=False, compaction_filter=filt)
    assert not sd.pipelined and sd.input_records == 40000
    assert _sst_bytes(env, dbdir, dev) == _sst_bytes(env, dbdir, cpu)
    assert (sd.sft_input_files, sd.sft_input_rows) == (4, 40000)
    assert sd.sft_output_files == len(dev) and sd.sft_output_rows > 0
    assert sd.sft_output_bytes == sd.output_bytes
    assert (sd.sft_scan_usec, sd.sft_build_usec) == (0, 0)


# -- files of any widths ------------------------------------------------------

def _varlen_entries(seed, n, fixed):
    """n sorted (internal key, value) entries: several versions of some
    user keys, deletions, one merge-free stream; widths fixed or not."""
    rng = np.random.default_rng(seed)
    uks = sorted({(b"%08d" % int(x)) if fixed
                  else rng.bytes(int(rng.integers(1, 140)))
                  for x in rng.integers(0, 10 ** 8, n)})
    out = []
    seq = 10 * n
    for uk in uks:
        for _ in range(int(rng.integers(1, 4))):
            seq -= 1
            dele = rng.random() < 0.1
            out.append((make_internal_key(
                uk, seq, ValueType.DELETION if dele else ValueType.VALUE),
                b"" if dele else (b"v" * 20 if fixed else rng.bytes(
                    int(rng.integers(0, 400))))))
    return out


def _to_columnar(entries):
    from toplingdb_tpu.ops.columnar_io import ColumnarKV

    kl = np.array([len(k) for k, _ in entries], np.int32)
    vl = np.array([len(v) for _, v in entries], np.int32)
    kv = ColumnarKV(
        np.frombuffer(b"".join(k for k, _ in entries), np.uint8),
        (np.cumsum(kl) - kl).astype(np.int32), kl,
        np.frombuffer(b"".join(v for _, v in entries) or b"\0", np.uint8),
        (np.cumsum(vl) - vl).astype(np.int32), vl)
    tr = np.array([int.from_bytes(k[-8:], "little") for k, _ in entries],
                  np.uint64)
    return kv, (tr >> np.uint64(8)), (tr & np.uint64(0xFF)).astype(np.int32)


def _tomb_frags(n):
    from toplingdb_tpu.db.range_del import RangeTombstone, fragment_tombstones
    from toplingdb_tpu.db.dbformat import BYTEWISE

    return list(fragment_tombstones(
        [RangeTombstone(7 + i, b"%02d" % i, b"%02d" % (i + 3))
         for i in range(n)], BYTEWISE))


@pytest.mark.parametrize("streamed", [False, True], ids=["array", "chunks"])
@pytest.mark.parametrize("tombs", [0, 2], ids=["cut", "tombstones"])
@pytest.mark.parametrize("fixed", [True, False], ids=["fixed", "varlen"])
@pytest.mark.parametrize("hash_index", [False, True],
                         ids=["plain", "hash_index"])
def test_columnar_writer_equals_the_builder(tmp_path, hash_index, fixed,
                                            tombs, streamed):
    """`write_tables_columnar` under `format="single_fast"` against
    `SingleFastTableBuilder` through `build_outputs`: the same files, byte
    for byte, the cut rule, a zeroed trailer and tombstones included."""
    from toplingdb_tpu.compaction.compaction_job import (
        CompactionStats, build_outputs,
    )
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops.columnar_io import write_tables_columnar

    env = default_env()
    topts = dataclasses.replace(SFT, hash_index=hash_index)
    entries = _varlen_entries(3 + fixed, 3000, fixed)
    kv, seqs, vtypes = _to_columnar(entries)
    frags = _tomb_frags(tombs)
    # The sequence of every other user key's oldest row is zeroed, as a
    # bottommost job zeroes a survivor's; every seventh row is no survivor.
    override = np.full(kv.n, -1, np.int64)
    last = [i for i in range(kv.n) if i + 1 == kv.n
            or entries[i][0][:-8] != entries[i + 1][0][:-8]]
    zeroed = np.array(last[::2])
    override[zeroed] = vtypes[zeroed]
    seqs = seqs.copy()
    seqs[zeroed] = 0
    stream = [(k[:-8] + int(override[i]).to_bytes(8, "little")
               if override[i] >= 0 else k, v)
              for i, (k, v) in enumerate(entries)]
    keep = [i for i in range(kv.n) if i % 7 != 3]
    max_file = 64 << 10
    c = Compaction(level=0, output_level=1, inputs=[], bottommost=False,
                   max_output_file_size=max_file)
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    os.makedirs(a), os.makedirs(b)
    want = build_outputs(env, a, ICMP, c, iter([stream[i] for i in keep]),
                         frags, _mk_alloc(10), topts, CompactionStats(), 7)
    order = np.array(keep, np.int32)
    feed = (iter(np.array_split(order, 7)) if streamed else order)
    st = CompactionStats()
    got = write_tables_columnar(
        env, b, _mk_alloc(10), ICMP, topts, kv, feed, override, vtypes,
        seqs, frags, 7, max_output_file_size=max_file, stats=st)
    assert [g[0] for g in got] == [m.number for m in want]
    assert len(got) == (1 if tombs else len(want)) and (tombs or len(got) > 2)
    for (fnum, path, props, smallest, largest, sel), m in zip(got, want):
        with open(path, "rb") as f, open(fn.table_file_name(a, fnum),
                                         "rb") as g:
            assert f.read() == g.read()
        assert (smallest, largest) == (m.smallest, m.largest)
        assert props.num_entries == m.num_entries == len(sel)
        r = open_table(env.new_random_access_file(path), ICMP, topts)
        assert r.has_hash_index == hash_index
        _assert_plain_reader_agrees(env, path)
    assert st.sft_build_usec > 0


def _one_file(env, path, entries, topts=SFT, tomb=False):
    w = env.new_writable_file(path)
    b = new_table_builder(w, ICMP, topts)
    for k, v in entries:
        b.add(k, v)
    if tomb:
        b.add_tombstone(make_internal_key(b"a", 9, ValueType.RANGE_DELETION),
                        b"b")
    b.finish()
    w.close()
    return open_table(env.new_random_access_file(path), ICMP, topts)


@pytest.mark.parametrize("fixed", [True, False], ids=["fixed", "varlen"])
def test_scan_ranges_and_bounds_equal_the_iterator(tmp_path, fixed):
    """`scan_columnar` / `scan_into` over `[lo, hi)`, also starting and
    ending mid-file, against the iterator over the same entries;
    `entry_lower_bound` against a sorted list; the candidates."""
    import bisect

    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops.columnar_io import ColumnarKV, scan_table_columnar

    env = default_env()
    entries = _varlen_entries(11, 2500, fixed)
    r = _one_file(env, str(tmp_path / "000009.sst"), entries, tomb=True)
    assert r.entry_plane == "sft" and r.scan_native_ready()
    n = len(entries)
    assert r.n == n
    for lo, hi in ((0, n), (0, 1), (17, 18), (1, n - 1), (n // 3, n // 2),
                   (n - 5, n), (40, 40)):
        kb, ko, kl, vb, vo, vl = r.scan_columnar(lo, hi)
        got = [(kb[ko[i]:ko[i] + kl[i]].tobytes(),
                vb[vo[i]:vo[i] + vl[i]].tobytes()) for i in range(hi - lo)]
        assert got == entries[lo:hi]
        # The same range laid into the middle of larger buffers.
        nk = sum(len(k) for k, _ in entries[lo:hi])
        nv = sum(len(v) for _, v in entries[lo:hi])
        kv = ColumnarKV(np.zeros(nk + 9, np.uint8), np.zeros(n + 3, np.int32),
                        np.zeros(n + 3, np.int32), np.zeros(nv + 9, np.uint8),
                        np.zeros(n + 3, np.int32), np.zeros(n + 3, np.int32))
        assert r.scan_into(lo, hi, kv, 3, 4, 5, nk, nv) == (nk, nv)
        assert [(kv.ikey(3 + i), kv.value(3 + i))
                for i in range(hi - lo)] == entries[lo:hi]
        if nk:
            from toplingdb_tpu.utils.status import NotSupported

            with pytest.raises(NotSupported):
                r.scan_into(lo, hi, kv, 3, 4, 5, nk - 1, nv)
    assert scan_table_columnar(r).to_entries() == entries
    keys = [k for k, _ in entries]
    skeys = [ICMP.sort_key(k) for k in keys]
    rng = np.random.default_rng(5)
    probes = [keys[int(i)] for i in rng.integers(0, n, 60)] + [
        make_internal_key(k[:-8] + b"\x00", 5, ValueType.VALUE)
        for k in keys[::97]] + [b"\x00" * 9, b"\xff" * 150]
    for t in probes:
        assert r.entry_lower_bound(t) == bisect.bisect_left(
            skeys, ICMP.sort_key(t))
    cands = r.split_candidates(4096)
    assert cands == sorted(cands) and len(cands) > 4
    p = r.properties
    per = (p.raw_key_size + p.raw_value_size) / len(cands)
    assert 2048 <= per <= 8192
    uks = {k[:-8] for k in keys}
    assert set(cands) <= uks
    # The whole-file scan of another format's subclass stays off the plane.
    from toplingdb_tpu.table.cuckoo import CuckooTableReader
    from toplingdb_tpu.table.plain import PlainTableReader

    assert CuckooTableReader.entry_plane is PlainTableReader.entry_plane \
        is None


def test_plain_and_cuckoo_inputs_leave_the_plan(tmp_path):
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import pipeline as pl

    env = default_env()
    ents = [(make_internal_key(b"%08d" % i, 5, ValueType.VALUE), b"v")
            for i in range(50)]
    r = _one_file(env, str(tmp_path / "000003.sst"), ents,
                  TableOptions(format="cuckoo"))
    with pytest.raises(pl.PipelineIneligible, match="non-block input"):
        pl._build_plan([r])


def test_shards_are_even_in_rows_at_ten_to_one(tmp_path, monkeypatch):
    """One SingleFastTable ten times the others' size, a block file among
    them: the plan's shards hold about the same number of ROWS."""
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import pipeline as pl

    env = default_env()
    dbdir = str(tmp_path)
    big = _build_runs(env, dbdir, 40000, SFT, runs=1, seed=3)
    small = _build_runs(env, dbdir, 12000, SFT, runs=3, seed=4,
                        first_fnum=40)
    blk = _build_runs(env, dbdir, 4000, BLOCK, runs=1, seed=5, first_fnum=60)
    metas = big + small + blk
    monkeypatch.setattr(ck, "shard_count", lambda n: 4)
    tc = TableCache(env, dbdir, ICMP, SFT)
    readers = [tc.get_reader(m.number) for m in metas]
    assert readers[0].n == 10 * readers[1].n
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
    # A cut lies within a candidate's rows (~150) a file of the quantile:
    # against a shard of 2^19 rows that is nothing (the rule leaves 2%).
    assert abs(rows - len(uks) / 4).max() <= 150 * len(readers), rows


# -- the flush ---------------------------------------------------------------

@pytest.mark.parametrize("fixed", [True, False], ids=["fixed", "varlen"])
@pytest.mark.parametrize("hash_index", [False, True],
                         ids=["plain", "hash_index"])
def test_columnar_flush_equals_the_iterator_flush(tmp_path, monkeypatch,
                                                  hash_index, fixed):
    from toplingdb_tpu.db import flush_job
    from toplingdb_tpu.db.memtable import MemTable
    from toplingdb_tpu.env import default_env

    env = default_env()
    topts = dataclasses.replace(SFT, hash_index=hash_index)
    from toplingdb_tpu.db.memtable import create_memtable_rep

    mem = MemTable(ICMP, create_memtable_rep("skiplist"))
    rng = np.random.default_rng(9)
    for seq in range(1, 4000):
        uk = (b"%08d" % int(rng.integers(0, 1500))) if fixed else rng.bytes(
            int(rng.integers(1, 60)))
        if rng.random() < 0.1:
            mem.add(seq, ValueType.DELETION, uk, b"")
        else:
            mem.add(seq, ValueType.VALUE, uk,
                    b"v" * 20 if fixed else rng.bytes(int(rng.integers(0, 90))))
    mem.add(4000, ValueType.RANGE_DELETION, b"0", b"1")
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    os.makedirs(a), os.makedirs(b)
    called = []
    real = flush_job._flush_columnar

    def spy(*args, **kw):
        called.append(real(*args, **kw))
        return called[-1]

    monkeypatch.setattr(flush_job, "_flush_columnar", spy)
    m1 = flush_job.flush_memtable_to_table(env, a, 5, ICMP, [mem], topts, 7)
    assert called and called[0] is m1            # the columnar route ran
    monkeypatch.setattr(flush_job, "_flush_columnar", lambda *a, **k: None)
    m2 = flush_job.flush_memtable_to_table(env, b, 5, ICMP, [mem], topts, 7)
    with open(fn.table_file_name(a, 5), "rb") as f, open(
            fn.table_file_name(b, 5), "rb") as g:
        assert f.read() == g.read()
    assert dataclasses.asdict(m1) == dataclasses.asdict(m2)
    assert sft_plain.is_single_fast_table(fn.table_file_name(a, 5))
    _assert_plain_reader_agrees(env, fn.table_file_name(a, 5))
    # What the columnar route refuses stays on the iterator path.
    assert real(env, a, 6, ICMP, mem,
                dataclasses.replace(topts, auto_sort=True), [], 7,
                (0, "default")) is None


# -- a remote job -------------------------------------------------------------

def _post_job(tmp_path, dbdir, metas, shards, monkeypatch, **params):
    from toplingdb_tpu.compaction import worker
    from toplingdb_tpu.compaction.executor import CompactionParams
    from toplingdb_tpu.utils import telemetry as tm

    monkeypatch.setattr(ck, "shard_count", lambda n: shards)
    job_dir = str(tmp_path / "job")
    os.makedirs(os.path.join(job_dir, "out"))
    p = CompactionParams(**{
        **dbside.job_params(
            1, dbdir, [fn.table_file_name(dbdir, m.number) for m in metas],
            2, True, 256 << 10),
        "device": "cpu-jax", "table_format": "single_fast",
        "output_dir": os.path.join(job_dir, "out"), **params})
    with open(os.path.join(job_dir, "params.json"), "w") as f:
        f.write(p.to_json())
    tracer = tm.Tracer(proc="dcompact-worker")
    with tracer.start_from(None, "dcompact.request"):
        assert worker.run_job(job_dir) == 0
    (trace,) = tracer.finished()
    with open(os.path.join(job_dir, "results.json")) as f:
        return job_dir, trace, json.load(f)


@pytest.mark.parametrize("shards", [4, 1])
def test_a_traced_job_names_its_spans_and_counters(
        inputs, tmp_path, monkeypatch, shards):
    """A worker's job of single_fast + block inputs to single_fast outputs,
    run as the service's handler runs it: the trace holds
    `pipeline.sft_scan`, `sst.sft_append` and `sst.sft_finish`, the
    writer's two inside `pipeline.encode_write`, and the reply's stats the
    seven counters."""
    env, dbdir, files = inputs
    _d, trace, reply = _post_job(tmp_path, dbdir,
                                 files["sft"] + files["block"], shards,
                                 monkeypatch)
    names = {s.name for s in trace.spans}
    assert {"pipeline.sft_scan", "sst.sft_append", "sst.sft_finish"} <= names
    by_id = {s.span_id: s for s in trace.spans}
    for s in trace.spans:
        if s.name in ("sst.sft_append", "sst.sft_finish"):
            parent = by_id[s.parent_id]
            assert parent.name == "pipeline.encode_write", s.name
            assert parent.start_us <= s.start_us + 2
            assert s.start_us + s.dur_us <= parent.start_us + parent.dur_us + 2
    stats = reply["stats"]
    assert stats["pipelined"] == (shards > 1)
    assert all(stats[k] > 0 for k in SFT_COUNTERS), stats
    build = sum(s.dur_us for s in trace.spans
                if s.name in ("sst.sft_append", "sst.sft_finish"))
    assert abs(stats["sft_build_usec"] - build) <= 0.2 * build + 2000


@pytest.mark.parametrize("hash_index,policy", [
    (False, None), (False, SFT.filter_policy.name()), (True, None),
    (False, ""), (True, "tpulsm.BloomFilter:12.0")],
    ids=["older_db", "deployment", "hash_index", "no_filter", "hash+bloom12"])
def test_a_remote_job_builds_what_the_db_would_build(
        inputs, tmp_path, monkeypatch, hash_index, policy):
    """The file a remote job writes and the file `run_compaction_to_tables`
    writes in the DB process for the same inputs and options are the same
    bytes: `CompactionParams` carries what shapes a SingleFastTable
    (`hash_index`, the filter policy's name)."""
    from toplingdb_tpu.table.filter import filter_policy_from_name

    env, dbdir, files = inputs
    topts = dataclasses.replace(SFT, hash_index=hash_index)
    if policy is not None:
        topts = dataclasses.replace(
            topts, filter_policy=filter_policy_from_name(policy))
    sent = {"hash_index": hash_index}
    if policy is not None:
        sent["filter_policy"] = policy
    job_dir, _trace, reply = _post_job(tmp_path, dbdir, files["sft"], 4,
                                       monkeypatch, creation_time=7, **sent)
    local, _st = _job(env, dbdir, files["sft"], topts,
                      14000 + 100 * hash_index + 1000 * len(policy or "x"),
                      [], True, device=False)
    remote = [open(os.path.join(job_dir, "out", d["path"]), "rb").read()
              for d in reply["output_files"]]
    assert remote == _sst_bytes(env, dbdir, local)
    r = open_table(env.new_random_access_file(os.path.join(
        job_dir, "out", reply["output_files"][0]["path"])), ICMP, SFT)
    assert r.has_hash_index == hash_index
    assert r.properties.filter_policy_name == (
        SFT.filter_policy.name() if policy is None else policy)


def test_a_job_an_older_db_wrote_is_still_read():
    """`params.json` without the new fields: the defaults."""
    from toplingdb_tpu.compaction.executor import CompactionParams

    p = json.loads(CompactionParams(**{
        **dbside.job_params(1, "/db", [], 1, False, 1 << 20),
        "device": "cpu"}).to_json())
    del p["hash_index"], p["filter_policy"]
    q = CompactionParams.from_json(json.dumps(p))
    assert (q.hash_index, q.filter_policy) == (False, None)


def test_the_executor_sends_the_format_of_every_level(tmp_path):
    """This deployment's remote jobs are asked for SingleFastTables at
    every level, with the DB's hash index and filter policy."""
    from toplingdb_tpu.compaction.executor import SubprocessCompactionExecutor
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options

    sent = []

    def spawn(job_dir, device):
        with open(os.path.join(job_dir, "params.json")) as f:
            sent.append(json.load(f))
        raise OSError("not run: the parameters are what is asked")

    topts = dataclasses.replace(SFT, hash_index=True)
    db = DB.open(str(tmp_path / "db"), Options(
        create_if_missing=True, table_options=topts))
    try:
        for level, bottommost in ((0, False), (1, False), (2, True),
                                  (5, True)):
            ex = SubprocessCompactionExecutor("cpu", None, spawn=spawn)
            c = Compaction(level=level, output_level=level + 1, inputs=[],
                           bottommost=bottommost,
                           max_output_file_size=1 << 20)
            with pytest.raises(Exception):
                ex.execute(db, c, [], lambda: 99)
        assert [p["table_format"] for p in sent] == ["single_fast"] * 4
        assert all(p["hash_index"] is True for p in sent)
        assert {p["filter_policy"] for p in sent} == {
            topts.filter_policy.name()}
    finally:
        db.close()


# -- a DB -----------------------------------------------------------------

@pytest.mark.parametrize("min_remote", [0, 1 << 40],
                         ids=["remote", "all_local"])
def test_db_of_single_fast_tables_behind_a_service(tmp_path, min_remote):
    """`TableOptions(format="single_fast")` behind an in-process dcompact
    service (and, with a threshold no job reaches, with every compaction in
    the DB process): every file a flush or a compaction installs is a
    SingleFastTable, every flush takes the columnar route, and every read
    equals the oracle, after reopen too."""
    from toplingdb_tpu.compaction.dcompact_service import (
        DcompactWorkerService,
    )
    from toplingdb_tpu.db import flush_job
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils.listener import EventListener

    n, draws = 30000, 90000
    wl = Workload(n, draws, seed=35)
    kb, vb = wl.encode(0, n + draws)
    svc = DcompactWorkerService(device="cpu-jax")
    port = svc.start()
    stats = dbside.JobStatistics()
    factory = dbside.TimedFactory(f"http://127.0.0.1:{port}", "cpu-jax",
                                  min_remote)
    installed = []

    class Witness(EventListener):
        def on_flush_completed(self, db, info):
            installed.append(("flush", sft_plain.is_single_fast_table(
                fn.table_file_name(db.dbname, info.file_number))))

        def on_compaction_completed(self, db, info):
            installed.extend(
                (info.device, sft_plain.is_single_fast_table(
                    fn.table_file_name(db.dbname, x)))
                for x in info.output_files if info.device != "move")

    columnar = []
    real = flush_job._flush_columnar

    def spy(*a, **kw):
        columnar.append(real(*a, **kw) is not None)
        return None if not columnar[-1] else real(*a, **kw)

    opts = Options(
        create_if_missing=True, write_buffer_size=256 << 10,
        target_file_size_base=256 << 10,
        max_bytes_for_level_base=512 << 10,
        level0_file_num_compaction_trigger=4, table_options=SFT,
        statistics=stats, compaction_executor_factory=factory,
        listeners=[Witness()], dcompact=dbside.ONE_ATTEMPT)
    dbdir = str(tmp_path / "db")
    db = DB.open(dbdir, opts)
    try:
        for w in range(0, n + draws, 1000):
            dbside.put_batches(db, kb[8 * w:8 * (w + 1000)],
                               vb[20 * w:20 * (w + 1000)], 1000, 500)
            db.wait_for_compactions()  # the tree is the put count's
        assert len(installed) > 20 and all(ok for _, ok in installed)
        assert {"flush"} < {who for who, _ in installed}
        jobs = stats.jobs
        assert jobs and all(s.remote == (min_remote == 0) for s in jobs)
        assert all(s.sft_input_rows == s.input_records > 0
                   and s.sft_output_bytes == s.output_bytes for s in jobs)
        if min_remote == 0:
            assert svc.job_sums["sft_input_rows"] == sum(
                s.sft_input_rows for s in jobs)
            assert svc.job_sums["sft_output_files"] == sum(
                s.sft_output_files for s in jobs) > 0
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


def test_a_short_pread_does_not_cut_a_file(tmp_path, monkeypatch):
    """One `os.pread` may return fewer bytes than asked (on the chip's
    host a 16 MB read of a whole SingleFastTable did, and the reader took
    the middle of the region for the footer: "bad SST magic"): the Posix
    env reads on to the end."""
    from toplingdb_tpu.env import default_env

    env = default_env()
    entries = _varlen_entries(21, 1200, True)
    path = str(tmp_path / "000031.sst")
    _one_file(env, path, entries)
    real = os.pread
    calls = []

    def short_pread(fd, n, offset):
        calls.append(n)
        return real(fd, min(n, 4096), offset)

    monkeypatch.setattr(os, "pread", short_pread)
    r = open_table(env.new_random_access_file(path), ICMP, SFT)
    assert max(calls) > 4096            # the whole file was asked for
    it = r.new_iterator()
    it.seek_to_first()
    assert list(it.entries()) == entries
    f = env.new_random_access_file(path)
    assert f.read(f.size() - 10, 100) == open(path, "rb").read()[-10:]
