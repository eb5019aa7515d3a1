"""Shards whose user keys differ in length (ops/compaction_kernels.py
`_fused_varlen_shard_impl`): the keys go up zero-padded to a width bucket
beside each row's true length, and the program gives, row for row, what the
host twin (`fused_encode_sort_gc_host`) and the CPU compaction path
(`compaction/compaction_iterator.py`) give. A shard of one key length keeps
the uniform program with the shapes and static arguments it always had.
Also LZ4 blocks (the YCSB binding's codec from L2 on) on the plane: the
native scan reads them, the native section builder writes them."""

import os

import numpy as np
import pytest

from toplingdb_tpu.db.dbformat import (
    InternalKeyComparator,
    ValueType,
    make_internal_key,
)
from toplingdb_tpu.ops import compaction_kernels as ck
from toplingdb_tpu.ops import pipeline as pl

ICMP = InternalKeyComparator()
_PIPELINE_ENABLED = pl.pipeline_enabled
_U64 = (1 << 64) - 1


def fnvhash64(v: int) -> int:
    """YCSB's Utils.fnvhash64: FNV-1a over the 8 low-order bytes, as a
    signed Java long, absolute value."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * 1099511628211) & _U64
        v >>= 8
    return abs(h - (1 << 64) if h >= 1 << 63 else h)


def _user_keys(rng, kind: str, n_distinct: int) -> list[bytes]:
    """Distinct user keys of mixed length. `lengths`: 1 to 24 bytes over
    a small alphabet with zero bytes in it, and the prefix family of
    b"ab" (b"ab\\0" is b"ab"'s zero-padded form, b"ab\\0\\0" too); `ycsb`:
    "user" + the decimal fnvhash64 of a record number, 5-23 bytes."""
    if kind == "ycsb":
        nums = rng.choice(1 << 40, n_distinct, replace=False)
        keys = {b"user%d" % fnvhash64(int(x)) for x in nums}
        return sorted(keys | {b"user1", b"user10", b"user100"})
    alphabet = np.array([0, 1, 97, 98, 255], dtype=np.uint8)
    keys = {b"a", b"ab", b"ab\0", b"ab\0\0", b"abc", b"ab\0c", b"\0"}
    while len(keys) < n_distinct:
        ln = int(rng.integers(1, 25))
        keys.add(alphabet[rng.integers(0, 5, ln)].tobytes())
    return sorted(keys)


def _rows(rng, kind: str, n: int, complex_: bool):
    """n rows over ~n/3 user keys: (user keys, seq, vtype), seqs unique.
    With complex_, deletes, single deletes and merges beside the puts."""
    uks = _user_keys(rng, kind, max(8, n // 3))
    pick = rng.integers(0, len(uks), n)
    seqs = rng.permutation(np.arange(1, n + 1, dtype=np.uint64) * 3)
    vt = np.full(n, int(ValueType.VALUE), dtype=np.int64)
    u = rng.random(n)
    vt[u < 0.12] = int(ValueType.DELETION)
    if complex_:
        vt[(u >= 0.12) & (u < 0.17)] = int(ValueType.SINGLE_DELETION)
        vt[(u >= 0.17) & (u < 0.25)] = int(ValueType.MERGE)
    return [uks[i] for i in pick], seqs, vt


def _stripe_cover(rng, seqs, snaps):
    """A per-row covering-tombstone seqno as the planes hand it over:
    stripe-clamped (a newer tombstone of the row's own stripe), 0 = none."""
    s = np.asarray(sorted(snaps), dtype=np.uint64)
    stripe = np.searchsorted(s, seqs, side="left")
    top = np.where(stripe < len(s), s[np.minimum(stripe, len(s) - 1)],
                   seqs + np.uint64(50))
    cov = np.minimum(seqs + rng.integers(1, 9, len(seqs)).astype(np.uint64),
                     top)
    cov[(cov <= seqs) | (rng.random(len(seqs)) < 0.7)] = 0
    return cov.astype(np.uint64)


def _shards(uk, seqs, vt, runs: int, n_shards: int, rng):
    """Cut the rows into `runs` presorted runs (files) and the key space
    into n_shards ranges: for each shard its pieces, one a run, each the
    row indices of its rows in internal-key order."""
    run_of = rng.integers(0, runs, len(uk))
    distinct = sorted(set(uk))
    cuts = [distinct[len(distinct) * s // n_shards] for s in range(1, n_shards)]
    out = []
    for s in range(n_shards):
        lo = cuts[s - 1] if s else None
        hi = cuts[s] if s < n_shards - 1 else None
        pieces = []
        for r in range(runs):
            rows = [i for i in range(len(uk)) if run_of[i] == r
                    and (lo is None or uk[i] >= lo)
                    and (hi is None or uk[i] < hi)]
            rows.sort(key=lambda i: (uk[i], -int(seqs[i])))
            if rows:
                pieces.append(rows)
        out.append(pieces)
    return out


def _ikeys(uk, seqs, vt, rows):
    keys = [make_internal_key(uk[i], int(seqs[i]), int(vt[i])) for i in rows]
    lens = np.array([len(k) for k in keys], dtype=np.int64)
    return np.frombuffer(b"".join(keys), np.uint8), lens


@pytest.mark.parametrize("mvcc", [False, True], ids=["plain", "mvcc"])
@pytest.mark.parametrize("bottommost", [True, False], ids=["bottom", "mid"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("kind", ["lengths", "ycsb"])
def test_varlen_shard_program_matches_the_host_twin(kind, n_shards,
                                                    bottommost, mvcc):
    """Each shard through prepare_varlen_chunk -> upload_varlen_shard ->
    the program -> fused_uniform_shard_finish equals the host twin over the
    same rows: survivor order, zero-seq flags, complex flags, count. With
    mvcc: snapshots, single deletes and merges (complex groups) and range
    tombstones (the cover planes)."""
    rng = np.random.default_rng(
        [n_shards, bottommost, mvcc, kind == "ycsb"])
    n = 1500
    uk, seqs, vt = _rows(rng, kind, n, complex_=mvcc)
    snaps = sorted(int(x) for x in rng.choice(seqs, 6)) if mvcc else []
    cover = _stripe_cover(rng, seqs, snaps) if mvcc else None
    checked = 0
    for pieces in _shards(uk, seqs, vt, 3, n_shards, rng):
        bufs = [_ikeys(uk, seqs, vt, rows) for rows in pieces]
        lens_all = np.concatenate([ln for _, ln in bufs])
        width = ck.varlen_width(int(lens_all.max()) - 8)
        chunks = [ck.prepare_varlen_chunk(b, ln, width) for b, ln in bufs]
        rows = [i for rows in pieces for i in rows]
        covers = None if cover is None else [cover[r] for r in pieces]
        h = ck.upload_shard(chunks, covers)
        assert "klen" in h and h["uk_len"] == width
        got = ck.fused_uniform_shard_finish(
            ck.fused_uniform_shard_start(h, snaps, bottommost))
        kb = np.concatenate([b for b, _ in bufs])
        offs = np.cumsum(lens_all) - lens_all
        want = ck.fused_encode_sort_gc_host(
            kb, offs, lens_all, width, snaps, bottommost,
            None if cover is None else cover[rows])
        for a, b in zip(got[:3], want[:3]):
            assert np.array_equal(a, b)
        assert got[3] == want[3]
        checked += len(got[0])
    assert checked > 0


def test_zero_padding_and_the_length_give_bytewise_order():
    """A key and its zero-padded forms ("ab" < "ab\\0" < "ab\\0\\0" <
    "abc"), at one width: the order is the bytewise one, and no two of them
    fall into one group (each keeps its own newest row)."""
    uks = [b"abc", b"ab\0\0", b"ab", b"ab\0", b"a", b"ab\0c", b"ab"]
    seqs = np.array([10, 20, 30, 40, 50, 60, 70], dtype=np.uint64)
    keys = [make_internal_key(k, int(s), int(ValueType.VALUE))
            for k, s in zip(uks, seqs)]
    lens = np.array([len(k) for k in keys], dtype=np.int64)
    kb = np.frombuffer(b"".join(keys), np.uint8)
    h = ck.upload_shard([ck.prepare_varlen_chunk(kb, lens, 8)])
    order, zero, cx, hc = ck.fused_uniform_shard_finish(
        ck.fused_uniform_shard_start(h, [], False))
    assert [uks[i] for i in order] == [
        b"a", b"ab", b"ab\0", b"ab\0\0", b"ab\0c", b"abc"]
    assert order[1] == 6  # of the two b"ab", the newer
    assert not zero.any() and not cx.any() and not hc


def test_prepare_varlen_chunk_lays_out_keys_lengths_and_trailers():
    keys = [make_internal_key(k, s, int(ValueType.VALUE))
            for k, s in ((b"x", 5), (b"user123", 6), (b"", 7))]
    lens = np.array([len(k) for k in keys])
    c = ck.prepare_varlen_chunk(np.frombuffer(b"".join(keys), np.uint8),
                                lens, 8)
    assert c.n == 3 and c.width == 8
    assert c.ukb.reshape(3, 8).tobytes() == (
        b"x" + b"\0" * 7 + b"user123\0" + b"\0" * 8)
    assert c.klen.tolist() == [1, 7, 0] and c.klen.dtype == np.uint8
    packed = (c.tw[:, 1].astype(np.uint64) << np.uint64(32)) | c.tw[:, 0]
    assert (packed >> np.uint64(8)).tolist() == [5, 6, 7]
    with pytest.raises(Exception, match="do not fit"):
        ck.prepare_varlen_chunk(np.frombuffer(b"".join(keys), np.uint8),
                                lens, 4)


def test_width_bucket_steps_by_eight():
    assert [ck.varlen_width(n) for n in (0, 1, 8, 9, 16, 17, 23, 24, 25)] \
        == [8, 8, 8, 16, 16, 24, 24, 24, 32]


@pytest.mark.parametrize("width", [8, 24, 128])
def test_varlen_program_sorts_two_operands_at_a_time(width):
    """The TPU compiler's time for a sort grows faster than its operand
    count: the uniform program's one sort over 11 operands, at a 24 B
    width, took 293 s of a cold set-up on a v5e. Every sort in the
    mixed-length program, whatever the width, is of two operands."""
    import re

    p = 1024
    z = lambda n, dt: np.zeros(n, dt)
    text = ck._fused_varlen_shard_impl.lower(
        z(p * width, np.uint8), z(p, np.uint8), z(p, np.uint32),
        z(p, np.uint32), z(1, np.uint32), z(1, np.uint32), z(64, np.uint32),
        z(64, np.uint32), np.int32(p), width=width,
        bottommost=np.bool_(True), has_tombs=False).as_text()
    sorts = re.findall(r'"stablehlo\.sort"\(([^)]*)\)', text)
    assert sorts and all(len(s.split(",")) == 2 for s in sorts), sorts


# ---- whole jobs: run_pipelined, the serial branch, the CPU path ----------


def _write_runs(env, dbdir, rng, kind, n_rows, runs, topts, merges=False,
                tombstone=False):
    """`runs` input files of mixed-length keys (TableBuilder); values are
    8-byte counters, so uint64add operands fold. Returns FileMetaData."""
    import toplingdb_tpu.db.filename as fn
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.table.builder import TableBuilder

    uks = _user_keys(rng, kind, n_rows // 3)
    metas = []
    seq = 1
    for r in range(runs):
        pick = rng.integers(0, len(uks), n_rows // runs)
        rows = []
        for i in pick:
            u = rng.random()
            vt = ValueType.VALUE
            if u < 0.1:
                vt = ValueType.DELETION
            elif merges and u < 0.2:
                vt = ValueType.MERGE
            elif merges and u < 0.25:
                vt = ValueType.SINGLE_DELETION
            val = b"" if vt in (ValueType.DELETION,
                                ValueType.SINGLE_DELETION) \
                else int(rng.integers(0, 1 << 30)).to_bytes(8, "little")
            rows.append((uks[i], seq, vt, val))
            seq += 1
        rows.sort(key=lambda t: (t[0], -t[1]))
        fnum = 100 + r
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, ICMP, topts)
        for k, s, vt, val in rows:
            b.add(make_internal_key(k, s, vt), val)
        if tombstone and r == runs - 1:
            lo, hi = uks[len(uks) // 5], uks[len(uks) // 3]
            b.add_tombstone(make_internal_key(
                lo, seq + 5, ValueType.RANGE_DELETION), hi)
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum,
            file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno))
    return metas, seq


def _job(env, dbdir, metas, topts, snapshots, bottommost, merge_op, base,
         device=True):
    from toplingdb_tpu.compaction.compaction_job import (
        run_compaction_to_tables,
    )
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.ops.device_compaction import run_device_compaction

    alloc = iter(range(base, base + 10_000)).__next__
    tc = TableCache(env, dbdir, ICMP, topts)
    c = Compaction(level=0, output_level=2, inputs=list(metas),
                   bottommost=bottommost, max_output_file_size=1 << 62)
    if device:
        return run_device_compaction(
            env, dbdir, ICMP, c, tc, topts, snapshots,
            merge_operator=merge_op, new_file_number=alloc,
            creation_time=7, device_name="cpu-jax")
    return run_compaction_to_tables(
        env, dbdir, ICMP, c, tc, topts, snapshots, merge_operator=merge_op,
        new_file_number=alloc, creation_time=7)


def _bytes(dbdir, outs):
    import toplingdb_tpu.db.filename as fn

    return [open(fn.table_file_name(dbdir, m.number), "rb").read()
            for m in outs]


@pytest.mark.parametrize("route", ["pipelined", "serial"])
@pytest.mark.parametrize("bottommost", [True, False], ids=["bottom", "mid"])
@pytest.mark.parametrize("kind", ["lengths", "ycsb"])
def test_mixed_length_job_matches_the_cpu_path(tmp_path, monkeypatch, kind,
                                               bottommost, route):
    """A job of mixed-length keys with snapshots, deletes, single deletes,
    uint64add merges and a range tombstone: through run_pipelined (four
    shards) and through the serial branch, its output files are byte for
    byte the CPU path's, and every shard took the mixed-length program."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableOptions
    from toplingdb_tpu.utils.merge_operator import create_merge_operator

    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 4)
    monkeypatch.setattr(pl, "pipeline_enabled", _PIPELINE_ENABLED
                        if route == "pipelined" else (lambda *_a: False))
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    rng = np.random.default_rng([bottommost, kind == "ycsb"])
    metas, last = _write_runs(env, dbdir, rng, kind, 4000, 3, topts,
                              merges=True, tombstone=True)
    snaps = [last // 3, last // 2]
    op = create_merge_operator("uint64add")
    out_dev, st = _job(env, dbdir, metas, topts, snaps, bottommost, op, 500)
    out_cpu, _ = _job(env, dbdir, metas, topts, snaps, bottommost, op, 900,
                      device=False)
    assert st.pipelined == (route == "pipelined") and (
        st.pipeline_exit == "")
    assert st.varlen_shards == 4 and st.varlen_shard_rows == st.input_records
    assert st.varlen_pad_bytes > 0 and st.merge_operand_rows > 0
    assert _bytes(dbdir, out_dev) == _bytes(dbdir, out_cpu)


def test_uniform_job_keeps_the_uniform_program(tmp_path, monkeypatch):
    """A job of one key length meets `_fused_uniform_shard_impl` with the
    shapes and static arguments it always had (the row bucket's and the
    key length's), so the compile-cache entries of a deployment of one key
    length are untouched; the mixed-length program is never traced."""
    from test_compaction_pipeline import _build_runs, _run_job

    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableOptions

    calls = []
    uniform = ck._fused_uniform_shard_impl

    def spy(ukb, packed_hi, packed_lo, tomb_hi, tomb_lo, snap_hi, snap_lo,
            total, num_key_words, uk_len, bottommost, has_tombs):
        calls.append((ukb.shape, packed_hi.shape, packed_lo.shape,
                      tomb_hi.shape, snap_hi.shape, num_key_words, uk_len,
                      has_tombs))
        return uniform(ukb, packed_hi, packed_lo, tomb_hi, tomb_lo, snap_hi,
                       snap_lo, total, num_key_words, uk_len, bottommost,
                       has_tombs)

    def never(*a, **k):
        raise AssertionError("a uniform shard met the mixed-length program")

    monkeypatch.setattr(ck, "_fused_uniform_shard_impl", spy)
    monkeypatch.setattr(ck, "_fused_varlen_shard_impl", never)
    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 4)
    env = default_env()
    topts = TableOptions(block_size=512)
    metas = _build_runs(env, str(tmp_path), 8000, topts, seed=5)
    _out, st = _run_job(env, str(tmp_path), metas, topts, topts, 2000, [])
    assert st.pipelined and st.varlen_shards == 0
    assert len(calls) == 4
    for shape in calls:  # 8-byte user keys, 2,000 rows a shard -> 2048
        assert shape == ((2048 * 8,), (2048,), (2048,), (1,), (64,), 2, 8,
                         False)


def test_service_sums_the_varlen_counters(tmp_path, monkeypatch):
    """The four counters reach the worker's reply and the service's
    /stats sums."""
    import json
    import urllib.request

    from test_job_trace import make_job, results_of

    from toplingdb_tpu.compaction.dcompact_service import (
        JOB_SUM_COUNTERS,
        DcompactWorkerService,
    )

    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 2)
    names = ("varlen_shards", "varlen_shard_rows", "varlen_key_bytes",
             "varlen_pad_bytes")
    assert set(names) <= set(JOB_SUM_COUNTERS)
    svc = DcompactWorkerService(device="cpu-jax")
    url = f"http://127.0.0.1:{svc.start()}"
    try:
        job_dir = make_job(tmp_path, last_run_key_len=13)
        req = urllib.request.Request(
            url + "/dcompact", data=json.dumps({"job_dir": job_dir}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=300) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(url + "/stats", timeout=30) as r:
            sums = json.loads(r.read())
    finally:
        svc.stop()
    st = results_of(job_dir)["stats"]
    assert st["varlen_shards"] == 2 and st["varlen_shard_rows"] == 9000
    # 6,000 rows of 8 B and 3,000 of 13 B user keys, at a width of 16.
    assert st["varlen_key_bytes"] == 6000 * 8 + 3000 * 13
    assert st["varlen_pad_bytes"] == 9000 * 16 - st["varlen_key_bytes"]
    assert {k: sums[k] for k in names} == {k: st[k] for k in names}


# ---- LZ4 blocks on the plane ----------------------------------------------


def _lz4_or_skip():
    from toplingdb_tpu.utils import codecs

    if not codecs.available("lz4"):
        pytest.skip("liblz4 unavailable")


def test_lz4_section_builder_matches_table_builder(tmp_path, monkeypatch):
    """The native compressed-section builder writes LZ4 blocks byte for
    byte as TableBuilder's per-block path does (varint32 raw length, then
    the LZ4 block), and the per-block path is not taken."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import columnar_io
    from toplingdb_tpu.ops.columnar_io import ColumnarKV
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions

    _lz4_or_skip()
    env = default_env()
    rng = np.random.default_rng(3)
    entries = sorted(
        (make_internal_key(b"user%d" % fnvhash64(i), i + 1,
                           ValueType.VALUE),
         b"field0" * 3 + rng.integers(97, 105, 40, dtype=np.uint8).tobytes())
        for i in range(3000))
    topts = TableOptions(block_size=1024, compression=fmt.LZ4_COMPRESSION)
    ref = str(tmp_path / "ref.sst")
    w = env.new_writable_file(ref)
    b = TableBuilder(w, ICMP, topts, creation_time=3,
                     column_family_name="default")
    for k, v in entries:
        b.add(k, v)
    b.finish()
    w.close()
    ks = [k for k, _ in entries]
    vs = [v for _, v in entries]
    kl = np.array([len(k) for k in ks], np.int32)
    vl = np.array([len(v) for v in vs], np.int32)
    kv = ColumnarKV(np.frombuffer(b"".join(ks), np.uint8),
                    (np.cumsum(kl) - kl).astype(np.int32), kl,
                    np.frombuffer(b"".join(vs), np.uint8),
                    (np.cumsum(vl) - vl).astype(np.int32), vl)

    compressed = []
    compress = fmt.compress

    def counting(raw, ctype, *a, **k):
        compressed.append(ctype)
        return compress(raw, ctype, *a, **k)

    monkeypatch.setattr(fmt, "compress", counting)
    n = len(entries)
    files = columnar_io.write_tables_columnar(
        env, str(tmp_path), iter(range(700, 800)).__next__, ICMP, topts, kv,
        np.arange(n, dtype=np.int32), np.full(n, -1, np.int64),
        np.full(n, int(ValueType.VALUE), np.int32),
        np.arange(1, n + 1, dtype=np.uint64), [], creation_time=3)
    assert open(files[0][1], "rb").read() == open(ref, "rb").read()
    # Python compressed the index block alone; the ~100 data blocks went
    # through the native section.
    assert compressed == [fmt.LZ4_COMPRESSION]


def test_lz4_inputs_stay_on_the_pipeline(tmp_path, monkeypatch):
    """A job whose inputs and outputs are LZ4 block files runs pipelined
    (the native scan inflates LZ4) and writes the CPU path's bytes."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableOptions

    _lz4_or_skip()
    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 2)
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=1024, compression=fmt.LZ4_COMPRESSION)
    rng = np.random.default_rng(9)
    metas, _ = _write_runs(env, dbdir, rng, "ycsb", 3000, 3, topts)
    out_dev, st = _job(env, dbdir, metas, topts, [], True, None, 500)
    out_cpu, _ = _job(env, dbdir, metas, topts, [], True, None, 900,
                      device=False)
    assert st.pipelined and st.pipeline_exit == "" and st.varlen_shards == 2
    assert _bytes(dbdir, out_dev) == _bytes(dbdir, out_cpu)


def test_lz4_levels_read_back_through_the_db(tmp_path):
    """compression_per_level none, none, LZ4 (the YCSB binding's shape):
    every key reads back through DB.get and an iterator from LZ4 files."""
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableOptions

    _lz4_or_skip()
    opts = Options(create_if_missing=True, write_buffer_size=64 << 10,
                   compression_per_level=[fmt.NO_COMPRESSION,
                                          fmt.NO_COMPRESSION,
                                          fmt.LZ4_COMPRESSION],
                   table_options=TableOptions(filter_policy=None))
    path = os.path.join(str(tmp_path), "db")
    db = DB.open(path, opts)
    want = {}
    try:
        for i in range(3000):
            k = b"user%d" % fnvhash64(i % 1000)
            want[k] = b"v%05d" % i * 20
            db.put(k, want[k])
        db.compact_range(None, None)
        assert int(db.get_property("tpulsm.num-files-at-level2") or 0) + int(
            db.get_property("tpulsm.num-files-at-level6") or 0) > 0
        assert all(db.get(k) == v for k, v in want.items())
    finally:
        db.close()
    db = DB.open(path, opts)
    try:
        it = db.new_iterator()
        it.seek_to_first()
        got = {}
        while it.valid():
            got[it.key()] = it.value()
            it.next()
        assert got == want
        assert all(db.get(k) == v for k, v in want.items())
    finally:
        db.close()
