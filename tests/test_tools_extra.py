"""Tool coverage: extra db_bench workloads, and the
SstFileWriter fuzz (reference fuzz/sst_file_writer_fuzzer.cc: random KVs →
writer → reader must round-trip and survive truncation checks)."""

import random

import pytest


def test_db_bench_extra_workloads(tmp_path):
    from toplingdb_tpu.tools import db_bench

    rc = db_bench.main([
        f"--db={tmp_path}/b",
        "--benchmarks=fillseq,seekrandom,mergerandom,fillrandombatch,stats",
        "--num=2000",
    ])
    assert rc == 0


@pytest.mark.parametrize("seed", [3, 9])
def test_sst_file_writer_fuzz(tmp_path, seed):
    from toplingdb_tpu.utilities.sst_file_writer import (
        SstFileReader, SstFileWriter,
    )
    from toplingdb_tpu.utils.status import Corruption

    rng = random.Random(seed)
    keys = sorted({bytes(rng.randrange(32, 127) for _ in
                         range(rng.randrange(1, 40)))
                   for _ in range(rng.randrange(10, 400))})
    vals = {k: bytes(rng.randrange(256) for _ in range(rng.randrange(0, 200)))
            for k in keys}
    path = str(tmp_path / f"f{seed}.sst")
    w = SstFileWriter()
    w.open(path)
    for k in keys:
        w.put(k, vals[k])
    w.finish()
    r = SstFileReader(path)
    assert r.properties.num_entries == len(keys)
    got = {}
    from toplingdb_tpu.db import dbformat
    from toplingdb_tpu.db.dbformat import InternalKeyComparator
    from toplingdb_tpu.env import PosixEnv
    from toplingdb_tpu.table.factory import open_table

    tr = open_table(PosixEnv().new_random_access_file(path),
                    InternalKeyComparator())
    it = tr.new_iterator()
    it.seek_to_first()
    for ik, v in it.entries():
        got[dbformat.extract_user_key(ik)] = v
    assert got == vals
    # Corrupt a byte mid-file: reads must fail loudly, not return garbage.
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0x5A
    open(path, "wb").write(bytes(data))
    with pytest.raises(Corruption):
        tr2 = open_table(PosixEnv().new_random_access_file(path),
                         InternalKeyComparator())
        it2 = tr2.new_iterator()
        it2.seek_to_first()
        for _ in it2.entries():
            pass


def test_db_bench_full_workload_matrix(tmp_path, capsys):
    """Every dispatchable workload runs green (the reference's ~40-name
    dispatch table, tools/db_bench_tool.cc:3784-3893)."""
    import re

    from toplingdb_tpu.tools import db_bench

    names = ("fillseq,readseq,readreverse,readrandom,readmissing,readhot,"
             "seekrandom,fillrandom,overwrite,updaterandom,appendrandom,"
             "readrandomwriterandom,mergerandom,readwhilemerging,"
             "readwhilewriting,seekrandomwhilewriting,multireadrandom,"
             "fillsync,fill100K,fillseekseq,deleterandom,deleteseq,flush,"
             "compact,compactall,waitforcompaction,verifychecksum,crc32c,"
             "xxhash,stats,levelstats,sstables,memstats,randomtransaction")
    rc = db_bench.main([
        "--num=400", f"--db={tmp_path / 'bench'}",
        f"--benchmarks={names}",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    for name in names.split(","):
        assert re.search(rf"^{name} ", out, re.M), \
            f"workload {name} produced no report line"
    assert "unknown benchmark" not in out


def test_db_start_trace_records_everything(tmp_path):
    """DB::StartTrace role: every Get/Write/MultiGet/Iterator-seek issued
    through the DB is captured (not just calls routed through the wrapper
    Tracer), and the Replayer reproduces the workload's end state."""
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils.trace import Replayer, read_trace

    src = str(tmp_path / "src")
    trace = str(tmp_path / "ops.trace")
    with DB.open(src, Options(create_if_missing=True)) as db:
        db.start_trace(trace)
        for i in range(200):
            db.put(b"k%04d" % i, b"v%d" % i)
        db.delete(b"k0007")
        db.get(b"k0005")
        db.multi_get([b"k0001", b"k0002"])
        it = db.new_iterator()
        it.seek(b"k0100")
        assert it.valid() and it.key() == b"k0100"
        db.end_trace()
        # post-end ops must NOT be recorded
        db.put(b"untraced", b"x")

    from toplingdb_tpu.env import default_env

    ops = list(read_trace(default_env(), trace))
    kinds = [op for op, _, _ in ops]
    from toplingdb_tpu.utils import trace as T

    assert kinds.count(T.OP_WRITE_BATCH) == 201  # 200 puts + 1 delete
    assert T.OP_GET in kinds and T.OP_MULTIGET in kinds
    assert T.OP_ITER_SEEK in kinds
    assert not any(s and s[0] == b"untraced" for _, _, s in ops)

    dst = str(tmp_path / "dst")
    with DB.open(dst, Options(create_if_missing=True)) as db2:
        n = Replayer(db2, trace).replay()
        assert n == len(ops)
        assert db2.get(b"k0005") == b"v5"
        assert db2.get(b"k0007") is None
        assert db2.get(b"untraced") is None


def test_trace_sampling_and_size_cap(tmp_path):
    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils.trace import TraceOptions, read_trace

    trace = str(tmp_path / "s.trace")
    with DB.open(str(tmp_path / "db"),
                 Options(create_if_missing=True)) as db:
        db.start_trace(trace, TraceOptions(sampling_frequency=10))
        for i in range(500):
            db.get(b"k%d" % i)
        db.end_trace()
    ops = list(read_trace(default_env(), trace))
    assert len(ops) == 50  # exactly 1-in-10

    cap = str(tmp_path / "cap.trace")
    with DB.open(str(tmp_path / "db2"),
                 Options(create_if_missing=True)) as db:
        db.start_trace(cap, TraceOptions(max_trace_file_size=2000))
        for i in range(5000):
            db.get(b"key%06d" % i)
        assert db._op_tracer.stopped
        db.end_trace()
    sz = len(open(cap, "rb").read())
    assert sz <= 4096  # stopped near the cap, not 5000 records


def test_replay_timing_faithful_speedup(tmp_path):
    import time as _time

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils.trace import Replayer

    trace = str(tmp_path / "t.trace")
    with DB.open(str(tmp_path / "db"),
                 Options(create_if_missing=True)) as db:
        db.start_trace(trace)
        db.put(b"a", b"1")
        _time.sleep(0.3)
        db.put(b"b", b"2")
        db.end_trace()
    with DB.open(str(tmp_path / "dst"),
                 Options(create_if_missing=True)) as db2:
        t0 = _time.time()
        Replayer(db2, trace).replay(fast_forward=False, speedup=10.0)
        dt = _time.time() - t0
        assert dt < 0.25, dt  # 0.3s gap compressed ~10x
        t0 = _time.time()
        Replayer(db2, trace).replay(fast_forward=False, speedup=1.0)
        assert _time.time() - t0 >= 0.25  # faithful replay keeps the gap


def test_ldb_backup_restore_idump_compact(tmp_path):
    """ldb gains compact / idump / backup / offline restore (reference
    ldb command surfaces)."""
    import subprocess
    import sys

    base = str(tmp_path)
    d = base + "/db"

    def run(*a):
        return subprocess.run(
            [sys.executable, "-m", "toplingdb_tpu.tools.ldb", *a],
            capture_output=True, text=True, timeout=120)

    assert run("--db", d, "put", "alpha", "one").returncode == 0
    assert run("--db", d, "put", "beta", "two").returncode == 0
    assert "compaction done" in run("--db", d, "compact").stdout
    out = run("--db", d, "idump", "--limit", "10").stdout
    assert "alpha" in out and "VALUE" in out
    assert "backup 1 created" in run("--db", d, "backup",
                                     base + "/bk").stdout
    assert run("--db", base + "/restored", "restore", base + "/bk",
               "1").returncode == 0
    assert run("--db", base + "/restored",
               "get", "alpha").stdout.strip() == "one"
