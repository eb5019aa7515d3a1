"""The YCSB deployment's cell (CPU; `python -m pytest benchmark/tests -q`):
a rehearsal of `ycsb-a-1kb.zipf` ends with every `compared` field present
and at its limit but the one a tiny scale cannot meet (a compared job of
two shards), and with its per-layer metrics under `--trace 1`; the control
(a service that loses one survivor in twenty) comes out wrong in the
window's reads and in the compared job; YCSB's generators against their
definitions; the oracle against a stale read; the plain reader of
mixed-length block tables against the program's writer."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from lib import reference_ycsb as ry  # noqa: E402

CELL = "ycsb-a-1kb.zipf"
COMPARED = ("read_mismatches_in_window", "read_mismatches",
            "reopen_read_mismatches", "rows_wrong", "records_misreported",
            "compared_job_short_of_two_varlen_shards", "fallback_local",
            "remote_job_failures", "jobs_off_device", "jobs_left_pipeline",
            "window_without_remote_job", "window_without_varlen_shards",
            "stream_ran_out", "harness_imported_jax")
NEW_METRICS = ("db.get_us_per_op", "db.get_p99_ms", "varlen.shard_row_share",
               "varlen.pad_share", "compaction.survivor_row_share")
SHARED_METRICS = ("db.write_us_per_op", "db.write_batch_p50_ms",
                  "db.write_batch_p95_ms", "db.write_batch_p99_ms",
                  "db.stall_share", "lsm.write_amp", "compactor.busy_share",
                  "compile.in_window.serve", "client.put_loop_share",
                  "client.build_us_per_op", "db.flush_busy_share")


def run_cell(*extra, seconds="2", seed="2147483659", scale="0.02"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", seed, "--seconds", seconds, "--rehearse-cpu", scale]
    p = subprocess.run(cmd + list(extra), cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines else None)


def test_rehearsal_ends_with_every_compared_field_present():
    p, line = run_cell("--trace", "0")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["correct"] is False            # a rehearsal never is
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == set(COMPARED)
    for name in COMPARED:
        # At a fiftieth of the size no job passes one shard's rows.
        want = 1 if name == "compared_job_short_of_two_varlen_shards" else 0
        assert line["compared"][name] == [want, 0], (name, line["compared"])
    assert set(line["metrics"]) == {"write_ops_s", "setup_s"}
    assert line["metrics"]["write_ops_s"]["value"] > 0
    assert "one file short of its trigger" in p.stderr


def test_traced_rehearsal_reports_the_cells_metrics():
    p, line = run_cell("--trace", "1")
    assert p.returncode == 4, p.stderr[-2000:]
    # kernel.varlen_roofline reads the device: a rehearsal gives nothing.
    assert set(line["metrics"]) == set(NEW_METRICS + SHARED_METRICS)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["varlen.shard_row_share"] == 100.0
    assert 0 < m["varlen.pad_share"] < 50
    assert 0 < m["compaction.survivor_row_share"] <= 100
    assert m["db.get_us_per_op"] > 0 and m["db.get_p99_ms"] > 0
    # The client's own time a put, the gets left out: well under a get's.
    assert 0 < m["client.build_us_per_op"] < 2 * m["db.get_us_per_op"]
    assert 0 <= m["db.flush_busy_share"] <= 100
    spans = dict(line["breakdown"]["idle_gaps"])
    assert "pipeline.varlen_pack" in spans or any(
        "varlen" in k for k in spans)


def test_control_loses_rows_and_the_checks_see_it():
    p, line = run_cell("--trace", "0", "--launcher", "faulty_service.py",
                       "--launcher-arg=--fault", "--launcher-arg=drop-5pct",
                       seconds="3")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["compared"]["rows_wrong"][0] > 0
    assert line["compared"]["read_mismatches"][0] > 0
    assert line["compared"]["read_mismatches_in_window"][0] > 0


# ---- the generators ---------------------------------------------------


def _java_fnvhash64(val: int) -> int:
    """Utils.fnvhash64 as Java runs it: signed 64-bit arithmetic."""
    def s64(x):
        x &= (1 << 64) - 1
        return x - (1 << 64) if x >= 1 << 63 else x

    h = s64(0xCBF29CE484222325)
    for _ in range(8):
        octet = val & 0xFF
        val >>= 8
        h = s64(h ^ octet)
        h = s64(h * 1099511628211)
    return abs(h)


def test_fnvhash64_is_javas():
    xs = [0, 1, 2, 255, 256, 1 << 32, 999_999, 10 ** 10 - 1]
    assert [ry.fnvhash64(x) for x in xs] == [_java_fnvhash64(x) for x in xs]
    got = ry.fnvhash64(np.array(xs, dtype=np.uint64)).tolist()
    assert got == [_java_fnvhash64(x) for x in xs]


def test_key_set_is_recordcount_distinct_keys_of_5_to_23_bytes():
    n = 200_000
    keys = ry.user_keys(n)
    assert len(set(keys)) == n
    lens = np.array([len(k) for k in keys])
    assert lens.min() >= 5 and lens.max() <= 23
    assert all(k.startswith(b"user") and k[4:].isdigit() for k in keys[:1000])


def test_zipfian_hottest_record_share_at_the_seed():
    n, m = 1_000_000, 2_000_000
    rec = ry.Stream(n, m, seed=2147483659).record
    assert rec.min() >= 0 and rec.max() < n
    share = np.bincount(rec, minlength=n).max() / m
    lo, hi = ry.hottest_share_bounds(m, n)
    assert lo <= share <= hi, (lo, share, hi)
    # Item 0 is the hottest: the record fnvhash64(0) % n.
    assert np.bincount(rec).argmax() == ry.fnvhash64(0) % n


def test_stream_is_the_seeds():
    a, b = ry.Stream(1000, 5000, 7), ry.Stream(1000, 5000, 7)
    c = ry.Stream(1000, 5000, 8)
    assert (a.record == b.record).all() and (a.kind == b.kind).all()
    assert not (a.record == c.record).all()
    assert 0.45 < a.kind.mean() < 0.55
    assert a.update_values(0, 5000) == b.update_values(0, 5000)


# ---- records and the oracle -------------------------------------------


def test_record_layout_is_the_bindings():
    rec = ry.record_bytes(np.arange(1, 11)[None, :], ry.filler_table(1))[0]
    assert len(rec) == ry.RECORD_BYTES == 1140
    assert rec[:14].tobytes() == b"\x00\x00\x00\x06field0\x00\x00\x00\x64"
    assert ry.stamps_of(rec[None, :]).tolist() == [list(range(1, 11))]


def _replay(stream, n_ops, drop=None):
    """Records after n_ops operations, write by write, in plain Python;
    `drop` is an update that is lost."""
    st = np.repeat(np.arange(1, stream.n + 1)[:, None], ry.FIELDS, 1)
    for j in range(n_ops):
        if stream.kind[j] == ry.UPDATE and j != drop:
            st[stream.record[j], stream.field[j]] = stream.stamp(j)
    return st


def test_oracle_agrees_with_a_write_by_write_replay():
    s = ry.Stream(300, 4000, 11)
    o = ry.Oracle(s, 4000)
    recs = np.arange(300)
    assert (o.final(recs) == _replay(s, 4000)).all()
    at = np.array([0, 17, 1999, 3999])
    for op in at:
        want = _replay(s, int(op))[s.record[at]]
        got = o.stamps_before(s.record[at], np.full(len(at), op))
        assert (got == want).all()


def test_oracle_catches_a_stale_read():
    s = ry.Stream(300, 4000, 13)
    lost = int(np.flatnonzero(s.kind == ry.UPDATE)[-1])
    stale = ry.record_bytes(_replay(s, 4000, drop=lost), s.fill)
    exact = ry.record_bytes(_replay(s, 4000), s.fill)
    o = ry.Oracle(s, 4000)
    recs = np.arange(300)
    want = list(o.expected(recs))
    assert ry.mismatches([r.tobytes() for r in exact], want) == 0
    assert ry.mismatches([r.tobytes() for r in stale], want) >= 1
    answers = [r.tobytes() for r in exact]
    answers[5] = None
    assert ry.mismatches(answers, want) == 1


# ---- the plain reader -------------------------------------------------


@pytest.mark.parametrize("codec", ["none", "lz4"])
def test_plain_reader_reads_what_the_program_wrote(tmp_path, codec):
    from toplingdb_tpu.db.dbformat import (
        InternalKeyComparator,
        ValueType,
        make_internal_key,
    )
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions

    comp = {"none": fmt.NO_COMPRESSION, "lz4": fmt.LZ4_COMPRESSION}[codec]
    keys = sorted(ry.user_keys(3000))
    recs = ry.record_bytes(
        np.repeat(np.arange(1, 3001)[:, None], ry.FIELDS, 1),
        ry.filler_table(3))
    path = str(tmp_path / "t.sst")
    w = default_env().new_writable_file(path)
    b = TableBuilder(w, InternalKeyComparator(), TableOptions(
        block_size=4096, compression=comp, filter_policy=None))
    for i, k in enumerate(keys):
        b.add(make_internal_key(k, i + 1, ValueType.VALUE), recs[i].tobytes())
    b.finish()
    w.close()
    ik, kl, vals = ry.read_rows(path)
    uk, seq, vt = ry.split_internal(ik, kl)
    assert uk.tolist() == keys
    assert seq.tolist() == list(range(1, 3001))
    assert (vt == int(ValueType.VALUE)).all()
    assert (vals == recs).all()
