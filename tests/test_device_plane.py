"""The device data plane's routes: the one shard rule
(ops/compaction_kernels.py::shard_count) at its edges, the two branches
that ask it, the `pipeline_exit` boundary the benchmark reckons with, and
the environment names that no longer choose a route."""

import os
import re

import pytest

from test_compaction_pipeline import (
    _build_runs,
    _run_job,
    _sst_bytes,
)
from toplingdb_tpu.ops import compaction_kernels as ck
from toplingdb_tpu.ops import pipeline as pl
from toplingdb_tpu.utils.status import NotSupported

_ROOM = ck.ROW_BUCKET - ck.ROW_BUCKET // 50  # 0.98 x ROW_BUCKET


@pytest.mark.parametrize("rows,shards", [
    (0, 1),
    (1 << 17, 1),
    (ck.ROW_BUCKET, 1),
    (ck.ROW_BUCKET + 1, 2),
    (2 * _ROOM + 1, 2),   # 0.98 x 2^20, rounded up: still two
    (2 * _ROOM + 2, 4),   # one row more a shard: the count doubles
    (1_500_000, 4),       # the parent's serial branch: 2, padded to 2^20
    (1 << 24, 32),
    ((1 << 24) + 1, 32),  # the cap
])
def test_shard_rule_at_its_edges(rows, shards):
    assert ck.shard_count(rows) == shards
    if rows <= 1 << 24:
        # An even cut of any job up to 2^24 rows stays in the one bucket.
        assert -(-rows // shards) <= ck.ROW_BUCKET


def test_even_shards_stay_in_the_row_bucket():
    """Over the whole range, not only at the edges: the largest shard of
    an even cut never passes ROW_BUCKET, and from two shards on it leaves
    the room the uneven cuts need until the cap of 32 is reached."""
    for rows in range(1, (1 << 24) + 1, 4099):
        s = ck.shard_count(rows)
        assert s in (1, 2, 4, 8, 16, 32)
        assert -(-rows // s) <= ck.ROW_BUCKET
        if 1 < s < 32:
            assert rows // s <= _ROOM


def test_a_job_that_leaves_the_pipeline_keeps_its_shard_count(
        tmp_path, monkeypatch):
    """A job of several shards that leaves the pipeline (here a
    NotSupported from the plan, after the plan has cut it) is cut by the
    serial branch through the same function into the same count, so its
    shards pad to the bucket whose program the deployment has compiled;
    the bytes are the CPU path's."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import mesh_compaction as mc
    from toplingdb_tpu.table.builder import TableOptions

    asked = []

    def four(total_rows):
        asked.append(total_rows)
        return 4

    monkeypatch.setattr(ck, "shard_count", four)
    build_plan = pl._build_plan

    def plan_then_refuse(*a, **k):
        build_plan(*a, **k)
        raise NotSupported("inputs the pipeline does not take")

    monkeypatch.setattr(pl, "_build_plan", plan_then_refuse)
    dispatched = []
    dispatch = mc.dispatch_shards

    def spy(shards, *a, **k):
        dispatched.append(len(shards))
        return dispatch(shards, *a, **k)

    monkeypatch.setattr(mc, "dispatch_shards", spy)

    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    metas = _build_runs(env, dbdir, 8_000, topts, seed=9)
    out_dev, st = _run_job(env, dbdir, metas, topts, topts, 1000, [3000])
    assert st.pipeline_exit.startswith("NotSupported: inputs the pipeline")
    assert not st.pipelined
    assert asked == [8_000, 8_000]  # the plan, then the serial branch
    assert dispatched == [4]
    out_cpu, _ = _run_job(env, dbdir, metas, topts, topts, 2000, [3000],
                          device=False)
    assert _sst_bytes(env, dbdir, out_dev) == _sst_bytes(env, dbdir, out_cpu)


@pytest.mark.parametrize("over", [0, 1])
def test_pipeline_exit_on_both_sides_of_the_rule(tmp_path, monkeypatch,
                                                 over):
    """A job of up to ROW_BUCKET input rows is one shard: it leaves the
    pipeline, says so in `pipeline_exit` (the service counts it in
    `jobs_left_pipeline`) and takes the serial branch; one row more is two
    shards and runs pipelined with `pipeline_exit` empty
    (benchmark/lib/dbside.py::PIPELINE_FLOOR_ROWS is ROW_BUCKET + 1). The
    rule is the real one, over a bucket of 2048 rows."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableOptions

    monkeypatch.setattr(ck, "ROW_BUCKET", 2048)
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    rows, runs = (2049, 3) if over else (2048, 4)
    metas = _build_runs(env, dbdir, rows, topts, seed=4, runs=runs)
    out_dev, st = _run_job(env, dbdir, metas, topts, topts, 1000, [])
    assert st.input_records == rows
    if over:
        assert st.pipelined and st.pipeline_exit == ""
    else:
        assert not st.pipelined
        assert st.pipeline_exit == "PipelineIneligible: single-shard job"
    out_cpu, _ = _run_job(env, dbdir, metas, topts, topts, 2000, [],
                          device=False)
    assert _sst_bytes(env, dbdir, out_dev) == _sst_bytes(env, dbdir, out_cpu)


def _mixed_length_keys(n):
    """n dense internal keys of two lengths: what still reaches the
    whole-job program (fused_encode_sort_gc) since no job leaves the shard
    program for its sequence numbers."""
    import numpy as np

    from toplingdb_tpu.db.dbformat import ValueType, make_internal_key

    keys = [make_internal_key(b"k%0*d" % (7 + 4 * (i % 2), i % 97), i + 1,
                              ValueType.VALUE) for i in range(n)]
    lens = np.array([len(k) for k in keys], dtype=np.int64)
    return (np.frombuffer(b"".join(keys), np.uint8),
            np.cumsum(lens) - lens, lens)


@pytest.mark.parametrize("over", [0, 1])
def test_whole_job_program_stops_at_the_row_bucket(monkeypatch, over):
    """The whole-job program pads to the job's own power of two, so it
    takes a job of up to ROW_BUCKET padded rows and refuses a larger one
    before anything is traced or compiled (its caller then runs the job
    per entry). Over a bucket of 256 rows."""
    import numpy as np

    monkeypatch.setattr(ck, "ROW_BUCKET", 256)
    kb, ko, kl = _mixed_length_keys(256 + over)
    before = ck._fused_encode_sort_gc_impl._cache_size()
    if over:
        with pytest.raises(NotSupported, match="at most 256 rows, got 257"):
            ck.fused_encode_sort_gc(kb, ko, kl, 12, [100], True)
        assert ck._fused_encode_sort_gc_impl._cache_size() == before
    else:
        got = ck.fused_encode_sort_gc(kb, ko, kl, 12, [100], True)
        want = ck.fused_encode_sort_gc_host(kb, ko, kl, 12, [100], True)
        for a, b in zip(got[:3], want[:3]):
            assert np.array_equal(a, b)
        assert len(got[0]) > 0


def test_job_of_two_key_lengths_over_the_bucket_runs_per_entry(
        tmp_path, monkeypatch):
    """What the refusal leads to: a job of mixed key lengths (no shard
    program) and more padded rows than the bucket (no whole-job program)
    is run per entry by the same worker, with the CPU worker's rows."""
    from test_job_trace import make_job, output_rows, results_of
    from toplingdb_tpu.compaction import worker
    from toplingdb_tpu.ops import device_compaction as dc

    monkeypatch.setattr(ck, "ROW_BUCKET", 4096)
    per_entry = []
    gc_entries = dc.device_gc_entries

    def spy(entries, *a, **k):
        per_entry.append(len(entries))
        return gc_entries(entries, *a, **k)

    monkeypatch.setattr(dc, "device_gc_entries", spy)
    job_dir = make_job(tmp_path, last_run_key_len=12)
    assert worker.run_job(job_dir) == 0
    st = results_of(job_dir)["stats"]
    assert per_entry == [9000] and not st["pipelined"]
    cpu_dir = make_job(tmp_path, name="cpu", last_run_key_len=12,
                       device="cpu")
    assert worker.run_job(cpu_dir) == 0
    assert output_rows(job_dir) == output_rows(cpu_dir)


# The variables that chose a route or a kernel on the compaction path until
# PR 30. The serial branch and the host twin stay reachable for tests by
# replacing pipeline.pipeline_enabled and ck.shard_count, not by a name a
# user can set.
_DELETED_NAMES = (
    "TPULSM_PIPELINE", "TPULSM_PIPELINE_SHARDS", "TPULSM_DEVICE_SHARDS",
    "TPULSM_SHARD_ROWS", "TPULSM_HOST_MERGE", "TPULSM_PALLAS_GC",
    "TPULSM_DEVICE_BLOCKS",
)


def test_no_deleted_environment_name_is_read():
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "toplingdb_tpu")
    pat = re.compile(r"\b(" + "|".join(_DELETED_NAMES) + r")\b")
    hits = []
    for d, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith((".py", ".cc", ".h")):
                p = os.path.join(d, f)
                with open(p, encoding="utf-8", errors="replace") as fh:
                    for i, line in enumerate(fh, 1):
                        if pat.search(line):
                            hits.append(f"{p}:{i}: {line.strip()}")
    assert not hits, "\n".join(hits)
