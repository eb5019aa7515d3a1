"""The merge deployment's cell (CPU; `python -m pytest benchmark/tests -q`):
a rehearsal of `dbbench-c3-universal-merge.mergerandom` ends with every
`compared` at its limit, and with every new per-layer metric a number under
`--trace 1`; the control (a fold that drops one operand of every chain)
comes out wrong in the reads and in the compared job; the oracle against a
write-by-write replay; the plain SST side of the reference against the
program's own reader."""

from __future__ import annotations

import ast
import json
import os
import re
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from lib import reference_merge as ref  # noqa: E402
from lib.workload_merge import MergeWorkload, key_bytes  # noqa: E402
from test_benchmark import SERVED_METRICS as SHARED_METRICS  # noqa: E402

CELL = "dbbench-c3-universal-merge.mergerandom"
NEW_METRICS = ("merge.operand_row_share", "merge.fold_share",
               "rangedel.cover_share")


def run_cell(*extra, seconds="2", seed="2147483659", scale="0.02"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", seed, "--seconds", seconds, "--rehearse-cpu", scale]
    p = subprocess.run(cmd + list(extra), cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines else None)


def test_rehearsal_ends_with_every_compared_at_its_limit():
    p, line = run_cell("--trace", "0")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["correct"] is False            # a rehearsal never is
    assert line["failed"] == 0 and line["attempted"] > 0
    for name in ("read_mismatches", "reopen_read_mismatches", "rows_wrong",
                 "records_misreported", "fallback_local",
                 "remote_job_failures", "jobs_off_device",
                 "jobs_left_pipeline", "window_without_remote_job",
                 "window_without_merge_rows", "stream_ran_out",
                 "harness_imported_jax"):
        assert line["compared"][name] == [0, 0], (name, line["compared"])
    assert set(line["metrics"]) == {"write_ops_s", "setup_s"}
    assert "last sequence" in p.stderr
    # The set-up ends in the memtable's own flush, so every sorted run is
    # of a whole write buffer and no merge of the window falls under
    # min_remote_input_bytes: every job of the window is a remote one.
    topped_up = re.search(r"keys and (\d+) operands \(to the memtable's own "
                          r"flush\)", p.stderr)
    assert topped_up and int(topped_up.group(1)) > 0
    jobs, listed = re.search(r", (\d+) jobs \(rows, .*?\): (\[.*\])",
                             p.stderr).groups()
    assert int(jobs) == len(ast.literal_eval(listed)) > 0


def test_traced_rehearsal_reports_every_new_metric():
    p, line = run_cell("--trace", "1")
    assert p.returncode == 4, p.stderr[-2000:]
    assert set(line["metrics"]) == set(NEW_METRICS + SHARED_METRICS)
    for name in NEW_METRICS + SHARED_METRICS:
        assert isinstance(line["metrics"][name]["value"], float), name
    assert line["metrics"]["client.build_us_per_op"]["value"] > 0
    assert line["metrics"]["db.write_us_per_op"]["value"] > 0
    assert line["metrics"]["merge.operand_row_share"]["value"] > 0
    assert line["metrics"]["merge.fold_share"]["value"] > 0
    assert line["metrics"]["rangedel.cover_share"]["value"] > 0
    assert all(v == [0, 0] for v in line["compared"].values())
    spans = json.loads(next(
        ln for ln in p.stderr.splitlines() if "span_summary" in ln
    ).split("span_summary ", 1)[1])["span_self_s"]
    assert "pipeline.merge_fold" in spans or spans == {}


def test_the_control_is_not_correct():
    p, line = run_cell("--trace", "0", "--launcher",
                       "faulty_merge_service.py", "--launcher-arg=--fault",
                       "--launcher-arg=drop-operand")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["compared"]["read_mismatches"][0] > 0
    assert line["compared"]["reopen_read_mismatches"][0] > 0
    assert line["compared"]["rows_wrong"][0] > 0


def test_oracle_against_a_replay():
    n, ops = 400, 5000
    wl = MergeWorkload(n, ops, seed=2147483777, every=250, width=30)
    for n_writes in (n, n + 1234, n + ops):
        state = {}
        nums = wl.numbers(0, n_writes)
        t = 0
        for w in range(n_writes):
            k = int(wl.key_of[w])
            if w < n:
                state[k] = int(nums[w])
            else:
                state[k] = (state.get(k, 0) + int(nums[w])) & (2 ** 64 - 1)
            while t < len(wl.tomb_at) and wl.tomb_at[t] <= w + 1:
                for d in range(int(wl.tomb_lo[t]), int(wl.tomb_lo[t]) + 30):
                    state.pop(d, None)
                t += 1
        keys = np.arange(0, n + 20, dtype=np.uint64)
        want = [state[k].to_bytes(8, "little") if k in state else None
                for k in range(n + 20)]
        assert ref.Oracle(wl, n_writes).expected(keys) == want
        assert None in want[:n]


def test_plain_reader_reads_rows_and_range_tombstones(tmp_path):
    """`read_job_side` (sst_plain + the range_del meta block) against the
    program's own reader, on an SST the program wrote."""
    from toplingdb_tpu.db.dbformat import (
        InternalKeyComparator, ValueType, make_internal_key,
    )
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions

    env = default_env()
    path = str(tmp_path / "000021.sst")
    w = env.new_writable_file(path)
    b = TableBuilder(w, InternalKeyComparator(), TableOptions(
        block_size=512, compression=fmt.SNAPPY_COMPRESSION))
    keys = key_bytes(np.arange(0, 3000, 3, dtype=np.uint64))
    for i, k in enumerate(keys):
        t = ValueType.MERGE if i % 3 else ValueType.VALUE
        b.add(make_internal_key(k.tobytes(), 10 + i, t),
              (i * 7).to_bytes(8, "little"))
    b.add_tombstone(make_internal_key(keys[5].tobytes(), 9000,
                                      ValueType.RANGE_DELETION),
                    keys[40].tobytes())
    b.finish()
    w.close()
    (uk, seq, vt, val), tombs, odd = ref.read_job_side([path])
    assert odd == 0
    assert list(uk) == list(range(0, 3000, 3))
    assert list(seq) == [10 + i for i in range(1000)]
    assert list(val) == [i * 7 for i in range(1000)]
    assert list(vt) == [2 if i % 3 else 1 for i in range(1000)]
    assert [int(x[0]) for x in tombs] == [9000, 15, 120]


def test_the_program_can_run_the_cell():
    from lib import dbside_merge

    assert dbside_merge.program_lacks() == ""
