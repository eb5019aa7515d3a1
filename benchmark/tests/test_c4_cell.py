"""The cold-level deployment's cell (CPU; `python -m pytest benchmark/tests
-q`): the cell's files are found; a rehearsal of
`dbbench-c4-zip-l2plus.overwrite-zip` ends with every `compared` at its
limit, and with every new per-layer metric a number under `--trace 1`; the
control (a zip scan that loses the last entry of every key group) comes
out wrong in the reads and in the compared job; the plain ZipTable reader
against the program's own."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from lib import zip_plain  # noqa: E402
from lib.workload import Workload  # noqa: E402
from test_benchmark import SERVED_METRICS as SHARED_METRICS  # noqa: E402

CELL = "dbbench-c4-zip-l2plus.overwrite-zip"
NEW_METRICS = ("zip.input_row_share", "zip.output_byte_share",
               "zip.encode_share", "zip.scan_share", "zip.space_ratio")
COMPARED = ("read_mismatches", "reopen_read_mismatches", "rows_wrong",
            "records_misreported", "bottommost_outputs_not_zip",
            "fallback_local", "remote_job_failures", "jobs_off_device",
            "jobs_left_pipeline", "window_without_remote_job",
            "window_without_zip_input_rows", "stream_ran_out",
            "harness_imported_jax")


def run_cell(*extra, seconds="3", seed="2147483659", scale="0.02"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", seed, "--seconds", seconds, "--rehearse-cpu", scale]
    p = subprocess.run(cmd + list(extra), cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines else None)


def test_the_cells_files_are_found():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert len(conf["source"]) <= 200
    assert conf["reduced"] == ["keys", "lsm", "table", "chips"]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    assert config["source"] == conf["source"]
    assert set(conf["reduced"]) == set(config["reduced"])
    assert config["table"]["bottommost_format"] == "zip"
    with open(os.path.join(BENCH, "configs", "dbbench-c2-8b20b.json")) as f:
        sibling = json.load(f)
    assert set(sibling) <= set(config)          # the sibling's keys
    for k in ("key_bytes", "value_bytes", "batch_size", "writers", "lsm",
              "wal", "sync_every_write", "block_cache_bytes", "service"):
        assert config[k] == sibling[k], k       # the pairing: one format
    assert config["keys"] == 2 * sibling["keys"]
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert os.path.exists(os.path.join(BENCH, "traffic", "kinds",
                                       mix["kind"] + ".py"))
    reports = {m["name"] for s in ("end_to_end", "per_layer")
               for m in bench[s] if CELL in m.get("workloads", [])}
    assert reports == {"write_ops_s", *NEW_METRICS, *SHARED_METRICS}
    for name in NEW_METRICS:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "ratio"
        for fact in (spec["args"]["num"], spec["args"]["den"]):
            assert fact.split("sum.", 1)[1] in mix["job_stats"]


def test_rehearsal_ends_with_every_compared_at_its_limit():
    p, line = run_cell("--trace", "0")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["correct"] is False            # a rehearsal never is
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == set(COMPARED)
    for name in COMPARED:
        assert line["compared"][name] == [0, 0], (name, line["compared"])
    assert set(line["metrics"]) == {"write_ops_s", "setup_s"}
    assert "(to the memtable's own flush)" in p.stderr
    assert "in the DB process: 0 []" in p.stderr


def test_traced_rehearsal_reports_every_metric():
    p, line = run_cell("--trace", "1", seed="2147483660")
    assert p.returncode == 4, p.stderr[-2000:]
    for name in NEW_METRICS + SHARED_METRICS:
        assert isinstance(line["metrics"][name]["value"], float), name
    for name in NEW_METRICS:
        assert line["metrics"][name]["value"] > 0, name
    assert line["metrics"]["zip.space_ratio"]["value"] < 1
    assert all(v == [0, 0] for v in line["compared"].values())


def test_the_control_is_not_correct():
    p, line = run_cell("--trace", "0", "--launcher",
                       "faulty_zip_service.py", "--launcher-arg=--fault",
                       "--launcher-arg=drop-group-tail")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["compared"]["read_mismatches"][0] > 100
    assert line["compared"]["reopen_read_mismatches"][0] > 100
    assert line["compared"]["rows_wrong"][0] > 100
    assert line["compared"]["jobs_left_pipeline"] == [0, 0]


def test_plain_reader_against_the_programs(tmp_path):
    """`zip_plain` on a ZipTable the program's per-entry builder wrote:
    the deployment's record shape, a dictionary, a range tombstone."""
    from toplingdb_tpu.db.dbformat import (
        InternalKeyComparator, ValueType, make_internal_key,
    )
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableOptions
    from toplingdb_tpu.table.factory import new_table_builder, open_table

    env = default_env()
    icmp = InternalKeyComparator()
    topts = TableOptions(format="zip", compression=fmt.SNAPPY_COMPRESSION)
    wl = Workload(5000, 0, seed=4)
    keys = np.arange(0, 5000, dtype=np.uint64)
    kb = wl.key_bytes(keys)
    vb = wl.value_bytes(keys, keys * np.uint64(3))
    path = str(tmp_path / "000021.sst")
    w = env.new_writable_file(path)
    b = new_table_builder(w, icmp, topts)
    for i in range(len(keys)):
        b.add(make_internal_key(kb[i].tobytes(), 10 + i, ValueType.VALUE),
              vb[i].tobytes())
    b.add_tombstone(make_internal_key(kb[5].tobytes(), 9000,
                                      ValueType.RANGE_DELETION),
                    kb[40].tobytes())
    b.finish()
    w.close()
    assert zip_plain.is_zip_table(path)
    t = zip_plain.read_table(path)
    assert t["dict_len"] > 0
    assert t["tombstones"] == [(kb[5].tobytes(), 9000, kb[40].tobytes())]
    ik, vals = zip_plain.read_rows(path)
    r = open_table(env.new_random_access_file(path), icmp, topts)
    it = r.new_iterator()
    it.seek_to_first()
    assert [(ik[i].tobytes(), vals[i].tobytes())
            for i in range(len(ik))] == list(it.entries())


def test_the_program_can_run_the_cell():
    from lib import dbside_zip

    assert dbside_zip.program_lacks() == ""
