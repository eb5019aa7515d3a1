"""The fan-out deployment's cell (CPU; `python -m pytest benchmark/tests
-q`): the cell's files are found; a rehearsal of
`pod4-c5-zip-l2plus.overwrite-fanout` on four virtual devices ends with
every `compared` at its limit, and with every new per-layer metric a number
under `--trace 1`; the cold cell's control (a zip scan that loses the last
entry of every key group) comes out wrong under `--chips 4 --workers 4`; a
program or a service without the chip pool's counters refuses the cell
with exit code 5."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402
from test_benchmark import SERVED_METRICS as SHARED_METRICS  # noqa: E402

CELL = "pod4-c5-zip-l2plus.overwrite-fanout"
SIBLING = "dbbench-c4-zip-l2plus"
ZIP_METRICS = ("zip.input_row_share", "zip.output_byte_share",
               "zip.encode_share", "zip.scan_share", "zip.space_ratio")
NEW_METRICS = ("fanout.jobs_in_flight_mean", "fanout.chip_busy_min_share",
               "fanout.grant_wait_share")
COMPARED = ("read_mismatches", "reopen_read_mismatches", "rows_wrong",
            "rows_wrong_overlapped", "records_misreported",
            "bottommost_outputs_not_zip", "fallback_local",
            "remote_job_failures", "jobs_off_device", "jobs_off_grant",
            "jobs_left_pipeline", "window_without_remote_job",
            "window_without_zip_input_rows", "window_chips_unused",
            "stream_ran_out", "harness_imported_jax")


def run_cell(*extra, seconds="3", seed="2147483659", scale="0.02"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
           "--seed", seed, "--seconds", seconds, "--rehearse-cpu", scale]
    p = subprocess.run(cmd + list(extra), cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if lines else None)


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


def test_the_cells_files_are_found():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 4 and len(cell["why"]) <= 200
    assert [c["name"] for c in bench["workloads"] if c["chips"] != 1] == [CELL]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert len(conf["source"]) <= 200 and len(conf["why"]) <= 200
    assert conf["reduced"] == ["keys", "lsm", "table", "chips"]
    config = load(ROOT, conf["file"])
    assert config["source"] == conf["source"]
    assert set(conf["reduced"]) == set(config["reduced"])
    sibling = load(BENCH, "configs", SIBLING + ".json")
    # The sibling with the fan-out: keys, the service, the DB's jobs.
    assert set(sibling) == set(config)
    for k in set(sibling) - {"name", "source", "sources", "keys", "lsm",
                             "service", "guarantees", "reduced", "assumed",
                             "source_values"}:
        assert config[k] == sibling[k], k
    assert config["keys"] == 2 * sibling["keys"]
    assert config["lsm"] == {**sibling["lsm"], "max_background_jobs": 4}
    assert config["service"]["chips"] == config["service"]["workers"] == 4
    assert config["guarantees"][:len(sibling["guarantees"])] == \
        sibling["guarantees"]
    mix = load(BENCH, "traffic", cell["traffic"] + ".json")
    assert mix["kind"] == "puts_fanout"
    assert mix["max_puts_per_s"] >= 800_000
    assert os.path.exists(os.path.join(BENCH, "traffic", "kinds",
                                       mix["kind"] + ".py"))
    reports = {m["name"] for s in ("end_to_end", "per_layer")
               for m in bench[s] if CELL in m.get("workloads", [])}
    assert reports == {"write_ops_s", *SHARED_METRICS, *ZIP_METRICS,
                       *NEW_METRICS}
    for name in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "executor boundary"
        assert load(BENCH, "metrics", name + ".json")["reader"] == "ratio"


def test_rehearsal_ends_with_every_compared_at_its_limit():
    p, line = run_cell("--trace", "0")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["correct"] is False            # a rehearsal never is
    assert line["device"]["count"] == 4
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["compared"]) == set(COMPARED)
    for name in COMPARED:
        assert line["compared"][name] == [0, 0], (name, line["compared"])
    assert set(line["metrics"]) == {"write_ops_s", "setup_s"}
    assert "beside it on chips [" in p.stderr


def test_traced_rehearsal_reports_every_metric():
    p, line = run_cell("--trace", "1", seed="2147483660")
    assert p.returncode == 4, p.stderr[-2000:]
    for name in NEW_METRICS + ZIP_METRICS + SHARED_METRICS:
        assert isinstance(line["metrics"][name]["value"], float), name
    assert line["metrics"]["fanout.jobs_in_flight_mean"]["value"] > 0
    assert line["metrics"]["fanout.chip_busy_min_share"]["value"] > 0
    assert all(v == [0, 0] for v in line["compared"].values())


def test_the_control_is_not_correct():
    p, line = run_cell("--trace", "0", "--launcher",
                       "faulty_zip_service.py", "--launcher-arg=--fault",
                       "--launcher-arg=drop-group-tail")
    assert p.returncode == 4, p.stderr[-2000:]
    assert line["compared"]["read_mismatches"][0] > 100
    assert line["compared"]["rows_wrong"][0] > 100
    assert line["compared"]["jobs_off_grant"] == [0, 0]


def _kind():
    cell = bench_run.find_cell(bench_run.load_bench(), CELL)
    mix = load(BENCH, "traffic", cell["traffic"] + ".json")
    return bench_run.load_module(os.path.join(
        BENCH, "traffic", "kinds", mix["kind"] + ".py")), cell, mix


def test_a_program_without_the_pool_counters_exits_5(monkeypatch):
    from toplingdb_tpu.compaction.dcompact_service import ChipPool

    kind, _cell, _mix = _kind()
    assert kind.program_lacks() == ""
    monkeypatch.delattr(ChipPool, "counters")
    assert "counters" in kind.program_lacks()
    with pytest.raises(SystemExit) as e:
        kind.drive(None)
    assert e.value.code == kind.EXIT_PROGRAM_LACKS == 5


def test_a_service_without_the_pool_counters_exits_5():
    """A service that answers /stats without the pool's counters (one
    started without --chips, or an older one) is refused before the
    load."""
    kind, cell, mix = _kind()
    config = load(ROOT, "benchmark", "configs", cell["config"] + ".json")
    svc = types.SimpleNamespace(get=lambda path: {"jobs_done": 0})
    run = types.SimpleNamespace(
        traffic=mix, config=config, scale=1e-6, seconds=0.001, seed=1,
        svc=svc, wait_service=lambda: None)
    with pytest.raises(SystemExit) as e:
        kind.drive(run)
    assert e.value.code == 5
