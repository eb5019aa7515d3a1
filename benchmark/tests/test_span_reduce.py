"""`lib/span_reduce.py` and the launcher that uses it (CPU;
`python -m pytest benchmark/tests -q`): the attribution of device-idle time
to program spans on a hand-made trace with a gap under each rule, the
device time by scope, self time and slow jobs, agreement with
`trace_reduce.reduce` on the same events (hand-made, and a piece of a
recorded chip trace of PR 27), and a rehearsal of the job cell through
`--trace 1`, whose launcher is `span_service.py`.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from lib import span_reduce as sr  # noqa: E402
from lib import trace_reduce  # noqa: E402
from test_benchmark import JOBS, run_cell  # noqa: E402

S = 1_000_000_000  # the hand-made trace is written in seconds


def span(name, a, b, line, **tags):
    return [name, a * S, (b - a) * S, line, tags]


def hand_made():
    """One job of 50 s in a window of 100 s, two device ops. Line 1 is the
    job's own thread, line 2 the compute thread, line 3 a reader."""
    host = [
        [trace_reduce.WINDOW_OPEN, 0, 10, 0, {}],
        [trace_reduce.WINDOW_CLOSE, 100 * S - 10, 10, 0, {}],
        span(sr.REQUEST, 8, 62, 1),
        span(sr.WORKER, 10, 60, 1, input_records=1000, pipelined=True),
        span("compaction.prepare", 10, 12, 1),
        span("pipeline.stall", 12, 40, 1),
        span("pipeline.encode_write", 40, 50, 1, chunk=0),
        span("sst.sync_close", 45, 50, 1),
        span("pipeline.wait_scan", 12, 15, 2, shard=0),
        span("pipeline.upload", 15, 18, 2, h2d_bytes=4096),
        span("pipeline.merge_gc", 18, 40, 2, d2h_bytes=1024),
        span("runtime.gc_pause", 31, 33, 2, generation=2),
        span("pipeline.scan", 12, 14, 3),
    ]
    ops = [["fusion.2 = u8[4194304] fusion(...)", 20 * S, 10 * S, "compact"],
           ["fusion.7", 35 * S, 3 * S, "sort"]]
    return {"device_ops": {"/device:TPU:0": ops}, "host": host}


def test_every_idle_second_gets_a_name_by_the_rules():
    out = sr.reduce(hand_made())
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({
        "pipeline.merge_gc": 2 + 3 + 2,   # [18,20) [30,31)+[33,35) [38,40)
        "runtime.gc_pause": 2,            # innermost on the compute thread
        "pipeline.wait_scan": 3, "pipeline.upload": 3,
        "compaction.prepare": 2,          # compute thread not started yet
        "pipeline.encode_write": 5,       # compute thread gone: the writer
        "sst.sync_close": 5,
        sr.UNATTRIBUTED: 10,              # [50,60): only the job's root
        sr.REQUEST: 2 + 2,                # [8,10) and [60,62)
        sr.NO_REQUEST: 8 + 38})
    # `pipeline.stall` (the writer waiting) never names a gap while the
    # compute thread, which feeds the device, has a span open.
    assert "pipeline.stall" not in gaps
    place = {p: dict(t) for p, t in out["idle_by_place"].items()}
    assert place[sr.HEAD] == pytest.approx({
        "compaction.prepare": 2, "pipeline.wait_scan": 3,
        "pipeline.upload": 3, "pipeline.merge_gc": 2})
    assert place[sr.BETWEEN] == pytest.approx({
        "pipeline.merge_gc": 3, "runtime.gc_pause": 2})
    assert place[sr.TAIL] == pytest.approx({
        "pipeline.merge_gc": 2, "pipeline.encode_write": 5,
        "sst.sync_close": 5, sr.UNATTRIBUTED: 10})
    assert out["in_job_idle_s"] == pytest.approx(37)
    assert out["unattributed_s"] == pytest.approx(10)
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_a_zip_span_inside_the_writer_names_its_own_idle_seconds():
    """The zip writer's segments are children of `pipeline.encode_write`
    (the program records `zip.index_build`, `zip.dict_train`, `zip.encode`
    there): the innermost names the gap, the parent keeps the rest, and
    the device's own time does not move."""
    events = hand_made()
    before = sr.reduce(events)
    events["host"] += [span("zip.dict_train", 41, 44, 1, files=1),
                       span("zip.group_decode", 12, 13, 3)]
    out = sr.reduce(events)

    def in_job(summary):               # every name, not the ten largest
        gaps = {}
        for table in summary["idle_by_place"].values():
            for name, s in table:
                gaps[name] = gaps.get(name, 0.0) + s
        return gaps

    gaps, was = in_job(out), in_job(before)
    assert dict(out["idle_gaps"])["zip.dict_train"] == pytest.approx(3)
    assert gaps["zip.dict_train"] == pytest.approx(3)
    assert gaps["pipeline.encode_write"] == pytest.approx(
        was["pipeline.encode_write"] - 3)
    assert {k: v for k, v in gaps.items() if k not in (
        "zip.dict_train", "pipeline.encode_write")} == pytest.approx(
        {k: v for k, v in was.items() if k != "pipeline.encode_write"})
    # A reader thread's span (`zip.group_decode` inside `pipeline.scan`)
    # names no gap: readers do not feed the device. Its self time is kept.
    assert "zip.group_decode" not in gaps
    assert out["span_self_s"]["zip.group_decode"] == pytest.approx(1)
    assert out["span_self_s"]["zip.dict_train"] == pytest.approx(3)
    assert out["span_self_s"]["pipeline.encode_write"] == pytest.approx(
        before["span_self_s"]["pipeline.encode_write"] - 3)
    assert out["device_ops"] == before["device_ops"]
    assert out["busy_s"] == before["busy_s"]
    assert sum(gaps.values()) == pytest.approx(out["in_job_idle_s"])
    assert out["in_job_idle_s"] == before["in_job_idle_s"]


def test_device_time_goes_by_scope_and_transfers_by_span():
    out = sr.reduce(hand_made())
    assert dict(out["device_ops"]) == pytest.approx(
        {"compact": 10, "sort": 3})
    assert out["device_ops_by_hlo"][0][0].startswith("fusion.2")
    assert out["h2d_s"] == pytest.approx(3) and out["h2d_bytes"] == 4096
    assert out["d2h_wait_s"] == pytest.approx(22) and out["d2h_bytes"] == 1024
    self_s = out["span_self_s"]
    assert self_s["pipeline.merge_gc"] == pytest.approx(20)  # less the pause
    assert self_s["pipeline.encode_write"] == pytest.approx(5)
    assert self_s[sr.WORKER] == pytest.approx(10)
    assert self_s[sr.REQUEST] == pytest.approx(4)
    assert out["jobs"] == [{"start_s": 10.0, "wall_s": 50.0, "rows": 1000,
                            "pipelined": True, "whole": True}]


@pytest.mark.parametrize("name,stats,scope", [
    ("fusion.2 = u8[4194304]{0} fusion(p0), kind=kLoop",
     {"tf_op": "jit(_fused_uniform_shard_impl)/encode_words/gather"},
     "encode_words"),
    ("sort.3", {"long_name": "x", "hlo_category": "sort", "name":
                "jit(_fused_uniform_shard_impl)/sort/jit(_sort_impl)/sort"},
     "sort"),
    ("custom-call.1", {"tf_op":
                       "jit(f)/gc_mask/jit(_gc_mask_impl)/pallas_call/gc_rows"},
     "gc_rows"),
    ("gc_rows.2", {}, "gc_rows"),
    ("copy-start.4 = (u8[8]) copy-start(x)", {"flops": 0},
     "copy-start.4 = (u8[8]) copy-start(x)"),
])
def test_scope_of_a_device_operation(name, stats, scope):
    assert sr.scope_of(name, stats) == scope


def test_flatten_gives_each_instant_to_the_innermost_span():
    segs = sr.flatten([["a", 0, 10], ["b", 2, 3], ["c", 3, 1], ["d", 6, 2],
                       ["e", 20, 5]])
    assert segs == [[0, 2, "a", "a"], [2, 3, "b", "a"], [3, 4, "c", "a"],
                    [4, 5, "b", "a"], [5, 6, "a", "a"], [6, 8, "d", "a"],
                    [8, 10, "a", "a"], [20, 25, "e", "e"]]


def test_slow_job_names_the_spans_that_grew():
    def job(start, wall, **self_s):
        return {"start_s": start, "wall_s": wall, "rows": 3_264_814,
                "pipelined": True, "whole": True, "self_s": self_s}

    usual = {"pipeline.encode_write": 0.1, "sst.build_data": 0.7}
    rows = [job(0, 1.9, **usual), job(2, 2.0, **usual),
            job(4, 6.9, **{"pipeline.encode_write": 0.1,
                           "sst.build_data": 0.75, "runtime.gc_pause": 4.9}),
            {**job(11, 9.0), "whole": False},      # cut by the window
            {**job(12, 30.0), "rows": 5}]          # alone with its count
    (slow,) = sr.slow_jobs(rows)
    assert slow["start_s"] == 4 and slow["median_wall_s"] == 2.0
    assert [n for n, _ in slow["grew"]] == ["runtime.gc_pause",
                                            "sst.build_data"]
    assert slow["grew"][0][1] == pytest.approx(4.9)


def agrees_with_trace_reduce(events, out):
    old = trace_reduce.reduce(sr.as_trace_reduce_events(events))
    for key in ("window_s", "busy_s", "busy_in_jobs_chip_s", "jobs_seen",
                "job_s"):
        assert out[key] == pytest.approx(old[key], rel=0.01), key
    old_gaps = dict(old["idle_gaps"])
    for place, total in out["gap_totals_s"].items():
        assert total == pytest.approx(old_gaps.get(place, 0.0), rel=0.01,
                                      abs=1e-6), place
    by_place = sum(s for t in out["idle_by_place"].values() for _, s in t)
    assert by_place == pytest.approx(out["in_job_idle_s"], rel=1e-6)


def test_agrees_with_trace_reduce_on_the_hand_made_trace():
    events = hand_made()
    agrees_with_trace_reduce(events, sr.reduce(events))


def test_recorded_chip_trace():
    """A piece of the trace of one `--trace 1` run of the job cell on a TPU
    v5e (PR 27, my chip run; data/span_events.md has its numbers): no gap
    of a job is left without a name, no large device operation without a
    scope, and the scope that PR 26 deleted is gone."""
    with open(os.path.join(HERE, "data", "span_events.json")) as f:
        rec = json.load(f)
    out = sr.reduce(rec["events"])
    for key, want in rec["expected"].items():
        assert out[key] == pytest.approx(want, rel=1e-9), key
    agrees_with_trace_reduce(rec["events"], out)
    assert out["unattributed_s"] < 0.05 * out["in_job_idle_s"]
    device_s = sum(s for _, s in out["device_ops_by_hlo"])
    for scope, s in out["device_ops"]:
        if s >= 0.01 * device_s:
            assert "fusion" not in scope, scope
    assert out["device_ops"][0][0] == "compact"
    assert "fc_decode" not in out["device_s_by_scope"]
    names = {n for n, _ in out["idle_gaps"]}
    assert names & {"compaction.prepare", "pipeline.plan",
                    "sst.build_data", "sst.sync_close"}


def test_rehearsal_through_the_span_launcher(tmp_path):
    events_path = str(tmp_path / "events.json")
    p, line = run_cell(JOBS, "--trace", "1", "--keep-events", events_path)
    assert p.returncode == 4, p.stderr[-2000:]  # a rehearsal is never a pass
    assert line["compared"]["harness_imported_jax"] == [0, 0]
    gaps = dict(line["breakdown"]["idle_gaps"])
    in_job = {n: s for n, s in gaps.items()
              if n not in (sr.REQUEST, sr.NO_REQUEST)}
    assert in_job, gaps
    for name in in_job:
        assert name == sr.UNATTRIBUTED or name.startswith(sr.SPAN_PREFIXES)
    assert in_job.get(sr.UNATTRIBUTED, 0.0) < 0.05 * sum(in_job.values())
    # The program recorded the jobs itself: nothing named them from outside.
    with open(events_path) as f:
        events = json.load(f)
    names = {e[0] for e in events["host"]}
    assert sr.WORKER in names and sr.REQUEST in names
    assert "bench:job" not in names
    out = sr.reduce(events)
    assert out["jobs_seen"] >= 1
    agrees_with_trace_reduce(events, out)
    # What run.py does not print reaches its stderr by the "[traced]"
    # prefix: one line, the summary's other keys.
    (said,) = [ln for ln in p.stderr.splitlines() if "[traced]" in ln]
    printed = json.loads(said.split("[traced] span_summary ", 1)[1])
    assert printed["jobs_seen"] == out["jobs_seen"]
    assert printed["unattributed_s"] == pytest.approx(out["unattributed_s"])
    assert "slow_jobs" in printed and "idle_by_place" in printed
