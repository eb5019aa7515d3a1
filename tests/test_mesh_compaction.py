"""Mesh compaction execution mode (ops/mesh_compaction.py +
parallel/mesh_plan.py): byte parity with the single-chip plane across
codecs x block/zip x range tombstones x snapshots, mid-job chip-failure
demotion, the eligibility/fallback matrix, and the dcompact worker's
pod-level chip pool (per-chip queues, wedge demotion, /metrics gauges).

Runs on the conftest-provided 8 virtual CPU devices
(--xla_force_host_platform_device_count); mesh runs are capped to 2 chips
via TPULSM_MESH_DEVICES so per-device jit compiles stay affordable."""

import json
import urllib.request

import pytest

from test_compaction_pipeline import (
    ICMP,
    _build_runs,
    _mk_alloc,
    _run_job,
    _sst_bytes,
)
from toplingdb_tpu.parallel import mesh_plan


def _mesh_env(monkeypatch, on: bool, devices: int = 2,
              pipelined: bool = False):
    """Four shards a job and the mesh knobs; the serial branch unless
    `pipelined`."""
    from test_compaction_pipeline import _enable_small_pipeline, _pipeline

    _enable_small_pipeline(monkeypatch)
    _pipeline(monkeypatch, pipelined)
    monkeypatch.setenv("TPULSM_MESH_MIN_ROWS", "1")
    monkeypatch.setenv("TPULSM_MESH_DEVICES", str(devices))
    if on:
        monkeypatch.setenv("TPULSM_MESH_COMPACT", "1")
    else:
        monkeypatch.delenv("TPULSM_MESH_COMPACT", raising=False)


@pytest.mark.parametrize("fmt_name,codec", [
    ("block", "none"), ("block", "zstd"),
    ("zip", "none"), ("zip", "zstd"),
])
def test_mesh_byte_parity(tmp_path, monkeypatch, fmt_name, codec):
    """Mesh outputs are byte-identical to the single-chip sharded plane
    for block and zip emission, with a surviving range tombstone and live
    snapshots in the job — the ISSUE's parity matrix."""
    from toplingdb_tpu.compaction.scheduler import CompactionScheduler
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.utils import codecs

    if codec != "none" and not codecs.available(codec):
        pytest.skip(f"{codec} unavailable")
    from toplingdb_tpu.table.builder import TableOptions

    comp = {"none": fmt.NO_COMPRESSION,
            "zstd": fmt.ZSTD_COMPRESSION}[codec]
    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    out_topts = TableOptions(block_size=512, compression=comp) \
        if fmt_name == "block" else \
        TableOptions(format="zip", compression=comp)
    n = 9_000
    metas = _build_runs(env, dbdir, n, topts, seed=3, tombstone_file=True)
    snapshots = [n // 3, 2 * n // 3]

    _mesh_env(monkeypatch, on=False)
    out_ref, ref_stats = _run_job(env, dbdir, metas, topts, out_topts,
                                  1000, snapshots)
    assert getattr(ref_stats, "mesh_chips", 0) == 0

    _mesh_env(monkeypatch, on=True)
    out_mesh, stats = _run_job(env, dbdir, metas, topts, out_topts,
                               2000, snapshots)
    assert stats.mesh_chips == 2, "mesh plane did not engage"
    assert stats.mesh_shards >= 2
    assert CompactionScheduler._compaction_mode(stats) == "mesh"

    assert len(out_ref) == len(out_mesh) >= 1
    assert _sst_bytes(env, dbdir, out_mesh) == \
        _sst_bytes(env, dbdir, out_ref), \
        f"{fmt_name}/{codec}: mesh SST bytes differ from single-chip"


@pytest.mark.parametrize("kill_all", [False, True])
def test_mesh_chip_failure_demotion(tmp_path, monkeypatch, kill_all):
    """A chip that dies mid-job wedges: its shards re-dispatch on the
    survivors (kill_all=False) or the default device (kill_all=True) and
    the job completes with byte-identical outputs — zero corrupted or
    partial files. Demotions are counted on stats.mesh_fallbacks."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.ops import mesh_compaction as mc
    from toplingdb_tpu.table.builder import TableOptions

    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    n = 9_000
    metas = _build_runs(env, dbdir, n, topts, seed=4, tombstone_file=True)
    snapshots = [n // 2]

    _mesh_env(monkeypatch, on=False)
    out_ref, _ = _run_job(env, dbdir, metas, topts, topts, 1000, snapshots)

    _mesh_env(monkeypatch, on=True)
    dead = set()
    limit = 2 if kill_all else 1

    def hook(_s, device):
        if device is None:
            return  # default device must stay healthy
        if str(device) in dead:
            raise RuntimeError("chip down")
        if len(dead) < limit:
            dead.add(str(device))
            raise RuntimeError("chip down")

    monkeypatch.setattr(mc, "_FAULT_HOOK", hook)
    out_mesh, stats = _run_job(env, dbdir, metas, topts, topts, 2000,
                               snapshots)
    assert len(dead) == limit
    assert stats.mesh_fallbacks >= limit
    assert stats.mesh_chips == 1  # demoted from the 2-chip plan
    assert _sst_bytes(env, dbdir, out_mesh) == \
        _sst_bytes(env, dbdir, out_ref), "demoted job bytes differ"


def test_mesh_pipeline_parity(tmp_path, monkeypatch):
    """The pipelined plane's compute stage places shards over the mesh
    too (ops/pipeline.py _device_compute): bytes match the mesh-off
    pipelined run and the mode engages on stats."""
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableOptions

    env = default_env()
    dbdir = str(tmp_path)
    topts = TableOptions(block_size=512)
    n = 9_000
    metas = _build_runs(env, dbdir, n, topts, seed=5, tombstone_file=True)
    snapshots = [n // 3]

    _mesh_env(monkeypatch, on=False, pipelined=True)
    out_ref, _ = _run_job(env, dbdir, metas, topts, topts, 1000, snapshots)
    _mesh_env(monkeypatch, on=True, pipelined=True)
    out_mesh, stats = _run_job(env, dbdir, metas, topts, topts, 2000,
                               snapshots)
    assert stats.pipelined, "the job left the pipeline"
    assert stats.mesh_chips == 2, "pipeline mesh placement did not engage"
    assert _sst_bytes(env, dbdir, out_mesh) == \
        _sst_bytes(env, dbdir, out_ref), "pipelined mesh bytes differ"


def test_eligibility_matrix():
    """mesh_plan.check_eligibility is the one fallback matrix: every
    reason string, and the happy-path plan shape."""
    devs = ["d0", "d1", "d2"]
    shards = mesh_plan._make_uniform_shards(4, 64, key_len=20)

    assert mesh_plan.check_eligibility(None, False, devs)[0] == \
        "no-uniform-shards"
    assert mesh_plan.check_eligibility([], False, devs)[0] == \
        "no-uniform-shards"
    assert mesh_plan.check_eligibility(shards[:1], False, devs,
                                       min_rows=1)[0] == "single-shard"
    assert mesh_plan.check_eligibility(shards, True, devs,
                                       min_rows=1)[0] == "complex-groups"
    assert mesh_plan.check_eligibility(shards, False, devs,
                                       min_rows=10**9)[0] == \
        "below-row-floor"
    assert mesh_plan.check_eligibility(shards, False, devs[:1],
                                       min_rows=1)[0] == "single-device"
    reason, total = mesh_plan.check_eligibility(shards, False, devs,
                                                min_rows=1)
    assert reason is None and total == 4 * 64

    plan, reason = mesh_plan.plan_shards(shards, devices=devs, min_rows=1)
    assert reason is None
    assert plan.assignments == [0, 1, 2, 0]
    assert plan.n_devices == 3
    assert plan.window == mesh_plan.UPLOAD_DEPTH * 3

    plan, reason = mesh_plan.plan_shards(shards, any_complex=True,
                                         devices=devs, min_rows=1)
    assert plan is None and reason == "complex-groups"


def test_maybe_plan_gating(monkeypatch):
    """Knob off -> None with no fallback tick; knob on + ineligible ->
    None WITH a fallback tick; knob on + eligible -> plan + stats."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats
    from toplingdb_tpu.ops import mesh_compaction as mc

    shards = mesh_plan._make_uniform_shards(4, 64, key_len=20)
    monkeypatch.delenv("TPULSM_MESH_COMPACT", raising=False)
    stats = CompactionStats()
    assert mc.maybe_plan(shards, stats=stats) is None
    assert stats.mesh_fallbacks == 0

    monkeypatch.setenv("TPULSM_MESH_COMPACT", "1")
    monkeypatch.setenv("TPULSM_MESH_MIN_ROWS", "1")
    monkeypatch.setenv("TPULSM_MESH_DEVICES", "2")
    assert mc.maybe_plan(shards, any_complex=True, stats=stats) is None
    assert stats.mesh_fallbacks == 1

    plan = mc.maybe_plan(shards, stats=stats)
    assert plan is not None and plan.n_devices == 2
    assert stats.mesh_chips == 2 and stats.mesh_shards == 4


def test_mesh_statistics_tickers():
    """CompactionStats mesh fields land on the DCOMPACTION_MESH_* tickers
    through Statistics.record_compaction."""
    from toplingdb_tpu.compaction.compaction_job import CompactionStats
    from toplingdb_tpu.utils import statistics as st

    stats = st.Statistics()
    cs = CompactionStats(device="cpu")
    cs.mesh_chips = 4
    cs.mesh_shards = 16
    cs.mesh_fallbacks = 2
    stats.record_compaction(cs)
    t = stats.tickers()
    assert t[st.DCOMPACTION_MESH_JOBS] == 1
    assert t[st.DCOMPACTION_MESH_SHARDS] == 16
    assert t[st.DCOMPACTION_MESH_FALLBACKS] == 2

    # Single-chip jobs don't tick the mesh counters.
    stats2 = st.Statistics()
    stats2.record_compaction(CompactionStats(device="cpu"))
    t2 = stats2.tickers()
    assert st.DCOMPACTION_MESH_JOBS not in t2


def test_chip_pool_admission_and_demotion():
    """ChipPool: least-loaded targeting, wedge-aware demotion, failure
    feedback through the chip breakers, and queue-depth accounting."""
    from toplingdb_tpu.compaction.dcompact_service import ChipPool

    pool = ChipPool(4)
    g1 = pool.admit(want=2)
    assert len(g1) == 2
    # Next grant targets the two idle chips (least depth first).
    g2 = pool.admit(want=2)
    assert len(g2) == 2 and not set(g1) & set(g2)
    depths = pool.queue_depths()
    assert all(depths[c] == 1 for c in g1 + g2)
    pool.release(g1, ok=True)
    pool.release(g2, ok=True)
    assert all(v == 0 for v in pool.queue_depths().values())

    # Open chip:0's breaker: it drops out of future grants.
    for _ in range(3):
        pool.health.record_failure("chip:0")
    g3 = pool.admit()
    assert "chip:0" not in g3 and len(g3) == 3
    pool.release(g3, ok=True)

    # A full-pool failure opens every breaker -> admit returns [] (the
    # caller runs local) instead of blocking forever.
    pool2 = ChipPool(2)
    for _ in range(3):
        g = pool2.admit()
        pool2.release(g, ok=False, failed_chips=set(g))
    assert pool2.admit(timeout=0.1) == []


def test_chip_pool_timeout_partial_grant():
    """A gang-wait that times out takes the free subset instead of
    stalling the job behind a busy chip."""
    from toplingdb_tpu.compaction.dcompact_service import ChipPool

    pool = ChipPool(2)
    hold = pool.admit(want=1)
    assert len(hold) == 1
    g = pool.admit(want=2, timeout=0.15)
    assert len(g) == 1 and g[0] not in hold
    pool.release(g)
    pool.release(hold)
    assert all(v == 0 for v in pool.queue_depths().values())


def test_service_chip_metrics(tmp_path):
    """DcompactWorkerService --chips exposes per-chip queue-depth /
    busy / wedged gauges on /metrics and the pool snapshot on /stats."""
    from toplingdb_tpu.compaction.dcompact_service import (
        DcompactWorkerService,
    )

    svc = DcompactWorkerService(device="cpu", chips=2)
    port = svc.start()
    try:
        for _ in range(3):
            svc.pool.health.record_failure("chip:1")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as r:
            body = r.read().decode()
        assert 'tpulsm_dcompact_chip_queue_depth{chip="chip:0"} 0' in body
        assert 'tpulsm_dcompact_chip_wedged{chip="chip:1"} 1' in body
        assert 'tpulsm_dcompact_chip_busy{chip="chip:0"} 0' in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats") as r:
            stats = json.loads(r.read())
        assert stats["chips"]["chip:1"]["state"] == "open"
        assert stats["chips"]["chip:0"]["queue_depth"] == 0
    finally:
        svc.stop()


def test_probe_cli_exit_codes(monkeypatch, capsys):
    """scaling_probe distinguishes skip (environment) from failure
    (measurement): requesting more devices than exist is EXIT_SKIP."""
    import os

    from toplingdb_tpu.parallel import scaling_probe

    # configure_virtual_devices rewrites these; pin them so monkeypatch
    # restores the suite's 8-device flags afterwards.
    for k in ("XLA_FLAGS", "JAX_PLATFORMS"):
        monkeypatch.setenv(k, os.environ.get(k, ""))
    rc = scaling_probe.main(["--devices", "4096"])
    out = capsys.readouterr().out
    assert rc == mesh_plan.EXIT_SKIP
    assert "skip" in json.loads(out.strip().splitlines()[-1])

    def boom(*a, **k):
        raise RuntimeError("measurement broke")

    monkeypatch.setattr(mesh_plan, "weak_scaling_rows", boom)
    rc = scaling_probe.main(["--devices", "1", "--rows-per-device", "64"])
    out = capsys.readouterr().out
    assert rc == mesh_plan.EXIT_FAILURE
    assert "error" in json.loads(out.strip().splitlines()[-1])
