"""One CompactionJob a chip: the dcompact service's chip grants
(`--chips N --workers W`, compaction/dcompact_service.py::ChipPool), the
granted `jax.Device`s carried down to the uploads (worker.run_job →
run_device_compaction → ops/pipeline.py and ops/mesh_compaction.py), and
the pool's /stats counters.

Runs on four of the conftest's virtual CPU devices
(--xla_force_host_platform_device_count): what is checked is placement and
bytes, which the CPU shows as the chip would; no time is measured."""

import json
import os
import shutil
import threading
import urllib.request

import pytest

from test_job_trace import make_job
from toplingdb_tpu.compaction import worker
from toplingdb_tpu.compaction.dcompact_service import (
    ChipPool, DcompactWorkerService,
)

CHIPS = 4


@pytest.fixture
def sharded(monkeypatch):
    """A test-sized job is cut into four shards (the rule would leave it
    one), so it runs pipelined."""
    from toplingdb_tpu.ops import compaction_kernels as ck

    monkeypatch.setattr(ck, "shard_count", lambda total_rows: 4)


@pytest.fixture
def serial(monkeypatch, sharded):
    """Four shards, down the serial branch; a grant of two chips or more
    meshes whatever the row count."""
    from toplingdb_tpu.ops import pipeline as pl

    monkeypatch.setattr(pl, "pipeline_enabled", lambda *_a: False)
    monkeypatch.setenv("TPULSM_MESH_MIN_ROWS", "1")


def devices():
    import jax

    return jax.devices()[:CHIPS]


def copy_job(job_dir, name, device=None):
    """The same job's inputs and params in a directory of its own, with an
    empty output directory (and another device, when given)."""
    dst = os.path.join(os.path.dirname(job_dir), name)
    shutil.copytree(job_dir, dst)
    shutil.rmtree(os.path.join(dst, "out"))
    os.makedirs(os.path.join(dst, "out"))
    with open(os.path.join(dst, "params.json")) as f:
        params = json.load(f)
    params["output_dir"] = os.path.join(dst, "out")
    params["input_files"] = [os.path.join(dst, "in", os.path.basename(p))
                             for p in params["input_files"]]
    params["dbname"] = os.path.join(dst, "in")
    if device is not None:
        params["device"] = device
    with open(os.path.join(dst, "params.json"), "w") as f:
        json.dump(params, f)
    return dst


def results_of(job_dir):
    with open(os.path.join(job_dir, "results.json")) as f:
        return json.load(f)


def output_bytes(job_dir):
    """Every output file's bytes, in the job's order."""
    out = []
    for d in results_of(job_dir)["output_files"]:
        with open(os.path.join(job_dir, "out", d["path"]), "rb") as f:
            out.append(f.read())
    assert out
    return out


def post(port, job_dir):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/dcompact",
        data=json.dumps({"job_dir": job_dir}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        return json.loads(r.read())


@pytest.fixture
def service(request):
    """A cpu-jax service owning the first four devices, with
    `--workers` from the test's parameter (default: four)."""
    workers = getattr(request, "param", CHIPS)
    svc = DcompactWorkerService(device="cpu-jax", max_workers=workers,
                                chips=CHIPS)
    port = svc.start()
    yield svc, port
    svc.stop()


def side_by_side(monkeypatch, n):
    """Hold each of the next `n` jobs at the start of worker.run_job until
    all `n` hold their grants, so that they run at once; return what each
    saw of the environment there, and the call that lets jobs go alone
    again."""
    barrier = threading.Barrier(n, timeout=120)
    seen = []
    run_job = worker.run_job

    def held(job_dir, devices=None):
        seen.append(dict(os.environ))
        barrier.wait()
        return run_job(job_dir, devices=devices)

    monkeypatch.setattr(worker, "run_job", held)
    return seen, lambda: monkeypatch.setattr(worker, "run_job", run_job)


def post_all(port, jobs):
    replies = [None] * len(jobs)
    errors = []

    def one(i):
        try:
            replies[i] = post(port, jobs[i])
        except Exception as e:  # the assertion below names it
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not errors, errors
    return replies


def test_four_jobs_at_once_each_on_its_own_chip(tmp_path, sharded, service,
                                                monkeypatch):
    """Four jobs posted at once to `--chips 4 --workers 4`, side by side:
    each on a chip of its own, named by its stats and its spans, and each
    output the same bytes as the job run alone and as the per-entry
    compaction of the same inputs. Nothing reaches os.environ."""
    svc, port = service
    jobs = [make_job(tmp_path, name=f"j{i}", seed=100 + i)
            for i in range(CHIPS)]
    alone = [copy_job(j, f"alone{i}") for i, j in enumerate(jobs)]
    entry = [copy_job(j, f"entry{i}", device="cpu")
             for i, j in enumerate(jobs)]
    env_before = dict(os.environ)
    seen, alone_again = side_by_side(monkeypatch, CHIPS)
    replies = post_all(port, jobs)
    alone_again()

    assert seen == [env_before] * CHIPS and dict(os.environ) == env_before
    used = []
    for reply in replies:
        st = reply["stats"]
        assert st["pipelined"] and st["mesh_chips"] == 0
        assert len(st["devices_granted"]) == 1
        assert st["devices_used"] == st["devices_granted"]
        used += st["devices_used"]
    assert sorted(used) == list(range(CHIPS))

    for j, a, e in zip(jobs, alone, entry):
        assert worker.run_job(a) == 0
        assert worker.run_job(e) == 0
        assert results_of(a)["stats"]["devices_used"] == [0]
        assert output_bytes(j) == output_bytes(a) == output_bytes(e)

    spans = [s for t in svc.tracer.finished() for s in t.spans]
    grants = [s.tags["chips"] for s in spans if s.name == "dcompact.grant"]
    roots = [s.tags["chip"] for s in spans if s.name == "dcompact.worker"]
    assert sorted(grants) == sorted(roots) == ["0", "1", "2", "3"]


@pytest.mark.parametrize("route", ["pipelined", "serial"])
def test_a_grant_of_chips_1_and_3_places_every_shard_there(
        tmp_path, route, request):
    """A grant names the chips, not their number: chips 1 and 3 run the
    job's shards, chips 0 and 2 nothing, and the bytes are those of the
    job on the default device."""
    request.getfixturevalue("serial" if route == "serial" else "sharded")
    job = make_job(tmp_path, seed=11)
    ref = copy_job(job, "ref")
    devs = devices()
    assert worker.run_job(job, devices=[devs[1], devs[3]]) == 0
    assert worker.run_job(ref) == 0
    st = results_of(job)["stats"]
    assert st["devices_granted"] == [1, 3]
    assert st["devices_used"] == [1, 3]
    assert st["mesh_chips"] == 2
    assert bool(st["pipelined"]) == (route == "pipelined")
    assert output_bytes(job) == output_bytes(ref)


@pytest.mark.parametrize("route", ["pipelined", "serial"])
def test_a_one_chip_grant_keeps_the_job_on_that_chip(tmp_path, route,
                                                     request):
    request.getfixturevalue("serial" if route == "serial" else "sharded")
    job = make_job(tmp_path, seed=12)
    ref = copy_job(job, "ref")
    assert worker.run_job(job, devices=[devices()[2]]) == 0
    assert worker.run_job(ref) == 0
    st = results_of(job)["stats"]
    assert st["devices_granted"] == st["devices_used"] == [2]
    assert st["mesh_chips"] == 0
    assert output_bytes(job) == output_bytes(ref)


@pytest.mark.parametrize("service", [1], indirect=True)
def test_one_worker_meshes_one_job_over_the_four_chips(tmp_path, sharded,
                                                       service):
    """`--chips 4 --workers 1`: today's shape, one job's shards over all
    four chips."""
    _svc, port = service
    job = make_job(tmp_path, seed=13)
    ref = copy_job(job, "ref")
    st = post(port, job)["stats"]
    assert st["devices_granted"] == st["devices_used"] == [0, 1, 2, 3]
    assert st["mesh_chips"] == CHIPS and st["mesh_shards"] == 4
    assert worker.run_job(ref) == 0
    assert output_bytes(job) == output_bytes(ref)
    assert get(port, "/stats")["chip_jobs"] == [1] * CHIPS


@pytest.mark.parametrize("route", ["pipelined", "serial"])
def test_a_wedged_chip_demotes_its_job_with_the_same_bytes(
        tmp_path, route, request, monkeypatch):
    """Chip 1 fails at its first upload: the job granted it finishes on
    the default device, counts the demotion, and writes the same bytes."""
    from toplingdb_tpu.ops import mesh_compaction as mc

    request.getfixturevalue("serial" if route == "serial" else "sharded")
    job = make_job(tmp_path, seed=14)
    ref = copy_job(job, "ref")
    devs = devices()
    failed = []

    def hook(_s, device):
        if device is devs[1]:
            failed.append(device)
            raise RuntimeError("chip down")

    monkeypatch.setattr(mc, "_FAULT_HOOK", hook)
    assert worker.run_job(job, devices=[devs[1]]) == 0
    monkeypatch.setattr(mc, "_FAULT_HOOK", None)
    assert worker.run_job(ref) == 0
    st = results_of(job)["stats"]
    assert failed and st["mesh_fallbacks"] >= 1
    assert st["devices_granted"] == [1] and st["devices_used"] == [0]
    assert output_bytes(job) == output_bytes(ref)


def test_rotation_spreads_jobs_that_come_one_at_a_time(tmp_path, sharded,
                                                       service):
    """Jobs posted one after another each find every chip idle; the tie
    goes round, so each chip takes one."""
    _svc, port = service
    chips = []
    for i in range(CHIPS):
        st = post(port, make_job(tmp_path, name=f"j{i}", seed=20 + i))
        chips += st["stats"]["devices_used"]
    assert chips == [0, 1, 2, 3]
    assert get(port, "/stats")["chip_jobs"] == [1] * CHIPS


def test_the_pool_grants_in_rotation_and_waits_for_a_free_chip():
    pool = ChipPool(CHIPS, per_job=1)
    order = []
    for _ in range(2 * CHIPS):
        g = pool.admit()
        order += g
        pool.release(g)
    assert order == ["chip:%d" % (i % CHIPS) for i in range(2 * CHIPS)]
    held = [pool.admit() for _ in range(CHIPS)]
    assert sorted(c for g in held for c in g) == pool.chips
    assert pool.admit(timeout=0.1) == []   # all four held: none free now
    for g in held:
        pool.release(g)
    assert ChipPool(CHIPS, per_job=2).admit() == ["chip:0", "chip:1"]
    assert ChipPool(CHIPS).per_job == CHIPS


def test_the_stats_counters_add_up(tmp_path, sharded, service, monkeypatch):
    """/stats: with one-chip grants the chips' hold times add up to the
    time integral of the jobs holding a grant, to the microsecond; the
    job counts add up to the jobs; the grant waits are counted."""
    _svc, port = service
    before = get(port, "/stats")
    assert before["jobs_running_usec"] == 0
    assert before["chip_held_usec"] == [0] * CHIPS
    _seen, alone_again = side_by_side(monkeypatch, 2)
    post_all(port, [make_job(tmp_path, name=f"j{i}", seed=30 + i)
                    for i in range(2)])
    alone_again()
    post(port, make_job(tmp_path, name="j2", seed=32))
    st = get(port, "/stats")
    assert sum(st["chip_held_usec"]) == st["jobs_running_usec"] > 0
    assert sum(st["chip_jobs"]) == 3 == st["jobs_done"]
    assert st["grant_wait_usec"] >= 0
    assert st["chips"]["chip:0"]["held_usec"] == st["chip_held_usec"][0]
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
        body = r.read().decode()
    assert 'tpulsm_dcompact_chip_jobs{chip="chip:2"} 1' in body
