"""chip_smoke.py and the rule it enforces: `device="tpu"` is the TPU or an
error. On this CPU every door to the chip must close loudly — the smoke, the
dcompact service, a device job — and a kernel that fails must raise out of
run_device_compaction instead of being retried behind the caller's back."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run(argv, timeout=600):
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=CPU_ENV,
                          capture_output=True, timeout=timeout)


def test_smoke_without_a_tpu_fails_and_names_the_platform():
    r = _run(["chip_smoke.py", "--keys", "1000"])
    assert r.returncode not in (0, 4)
    assert r.stdout.strip() == b"", "no result may be printed without a chip"
    assert b"'cpu'" in r.stderr, r.stderr[-600:]


def test_smoke_rehearsal_exercises_every_phase_and_is_never_a_pass():
    r = _run(["chip_smoke.py", "--rehearse-cpu", "--keys", "200000"])
    assert r.returncode == 4, r.stderr[-1500:].decode()
    lines = r.stdout.decode().strip().splitlines()
    last, record = json.loads(lines[-1]), json.loads(lines[-2])
    assert last["ok"] is False and last["rehearsal_ok"] is True
    assert last["device"]["platform"] == "cpu"
    assert record["ok"] is False and record["rehearsal"] is True
    phases = {p["phase"]: p for p in record["phases"]}
    assert all(p["ok"] for p in phases.values())
    for name in ("native_build", "service_start", "fillrandom", "overwrite",
                 "compact_range", "queries", "reopen_and_queries",
                 "witnesses", "byte_parity_tpu_vs_cpu_worker",
                 "sequence_span_job", "fresh_worker_compiles_nothing",
                 "pallas_kernels"):
        assert name in phases, name
    assert phases["queries"]["gets"] >= 10_000
    assert phases["queries"]["get_misses"] > 0
    assert phases["witnesses"]["dcompaction_fallback_local"] == 0
    assert phases["byte_parity_tpu_vs_cpu_worker"]["output_bytes"] > 0
    assert phases["sequence_span_job"]["output_bytes"] > 0
    for tag in ("job", "span"):
        assert phases["fresh_worker_compiles_nothing"][tag][
            "jit_compiles"] == 0
    assert record["reduced"], "the cut of the source's scale is stated"


def test_smoke_oracle_is_last_writer_wins():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    wl = chip_smoke.Workload(500, seed=7)
    last = {}
    for w, k in enumerate(wl.key_of.tolist()):
        last[k] = w
    assert sorted(last) == list(range(500))  # fillrandom: every key once
    import numpy as np

    keys = np.array([0, 17, 499, 500, 999], dtype=np.uint64)
    got = wl.expected(keys)
    assert got[3] is None and got[4] is None
    for k, v in zip((0, 17, 499), got):
        want = wl.value_bytes(np.array([k], np.uint64),
                              np.array([last[k]], np.uint64))
        assert v == want.tobytes() and len(v) == 20
        assert int.from_bytes(v[:8], "little") == last[k]


def test_service_asked_for_a_tpu_exits_before_listening():
    r = _run(["-m", "toplingdb_tpu.compaction.dcompact_service",
              "--device", "tpu", "--port", "0", "--host", "127.0.0.1"],
             timeout=120)
    assert r.returncode != 0
    assert b"listening" not in r.stdout
    assert b"'cpu'" in r.stderr


def _two_run_job(tmp_path, rows=1500):
    """Two overlapping sorted runs of uniform 8 B keys as real SSTs."""
    import toplingdb_tpu.db.filename as fn
    from toplingdb_tpu.compaction.picker import Compaction
    from toplingdb_tpu.db.dbformat import (
        InternalKeyComparator, ValueType, make_internal_key,
    )
    from toplingdb_tpu.db.table_cache import TableCache
    from toplingdb_tpu.db.version_edit import FileMetaData
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions

    env, icmp, topts = default_env(), InternalKeyComparator(), TableOptions()
    dbdir = str(tmp_path)
    metas = []
    for run, fnum in enumerate((11, 12)):
        w = env.new_writable_file(fn.table_file_name(dbdir, fnum))
        b = TableBuilder(w, icmp, topts)
        for i in range(rows):
            b.add(make_internal_key(b"%08d" % (2 * i + run),
                                    run * rows + i + 1, ValueType.VALUE),
                  b"v%07d" % i)
        props = b.finish()
        w.close()
        metas.append(FileMetaData(
            number=fnum,
            file_size=env.get_file_size(fn.table_file_name(dbdir, fnum)),
            smallest=b.smallest_key, largest=b.largest_key,
            smallest_seqno=props.smallest_seqno,
            largest_seqno=props.largest_seqno,
            num_entries=props.num_entries))
    c = Compaction(level=0, output_level=1, inputs=metas, bottommost=True,
                   max_output_file_size=1 << 30)
    return env, dbdir, icmp, c, TableCache(env, dbdir, icmp, topts), topts


def _alloc(start=100):
    state = [start]

    def alloc():
        state[0] += 1
        return state[0]

    return alloc


def test_a_tpu_job_on_xla_cpu_raises_instead_of_returning_tpu_stats(tmp_path):
    from toplingdb_tpu.ops.device_compaction import run_device_compaction
    from toplingdb_tpu.utils.status import InvalidArgument, NotSupported

    env, dbdir, icmp, c, tc, topts = _two_run_job(tmp_path, rows=50)
    with pytest.raises(NotSupported, match="'cpu'"):
        run_device_compaction(env, dbdir, icmp, c, tc, topts, [],
                              new_file_number=_alloc(), creation_time=1,
                              device_name="tpu")
    with pytest.raises(InvalidArgument):
        run_device_compaction(env, dbdir, icmp, c, tc, topts, [],
                              new_file_number=_alloc(), creation_time=1,
                              device_name="gpu?")
    assert [f for f in os.listdir(dbdir) if f.endswith(".sst")] == \
        ["000011.sst", "000012.sst"], "a refused job writes nothing"


def test_a_failing_kernel_raises_and_leaves_the_environment_alone(
        tmp_path, monkeypatch):
    """Regression for the deleted retry: it used to switch the kernels
    through os.environ, clear the jit caches and run the job again, so a
    Mosaic refusal never reached a caller."""
    import jax

    from toplingdb_tpu.ops import compaction_kernels as ck
    from toplingdb_tpu.ops import pallas_kernels
    from toplingdb_tpu.ops.device_compaction import run_device_compaction

    class MosaicRefusal(RuntimeError):
        pass

    def refuse(*_a, **_kw):
        raise MosaicRefusal("unsupported shape cast")

    # the accelerator's program, on this backend
    monkeypatch.setattr(ck, "_want_pallas_gc", lambda: True)
    monkeypatch.setattr(pallas_kernels, "gc_rows", refuse)
    env, dbdir, icmp, c, tc, topts = _two_run_job(tmp_path)
    jax.clear_caches()  # the kernel choice is made at trace time
    before = dict(os.environ)
    try:
        with pytest.raises(MosaicRefusal):
            run_device_compaction(env, dbdir, icmp, c, tc, topts, [],
                                  new_file_number=_alloc(), creation_time=1,
                                  device_name="cpu-jax")
    finally:
        jax.clear_caches()
    assert dict(os.environ) == before
