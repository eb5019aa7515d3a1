"""Parity: native fused merge+GC (tpulsm_merge_gc_runs) vs the two-pass
host twin (sort/merge + host_gc_mask) across randomized run mixes,
snapshots, covers, and complex (MERGE/SINGLE_DELETE) groups — including
the group-aligned splitter logic, forced multi-threaded via
TPULSM_MERGE_THREADS (a 1-CPU box would otherwise never exercise it)."""

import os

import numpy as np
import pytest

from toplingdb_tpu import native
from toplingdb_tpu.db.dbformat import ValueType
from toplingdb_tpu.ops import compaction_kernels as ck

pytestmark = pytest.mark.skipif(
    native.lib() is None or not hasattr(native.lib(), "tpulsm_merge_gc_runs"),
    reason="native fused merge+GC unavailable",
)


def _make_runs(rng, n_runs, per_run, key_space, p_merge=0.0, p_sd=0.0,
               p_del=0.15):
    """Columnar (key_buf, key_offs, key_lens, run_starts, seqs) of sorted
    runs with 8B decimal user keys."""
    bufs = []
    seq_counter = 1
    run_starts = [0]
    total = 0
    for _ in range(n_runs):
        draws = rng.integers(0, key_space, per_run)
        seqs = np.arange(seq_counter, seq_counter + per_run, dtype=np.uint64)
        seq_counter += per_run
        vts = np.full(per_run, int(ValueType.VALUE), dtype=np.uint64)
        r = rng.random(per_run)
        vts[r < p_del] = int(ValueType.DELETION)
        vts[r > 1 - p_merge] = int(ValueType.MERGE)
        vts[(r > p_del) & (r < p_del + p_sd)] = int(
            ValueType.SINGLE_DELETION)
        order = np.lexsort(
            (np.iinfo(np.int64).max - seqs.view(np.int64), draws))
        keys = []
        for i in order:
            uk = b"%08d" % draws[i]
            packed = (int(seqs[i]) << 8) | int(vts[i])
            keys.append(uk + packed.to_bytes(8, "little"))
        bufs.extend(keys)
        total += per_run
        run_starts.append(total)
    key_buf = np.frombuffer(b"".join(bufs), dtype=np.uint8)
    key_lens = np.full(total, 16, dtype=np.int64)
    key_offs = np.arange(total, dtype=np.int64) * 16
    return key_buf, key_offs, key_lens, np.array(run_starts, dtype=np.int64)


def _two_pass(key_buf, key_offs, key_lens, snapshots, bottommost, cover,
              run_starts):
    """The pre-fusion reference pipeline (native sort + numpy masks)."""
    s, new_key, seq, vtype = ck.host_sort_with_boundaries(
        key_buf, key_offs, key_lens, run_starts=run_starts)
    keep, zero_seq, host_resolve, _ = ck.host_gc_mask(
        new_key, seq[s], vtype[s], snapshots,
        None if cover is None else cover[s], bottommost)
    out = keep | host_resolve
    order = s[out].astype(np.int32)
    return (order, zero_seq[out], host_resolve[out],
            bool(host_resolve.any()), seq, vtype)


@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("case", [
    dict(n_runs=4, per_run=3000, key_space=1500, snaps=[], bottom=True),
    dict(n_runs=4, per_run=3000, key_space=1500, snaps=[2000, 7000],
         bottom=True),
    dict(n_runs=3, per_run=2000, key_space=400, snaps=[500, 1500, 3000],
         bottom=False),
    dict(n_runs=2, per_run=2500, key_space=800, snaps=[], bottom=True,
         p_merge=0.05, p_sd=0.03),
    dict(n_runs=5, per_run=1000, key_space=50, snaps=[1200], bottom=True,
         p_merge=0.02),
    dict(n_runs=4, per_run=1500, key_space=99999999, snaps=[], bottom=True),
])
def test_fused_matches_two_pass(case, threads, monkeypatch):
    monkeypatch.setenv("TPULSM_MERGE_THREADS", str(threads))
    rng = np.random.default_rng(42 + threads)
    kb, ko, kl, rs = _make_runs(
        rng, case["n_runs"], case["per_run"], case["key_space"],
        p_merge=case.get("p_merge", 0.0), p_sd=case.get("p_sd", 0.0))
    cover = None
    if case.get("with_cover"):
        cover = rng.integers(0, 5000, len(ko)).astype(np.uint64)
    got = ck.host_merge_gc(kb, ko, kl, case["snaps"], case["bottom"],
                           cover, rs)
    assert got is not None
    want = _two_pass(kb, ko, kl, case["snaps"], case["bottom"], cover, rs)
    np.testing.assert_array_equal(got[0], want[0], err_msg="order")
    # Two-pass zero flags on complex rows are PROVISIONAL (the caller
    # masks them with ~cx before use); the fused path emits the effective
    # value directly — compare post-mask semantics.
    np.testing.assert_array_equal(got[1], want[1] & ~want[2],
                                  err_msg="zero")
    np.testing.assert_array_equal(got[2], want[2], err_msg="cx")
    assert got[3] == want[3]
    np.testing.assert_array_equal(got[4], want[4], err_msg="seq")
    np.testing.assert_array_equal(got[5], want[5], err_msg="vtype")


@pytest.mark.parametrize("threads", [1, 4])
def test_fused_with_cover(threads, monkeypatch):
    """Range-tombstone cover input: covered rows drop unless complex."""
    monkeypatch.setenv("TPULSM_MERGE_THREADS", str(threads))
    rng = np.random.default_rng(7)
    kb, ko, kl, rs = _make_runs(rng, 4, 2000, 600, p_merge=0.02)
    cover = rng.integers(0, 9000, len(ko)).astype(np.uint64)
    cover[rng.random(len(ko)) < 0.5] = 0
    for snaps in ([], [3000], [1000, 5000]):
        got = ck.host_merge_gc(kb, ko, kl, snaps, True, cover, rs)
        want = _two_pass(kb, ko, kl, snaps, True, cover, rs)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1] & ~want[2])
        np.testing.assert_array_equal(got[2], want[2])


def test_fused_ineligible_long_keys():
    """>8B user keys must return None (two-pass path handles them)."""
    keys = [b"averylongkey1" + (1 << 8 | 1).to_bytes(8, "little"),
            b"averylongkey2" + (2 << 8 | 1).to_bytes(8, "little")]
    kb = np.frombuffer(b"".join(keys), dtype=np.uint8)
    kl = np.full(2, 21, dtype=np.int64)
    ko = np.arange(2, dtype=np.int64) * 21
    rs = np.array([0, 1, 2], dtype=np.int64)
    assert ck.host_merge_gc(kb, ko, kl, [], True, None, rs) is None
