"""Pallas kernels vs their references (interpret mode on the CPU; the same
checks run compiled on the chip in chip_smoke.py)."""

import numpy as np
import pytest

from toplingdb_tpu.ops.pallas_kernels import shared_prefix_lengths


def ref_prefix(keys: list[bytes]) -> list[int]:
    out = [0]
    for a, b in zip(keys, keys[1:]):
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        out.append(n)
    return out


def to_matrix(keys, k=32):
    m = np.zeros((len(keys), k), dtype=np.uint8)
    for i, key in enumerate(keys):
        m[i, : len(key)] = np.frombuffer(key, dtype=np.uint8)
    return m, np.array([len(key) for key in keys], dtype=np.int32)


def test_prefix_kernel_matches_reference():
    keys = sorted(
        b"key%05d" % (i * 7 % 1000) for i in range(500)
    )
    m, lens = to_matrix(keys)
    got = shared_prefix_lengths(m, lens)
    assert got.tolist() == ref_prefix(keys)


def test_prefix_kernel_zero_padding_not_counted():
    # "ab" vs "ab\x00cd": zero padding of the shorter key must not extend
    # the shared prefix beyond its true length.
    keys = [b"ab", b"ab\x00cd"]
    m, lens = to_matrix(keys, k=8)
    got = shared_prefix_lengths(m, lens)
    assert got.tolist() == [0, 2]


def test_prefix_kernel_random():
    import random

    rng = random.Random(3)
    keys = sorted({rng.randbytes(rng.randint(1, 30)) for _ in range(700)})
    m, lens = to_matrix(keys)
    got = shared_prefix_lengths(m, lens)
    assert got.tolist() == ref_prefix(keys)


def test_prefix_kernel_single_and_empty():
    m, lens = to_matrix([b"solo"])
    assert shared_prefix_lengths(m, lens).tolist() == [0]


def test_gc_rows_matches_lax_mask():
    """pallas_kernels.gc_rows (interpret mode on CPU) must agree with the
    lax formulation of stripe / first-in-stripe / tombstone shadowing /
    complex flags for random sorted streams with snapshots+tombstones."""
    import jax.numpy as jnp
    import numpy as np

    from toplingdb_tpu.ops import pallas_kernels as pk

    rng = np.random.default_rng(5)
    n, s = 2048, 64
    seq = np.sort(rng.integers(0, 1 << 40, n).astype(np.uint64))[::-1]
    snaps = np.sort(rng.integers(0, 1 << 40, 5).astype(np.uint64))
    snap_pad = np.full(s, 1 << 56, np.uint64)
    snap_pad[:5] = snaps
    tomb = np.where(rng.random(n) < 0.3,
                    rng.integers(0, 1 << 40, n).astype(np.uint64),
                    np.uint64(0))
    vtype = rng.choice([0, 1, 2, 7], n).astype(np.int32)
    new_key = rng.random(n) < 0.4
    new_key[0] = True

    hi = lambda x: (x >> np.uint64(32)).astype(np.uint32)
    lo = lambda x: (x & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    pseq = np.roll(seq, 1)
    stripe, fis, covered, cx = pk.gc_rows(
        jnp.asarray(hi(seq)), jnp.asarray(lo(seq)),
        jnp.asarray(hi(pseq)), jnp.asarray(lo(pseq)),
        jnp.asarray(new_key), jnp.asarray(hi(tomb)), jnp.asarray(lo(tomb)),
        jnp.asarray(vtype), jnp.asarray(hi(snap_pad)),
        jnp.asarray(lo(snap_pad)), interpret=True,
    )
    # numpy reference
    want_stripe = np.searchsorted(snap_pad, seq, side="left")
    want_fis = new_key | (want_stripe != np.roll(want_stripe, 1))
    tomb_stripe = np.searchsorted(snap_pad, tomb, side="left")
    want_cov = (tomb != 0) & (tomb > seq) & (tomb_stripe == want_stripe)
    want_cx = (vtype == 2) | (vtype == 7)
    assert np.array_equal(np.asarray(stripe), want_stripe)
    assert np.array_equal(np.asarray(fis) | new_key, want_fis | new_key)
    assert np.array_equal(np.asarray(covered), want_cov)
    assert np.array_equal(np.asarray(cx), want_cx)
