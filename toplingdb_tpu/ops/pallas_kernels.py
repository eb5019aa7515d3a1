"""Pallas TPU kernels.

`gc_rows`: the per-row core of the MVCC GC mask, called from the fused
compaction program (compaction_kernels._gc_mask_impl) on accelerator
backends; its twin is the lax mask in the same function.

`shared_prefix_lengths`: the block-encoding prep op — shared-prefix lengths
between consecutive sorted keys, the per-entry scalar loop at the heart of
the reference's BlockBuilder::Add (table/block_based/block_builder.cc)
re-expressed as a VPU program: keys live as [N, 128] byte lanes (TPU-native
last dim), each row compared against the previous one.

Both run interpreted on the CPU (tests) and compiled by Mosaic on a TPU;
both were compiled on a TPU v5e with interpret=False and matched their
twins there (chip_smoke.py repeats that on every run). A kernel Mosaic
refuses is deleted, not kept for the interpreter: the bitonic run-merge
that used to live here went that way (PR 21, "unsupported shape cast").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from toplingdb_tpu.ops import device_runtime  # noqa: F401  (compile cache)

KEY_LANES = 128  # last-dim tile width on TPU
# 1024 rows per grid step: XLA lays out 1-D s32 outputs with tile
# T(min(n, 1024)), and the Mosaic block shape must match it exactly.
_BLOCK_ROWS = 1024


def _prefix_kernel(keys_ref, prev_ref, out_ref):
    keys = keys_ref[:]          # [B, 128] int32 (one byte per lane)
    prev = prev_ref[:]
    neq = keys != prev
    # Common prefix = index of the first differing lane (cumprod doesn't
    # lower in Mosaic; iota + reduce-min does).
    lane = jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    first_diff = jnp.min(
        jnp.where(neq, lane, jnp.int32(KEY_LANES)), axis=1
    )
    out_ref[:] = first_diff


@functools.partial(jax.jit, static_argnames=("interpret",))
def _shared_prefix_impl(keys, prev, interpret):
    from jax.experimental import pallas as pl

    n = keys.shape[0]
    grid = (n // _BLOCK_ROWS,)
    return pl.pallas_call(
        _prefix_kernel,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, KEY_LANES), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, KEY_LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_BLOCK_ROWS,), lambda i: (i,)),
        interpret=interpret,
    )(keys, prev)


def _gc_rows_kernel(seq_hi_ref, seq_lo_ref, pseq_hi_ref, pseq_lo_ref,
                    new_key_ref, tomb_hi_ref, tomb_lo_ref, vtype_ref,
                    snap_hi_ref, snap_lo_ref,
                    stripe_ref, fis_ref, covered_ref, cx_ref):
    """Per-row MVCC GC mask core (reference CompactionIterator::
    NextFromInput's visibility decisions, compaction_iterator.cc:475):
    snapshot stripe via a [B, S] broadcast compare against the resident
    snapshot words, first-in-stripe from the previous row's stripe, and
    same-stripe range-tombstone shadowing. All u32 compares run as two
    i32 word compares on the VPU; the group-complex propagation (a
    segment reduction across arbitrary spans) stays in lax."""
    i32 = jnp.int32
    # Signed-compare trick: XOR the sign bit so i32 < == u32 <.
    sign = jnp.int32(-0x80000000)
    sh = seq_hi_ref[:] ^ sign      # [B, 1]
    sl = seq_lo_ref[:] ^ sign
    ph = pseq_hi_ref[:] ^ sign
    pl_ = pseq_lo_ref[:] ^ sign
    th = tomb_hi_ref[:] ^ sign
    tl = tomb_lo_ref[:] ^ sign
    nh = snap_hi_ref[:] ^ sign     # [1, S]
    nl = snap_lo_ref[:] ^ sign

    def stripe_of(hi, lo):
        lt = (nh < hi) | ((nh == hi) & (nl < lo))
        return jnp.sum(lt.astype(i32), axis=1, keepdims=True)

    stripe = stripe_of(sh, sl)
    pstripe = stripe_of(ph, pl_)
    tstripe = stripe_of(th, tl)
    has_tomb = (tomb_hi_ref[:] | tomb_lo_ref[:]) != 0
    tomb_newer = (th > sh) | ((th == sh) & (tl > sl))
    covered = has_tomb & tomb_newer & (tstripe == stripe)
    fis = (new_key_ref[:] != 0) | (stripe != pstripe)
    vt = vtype_ref[:]
    cx = (vt == i32(2)) | (vt == i32(7))   # MERGE | SINGLE_DELETION
    stripe_ref[:] = stripe
    fis_ref[:] = fis.astype(i32)
    covered_ref[:] = covered.astype(i32)
    cx_ref[:] = cx.astype(i32)


_GC_BLOCK_ROWS = 1024


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gc_rows_impl(seq_hi, seq_lo, pseq_hi, pseq_lo, new_key,
                  tomb_hi, tomb_lo, vtype, snap_hi, snap_lo, interpret):
    from jax.experimental import pallas as pl

    n = seq_hi.shape[0]
    s = snap_hi.shape[0]
    grid = (n // _GC_BLOCK_ROWS,)
    row = lambda: pl.BlockSpec((_GC_BLOCK_ROWS, 1), lambda i: (i, 0))
    snap = lambda: pl.BlockSpec((1, s), lambda i: (0, 0))
    col = lambda a: a.reshape(n, 1)
    outs = pl.pallas_call(
        _gc_rows_kernel,
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32)] * 4,
        grid=grid,
        in_specs=[row(), row(), row(), row(), row(), row(), row(), row(),
                  snap(), snap()],
        out_specs=[row()] * 4,
        interpret=interpret,
    )(col(seq_hi), col(seq_lo), col(pseq_hi), col(pseq_lo), col(new_key),
      col(tomb_hi), col(tomb_lo), col(vtype),
      snap_hi.reshape(1, s), snap_lo.reshape(1, s))
    stripe, fis, covered, cx = (o.reshape(n) for o in outs)
    return stripe, fis, covered, cx


def gc_rows(seq_hi, seq_lo, pseq_hi, pseq_lo, new_key, tomb_hi, tomb_lo,
            vtype, snap_hi, snap_lo, interpret=None):
    """Traced entry: per-row (stripe, first_in_stripe, covered, complex)
    for SORTED u32 seqno word columns. Inputs may be traced jax arrays
    (called inside the fused compaction jit). Rows must be a multiple of
    1024 (the caller's pow2 padding guarantees >= that when used)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    u = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    stripe, fis, covered, cx = _gc_rows_impl(
        u(seq_hi), u(seq_lo), u(pseq_hi), u(pseq_lo),
        new_key.astype(jnp.int32), u(tomb_hi), u(tomb_lo),
        vtype.astype(jnp.int32), u(snap_hi), u(snap_lo),
        bool(interpret),
    )
    return stripe, fis != 0, covered != 0, cx != 0


def shared_prefix_lengths(key_bytes: np.ndarray,
                          key_lens: np.ndarray | None = None,
                          interpret: bool | None = None) -> np.ndarray:
    """out[i] = length of the common prefix of row i and row i-1 (out[0]=0).

    key_bytes: [N, K] uint8 (K <= 128), zero-padded rows of SORTED keys.
    key_lens: optional true lengths; the result is clamped to
    min(len[i], len[i-1]) so zero padding can't inflate prefixes.
    """
    n, k = key_bytes.shape
    if k > KEY_LANES:
        raise ValueError(f"keys wider than {KEY_LANES} bytes")
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    pad_n = -(-max(n, 1) // _BLOCK_ROWS) * _BLOCK_ROWS
    buf = np.zeros((pad_n, KEY_LANES), dtype=np.int32)
    buf[:n, :k] = key_bytes
    prev = np.zeros_like(buf)
    prev[1:] = buf[:-1]
    prev[0, :] = -1  # row 0 matches nothing
    out = np.asarray(_shared_prefix_impl(buf, prev, interpret))[:n]
    if key_lens is not None and n:
        lens = key_lens.astype(np.int64)
        cap = np.minimum(lens, np.roll(lens, 1))
        cap[0] = 0
        out = np.minimum(out, cap).astype(np.int32)
    return out
