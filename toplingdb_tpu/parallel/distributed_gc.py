"""Distributed compaction data plane: sample-sort + GC over a device mesh.

One compaction's key-range sharded over the 'range' mesh axis (the
subcompaction analogue), many independent jobs over the 'jobs' axis (the
dcompact analogue). The step is a single jitted shard_map program:

  1. local multi-operand sort of each shard's slice            (VPU)
  2. regular-sample splitters, all_gather over 'range'         (ICI)
  3. bucket partition + all_to_all redistribution              (ICI)
  4. local merge sort of received buckets                      (VPU)
  5. halo exchange of boundary (key, stripe) via ppermute      (ICI)
  6. vectorized GC mask (stripes / first-in-group)             (VPU)

Entries travel as fixed-width sort columns (key words + len + inv seqno
words); values never leave the host. Bucket skew is handled with a capacity
factor; overflow is reported per shard so the host can retry single-device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from toplingdb_tpu.db.dbformat import ValueType
from toplingdb_tpu.ops import device_runtime  # noqa: F401  (compile cache)

_SIGN = 0x80000000
INT32MAX = np.iinfo(np.int32).max


def _lex_less(a, b):
    """Lexicographic a < b over trailing column dim. a: [..., C], b: [..., C]."""
    # Walk columns from most-significant; strict-less decided at first diff.
    c = a.shape[-1]
    lt = jnp.zeros(a.shape[:-1], dtype=bool)
    eq = jnp.ones(a.shape[:-1], dtype=bool)
    for i in range(c):
        ai = a[..., i]
        bi = b[..., i]
        lt = lt | (eq & (ai < bi))
        eq = eq & (ai == bi)
    return lt


def _local_sort(cols, payload):
    """cols: [P, C] sort columns; payload: [P, K] carried along."""
    c = cols.shape[-1]
    k = payload.shape[-1]
    operands = tuple(cols[:, i] for i in range(c)) + tuple(
        payload[:, i] for i in range(k)
    )
    out = jax.lax.sort(operands, num_keys=c)
    return (
        jnp.stack(out[:c], axis=1),
        jnp.stack(out[c:], axis=1),
    )


def _gc_mask_local(cols, vtype, tomb_hi_i32, tomb_lo_i32, prev_last_cols,
                   prev_last_stripe, prev_valid, snap_hi, snap_lo,
                   bottommost):
    """Mask survivors within one locally-sorted shard; the halo (previous
    shard's last key/stripe) stitches group/stripe continuity. tomb_*:
    per-row max covering range-tombstone seqno words (rode the sort as
    payload; zero = uncovered)."""
    n = cols.shape[0]
    w = cols.shape[1] - 3  # key words + len + inv_hi + inv_lo
    key_cols = cols[:, : w + 1]  # words + len identify the user key
    prev_rows = jnp.roll(key_cols, 1, axis=0)
    prev_rows = prev_rows.at[0].set(prev_last_cols[: w + 1])
    same_key = jnp.all(key_cols == prev_rows, axis=1)
    same_key = jnp.where(
        jnp.arange(n) == 0, same_key & prev_valid, same_key
    )
    new_key = ~same_key

    u = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
    inv_hi = u(cols[:, w + 1]) ^ jnp.uint32(_SIGN)
    inv_lo = u(cols[:, w + 2]) ^ jnp.uint32(_SIGN)
    packed_hi = ~inv_hi
    packed_lo = ~inv_lo
    seq_hi = packed_hi >> 8
    seq_lo = (packed_hi << 24) | (packed_lo >> 8)
    snap_lt = (snap_hi[None, :] < seq_hi[:, None]) | (
        (snap_hi[None, :] == seq_hi[:, None]) & (snap_lo[None, :] < seq_lo[:, None])
    )
    stripe = jnp.sum(snap_lt, axis=1).astype(jnp.int32)
    prev_stripe = jnp.roll(stripe, 1)
    prev_stripe = prev_stripe.at[0].set(prev_last_stripe)
    first_in_stripe = new_key | (stripe != prev_stripe)

    # Range-tombstone shadowing: the SAME traced rule as the single-chip
    # GC mask (shared helper, so the two cannot diverge).
    from toplingdb_tpu.ops.compaction_kernels import _tomb_covered

    covered = _tomb_covered(seq_hi, seq_lo, u(tomb_hi_i32), u(tomb_lo_i32),
                            snap_hi, snap_lo, stripe)

    is_pad = vtype < 0
    keep = first_in_stripe & ~covered & ~is_pad
    drop_bottom_del = bottommost & (stripe == 0) & (vtype == int(ValueType.DELETION))
    keep = keep & ~drop_bottom_del
    zero_seq = keep & bottommost & (stripe == 0) & (vtype == int(ValueType.VALUE))
    # Complex rows (MERGE / SINGLE_DELETE) flag per row; the group-level
    # broadcast happens on the host, which sees the global sorted order
    # (groups may span shard boundaries).
    is_complex = ((vtype == int(ValueType.MERGE))
                  | (vtype == int(ValueType.SINGLE_DELETION))) & ~is_pad
    return keep, zero_seq, stripe, is_complex


def make_distributed_gc_step(mesh: Mesh, num_key_words: int,
                             bottommost: bool, capacity_factor: float = 2.0):
    """Builds the jitted multi-chip compaction step over `mesh` with axes
    ('jobs', 'range').

    Input (per job, stacked on the leading jobs axis):
      cols   [J, P, C] int32 — C = num_key_words + 3 sort columns
      vtype  [J, P]    int32 — value types (-1 = padding)
      idx    [J, P]    int32 — original entry indices (host value lookup)
      snap_hi/lo [S]   uint32 — padded snapshot words (replicated)
    Output:
      keep, zero_seq [J, P] bool; sorted idx [J, P]; overflow [J, R] int32
    """
    r = mesh.shape["range"]
    c = num_key_words + 3

    def step(cols, vtype, idx, tomb_hi, tomb_lo, snap_hi, snap_lo):
        j, p_local = vtype.shape  # inside shard_map: local job count, local rows

        def one_job(cols1, vtype1, idx1, th1, tl1):
            cap = int(capacity_factor * p_local / r) if r > 1 else p_local
            cap = max(cap, 1)
            payload = jnp.concatenate(
                [vtype1[:, None], idx1[:, None],
                 th1[:, None], tl1[:, None]], axis=1
            )
            cols_s, pay_s = _local_sort(cols1, payload)

            if r > 1:
                # --- splitters: sample r-1 local, all_gather, take global ---
                stride = max(p_local // r, 1)
                samples = cols_s[::stride][: r]  # [<=r, C]
                samples = jnp.pad(
                    samples, ((0, r - samples.shape[0]), (0, 0)),
                    constant_values=INT32MAX,
                )
                all_samples = jax.lax.all_gather(
                    samples, "range", tiled=True
                )  # [r*r, C]
                srt, _ = _local_sort(all_samples, jnp.zeros((r * r, 1), jnp.int32))
                splitters = srt[r:: r][: r - 1]  # [r-1, C] global splitters

                # --- bucket id per row: count of splitters <= row ---
                ge = ~_lex_less(
                    cols_s[:, None, :], splitters[None, :, :]
                )  # row >= splitter
                bucket = jnp.sum(ge, axis=1).astype(jnp.int32)  # [p_local]

                # --- scatter into [r, cap(+1 spill slot), C+K] ---
                # Pad rows (vtype -1 payload) don't consume capacity: they go
                # straight to the spill slot and are reconstructed as padding
                # on the receive side. Only real rows count toward overflow.
                is_pad_row = pay_s[:, 0] < 0
                onehot = jax.nn.one_hot(bucket, r, dtype=jnp.int32) * (
                    ~is_pad_row[:, None]
                )  # [p, r]
                pos = jnp.cumsum(onehot, axis=0) - onehot  # pos within bucket
                slot = jnp.sum(pos * onehot, axis=1)
                overflow = jnp.sum(
                    ((slot >= cap) & ~is_pad_row).astype(jnp.int32)
                )
                slot = jnp.where(is_pad_row, cap, jnp.minimum(slot, cap))
                send_cols = jnp.full((r, cap + 1, c), INT32MAX, dtype=jnp.int32)
                send_pay = jnp.full((r, cap + 1, 4), -1, dtype=jnp.int32)
                # Pad-slot cover words must be ZERO (not -1): an all-ones
                # word would read as a huge covering tombstone.
                send_pay = send_pay.at[:, :, 2:].set(0)
                send_cols = send_cols.at[bucket, slot].set(cols_s)
                send_pay = send_pay.at[bucket, slot].set(pay_s)
                send_cols = send_cols[:, :cap]
                send_pay = send_pay[:, :cap]

                # --- all_to_all over 'range' ---
                recv_cols = jax.lax.all_to_all(
                    send_cols, "range", split_axis=0, concat_axis=0, tiled=True
                ).reshape(r * cap, c)
                recv_pay = jax.lax.all_to_all(
                    send_pay, "range", split_axis=0, concat_axis=0, tiled=True
                ).reshape(r * cap, 4)
                cols_s, pay_s = _local_sort(recv_cols, recv_pay)
            else:
                overflow = jnp.zeros((), dtype=jnp.int32)

            return cols_s, pay_s, overflow

        cols_s, pay_s, overflow = jax.vmap(one_job)(cols, vtype, idx,
                                                    tomb_hi, tomb_lo)

        # --- halo: previous shard's last row (key cols + stripe) ---
        # Recompute stripe needs snapshots; do mask per job via vmap with halo.
        perm = [(i, (i + 1) % r) for i in range(r)]

        def job_mask(cols1, pay1):
            # Halo values: the last REAL (non-pad) row of this shard → next
            # shard. Pad rows sort to the shard's tail, so index by count.
            valid = pay1[:, 0] >= 0
            n_real = jnp.sum(valid.astype(jnp.int32))
            last_idx = jnp.maximum(n_real - 1, 0)
            last_cols = jnp.where(n_real > 0, cols1[last_idx],
                                  jnp.full((c,), INT32MAX, dtype=jnp.int32))
            u = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)
            w = c - 3
            inv_hi = u(last_cols[w + 1]) ^ jnp.uint32(_SIGN)
            packed_hi = ~inv_hi
            inv_lo = u(last_cols[w + 2]) ^ jnp.uint32(_SIGN)
            packed_lo = ~inv_lo
            seq_hi = packed_hi >> 8
            seq_lo = (packed_hi << 24) | (packed_lo >> 8)
            lt = (snap_hi < seq_hi) | ((snap_hi == seq_hi) & (snap_lo < seq_lo))
            last_stripe = jnp.sum(lt).astype(jnp.int32)
            return last_cols, last_stripe

        last_cols, last_stripe = jax.vmap(job_mask)(cols_s, pay_s)
        if r > 1:
            prev_cols = jax.lax.ppermute(last_cols, "range", perm)
            prev_stripe = jax.lax.ppermute(last_stripe, "range", perm)
            shard_idx = jax.lax.axis_index("range")
            prev_valid = shard_idx > 0
        else:
            prev_cols = jnp.full_like(last_cols, INT32MAX)
            prev_stripe = jnp.zeros_like(last_stripe)
            prev_valid = jnp.array(False)

        def job_final(cols1, pay1, pcols, pstripe):
            keep, zero_seq, stripe, is_cx = _gc_mask_local(
                cols1, pay1[:, 0], pay1[:, 2], pay1[:, 3], pcols, pstripe,
                prev_valid, snap_hi, snap_lo, bottommost,
            )
            return keep, zero_seq, pay1[:, 1], is_cx

        keep, zero_seq, sidx, is_cx = jax.vmap(job_final)(
            cols_s, pay_s, prev_cols, prev_stripe
        )
        # Total overflow per job across all source shards (psum over ICI).
        total_overflow = jax.lax.psum(overflow, "range")
        return keep, zero_seq, sidx, is_cx, total_overflow

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(
            P("jobs", "range", None), P("jobs", "range"), P("jobs", "range"),
            P("jobs", "range"), P("jobs", "range"),
            P(), P(),
        ),
        out_specs=(
            P("jobs", "range"), P("jobs", "range"), P("jobs", "range"),
            P("jobs", "range"), P("jobs"),
        ),
        check_vma=False,
    )
    return jax.jit(sharded)


def run_distributed_gc(mesh: Mesh, jobs: list, snapshots: list[int],
                       bottommost: bool):
    """Host driver: jobs = list of padded column dicts (ck.pad_columns).
    All jobs must share the padded length and word count; the jobs list is
    padded to the 'jobs' mesh dim. Jobs may carry a "tomb_cover" uint64
    array (per-row max covering tombstone seqno). Returns per-job
    (keep, zero_seq, sorted_idx, is_complex) numpy arrays in global
    sorted order; complex rows (MERGE/SINGLE_DELETE) are flagged per row —
    group-level resolution is the host's job (groups can span shards)."""
    from toplingdb_tpu.ops.compaction_kernels import _split_snapshots

    jdim = mesh.shape["jobs"]
    rdim = mesh.shape["range"]
    w = jobs[0]["w"]
    p = jobs[0]["key_words"].shape[0]
    p = max(p, rdim)  # at least one row per shard
    nj = len(jobs)
    jpad = -(-nj // jdim) * jdim
    cols = np.full((jpad, p, w + 3), INT32MAX, dtype=np.int32)
    vtype = np.full((jpad, p), -1, dtype=np.int32)
    # -1 marks pad rows even on range=1 meshes (no all_to_all refill).
    idx = np.full((jpad, p), -1, dtype=np.int32)
    tomb_hi = np.zeros((jpad, p), dtype=np.int32)
    tomb_lo = np.zeros((jpad, p), dtype=np.int32)
    for i, job in enumerate(jobs):
        n = job["key_words"].shape[0]
        cols[i, :n, :w] = job["key_words"]
        cols[i, :n, w] = job["key_len"]
        cols[i, :n, w + 1] = job["inv_hi"]
        cols[i, :n, w + 2] = job["inv_lo"]
        vtype[i, :n] = job["vtype"]
        n_real = job["n"]
        idx[i, :n_real] = np.arange(n_real, dtype=np.int32)
        cv = job.get("tomb_cover")
        if cv is not None and len(cv):
            from toplingdb_tpu.ops.compaction_kernels import _split_cover

            # Per ORIGINAL row (uint64): rides the sort as payload words.
            hi_w, lo_w = _split_cover(np.asarray(cv, dtype=np.uint64), p)
            tomb_hi[i] = hi_w.view(np.int32)
            tomb_lo[i] = lo_w.view(np.int32)
    snap_hi, snap_lo = _split_snapshots(snapshots)  # pow2 bucket pad >= 64

    step = make_distributed_gc_step(mesh, w, bottommost)
    keep, zero_seq, sidx, is_cx, overflow = step(
        cols, vtype, idx, tomb_hi, tomb_lo, snap_hi, snap_lo)
    if int(np.max(np.asarray(overflow))) > 0:
        from toplingdb_tpu.utils.status import TryAgain

        raise TryAgain("bucket overflow in distributed sort; retry 1-chip")
    return (
        np.asarray(keep)[:nj], np.asarray(zero_seq)[:nj],
        np.asarray(sidx)[:nj], np.asarray(is_cx)[:nj],
    )
