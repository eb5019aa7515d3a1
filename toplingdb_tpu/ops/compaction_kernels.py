"""Device kernels: sort-merge + MVCC GC masking.

The k-way merge + CompactionIterator state machine (reference
table/merging_iterator.cc + db/compaction/compaction_iterator.cc:475),
re-expressed as two jitted array programs:

  pad_columns(...) + device_sort(...)   one multi-operand `jax.lax.sort`
      realizes internal-key order over all input runs at once (the whole
      merge); sorted columns stay on device for the GC kernel.
  gc_mask(...)   survivor decisions as shifted/segment comparisons over the
      sorted stream — no data-dependent control flow.

Shapes are padded to the next power of two so XLA compiles one program per
size bucket, not per job. All lanes are 32-bit (TPU-native); 64-bit packed
(seqno,type) values travel as hi/lo uint32 word pairs.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from toplingdb_tpu.db.dbformat import ValueType
from toplingdb_tpu.ops import device_runtime  # noqa: F401  (compile cache)
from toplingdb_tpu.utils import concurrency as ccy
from toplingdb_tpu.utils.status import NotSupported

_SIGN = 0x80000000
# Stripe computation is an [N, S] broadcast compare, linear in the padded
# snapshot count; pad to pow2 buckets (>=64) so the jit cache stays small
# and typical jobs pay the 64-wide compare. Above the cap the scheduler
# falls back to the host path.
MAX_SNAPSHOTS = 1024
_MIN_SNAP_BUCKET = 64



def _split_snapshots(snapshots: list[int]) -> tuple[np.ndarray, np.ndarray]:
    """Sorted snapshot seqnos padded to the next pow2 bucket (>=64) with the
    2^56 sentinel, split into (hi, lo) uint32 word arrays for the device
    kernels."""
    pad_snap = 1 << 56
    bucket = _MIN_SNAP_BUCKET
    while bucket < len(snapshots):
        bucket *= 2
    snaps = sorted(snapshots) + [pad_snap] * (bucket - len(snapshots))
    snap_hi = np.array([x >> 32 for x in snaps], dtype=np.uint32)
    snap_lo = np.array([x & 0xFFFFFFFF for x in snaps], dtype=np.uint32)
    return snap_hi, snap_lo


def _split_cover(cover: np.ndarray, p: int):
    """uint64 per-row max-covering-tombstone seqnos → (hi, lo) u32 word
    arrays padded to p rows (shared by the single-chip and mesh drivers)."""
    tc = np.zeros(p, dtype=np.uint64)
    tc[: len(cover)] = cover
    return ((tc >> np.uint64(32)).astype(np.uint32),
            (tc & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def _tomb_covered(seq_hi, seq_lo, tomb_hi, tomb_lo, snap_hi, snap_lo,
                  stripe):
    """Same-stripe range-tombstone shadowing (traced; shared by the
    single-chip GC mask and the mesh kernel so they cannot diverge)."""
    has_tomb = (tomb_hi | tomb_lo) != 0
    tomb_newer = (tomb_hi > seq_hi) | ((tomb_hi == seq_hi)
                                       & (tomb_lo > seq_lo))
    tsnap_lt = (snap_hi[None, :] < tomb_hi[:, None]) | (
        (snap_hi[None, :] == tomb_hi[:, None])
        & (snap_lo[None, :] < tomb_lo[:, None])
    )
    tomb_stripe = jnp.sum(tsnap_lt, axis=1).astype(jnp.int32)
    return has_tomb & tomb_newer & (tomb_stripe == stripe)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _want_pallas_gc() -> bool:
    """Use the Pallas GC-row kernel inside _gc_mask_impl. Decided at TRACE
    time (the jit cache does not key on this): ON for accelerator
    backends, OFF on cpu (where interpret mode would crawl)."""
    return jax.default_backend() != "cpu"


def pad_columns(col) -> dict:
    """Pad a ColumnarEntries to the next power of two. Sentinel rows sort
    last (int32 max keys) and carry vtype=-1."""
    n = col.n
    p = _next_pow2(max(1, n))
    w = col.key_words.shape[1]
    int32max = np.iinfo(np.int32).max
    out = {
        "n": n, "w": w,
        "key_words": np.full((p, w), int32max, dtype=np.int32),
        "key_len": np.full(p, int32max, dtype=np.int32),
        "inv_hi": np.full(p, int32max, dtype=np.int32),
        "inv_lo": np.full(p, int32max, dtype=np.int32),
        "vtype": np.full(p, -1, dtype=np.int32),
    }
    out["key_words"][:n] = col.key_words
    out["key_len"][:n] = col.key_len
    out["inv_hi"][:n] = col.inv_hi
    out["inv_lo"][:n] = col.inv_lo
    out["vtype"][:n] = col.vtype
    return out


# ---------------------------------------------------------------------------
# Sort
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("num_key_words",))
def _sort_impl(key_words, key_len, inv_hi, inv_lo, vtype, idx, num_key_words):
    operands = tuple(key_words[:, w] for w in range(num_key_words)) + (
        key_len, inv_hi, inv_lo, vtype, idx,
    )
    out = jax.lax.sort(operands, num_keys=num_key_words + 3)
    key_words_sorted = jnp.stack(out[:num_key_words], axis=1)
    key_len_s, inv_hi_s, inv_lo_s, vtype_s, perm = out[num_key_words:]
    return key_words_sorted, key_len_s, inv_hi_s, inv_lo_s, vtype_s, perm


def device_sort(padded: dict):
    """Sort padded columns into internal-key order on device. Returns a dict
    of SORTED on-device columns (padding rows last) plus the permutation of
    original indices as np.ndarray[:n]."""
    p = padded["key_words"].shape[0]
    idx = np.arange(p, dtype=np.int32)
    kw, kl, ih, il, vt, perm = _sort_impl(
        padded["key_words"], padded["key_len"], padded["inv_hi"],
        padded["inv_lo"], padded["vtype"], idx, padded["w"],
    )
    sorted_cols = {
        "n": padded["n"], "w": padded["w"],
        "key_words": kw, "key_len": kl, "inv_hi": ih, "inv_lo": il,
        "vtype": vt,
    }
    return sorted_cols, np.asarray(perm)[: padded["n"]]


@functools.partial(jax.jit, static_argnames=("num_key_words",))
def _gc_mask_impl(key_words, key_len, inv_hi, inv_lo, vtype,
                  snap_hi, snap_lo, tomb_hi, tomb_lo,
                  num_key_words, bottommost):
    """All inputs are SORTED columns (internal-key order, padded).
    tomb_hi/lo: per-entry max covering tombstone seqno words (0 = none).
    `bottommost` is a traced scalar, not a static argument: it only gates
    two masks, and a second copy of every fused program for it would cost
    a second compile (~100 s each on a v5e, PERF.md).
    Returns keep, zero_seq, host_resolve, group_id (all padded length)."""
    n = key_words.shape[0]
    bottommost = jnp.asarray(bottommost, dtype=bool)
    u = lambda x: jax.lax.bitcast_convert_type(x, jnp.uint32)

    # --- group boundaries: user key change ---
    prev_words = jnp.roll(key_words, 1, axis=0)
    same_words = jnp.all(key_words == prev_words, axis=1)
    same_len = key_len == jnp.roll(key_len, 1)
    same_key = (same_words & same_len).at[0].set(False)
    new_key = ~same_key
    group_id = jnp.cumsum(new_key.astype(jnp.int32)) - 1

    # --- seqno recovery: packed = ~inv (64-bit), seq = packed >> 8 ---
    inv_hi_u = u(inv_hi) ^ jnp.uint32(_SIGN)
    inv_lo_u = u(inv_lo) ^ jnp.uint32(_SIGN)
    packed_hi = ~inv_hi_u
    packed_lo = ~inv_lo_u
    seq_hi = packed_hi >> 8                                   # top 24 bits
    seq_lo = (packed_hi << 24) | (packed_lo >> 8)             # low 32 bits

    if _want_pallas_gc() and n % 1024 == 0 and tomb_hi.shape[0] == n:
        # Pallas VPU kernel for the per-row mask core (stripe /
        # first-in-stripe / tombstone shadowing / complex flag); the
        # group-complex segment reduction below stays in lax.
        from toplingdb_tpu.ops import pallas_kernels as _pk

        stripe, first_in_stripe, covered, is_complex = _pk.gc_rows(
            seq_hi, seq_lo, jnp.roll(seq_hi, 1), jnp.roll(seq_lo, 1),
            new_key, tomb_hi, tomb_lo, vtype, snap_hi, snap_lo,
        )
        first_in_stripe = first_in_stripe | new_key
    else:
        # --- snapshot stripe: count of snapshots strictly below seq ---
        # snap arrays sorted ascending, padded with 2^56 (never < any seq).
        s_hi = snap_hi[None, :]
        s_lo = snap_lo[None, :]
        e_hi = seq_hi[:, None]
        e_lo = seq_lo[:, None]
        snap_lt = (s_hi < e_hi) | ((s_hi == e_hi) & (s_lo < e_lo))
        stripe = jnp.sum(snap_lt, axis=1).astype(jnp.int32)

        # --- first-in-(group, stripe): the only candidate survivor ---
        prev_stripe = jnp.roll(stripe, 1)
        first_in_stripe = new_key | (stripe != prev_stripe)

        # --- tombstone coverage (same-stripe shadowing) ---
        covered = _tomb_covered(seq_hi, seq_lo, tomb_hi, tomb_lo,
                                snap_hi, snap_lo, stripe)

        # --- complex groups: MERGE or SINGLE_DELETION → host resolves ---
        is_complex = (vtype == int(ValueType.MERGE)) | (
            vtype == int(ValueType.SINGLE_DELETION)
        )
    group_complex = jax.ops.segment_max(
        is_complex.astype(jnp.int32), group_id, num_segments=n,
        indices_are_sorted=True,
    )
    host_resolve = group_complex[group_id] > 0

    # --- survivor rules (simple groups) ---
    is_pad = vtype < 0
    keep = first_in_stripe & ~covered & ~is_pad
    drop_bottom_del = (
        bottommost
        & (stripe == 0)
        & (vtype == int(ValueType.DELETION))
    )
    keep = keep & ~drop_bottom_del
    zero_seq = (
        keep
        & bottommost
        & (stripe == 0)
        & (vtype == int(ValueType.VALUE))
    )
    keep = keep & ~host_resolve
    return keep, zero_seq, host_resolve & ~is_pad, group_id



def _sort_gc_compact_tail(key_words, key_len, inv_hi, inv_lo, vtype,
                          snap_hi, snap_lo, num_key_words, bottommost,
                          tomb_hi_orig=None, tomb_lo_orig=None):
    """Traced tail of the whole-job program: sort → GC mask → survivors
    compacted to the front in sorted order. Rows of complex groups (MERGE /
    SINGLE_DELETE present) are INCLUDED in the output stream, flagged via
    cx_flags, so the host can fold them without abandoning the columnar
    path. tomb_*_orig: per-ORIGINAL-index max covering tombstone seqno
    words (None = tombstone-free job)."""
    n = key_words.shape[0]
    idxs = jnp.arange(n, dtype=jnp.int32)
    kw, kl, ih, il, vt, perm = _sort_impl(
        key_words, key_len, inv_hi, inv_lo, vtype, idxs, num_key_words
    )
    if tomb_hi_orig is None:
        tomb_hi = tomb_lo = jnp.zeros(n, dtype=jnp.uint32)
    else:
        tomb_hi = tomb_hi_orig[perm]
        tomb_lo = tomb_lo_orig[perm]
    keep, zero_seq, host_resolve, _ = _gc_mask_impl(
        kw, kl, ih, il, vt, snap_hi, snap_lo, tomb_hi, tomb_lo,
        num_key_words, bottommost,
    )
    out = keep | host_resolve
    take = jnp.argsort(~out, stable=True)
    order = perm[take]
    zero_flags = zero_seq[take]
    cx_flags = host_resolve[take]
    count = jnp.sum(out.astype(jnp.int32))
    has_complex = jnp.any(host_resolve)
    return order, zero_flags, cx_flags, count, has_complex


def host_sort_order(key_buf: np.ndarray, key_offs: np.ndarray,
                    key_lens: np.ndarray, run_starts=None):
    """(order, new_key, packed) via the native byte-span comparator —
    same order as the device sort; `packed` = per-ORIGINAL-index
    (seq<<8|type) trailers so callers skip re-gathering them in numpy.
    With `run_starts` ([R+1] boundaries of PRESORTED input runs), the
    multi-threaded k-way run merge replaces the full sort (the
    reference's heap-merge role).
    None when the native lib is unavailable."""
    import ctypes

    from toplingdb_tpu import native

    lib = native.lib()
    if lib is None or not hasattr(lib, "tpulsm_sort_entries"):
        return None
    n = len(key_offs)
    offs = np.ascontiguousarray(key_offs, dtype=np.int64)
    lens = np.ascontiguousarray(key_lens, dtype=np.int64)
    kb = np.ascontiguousarray(key_buf)
    order = np.empty(n, dtype=np.int32)
    new_key = np.empty(n, dtype=np.uint8)
    packed = np.empty(n, dtype=np.uint64)
    rc = -1
    if (run_starts is not None and len(run_starts) > 1 and n
            and hasattr(lib, "tpulsm_merge_runs")):
        rs = np.ascontiguousarray(run_starts, dtype=np.int64)
        # Malformed boundaries would leave output rows unmerged (silent
        # corruption) or index past the entry array in C: validate here,
        # falling back to the full sort.
        if (int(rs[0]) != 0 or int(rs[-1]) != n
                or not np.all(np.diff(rs) >= 0)):
            rs = None
    else:
        rs = None
    if rs is not None:
        rc = lib.tpulsm_merge_runs(
            native.np_u8p(kb), native.np_i64p(offs), native.np_i64p(lens),
            n, native.np_i64p(rs), len(rs) - 1,
            native.np_i32p(order), native.np_u8p(new_key),
            packed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        )
    if rc != 0:
        rc = lib.tpulsm_sort_entries(
            native.np_u8p(kb), native.np_i64p(offs), native.np_i64p(lens),
            n, native.np_i32p(order), native.np_u8p(new_key),
            packed.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        )
    if rc != 0:
        return None
    return order, new_key.astype(bool), packed


def host_merge_gc(key_buf, key_offs, key_lens, snapshots, bottommost,
                  cover, run_starts):
    """ONE native pass: k-way merge of presorted runs + inline GC mask —
    returns the host_fused_full 6-tuple, or None when the native fused
    routine is unavailable/ineligible (then the two-pass path runs)."""
    import ctypes

    from toplingdb_tpu import native

    lib = native.lib()
    if lib is None or not hasattr(lib, "tpulsm_merge_gc_runs"):
        return None
    if run_starts is None or len(run_starts) < 2:
        return None
    n = len(key_offs)
    rs = np.ascontiguousarray(run_starts, dtype=np.int64)
    if int(rs[0]) != 0 or int(rs[-1]) != n or not np.all(np.diff(rs) >= 0):
        return None
    offs = np.ascontiguousarray(key_offs, dtype=np.int64)
    lens = np.ascontiguousarray(key_lens, dtype=np.int64)
    kb = np.ascontiguousarray(key_buf)
    order = np.empty(n, dtype=np.int32)
    zero = np.empty(n, dtype=np.uint8)
    cx = np.empty(n, dtype=np.uint8)
    packed = np.empty(n, dtype=np.uint64)
    hc = np.zeros(1, dtype=np.int32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    snaps = np.asarray(sorted(snapshots), dtype=np.uint64)
    cov = (np.ascontiguousarray(cover, dtype=np.uint64)
           if cover is not None else None)
    n_out = lib.tpulsm_merge_gc_runs(
        native.np_u8p(kb), native.np_i64p(offs), native.np_i64p(lens), n,
        native.np_i64p(rs), len(rs) - 1,
        snaps.ctypes.data_as(u64p) if len(snaps) else None, len(snaps),
        cov.ctypes.data_as(u64p) if cov is not None else None,
        1 if bottommost else 0,
        native.np_i32p(order), native.np_u8p(zero), native.np_u8p(cx),
        packed.ctypes.data_as(u64p), native.np_i32p(hc),
    )
    if n_out < 0:
        return None
    seq = packed >> np.uint64(8)
    vtype = (packed & np.uint64(0xFF)).astype(np.int32)
    return (order[:n_out], zero[:n_out].astype(bool),
            cx[:n_out].astype(bool), bool(hc[0]), seq, vtype)


def host_gc_mask(new_key, sseq, svt, snapshots, cover, bottommost):
    """NumPy twin of the GC mask over SORTED columns; `new_key` marks
    user-key group starts, `cover` is the per-sorted-entry stripe-clamped
    max covering tombstone seq (or None). Returns (keep, zero_seq,
    host_resolve, group_id) like gc_mask."""
    n = len(sseq)
    snaps = np.asarray(sorted(snapshots), dtype=np.uint64)
    stripe = np.searchsorted(snaps, sseq, side="left").astype(np.int64)
    first_in_stripe = new_key.copy()
    if n > 1:
        first_in_stripe[1:] |= stripe[1:] != stripe[:-1]

    is_complex = (svt == int(ValueType.MERGE)) | (
        svt == int(ValueType.SINGLE_DELETION)
    )
    group_id = np.cumsum(new_key) - 1
    starts = np.flatnonzero(new_key)
    group_complex = (np.bitwise_or.reduceat(is_complex, starts)
                     if n else np.zeros(0, dtype=bool))
    host_resolve = group_complex[group_id] if n else is_complex

    covered = np.zeros(n, dtype=bool)
    if cover is not None:
        c = np.asarray(cover, dtype=np.uint64)
        covered = (c != 0) & (c > sseq)  # cover is stripe-clamped already

    keep = first_in_stripe & ~covered
    if bottommost:
        keep &= ~((stripe == 0) & (svt == int(ValueType.DELETION)))
    zero_seq = (
        keep & bool(bottommost) & (stripe == 0)
        & (svt == int(ValueType.VALUE))
    )
    keep &= ~host_resolve
    return keep, zero_seq, host_resolve, group_id


def fused_encode_sort_gc_host(key_buf: np.ndarray, key_offs: np.ndarray,
                              key_lens: np.ndarray, max_key_bytes: int,
                              snapshots: list[int], bottommost: bool,
                              cover: np.ndarray | None = None):
    """Host twin of fused_encode_sort_gc (same 4-tuple contract)."""
    r = host_fused_full(key_buf, key_offs, key_lens, max_key_bytes,
                        snapshots, bottommost, cover)
    return r[0], r[1], r[2], r[3]


def host_fused_full(key_buf: np.ndarray, key_offs: np.ndarray,
                    key_lens: np.ndarray, max_key_bytes: int,
                    snapshots: list[int], bottommost: bool,
                    cover: np.ndarray | None = None, run_starts=None):
    """Host twin of the fused kernel for accelerator-less deployments
    (TPULSM_HOST_SORT=1): native order + vectorized GC mask — outputs
    identical to the jax path (parity-tested; `max_key_bytes` is the jax
    twin's argument and unused here). `cover`: optional
    per-ORIGINAL-row uint64 max covering tombstone seqno. Returns
    (order, zero_flags, cx_flags, has_complex, seq, vtype) with seq/vtype
    per ORIGINAL index so callers skip their own trailer gather; `order`
    includes complex-group rows, flagged by cx_flags."""
    if len(snapshots) > MAX_SNAPSHOTS:
        raise NotSupported(
            f"device GC supports <= {MAX_SNAPSHOTS} live snapshots"
        )
    n = len(key_offs)
    if n == 0:
        e = np.empty(0, np.uint64)
        return (np.empty(0, np.int32), np.empty(0, bool),
                np.empty(0, bool), False, e, e.astype(np.int32))
    fused = host_merge_gc(key_buf, key_offs, key_lens, snapshots,
                          bottommost, cover, run_starts)
    if fused is not None:
        return fused
    s, new_key, seq, vtype = host_sort_with_boundaries(
        key_buf, key_offs, key_lens, run_starts=run_starts
    )
    keep, zero_seq, host_resolve, _ = host_gc_mask(
        new_key, seq[s], vtype[s], snapshots,
        None if cover is None else cover[s], bottommost
    )
    out = keep | host_resolve
    order = s[out].astype(np.int32)
    zero_flags = zero_seq[out]
    cx_flags = host_resolve[out]
    return order, zero_flags, cx_flags, bool(host_resolve.any()), seq, vtype


def host_sort_with_boundaries(key_buf, key_offs, key_lens, run_starts=None):
    """Shared host-path front half: (s, new_key, seq, vtype) through the
    native comparator; NotSupported without the native library."""
    nat = host_sort_order(key_buf, key_offs, key_lens,
                          run_starts=run_starts)
    if nat is None:
        raise NotSupported("the host twin needs the native sort")
    s, new_key, packed = nat
    seq = packed >> np.uint64(8)
    vtype = (packed & np.uint64(0xFF)).astype(np.int32)
    return s, new_key, seq, vtype


def _encode_from_bytes(key_buf, key_offs, key_lens, valid, num_key_words):
    """Shared traced encode from raw internal-key bytes: trailer unpack +
    BE user-key word pack, invalid rows masked to the int32max sentinel.
    Returns (key_words, key_len, inv_hi, inv_lo, vtype)."""
    n = key_lens.shape[0]
    span = num_key_words * 4
    u32 = jnp.uint32
    sign = u32(_SIGN)
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    int32max = jnp.int32(2**31 - 1)

    # --- trailer: 8 LE bytes at offs+len-8 → packed (seq<<8|type) ---
    tr_idx = (key_offs + key_lens - 8)[:, None] + jnp.arange(8)[None, :]
    tr = key_buf[jnp.clip(tr_idx, 0, key_buf.shape[0] - 1)].astype(u32)
    packed_lo = tr[:, 0] | (tr[:, 1] << 8) | (tr[:, 2] << 16) | (tr[:, 3] << 24)
    packed_hi = tr[:, 4] | (tr[:, 5] << 8) | (tr[:, 6] << 16) | (tr[:, 7] << 24)
    vtype = jnp.where(valid, (packed_lo & u32(0xFF)).astype(jnp.int32), -1)
    inv_hi = jnp.where(valid, i32(~packed_hi ^ sign), int32max)
    inv_lo = jnp.where(valid, i32(~packed_lo ^ sign), int32max)

    # --- user-key words: gather span bytes, mask past uk_len, pack BE ---
    uk_len = (key_lens - 8).astype(jnp.int32)
    idx = key_offs[:, None] + jnp.arange(span)[None, :]
    kb = key_buf[jnp.clip(idx, 0, key_buf.shape[0] - 1)].astype(u32)
    kb = kb * (jnp.arange(span)[None, :] < uk_len[:, None])
    kb = kb.reshape(n, num_key_words, 4)
    words = (kb[:, :, 0] << 24) | (kb[:, :, 1] << 16) | (kb[:, :, 2] << 8) | kb[:, :, 3]
    key_words = jnp.where(valid[:, None], i32(words ^ sign), int32max)
    key_len = jnp.where(valid, uk_len, int32max)
    return key_words, key_len, inv_hi, inv_lo, vtype



@functools.partial(
    jax.jit, static_argnames=("num_key_words", "bottommost", "has_tombs")
)
def _fused_encode_sort_gc_impl(key_buf, key_lens, valid, tomb_hi, tomb_lo,
                               snap_hi, snap_lo, num_key_words, bottommost,
                               has_tombs):
    """Columnar encode + sort + GC mask, all ON DEVICE: the host uploads raw
    internal-key bytes + lengths only (entries are densely packed, so the
    offsets are an on-device exclusive cumsum) and downloads the survivor
    order. With has_tombs, tomb_hi/lo carry each original row's max
    covering range-tombstone seqno words (the host interval-maps the few
    fragments over the sorted input parts)."""
    key_offs = jnp.cumsum(key_lens) - key_lens  # dense layout: offs from lens
    key_words, key_len, inv_hi, inv_lo, vtype = _encode_from_bytes(
        key_buf, key_offs, key_lens, valid, num_key_words,
    )
    return _sort_gc_compact_tail(
        key_words, key_len, inv_hi, inv_lo, vtype, snap_hi, snap_lo,
        num_key_words, bottommost,
        tomb_hi_orig=tomb_hi if has_tombs else None,
        tomb_lo_orig=tomb_lo if has_tombs else None,
    )


# Per-shard row budget for the 3-byte packed-order download: local row ids
# must fit 22 bits (bit 23 carries the zero-seq flag, bit 22 the
# complex-group flag).
MAX_SHARD_ROWS = 1 << 22


@functools.partial(
    jax.jit, static_argnames=("num_key_words", "uk_len", "has_tombs"),
)
def _fused_uniform_shard_impl(ukb, packed_hi, packed_lo, tomb_hi, tomb_lo,
                              snap_hi, snap_lo, total, num_key_words, uk_len,
                              bottommost, has_tombs):
    """ONE range-shard's encode+sort+GC over THREE uploaded buffers:
    `ukb` = trailer-stripped user-key bytes of every chunk packed
    contiguously (padded rows zero), `packed_hi` / `packed_lo` = the two
    32-bit words of every row's 8-byte trailer (seq << 8 | vtype) as the
    key holds it, whatever sequence numbers the rows span. The shapes are
    the row bucket's and the key length's alone, so chunks of any count
    and size reuse one compilation.
    The result is (packed_bytes u8[3p], meta i32[2]): three
    byte-planes of the 24-bit survivor row ids (bit 23 = zero-seq flag,
    bit 22 = complex-group flag) — 3/4 the download of int32 orders — plus
    [count, has_complex]. With has_tombs, tomb_hi/lo carry each local row's
    max covering range-tombstone seqno words.

    The reorder is ONE multi-operand lax.sort whatever the chunk count.
    The chunks are presorted runs, and two cheaper-looking reorders were
    tried on a TPU v5e and lost (PERF.md, PR 21): a rank-merge of the
    runs by vectorized binary search ran 30x slower than the sort (its
    gathers), and skipping the reorder for a single-chunk shard saved
    nothing measurable — so there is one program per shape, on every
    backend."""
    u32 = jnp.uint32
    int32max = jnp.int32(2**31 - 1)
    sign = u32(_SIGN)
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    span = num_key_words * 4
    p = packed_lo.shape[0]
    iota = jnp.arange(p, dtype=jnp.int32)
    valid = iota < total

    # The scopes below name the steps in the device's trace (PERF.md §3:
    # `kernel.<scope>_ms_per_Mrow`); they are metadata, the program the
    # compiler builds is the same with and without them.
    with jax.named_scope("encode_words"):
        kbp = ukb.reshape(p, uk_len)
        if span > uk_len:
            kbp = jnp.pad(kbp, ((0, 0), (0, span - uk_len)))
        kbp = kbp.astype(u32).reshape(p, num_key_words, 4)
        words = (
            (kbp[:, :, 0] << 24) | (kbp[:, :, 1] << 16)
            | (kbp[:, :, 2] << 8) | kbp[:, :, 3]
        )
        key_words = jnp.where(valid[:, None], i32(words ^ sign), int32max)

        vt0 = packed_lo & u32(0xFF)
        inv_hi = jnp.where(valid, i32(~packed_hi ^ sign), int32max)
        inv_lo = jnp.where(valid, i32(~packed_lo ^ sign), int32max)
        vtype = jnp.where(valid, vt0.astype(jnp.int32), -1)
        key_len = jnp.where(valid, jnp.int32(uk_len), int32max)

    with jax.named_scope("sort"):
        kw, kl, ih, il, vt, perm = _sort_impl(
            key_words, key_len, inv_hi, inv_lo, vtype, iota, num_key_words,
        )
    with jax.named_scope("gc_mask"):
        if has_tombs:
            th = tomb_hi[perm]
            tl = tomb_lo[perm]
        else:
            th = tl = jnp.zeros(p, dtype=jnp.uint32)
        keep, zero_seq, host_resolve, _ = _gc_mask_impl(
            kw, kl, ih, il, vt, snap_hi, snap_lo, th, tl,
            num_key_words, bottommost,
        )
    with jax.named_scope("compact"):
        out = keep | host_resolve
        take = jnp.argsort(~out, stable=True)
        po = (
            jax.lax.bitcast_convert_type(perm[take], u32)
            | (zero_seq[take].astype(u32) << 23)
            | (host_resolve[take].astype(u32) << 22)
        )
    with jax.named_scope("pack"):
        packed_bytes = jnp.concatenate([
            (po & u32(0xFF)).astype(jnp.uint8),
            ((po >> 8) & u32(0xFF)).astype(jnp.uint8),
            ((po >> 16) & u32(0xFF)).astype(jnp.uint8),
        ])
        meta = jnp.stack([
            jnp.sum(out.astype(jnp.int32)),
            jnp.any(host_resolve).astype(jnp.int32),
        ])
    return packed_bytes, meta


def prepare_uniform_chunk(key_buf: np.ndarray, n: int, key_len: int):
    """Host half of the uniform upload: split one dense uniform-length key
    slice into its user-key bytes and its 8-byte trailers (seq << 8 |
    vtype, little-endian) as uint32 words, column 0 the low word; no device
    traffic. Returns (uk_bytes, trailer_words[n, 2], n, uk_len)."""
    import sys as _sys

    kb2 = key_buf[: n * key_len].reshape(n, key_len)
    tw = np.ascontiguousarray(kb2[:, -8:]).view(np.uint32).reshape(n, 2)
    if _sys.byteorder == "big":
        tw = tw.byteswap()
    uk_len = key_len - 8
    uk = np.ascontiguousarray(kb2[:, :uk_len]).reshape(-1)
    return (uk, tw, n, uk_len)


# One row bucket for every shard a deployment's jobs produce: a shard of
# ROW_BUCKET_FROM..ROW_BUCKET rows pads to ROW_BUCKET, not to its own next
# power of two. A fused program is minutes of compile on the chip
# (PERF.md: ~110 s at key length 8, ~195 s at 16) against milliseconds of
# device time for the pad rows, and which power of two a small job or an
# uneven shard falls under is chance: a job of 250,000 rows after a
# thousand of 400,000 must not meet a program of its own.
ROW_BUCKET = 1 << 19
ROW_BUCKET_FROM = 1 << 17


def shard_count(total_rows: int) -> int:
    """How many key-range shards a job of `total_rows` input rows is cut
    into: one up to ROW_BUCKET rows; from two on the count doubles while a
    shard of an even cut would pass 0.98 x ROW_BUCKET (cuts fall on block
    or key boundaries and come out uneven by a few blocks a file: the
    fiftieth is their room), at most 32. The pipeline (ops/pipeline.py::
    _build_plan) and the serial branch (ops/device_compaction.py::
    _prepare_uniform_shards) both ask here, and that is the point of the
    rule living beside ROW_BUCKET: a job of several shards that leaves the
    pipeline is cut to the same bucket by the serial branch and meets the
    program its deployment has compiled, not a second one."""
    if total_rows <= ROW_BUCKET:
        return 1
    target = ROW_BUCKET - ROW_BUCKET // 50
    s = 2
    while s < 32 and total_rows // s > target:
        s *= 2
    return s


def upload_uniform_shard(chunks, covers=None, device=None):
    """Pack one shard's prepared chunks (prepare_uniform_chunk outputs, in
    row order) into device buffers, pad rows to the next power of two
    (to ROW_BUCKET from ROW_BUCKET_FROM rows on), and
    START the host→device transfers (device_put is async): three bulk
    transfers per shard, not three per chunk. The user-key bytes and the
    trailer words go up as they are, so a shard's program depends on its
    row bucket and key length only, never on the keys or their sequence
    numbers.
    `covers`: optional per-chunk uint64 max-covering-tombstone arrays
    (None = a job without range tombstones); uploaded as two extra u32
    planes.
    `device` (None = backend default): COMMIT the shard's buffers to one
    specific chip — the fused program carries no pin of its own, so the
    committed inputs decide where it runs (ops/mesh_compaction.py places
    shards round-robin over a mesh this way)."""
    uk_len = chunks[0][3]
    total = sum(int(c[2]) for c in chunks)
    if total > MAX_SHARD_ROWS:
        raise NotSupported(
            f"shard rows {total} exceed the 24-bit packed-order budget"
        )
    p = (ROW_BUCKET if ROW_BUCKET_FROM <= total <= ROW_BUCKET
         else _next_pow2(max(1, total)))
    ukb = np.zeros(p * uk_len, dtype=np.uint8)
    packed_hi = np.zeros(p, dtype=np.uint32)
    packed_lo = np.zeros(p, dtype=np.uint32)
    # A job whose inputs hold range tombstones runs the tombstone variant
    # of the program in every shard, covered rows or not: which of the two
    # programs a shard meets is then a property of the job, and a
    # deployment that deletes ranges warms one program, not two.
    has_tombs = covers is not None
    if has_tombs:
        tomb_hi = np.zeros(p, dtype=np.uint32)
        tomb_lo = np.zeros(p, dtype=np.uint32)
    pos = 0
    for ci, (uk, tw, n, _l) in enumerate(chunks):
        ukb[pos * uk_len:(pos + n) * uk_len] = uk
        packed_lo[pos:pos + n] = tw[:, 0]
        packed_hi[pos:pos + n] = tw[:, 1]
        if has_tombs and covers[ci] is not None:
            cv = covers[ci]
            tomb_hi[pos:pos + n] = (cv >> np.uint64(32)).astype(np.uint32)
            tomb_lo[pos:pos + n] = (cv & np.uint64(0xFFFFFFFF)).astype(
                np.uint32)
        pos += n

    # A committed transfer (a device) pins the downstream jit program to
    # that chip; None keeps the backend-default placement.
    put = functools.partial(jax.device_put, device=device)
    return {
        "ukb": put(ukb), "packed_hi": put(packed_hi),
        "packed_lo": put(packed_lo), "total": total, "uk_len": uk_len,
        "tomb_hi": put(tomb_hi) if has_tombs else None,
        "tomb_lo": put(tomb_lo) if has_tombs else None,
    }


def shard_upload_nbytes(handle) -> int:
    """Bytes an upload_uniform_shard handle put on the device."""
    return sum(int(v.nbytes) for v in handle.values()
               if hasattr(v, "nbytes"))


def shard_device(handle):
    """The device an upload_uniform_shard handle's buffers live on: where
    the shard's program runs."""
    return next(iter(handle["ukb"].devices()))


# Chips of one process that share every fused program (a dcompact service's
# pool): the first dispatch of a program on one of them starts the same
# program on zeros on each of the others, each on a thread of its own, so a
# host of four chips waits for the compiles side by side, not chip after
# chip; a dispatch that finds its chip's compile under way waits for it.
# Every chip still runs an executable of its own (the device assignment is
# part of the program), made once a process, or loaded from the persistent
# cache.
_shared = {"devices": (), "met": {}}
_shared_mu = ccy.Lock("compaction_kernels.share_programs._shared_mu")


def share_programs(devices) -> None:
    """The chips whose programs are compiled together (none: each chip
    compiles when it first meets a program)."""
    with _shared_mu:
        _shared["devices"] = tuple(devices)
        _shared["met"] = {}


def _await_shared_compile(h, snap_hi, bottommost) -> None:
    devices = _shared["devices"]
    if len(devices) < 2:
        return
    dev = shard_device(h)
    key = (h["ukb"].shape, h["uk_len"], h["tomb_hi"] is not None,
           snap_hi.shape)
    with _shared_mu:
        met = _shared["met"].get(key)
        if met is None:
            met = _shared["met"][key] = {}
            for d in devices:
                if d != dev:
                    done = threading.Event()
                    met[d.id] = done
                    ccy.spawn(f"tpulsm-warm-{d.id}", _warm_program,
                              owner=_shared,
                              args=(h, snap_hi, bottommost, d, done))
        done = met.get(dev.id)
    if done is not None:
        done.wait()


def _warm_program(h, snap_hi, bottommost, device, done) -> None:
    """Run the program of shard handle `h`'s shape on zeros on `device`."""
    from toplingdb_tpu.utils import errors as _errors

    try:
        put = functools.partial(jax.device_put, device=device)
        z = {k: (put(np.zeros(v.shape, v.dtype)) if hasattr(v, "shape")
                 else v) for k, v in h.items()}
        out = _dispatch_fused(z, np.zeros_like(snap_hi),
                              np.zeros_like(snap_hi), bottommost)
        jax.block_until_ready(out)
    except Exception as e:  # the job's own dispatch compiles then
        _errors.swallow(reason="shared-compile", exc=e)
    finally:
        done.set()


def fused_uniform_shard_start(handle, snapshots: list[int], bottommost: bool):
    """Dispatch one shard's program (decode: fused_uniform_shard_finish)."""
    if "klen" in handle:  # upload_varlen_shard's: keys of mixed length
        return fused_varlen_shard_start(handle, snapshots, bottommost)
    if len(snapshots) > MAX_SNAPSHOTS:
        raise NotSupported(
            f"device GC supports <= {MAX_SNAPSHOTS} live snapshots"
        )
    snap_hi, snap_lo = _split_snapshots(snapshots)
    _await_shared_compile(handle, snap_hi, bottommost)
    out = _dispatch_fused(handle, snap_hi, snap_lo, bottommost)
    for a in out:
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()
    return out


def _dispatch_fused(h, snap_hi, snap_lo, bottommost: bool):
    uk_len = h["uk_len"]
    w = (max(uk_len, 4) + 3) // 4
    has_tombs = h["tomb_hi"] is not None
    t_hi = h["tomb_hi"] if has_tombs else np.zeros(1, dtype=np.uint32)
    t_lo = h["tomb_lo"] if has_tombs else np.zeros(1, dtype=np.uint32)
    return _fused_uniform_shard_impl(
        h["ukb"], h["packed_hi"], h["packed_lo"], t_hi, t_lo, snap_hi, snap_lo,
        np.int32(h["total"]), w, uk_len, np.bool_(bottommost), has_tombs,
    )


def fused_uniform_shard_finish(pending):
    """Block on one shard's result: (order[count] int32 LOCAL shard rows,
    zero_flags[count] bool, cx_flags[count] bool, has_complex)."""
    packed_bytes, meta = pending
    m = np.asarray(meta)
    c = int(m[0])
    has_complex = bool(m[1])
    arr = np.asarray(packed_bytes)
    p = arr.size // 3
    a = arr.reshape(3, p)
    po = (
        a[0, :c].astype(np.uint32)
        | (a[1, :c].astype(np.uint32) << 8)
        | (a[2, :c].astype(np.uint32) << 16)
    )
    order = (po & np.uint32(MAX_SHARD_ROWS - 1)).astype(np.int32)
    zero_flags = (po >> np.uint32(23)).astype(bool)
    cx_flags = ((po >> np.uint32(22)) & np.uint32(1)).astype(bool)
    return order, zero_flags, cx_flags, has_complex


def fused_encode_sort_gc(key_buf: np.ndarray, key_offs: np.ndarray,
                         key_lens: np.ndarray, max_key_bytes: int,
                         snapshots: list[int], bottommost: bool,
                         cover: np.ndarray | None = None):
    """Host wrapper: raw flat key bytes in, survivor order out. `cover`:
    optional per-original-row uint64 max covering tombstone seqno (0 =
    uncovered). Returns (order[count], zero_flags[count], cx_flags[count],
    has_complex)."""
    if len(snapshots) > MAX_SNAPSHOTS:
        raise NotSupported(
            f"device GC supports <= {MAX_SNAPSHOTS} live snapshots"
        )
    n = len(key_offs)
    p = _next_pow2(max(1, n))
    if p > ROW_BUCKET:
        # This program pads to the job's own power of two: at 4,194,304
        # rows the chip compiled it ~400 s and then failed to allocate
        # (PERF.md, PR 29). The caller runs such a job per entry.
        raise NotSupported(
            f"the whole-job program takes at most {ROW_BUCKET} rows, "
            f"got {n}"
        )
    # The device derives offsets as an exclusive cumsum of the lengths; that
    # requires the dense end-to-end layout ColumnarKV scans produce.
    if n and (int(key_offs[0]) != 0
              or int(key_offs[-1]) + int(key_lens[-1]) != len(key_buf)
              or not np.array_equal(
                  key_offs[1:], (np.cumsum(key_lens) - key_lens)[1:]
              )):
        raise NotSupported("fused encode requires densely packed key buffers")
    w = (max_key_bytes + 3) // 4
    lens = np.zeros(p, dtype=np.int32)  # pad rows: zero-length (masked)
    valid = np.zeros(p, dtype=bool)
    lens[:n] = key_lens
    valid[:n] = True
    snap_hi, snap_lo = _split_snapshots(snapshots)
    has_tombs = cover is not None and bool(np.any(cover))
    if has_tombs:
        tomb_hi, tomb_lo = _split_cover(cover, p)
    else:
        tomb_hi = tomb_lo = np.zeros(1, dtype=np.uint32)  # unused dummy
    # Pad the raw byte buffer to a pow2 bucket too: otherwise every distinct
    # total-key-byte count compiles a fresh XLA program (the row count is
    # already bucketed; the gather clips, so over-length is semantically
    # safe).
    blen = _next_pow2(max(8, len(key_buf)))
    kb = np.zeros(blen, dtype=np.uint8)
    kb[: len(key_buf)] = key_buf
    order, zero_flags, cx_flags, count, has_complex = \
        _fused_encode_sort_gc_impl(
            kb, lens, valid, tomb_hi, tomb_lo, snap_hi, snap_lo, w,
            bool(bottommost), has_tombs,
        )
    for a in (order, zero_flags, cx_flags, count, has_complex):
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()  # stream D2H; sync np.asarray is ~15x
    c = int(count)
    return (np.asarray(order)[:c], np.asarray(zero_flags)[:c],
            np.asarray(cx_flags)[:c], bool(has_complex))


def gc_mask(sorted_cols: dict, snapshots: list[int],
            tomb_cover: np.ndarray | None, bottommost: bool):
    """Host wrapper over sorted on-device columns from device_sort().
    tomb_cover: [n] uint64 max covering tombstone seq per sorted entry
    (None = no tombstones). Returns (keep, zero_seq, host_resolve, group_id)
    as numpy arrays trimmed to n."""
    if len(snapshots) > MAX_SNAPSHOTS:
        # Falling back to the host path is the caller's job; silently
        # truncating would merge stripes and corrupt MVCC.
        raise NotSupported(
            f"device GC supports <= {MAX_SNAPSHOTS} live snapshots, "
            f"got {len(snapshots)}"
        )
    p = sorted_cols["key_words"].shape[0]
    n = sorted_cols["n"]
    snap_hi, snap_lo = _split_snapshots(snapshots)
    if tomb_cover is None:
        tomb_hi = np.zeros(p, dtype=np.uint32)
        tomb_lo = np.zeros(p, dtype=np.uint32)
    else:
        tomb_hi, tomb_lo = _split_cover(tomb_cover, p)
    keep, zero_seq, host_resolve, group_id = _gc_mask_impl(
        sorted_cols["key_words"], sorted_cols["key_len"],
        sorted_cols["inv_hi"], sorted_cols["inv_lo"], sorted_cols["vtype"],
        snap_hi, snap_lo, tomb_hi, tomb_lo,
        sorted_cols["w"], bool(bottommost),
    )
    return (
        np.asarray(keep)[:n], np.asarray(zero_seq)[:n],
        np.asarray(host_resolve)[:n], np.asarray(group_id)[:n],
    )


# ---------------------------------------------------------------------------
# Shards whose user keys differ in length (ARCHITECTURE.md §2.2.1.1). Each
# row's user key goes up zero-padded to the shard's width bucket, beside its
# true length (one byte a row); the program sorts on the padded words and
# then on the length. That is bytewise order exactly: where two padded keys
# differ, the first difference is a real byte or a real byte against a pad
# zero (the shorter key is then a prefix of the longer: it sorts first), and
# where they tie, the lengths decide ("ab" < "ab\0" < "abc"). Equal keys are
# equal words AND equal lengths, which `_gc_mask_impl` already asks for.
# The uniform program above stays line for line as it was: the lines of the
# frames that trace a program are part of its compile-cache key.
# ---------------------------------------------------------------------------

# The width bucket's step: a shard's longest user key rounded up to whole
# 8-byte steps, so keys of 17 to 24 bytes (YCSB's "user" + a decimal hash,
# 5-23 B) meet one program a row bucket.
VARLEN_WIDTH_STEP = 8

# The mixed-length program's row floor: a shard of VARLEN_ROW_BUCKET_FROM to
# ROW_BUCKET rows pads to ROW_BUCKET (the uniform rule's ROW_BUCKET_FROM is
# 2^17). A deployment of 1 KB records sends remote jobs from half a write
# buffer on, ~55,000 rows, and a one-shard job of 55,000 to 131,072 rows
# must not meet a program of its own: minutes of compile against
# milliseconds of pad rows (ROW_BUCKET's note above).
VARLEN_ROW_BUCKET_FROM = 1 << 15

from typing import NamedTuple  # noqa: E402  (here: see the note above)


class VarlenChunk(NamedTuple):
    """prepare_varlen_chunk's output: the first four fields stand where
    prepare_uniform_chunk's do (`n` is field 2, the width field 3)."""
    ukb: np.ndarray        # [n * width] u8, user keys zero-padded
    tw: np.ndarray         # [n, 2] u32, the trailer's (low, high) words
    n: int
    width: int
    klen: np.ndarray       # [n] u8, each user key's true length


def varlen_width(max_uk_len: int) -> int:
    """The width bucket of a shard whose longest user key has
    `max_uk_len` bytes."""
    step = VARLEN_WIDTH_STEP
    return max(step, -(-int(max_uk_len) // step) * step)


def prepare_varlen_chunk(key_buf: np.ndarray, key_lens: np.ndarray,
                         width: int) -> VarlenChunk:
    """Host half of the mixed-length upload: rows whose internal keys lie
    densely from key_buf[0] on, `key_lens` long each, as their user keys
    zero-padded to `width` bytes, their lengths and their trailers' words.
    Vectorised: one mask drops the trailer bytes of the dense span, and the
    user-key bytes left land, in row order, where a [n, width] mask of
    each row's first length bytes is set."""
    import sys as _sys

    lens = np.asarray(key_lens, dtype=np.int64)
    n = len(lens)
    uk = lens - 8
    if n and (int(uk.min()) < 0 or int(uk.max()) > width):
        raise NotSupported(f"user keys of {int(uk.min())}-{int(uk.max())} "
                           f"bytes do not fit a width of {width}")
    ends = np.cumsum(lens)
    tr = (ends - 8)[:, None] + np.arange(8)[None, :]
    tw = np.ascontiguousarray(key_buf[tr]).view(np.uint32).reshape(n, 2)
    if _sys.byteorder == "big":
        tw = tw.byteswap()
    span = int(ends[-1]) if n else 0
    is_uk = np.ones(span, dtype=bool)
    is_uk[tr.reshape(-1)] = False
    out = np.zeros((n, width), dtype=np.uint8)
    out[np.arange(width)[None, :] < uk[:, None]] = key_buf[:span][is_uk]
    return VarlenChunk(out.reshape(-1), tw, n, width, uk.astype(np.uint8))


def upload_varlen_shard(chunks, covers=None, device=None):
    """upload_uniform_shard for VarlenChunks of one width: the row bucket
    from VARLEN_ROW_BUCKET_FROM rows on, the same trailer and cover planes,
    the user keys at the width, and a fourth plane, `klen`, of each row's
    true length (pad rows 0)."""
    width = chunks[0].width
    total = sum(int(c.n) for c in chunks)
    if total > MAX_SHARD_ROWS:
        raise NotSupported(
            f"shard rows {total} exceed the 24-bit packed-order budget"
        )
    p = (ROW_BUCKET if VARLEN_ROW_BUCKET_FROM <= total <= ROW_BUCKET
         else _next_pow2(max(1, total)))
    ukb = np.zeros(p * width, dtype=np.uint8)
    klen = np.zeros(p, dtype=np.uint8)
    packed_hi = np.zeros(p, dtype=np.uint32)
    packed_lo = np.zeros(p, dtype=np.uint32)
    has_tombs = covers is not None
    if has_tombs:
        tomb_hi = np.zeros(p, dtype=np.uint32)
        tomb_lo = np.zeros(p, dtype=np.uint32)
    pos = 0
    for ci, c in enumerate(chunks):
        n = c.n
        ukb[pos * width:(pos + n) * width] = c.ukb
        klen[pos:pos + n] = c.klen
        packed_lo[pos:pos + n] = c.tw[:, 0]
        packed_hi[pos:pos + n] = c.tw[:, 1]
        if has_tombs and covers[ci] is not None:
            cv = covers[ci]
            tomb_hi[pos:pos + n] = (cv >> np.uint64(32)).astype(np.uint32)
            tomb_lo[pos:pos + n] = (cv & np.uint64(0xFFFFFFFF)).astype(
                np.uint32)
        pos += n
    put = functools.partial(jax.device_put, device=device)
    return {
        "ukb": put(ukb), "klen": put(klen), "packed_hi": put(packed_hi),
        "packed_lo": put(packed_lo), "total": total, "uk_len": width,
        "tomb_hi": put(tomb_hi) if has_tombs else None,
        "tomb_lo": put(tomb_lo) if has_tombs else None,
    }


def upload_shard(chunks, covers=None, device=None):
    """One shard's upload, by the kind of its chunks: VarlenChunks (keys of
    mixed length) or prepare_uniform_chunk's (one key length)."""
    if isinstance(chunks[0], VarlenChunk):
        return upload_varlen_shard(chunks, covers, device=device)
    return upload_uniform_shard(chunks, covers, device=device)


def fused_varlen_shard_start(handle, snapshots: list[int], bottommost: bool):
    """fused_uniform_shard_start for an upload_varlen_shard handle: the
    result decodes with fused_uniform_shard_finish. Each chip of a pool
    compiles it when it first meets it (or loads it from the persistent
    cache): `share_programs` warms the uniform program only."""
    if len(snapshots) > MAX_SNAPSHOTS:
        raise NotSupported(
            f"device GC supports <= {MAX_SNAPSHOTS} live snapshots"
        )
    snap_hi, snap_lo = _split_snapshots(snapshots)
    h = handle
    has_tombs = h["tomb_hi"] is not None
    t_hi = h["tomb_hi"] if has_tombs else np.zeros(1, dtype=np.uint32)
    t_lo = h["tomb_lo"] if has_tombs else np.zeros(1, dtype=np.uint32)
    out = _fused_varlen_shard_impl(
        h["ukb"], h["klen"], h["packed_hi"], h["packed_lo"], t_hi, t_lo,
        snap_hi, snap_lo, np.int32(h["total"]), h["uk_len"],
        np.bool_(bottommost), has_tombs,
    )
    for a in out:
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()  # results stream back as it finishes
    return out


@functools.partial(jax.jit, static_argnames=("width", "has_tombs"))
def _fused_varlen_shard_impl(ukb, klen, packed_hi, packed_lo, tomb_hi,
                             tomb_lo, snap_hi, snap_lo, total, width,
                             bottommost, has_tombs):
    """_fused_uniform_shard_impl over a mixed-length shard: the user keys
    zero-padded to `width` (a multiple of 4: width // 4 sort words) and
    `klen`, each row's true length, which becomes the `key_len` operand of
    the sort and of the GC mask. The same scopes, the same result; the
    sort is `_varlen_sort`'s passes, not one multi-operand sort."""
    u32 = jnp.uint32
    int32max = jnp.int32(2**31 - 1)
    sign = u32(_SIGN)
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    num_key_words = width // 4
    p = packed_lo.shape[0]
    iota = jnp.arange(p, dtype=jnp.int32)
    valid = iota < total

    with jax.named_scope("encode_words"):
        kbp = ukb.reshape(p, num_key_words, 4).astype(u32)
        words = (
            (kbp[:, :, 0] << 24) | (kbp[:, :, 1] << 16)
            | (kbp[:, :, 2] << 8) | kbp[:, :, 3]
        )
        key_words = jnp.where(valid[:, None], i32(words ^ sign), int32max)
        vt0 = packed_lo & u32(0xFF)
        inv_hi = jnp.where(valid, i32(~packed_hi ^ sign), int32max)
        inv_lo = jnp.where(valid, i32(~packed_lo ^ sign), int32max)
        vtype = jnp.where(valid, vt0.astype(jnp.int32), -1)
        key_len = jnp.where(valid, klen.astype(jnp.int32), int32max)

    with jax.named_scope("sort"):
        kw, kl, ih, il, vt, perm = _varlen_sort(
            key_words, key_len, inv_hi, inv_lo, vtype, iota,
        )
    with jax.named_scope("gc_mask"):
        if has_tombs:
            th = tomb_hi[perm]
            tl = tomb_lo[perm]
        else:
            th = tl = jnp.zeros(p, dtype=jnp.uint32)
        keep, zero_seq, host_resolve, _ = _gc_mask_impl(
            kw, kl, ih, il, vt, snap_hi, snap_lo, th, tl,
            num_key_words, bottommost,
        )
    with jax.named_scope("compact"):
        out = keep | host_resolve
        take = jnp.argsort(~out, stable=True)
        po = (
            jax.lax.bitcast_convert_type(perm[take], u32)
            | (zero_seq[take].astype(u32) << 23)
            | (host_resolve[take].astype(u32) << 22)
        )
    with jax.named_scope("pack"):
        packed_bytes = jnp.concatenate([
            (po & u32(0xFF)).astype(jnp.uint8),
            ((po >> 8) & u32(0xFF)).astype(jnp.uint8),
            ((po >> 16) & u32(0xFF)).astype(jnp.uint8),
        ])
        meta = jnp.stack([
            jnp.sum(out.astype(jnp.int32)),
            jnp.any(host_resolve).astype(jnp.int32),
        ])
    return packed_bytes, meta


def _varlen_sort(key_words, key_len, inv_hi, inv_lo, vtype, iota):
    """`_sort_impl`'s order (rows with every key equal: by row) as one pass
    a key, last key first, of ONE stable two-operand sort in a loop. The
    TPU compiler's time for a sort grows with its operands, and faster than
    their count: `_sort_impl` over the 11 operands of a 24-byte width took
    293 s of a cold set-up on a v5e (PERF.md, PR 42), where the loop
    compiles one small sort whatever the width."""
    keys = jnp.concatenate(
        [key_words.T, key_len[None], inv_hi[None], inv_lo[None]])
    last = keys.shape[0] - 1

    def one_pass(i, perm):
        key = jax.lax.dynamic_index_in_dim(keys, last - i, keepdims=False)
        return jax.lax.sort((key[perm], perm), num_keys=1, is_stable=True)[1]

    perm = jax.lax.fori_loop(0, last + 1, one_pass, iota)
    return (key_words[perm], key_len[perm], inv_hi[perm], inv_lo[perm],
            vtype[perm], perm)
