"""On-device SST block assembly.

The reference's per-entry block build loop
(/root/reference/table/block_based/block_builder.cc:66-180 BlockBuilder::Add,
/root/reference/table/block_based/block_based_table_builder.cc:961-1150) runs
entirely on the device: after the fused sort+GC, ONE jit program computes
restart-point prefix sharing, greedy block cuts, per-entry byte offsets and
scatters finished UNCOMPRESSED block payloads (records + restart arrays)
into a single output buffer. The host only adds the 5-byte block trailers
(type + masked crc32c), the index/meta blocks and the footer — so its CPU
cost per job is O(blocks), not O(entries), and on PCIe-class hosts the
whole data plane is device-bound.

Byte parity: payloads are bit-identical to the native C++ builder
(tpulsm_build_block) — the greedy cut rule `used + 4*num_restarts + 4 >=
block_size` is reproduced exactly with a residue-class searchsorted (block
start j cuts at the first i where a prefix-sum expression crosses the
budget; restart overhead folds into per-residue prefix sums because
restarts sit at i ≡ j (mod R)) followed by pointer-doubling over the
next-cut graph to mark actual block starts. tests/test_block_assembly.py
asserts whole-file byte equality against the CPU path.

Scope (falls back to the packed-order download path otherwise): uniform
key length < 120B, values < 128B (single-byte varints), NO_COMPRESSION,
whole-key (or no) filters, single output file, no complex groups /
blob refs. A survivor bitmap (1 bit/row) rides down so the host builds
the bloom byte-identically without the full order download.
Transfers: values ride UP and finished blocks ride DOWN, so this path
pays ~2x the bytes of the order-download path — it wins where the host
CPU, not the link, is the bottleneck (TPULSM_DEVICE_BLOCKS=1 opts in).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from toplingdb_tpu.db.dbformat import ValueType
from toplingdb_tpu.ops import compaction_kernels as ck
from toplingdb_tpu.ops import device_runtime  # noqa: F401  (compile cache)
from toplingdb_tpu.utils.status import NotSupported
from toplingdb_tpu.utils import errors as _errors

_I32MAX = 2 ** 31 - 1


def _log2ceil(n: int) -> int:
    b = 0
    while (1 << b) < n:
        b += 1
    return b


@functools.partial(jax.jit, static_argnames=(
    "num_key_words", "uk_len", "bottommost", "has_tombs",
    "R", "B", "max_rec", "ubp", "nbp",
))
def _assemble_blocks_impl(ukb, pkb, starts, min_his, min_los,
                          vlens, vflat, tomb_hi, tomb_lo, snap_hi, snap_lo,
                          total, num_key_words, uk_len, bottommost,
                          has_tombs, R, B, max_rec, ubp, nbp):
    """Sort + GC + FULL block assembly in one device program.

    Returns (out u8[ubp], meta i32[10], bcounts i32[nbp], bpayload i32[nbp],
    bfirst i32[nbp], blast i32[nbp], surv_bitmap u8[ceil(p/8)]):
      out      concatenated block payloads (no trailers)
      meta     [nb, m, total_payload, has_complex, num_deletions,
                raw_value, smin_hi, smin_lo, smax_hi, smax_lo]
      bcounts  entries per block
      bpayload payload bytes per block
      bfirst/blast  original LOCAL row of each block's first/last entry,
                    bit 30 = that entry's seq was zeroed
    """
    u32 = jnp.uint32
    i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    p = pkb.shape[0]
    kb = ukb.reshape(p, uk_len)
    core = ck._uniform_shard_core(
        kb, pkb, starts, min_his, min_los, tomb_hi, tomb_lo,
        snap_hi, snap_lo, total, num_key_words, uk_len, bottommost,
        has_tombs,
    )
    iota = jnp.arange(p, dtype=jnp.int32)
    K = uk_len + 8

    # --- survivor-ordered arrays (first m rows valid) ---
    take = core["take"]
    sorder = core["perm"][take]                 # original local row
    svalid = core["out"][take]
    m = jnp.sum(svalid.astype(jnp.int32))
    szero = core["zero_seq"][take] & svalid
    sp_hi = jnp.where(szero, u32(0), core["packed_hi"][sorder])
    sp_lo = jnp.where(
        szero, core["vtype_orig"][sorder].astype(u32),
        core["packed_lo"][sorder],
    )
    svt = core["vtype_orig"][sorder]
    svlen = jnp.where(svalid, vlens[sorder].astype(jnp.int32), 0)
    voff_all = jnp.cumsum(vlens.astype(jnp.int32)) - vlens.astype(jnp.int32)
    svoff = voff_all[sorder]

    # --- full internal-key matrix (user key + 8B LE trailer) ---
    skb = kb[sorder]                            # [p, uk_len]
    tcol = jnp.arange(8, dtype=jnp.int32)[None, :]
    tb = jnp.where(
        tcol < 4,
        (sp_lo[:, None] >> (8 * jnp.clip(tcol, 0, 3))) & u32(0xFF),
        (sp_hi[:, None] >> (8 * jnp.clip(tcol - 4, 0, 3))) & u32(0xFF),
    ).astype(jnp.uint8)
    ikey = jnp.concatenate([skb, tb], axis=1)   # [p, K]

    # --- shared-prefix lengths between consecutive survivors ---
    prev = jnp.roll(ikey, 1, axis=0)
    lane = jax.lax.broadcasted_iota(jnp.int32, ikey.shape, 1)
    lcp = jnp.min(jnp.where(ikey != prev, lane, jnp.int32(K)), axis=1)
    lcp = lcp.at[0].set(0)
    lcp = jnp.where(svalid & (iota > 0), lcp, 0)

    # --- per-entry sizes (single-byte varints; host gates K,vlen < 128) ---
    sz_cont = jnp.where(svalid, 3 + (K - lcp) + svlen, 0)
    sz_rst = jnp.where(svalid, 3 + K + svlen, 0)
    delta = sz_rst - sz_cont                    # == lcp for valid rows
    S = jnp.cumsum(sz_cont)                     # inclusive
    S0 = S - sz_cont                            # exclusive

    # --- greedy block cuts: next_start[j] for every possible start j ---
    # total(j, i) = S[i]-S0[j] + D_m[i]-D0_m[j] + 4*floor((i-j)/R) + 8
    # with m = j mod R and D_m = cumsum(delta at positions ≡ m (mod R)).
    nxt = jnp.full(p, p - 1, dtype=jnp.int32)
    for mc in range(R):
        cls = (iota % R) == mc
        D = jnp.cumsum(jnp.where(cls, delta, 0))
        D0 = D - jnp.where(cls, delta, 0)
        rm = (iota - mc) % R
        a = (iota - rm - mc) // R
        U = S + D + 4 * a
        b_j = (iota - mc) // R
        T = jnp.int32(B - 8) + S0 + D0 + 4 * b_j
        cand = jnp.searchsorted(U, T, side="left").astype(jnp.int32)
        nxt = jnp.where(cls, cand, nxt)
    f = jnp.clip(nxt + 1, 1, p)                 # cut AFTER entry nxt[j]
    f_ext = jnp.concatenate([f, jnp.array([p], jnp.int32)])

    # --- mark the orbit of 0 under f (actual block starts) ---
    reach = jnp.zeros(p + 1, dtype=jnp.bool_).at[0].set(True)
    g = f_ext
    for _ in range(_log2ceil(p) + 1):
        reach = reach | jnp.zeros_like(reach).at[g].max(reach)
        g = g[g]
    start = reach[:p] & (iota < m)

    # --- per-entry block geometry ---
    bstart = jax.lax.cummax(jnp.where(start, iota, jnp.int32(-1)))
    q = iota - bstart
    is_rst = (q % R) == 0
    sz = jnp.where(is_rst, sz_rst, sz_cont)
    Csz = jnp.cumsum(sz)
    E0 = Csz - sz                               # exclusive entry offsets
    eoff_in_blk = E0 - E0[jnp.clip(bstart, 0, p - 1)]
    shared = jnp.where(is_rst, 0, lcp)
    nonshared = K - shared

    # --- compact blocks to the front ---
    border = jnp.argsort(~start, stable=True)
    bpos = border[:nbp]                          # block start positions
    nb = jnp.sum(start.astype(jnp.int32))
    bidx = jnp.arange(nbp, dtype=jnp.int32)
    bvalid = bidx < nb
    bnext = jnp.minimum(f_ext[jnp.clip(bpos, 0, p - 1)], m)
    bcnt = jnp.where(bvalid, bnext - bpos, 0)
    blast = jnp.clip(bpos + bcnt - 1, 0, p - 1)
    bentry_bytes = jnp.where(bvalid, Csz[blast] - E0[bpos], 0)
    bnr = jnp.where(bvalid, 1 + (jnp.maximum(bcnt, 1) - 1) // R, 0)
    bpayload = jnp.where(bvalid, bentry_bytes + 4 * bnr + 4, 0)
    bout = jnp.cumsum(bpayload) - bpayload       # block payload start
    total_payload = jnp.sum(bpayload)

    blk_id = jnp.clip(jnp.cumsum(start.astype(jnp.int32)) - 1, 0, nbp - 1)
    entry_global = bout[blk_id] + eoff_in_blk

    # --- emit records: [p, max_rec] byte matrix scattered once ---
    col = jnp.arange(max_rec, dtype=jnp.int32)[None, :]
    keyb = jnp.take_along_axis(
        ikey, jnp.clip(shared[:, None] + col - 3, 0, K - 1), axis=1
    )
    vpos = svoff[:, None] + (col - 3 - nonshared[:, None])
    valb = vflat[jnp.clip(vpos, 0, vflat.shape[0] - 1)]
    rec = jnp.where(
        col == 0, shared[:, None].astype(jnp.uint8),
        jnp.where(
            col == 1, nonshared[:, None].astype(jnp.uint8),
            jnp.where(
                col == 2, svlen[:, None].astype(jnp.uint8),
                jnp.where(col < 3 + nonshared[:, None], keyb, valb),
            ),
        ),
    )
    in_rec = col < sz[:, None]
    flat_idx = jnp.where(
        in_rec & svalid[:, None], entry_global[:, None] + col, jnp.int32(ubp)
    )
    out = jnp.zeros(ubp, dtype=jnp.uint8)
    out = out.at[flat_idx.reshape(-1)].set(rec.reshape(-1), mode="drop")

    # --- emit restart arrays: [nbp, (max_rwords+1)*4] scattered once ---
    max_rwords = B // (3 * R) + 2
    w = jnp.arange(max_rwords + 1, dtype=jnp.int32)[None, :]
    rpos = jnp.clip(bpos[:, None] + w * R, 0, p - 1)
    roffs = E0[rpos] - E0[jnp.clip(bpos, 0, p - 1)][:, None]
    word = jnp.where(w < bnr[:, None], roffs, bnr[:, None])
    wb = jnp.arange((max_rwords + 1) * 4, dtype=jnp.int32)[None, :]
    wsel = wb // 4
    wbyte = wb % 4
    wvals = jnp.take_along_axis(word, wsel, axis=1)
    rbytes = ((wvals >> (8 * wbyte)) & 0xFF).astype(jnp.uint8)
    in_arr = wsel <= bnr[:, None]
    rdst = jnp.where(
        in_arr & bvalid[:, None],
        (bout + bentry_bytes)[:, None] + wb, jnp.int32(ubp),
    )
    out = out.at[rdst.reshape(-1)].set(rbytes.reshape(-1), mode="drop")

    # --- block boundary rows + stats ---
    zbit = jnp.int32(1 << 30)
    bfirst = jnp.where(
        bvalid,
        i32(sorder[jnp.clip(bpos, 0, p - 1)])
        | jnp.where(szero[jnp.clip(bpos, 0, p - 1)], zbit, 0), -1,
    )
    blast_r = jnp.where(
        bvalid,
        i32(sorder[blast]) | jnp.where(szero[blast], zbit, 0), -1,
    )
    # Survivor bitmap over ORIGINAL local rows (1 bit/row): the host
    # derives `sel` from it to build the bloom filter byte-identically to
    # the CPU path (and blob refs) without downloading the full order.
    surv = jnp.zeros(p, dtype=jnp.int32).at[sorder].max(
        svalid.astype(jnp.int32))
    sbytes = (p + 7) // 8
    pad_rows = (-p) % 8
    if pad_rows:
        surv = jnp.pad(surv, (0, pad_rows))
    bits = surv.reshape(sbytes, 8)
    weights = (jnp.uint32(1) << jnp.arange(8, dtype=jnp.uint32))[None, :]
    surv_bitmap = jnp.sum(
        bits.astype(jnp.uint32) * weights, axis=1).astype(jnp.uint8)

    num_del = jnp.sum(
        (svalid & ((svt == int(ValueType.DELETION))
                   | (svt == int(ValueType.SINGLE_DELETION)))
         ).astype(jnp.int32)
    )
    raw_value = jnp.sum(svlen)
    seq_hi = jnp.where(svalid, sp_hi >> 8, u32(0xFFFFFFFF))
    seq_lo = jnp.where(svalid, (sp_lo >> 8) | (sp_hi << 24), u32(0xFFFFFFFF))
    smin_hi = jnp.min(seq_hi)
    smin_lo = jnp.min(jnp.where(seq_hi == smin_hi, seq_lo, u32(0xFFFFFFFF)))
    seq_hi_mx = jnp.where(svalid, sp_hi >> 8, u32(0))
    seq_lo_mx = jnp.where(svalid, (sp_lo >> 8) | (sp_hi << 24), u32(0))
    smax_hi = jnp.max(seq_hi_mx)
    smax_lo = jnp.max(jnp.where(seq_hi_mx == smax_hi, seq_lo_mx, u32(0)))
    meta = jnp.stack([
        nb, m, total_payload,
        jnp.any(core["host_resolve"]).astype(jnp.int32),
        num_del, raw_value,
        i32(smin_hi), i32(smin_lo), i32(smax_hi), i32(smax_lo),
    ])
    return out, meta, bcnt, bpayload, bfirst, blast_r, surv_bitmap


def assembly_supported(table_options, kv, shards, any_complex,
                       max_output_file_size, vtypes) -> bool:
    """Gate for the on-device block-assembly path. Off unless
    TPULSM_DEVICE_BLOCKS=1 (transfers roughly double vs the order
    download, so it is a win only on PCIe-class links). `vtypes`: the
    caller's already-decoded per-row trailer types."""
    from toplingdb_tpu.table import format as fmt

    if os.environ.get("TPULSM_DEVICE_BLOCKS") != "1":
        return False
    if shards is None or len(shards) != 1 or any_complex:
        return False
    if getattr(table_options, "format", "block") != "block":
        return False
    if table_options.compression != fmt.NO_COMPRESSION:
        return False
    if table_options.filter_policy is not None and (
            not table_options.whole_key_filtering
            or getattr(table_options, "prefix_extractor", None) is not None):
        # Prefix filter keys only exist on the per-entry path; building a
        # whole-key-only bloom here would break byte parity.
        return False
    if not kv.n:
        return False
    K = int(kv.key_lens[0])
    if not (0 < K < 128):
        return False
    if int(kv.val_lens.max()) >= 128:
        return False
    # Single output file only (the block layout must match the unsplit
    # build): a generous 2x margin over the raw estimate covers block
    # trailers/restart/index overhead even at tiny block sizes.
    est = int(kv.key_lens.sum()) + int(kv.val_lens.sum()) + 8 * kv.n
    if est * 2 + 65536 >= max_output_file_size or est >= 2 ** 30:
        return False
    if bool(np.any(vtypes == int(ValueType.BLOB_INDEX))):
        return False
    if table_options.block_size < 64 or table_options.restart_interval < 1:
        return False
    return True


def run_block_assembly(env, dbname, icmp, kv, shard, cover, snapshots,
                       bottommost, table_options, new_file_number,
                       creation_time, tombs, column_family=(0, "default")):
    """Drive the device block-assembly program for a single-shard job and
    write the output SST (host: block trailers + index/meta/footer).
    Returns the same (fnum, path, props, smallest, largest, sel) tuples as
    write_tables_columnar; `sel` (from the downloaded survivor bitmap) is
    materialized only when a whole-key bloom must build from it."""
    from toplingdb_tpu import native
    from toplingdb_tpu.ops.columnar_io import _ColumnarSST
    from toplingdb_tpu.ops.device_compaction import _ranges_lmap
    from toplingdb_tpu.utils import crc32c

    if len(snapshots) > ck.MAX_SNAPSHOTS:
        raise NotSupported(
            f"device GC supports <= {ck.MAX_SNAPSHOTS} live snapshots"
        )
    chunks, ranges = shard
    covers_s = None if cover is None else [cover[lo:hi] for lo, hi in ranges]
    h = ck.upload_uniform_shard(chunks, covers_s)
    uk_len = h["uk_len"]
    K = uk_len + 8
    p = int(h["pkb"].shape[0])

    # Values: per-row lengths + dense bytes, in the same local row order.
    vlens = np.zeros(p, dtype=np.uint32)
    vparts = []
    pos = 0
    for lo, hi in ranges:
        vlens[pos:pos + (hi - lo)] = kv.val_lens[lo:hi]
        b0 = int(kv.val_offs[lo])
        b1 = int(kv.val_offs[hi - 1]) + int(kv.val_lens[hi - 1])
        vparts.append(kv.val_buf[b0:b1])
        pos += hi - lo
    vflat = np.concatenate(vparts) if vparts else np.zeros(0, np.uint8)
    vbp = ck._next_pow2(max(8, len(vflat)))
    vf = np.zeros(vbp, dtype=np.uint8)
    vf[: len(vflat)] = vflat

    R = int(table_options.restart_interval)
    B = int(table_options.block_size)
    max_vlen = int(kv.val_lens.max()) if kv.n else 0
    max_rec = 3 + K + max_vlen
    ub0 = int((3 + K) * p + int(vlens.sum()))
    nb_ub = ub0 // B + 2
    ub0 += 4 * (p // R + nb_ub + 2) + 4 * nb_ub
    ubp = ck._next_pow2(ub0)
    nbp = ck._next_pow2(nb_ub)

    snap_hi, snap_lo = ck._split_snapshots(snapshots)
    has_tombs = h["tomb_hi"] is not None
    t_hi = h["tomb_hi"] if has_tombs else np.zeros(1, dtype=np.uint32)
    t_lo = h["tomb_lo"] if has_tombs else np.zeros(1, dtype=np.uint32)
    w = (max(uk_len, 4) + 3) // 4
    (out, meta, bcnt, bpayload, bfirst, blast,
     surv_bitmap) = _assemble_blocks_impl(
        h["ukb"], h["pkb"], h["starts"], h["min_his"], h["min_los"],
        jax.device_put(vlens), jax.device_put(vf), t_hi, t_lo,
        snap_hi, snap_lo, np.int32(h["total"]), w, uk_len,
        bool(bottommost), has_tombs, R, B, max_rec, ubp, nbp,
    )
    for a in (meta, bcnt, bpayload, bfirst, blast, surv_bitmap):
        if hasattr(a, "copy_to_host_async"):
            a.copy_to_host_async()
    meta = np.asarray(meta)
    nb, mtot, total_payload, has_complex = (
        int(meta[0]), int(meta[1]), int(meta[2]), bool(meta[3]))
    if has_complex:
        raise NotSupported("complex groups reached block assembly")
    if nb > nbp or total_payload > ubp:
        # The static block/byte budgets were undersized for this shape
        # (belt and braces: the emission scatter drops out-of-range
        # writes, so nothing corrupt was produced — just fall back).
        raise NotSupported("block assembly budgets exceeded")
    bcnt = np.asarray(bcnt)[:nb]
    bpayload = np.asarray(bpayload)[:nb]
    bfirst = np.asarray(bfirst)[:nb]
    blast = np.asarray(blast)[:nb]
    # Download the payload in ~8 MiB sections cut at block boundaries,
    # with every section's D2H copy enqueued up front: the host frames
    # (crc + index bookkeeping) section k while sections k+1.. are still
    # streaming back, instead of blocking on one monolithic download.
    bends = np.cumsum(bpayload, dtype=np.int64) if nb else \
        np.zeros(0, np.int64)
    sections = []  # (blk_lo, blk_hi, base_off, device_slice)
    blk_lo = 0
    base_off = 0
    for b in range(nb):
        if int(bends[b]) - base_off >= (8 << 20) or b == nb - 1:
            dev = out[base_off:int(bends[b])]
            if hasattr(dev, "copy_to_host_async"):
                dev.copy_to_host_async()
            sections.append((blk_lo, b + 1, base_off, dev))
            blk_lo = b + 1
            base_off = int(bends[b])

    lmap = _ranges_lmap(ranges)
    want_bloom = (table_options.filter_policy is not None
                  and table_options.whole_key_filtering)
    if want_bloom:
        surv = np.unpackbits(np.asarray(surv_bitmap),
                             bitorder="little")[: len(lmap)]
        sel = lmap[np.flatnonzero(surv)]
    else:
        sel = np.empty(0, dtype=np.int64)  # nothing consumes it

    def boundary_ikey(enc: int) -> bytes:
        row = int(lmap[enc & ((1 << 30) - 1)])
        zero = bool(enc & (1 << 30))
        ik = kv.ikey(row)
        if zero:
            t = int(ik[-8]) & 0xFF  # vtype byte survives in a zeroed trailer
            ik = ik[:-8] + t.to_bytes(8, "little")
        return ik

    lib = native.lib()
    fnum = new_file_number()
    sst = _ColumnarSST(env, dbname, fnum, icmp, table_options, creation_time,
                       column_family)
    try:
        # Frame blocks: payload + type(0) + masked crc32c, one framed run
        # per downloaded section (consumed as its copy completes).
        for s_lo, s_hi, s_base, dev in sections:
            chunk = np.asarray(dev)  # blocks on THIS section's copy only
            section = bytearray()
            blocks = []
            off = 0
            for b in range(s_lo, s_hi):
                pl = int(bpayload[b])
                raw = chunk[off:off + pl].tobytes()
                off += pl
                crc = crc32c.mask(crc32c.extend(0, raw + b"\x00"))
                section += raw + b"\x00" + crc.to_bytes(4, "little")
                blocks.append((pl, pl, boundary_ikey(int(bfirst[b])),
                               boundary_ikey(int(blast[b])), int(bcnt[b])))
            sst.add_framed_section(bytes(section), blocks)
        pre = {
            "num_entries": mtot,
            "raw_key_size": mtot * K,
            "raw_value_size": int(meta[5]),
            "num_deletions": int(meta[4]),
            "num_merge_operands": 0,
            "smallest_seqno": ((int(np.uint32(meta[6])) << 32)
                               | int(np.uint32(meta[7]))) if mtot else 0,
            "largest_seqno": ((int(np.uint32(meta[8])) << 32)
                              | int(np.uint32(meta[9]))) if mtot else 0,
        }
        props, smallest, largest = sst.finish(
            lib, kv, sel, None, None, tombs, precomputed=pre,
        )
        return [(fnum, sst.path, props, smallest, largest, sel)]
    except BaseException:
        try:
            sst.w.close()
            env.delete_file(sst.path)
        except Exception as e:
            _errors.swallow(reason="sst-abort-cleanup", exc=e)
        raise
