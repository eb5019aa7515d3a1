"""Columnar SST IO: native bulk decode and native table building.

The host-side halves of the TPU compaction pipeline that the profile showed
dominating (SURVEY.md §7 step 5 "host↔device streaming"): whole-file scans
into flat buffers via the C++ block decoder, and output building via the C++
block builder + bloom fill — no per-entry Python. File framing (compression,
trailers, index/filter/props/metaindex/footer) reuses the same Python pieces
as TableBuilder, and write_tables_columnar replicates build_outputs' output
cutting (user-key boundary after max_output_file_size) exactly, so outputs
are byte-identical to the per-entry path for single- AND multi-output jobs;
tests/test_columnar_writer.py asserts it.
"""

from __future__ import annotations

import time

import numpy as np

from toplingdb_tpu import native
from toplingdb_tpu.db import dbformat
from toplingdb_tpu.table import format as fmt
from toplingdb_tpu.table.block import BlockBuilder, BlockIter
from toplingdb_tpu.table.builder import (
    METAINDEX_COMPRESSION_DICT,
    METAINDEX_FILTER,
    METAINDEX_PROPERTIES,
    METAINDEX_RANGE_DEL,
    CompressionOptions,
)
from toplingdb_tpu.table.properties import TableProperties
from toplingdb_tpu.utils.status import Corruption, NotSupported
from toplingdb_tpu.utils import errors as _errors
from toplingdb_tpu.utils import telemetry


# Soft per-native-call output budget for the bulk block builder: bounds the
# section buffer and the transient Python copy on arbitrarily large jobs.
_SECTION_RUN_BYTES = 8 << 20


class ColumnarKV:
    """Flat-buffer view of N (internal_key, value) entries."""

    __slots__ = ("key_buf", "key_offs", "key_lens", "val_buf", "val_offs",
                 "val_lens", "n")

    def __init__(self, key_buf, key_offs, key_lens, val_buf, val_offs, val_lens):
        self.key_buf = key_buf
        self.key_offs = key_offs
        self.key_lens = key_lens
        self.val_buf = val_buf
        self.val_offs = val_offs
        self.val_lens = val_lens
        self.n = len(key_offs)

    def ikey(self, i: int) -> bytes:
        o = self.key_offs[i]
        return self.key_buf[o : o + self.key_lens[i]].tobytes()

    def value(self, i: int) -> bytes:
        o = self.val_offs[i]
        return self.val_buf[o : o + self.val_lens[i]].tobytes()

    def to_entries(self) -> list[tuple[bytes, bytes]]:
        return [(self.ikey(i), self.value(i)) for i in range(self.n)]

    @staticmethod
    def concat(parts: list["ColumnarKV"]) -> "ColumnarKV":
        if len(parts) == 1:
            return parts[0]
        key_buf = np.concatenate([p.key_buf for p in parts])
        val_buf = np.concatenate([p.val_buf for p in parts])
        ko, vo = [], []
        k_shift = 0
        v_shift = 0
        for p in parts:
            ko.append(p.key_offs + k_shift)
            vo.append(p.val_offs + v_shift)
            k_shift += len(p.key_buf)
            v_shift += len(p.val_buf)
        return ColumnarKV(
            key_buf, np.concatenate(ko),
            np.concatenate([p.key_lens for p in parts]),
            val_buf, np.concatenate(vo),
            np.concatenate([p.val_lens for p in parts]),
        )


def _file_scan_prologue(reader):
    """Shared per-file scan setup: the whole raw file image plus the data
    block handles as arrays and objects — (raw, block_offs, block_lens,
    handles), or (None, None, None, []) for an empty file."""
    idx = reader.new_index_iterator()  # flat or partitioned
    idx.seek_to_first()
    handles = [
        fmt.BlockHandle.decode_exact(enc) for _, enc in idx.entries()
    ]
    if not handles:
        return None, None, None, []
    raw = reader._f.read(0, reader._f.size())
    block_offs = np.array([h.offset for h in handles], dtype=np.int64)
    block_lens = np.array([h.size for h in handles], dtype=np.int64)
    return raw, block_offs, block_lens, handles


def scan_tables_columnar_prealloc(readers, ranged_scan=None):
    """Scan EVERY input file into ONE preallocated pair of columnar
    buffers, sized exactly from each file's TableProperties
    (raw_key_size/raw_value_size/num_entries) — the fused native call
    inflates + decodes per block with absolute offsets, so there is no
    synthetic image, no per-file Python copies, and NO ColumnarKV.concat
    (the r04 known debt: ~0.3-0.5s of pure copy at 10M entries).

    Returns (kv, parts) where kv spans all files and parts[i] is a
    ZERO-COPY per-file view (buffer slices + rebased offsets) with the
    layout the shard/cover helpers expect — or None when ineligible
    (native/symbol missing, props absent or wrong, exotic codec, >int32
    buffers); the caller then uses the per-file scan + concat path. Block
    files and files read by entry ranges (ZipTables, SingleFastTables: one
    `scan_into` over every entry, run under `ranged_scan(reader, scan)`
    when the caller books such scans), in any mix."""
    lib = native.lib()
    if lib is None or not hasattr(lib, "tpulsm_scan_blocks"):
        return None
    infos = []
    tk = tv = tn = 0
    for r in readers:
        ranged = getattr(r, "entry_plane", None)  # no blocks: entry ranges
        if not ranged and not hasattr(r, "new_index_iterator"):
            return None
        if ranged and not r.scan_native_ready():
            return None
        if getattr(r, "_compression_dict", b""):
            # Dict-compressed frames need the stored dictionary; the
            # native scan decodes without one (would mis-report healthy
            # files as corrupt) — the per-file path carries the dict.
            return None
        p = getattr(r, "properties", None)
        if p is None:
            return None
        ne, rk, rv = int(p.num_entries), int(p.raw_key_size), int(
            p.raw_value_size)
        if ne < 0 or rk < 0 or rv < 0 or (ne > 0 and rk == 0):
            return None
        infos.append((ne, rk, rv))
        tk += rk
        tv += rv
        tn += ne
    if tk > 0x7FFFFF00 or tv > 0x7FFFFF00:
        return None
    key_buf = np.empty(tk, dtype=np.uint8)
    val_buf = np.empty(tv, dtype=np.uint8)
    key_offs = np.empty(tn, dtype=np.int32)
    key_lens = np.empty(tn, dtype=np.int32)
    val_offs = np.empty(tn, dtype=np.int32)
    val_lens = np.empty(tn, dtype=np.int32)
    kv = ColumnarKV(key_buf, key_offs, key_lens, val_buf, val_offs, val_lens)

    bases = []
    kb = vb = nb = 0
    for ne, rk, rv in infos:
        bases.append((nb, kb, vb))
        nb += ne
        kb += rk
        vb += rv

    import ctypes as _ct

    def scan_one(i):
        r = readers[i]
        ne, rk, rv = infos[i]
        if ne == 0:
            return 0
        n_base, k_base, v_base = bases[i]
        if getattr(r, "entry_plane", None):
            if r.n != ne:
                return -100

            def scan(r):
                return r.scan_into(0, ne, kv, n_base, k_base, v_base, rk, rv)

            used = ranged_scan(r, scan) if ranged_scan else scan(r)
            return ne if used == (rk, rv) else -100
        raw, b_offs, b_lens, _handles = _file_scan_prologue(r)
        if raw is None:
            return -100
        rawb = np.frombuffer(raw, dtype=np.uint8) \
            if not isinstance(raw, np.ndarray) else raw
        rc = lib.tpulsm_scan_blocks(
            native.np_u8p(rawb), len(rawb),
            native.np_i64p(b_offs), native.np_i64p(b_lens), len(b_offs),
            1 if r.opts.verify_checksums else 0,
            _ct.cast(key_buf.ctypes.data + k_base,
                     _ct.POINTER(_ct.c_uint8)), rk,
            _ct.cast(val_buf.ctypes.data + v_base,
                     _ct.POINTER(_ct.c_uint8)), rv,
            _ct.cast(key_offs.ctypes.data + 4 * n_base,
                     _ct.POINTER(_ct.c_int32)),
            _ct.cast(key_lens.ctypes.data + 4 * n_base,
                     _ct.POINTER(_ct.c_int32)),
            _ct.cast(val_offs.ctypes.data + 4 * n_base,
                     _ct.POINTER(_ct.c_int32)),
            _ct.cast(val_lens.ctypes.data + 4 * n_base,
                     _ct.POINTER(_ct.c_int32)),
            ne, k_base, v_base,
        )
        return rc

    if len(readers) > 1:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(min(8, len(readers))) as ex:
            rcs = list(ex.map(scan_one, range(len(readers))))
    else:
        rcs = [scan_one(0)]
    for i, rc in enumerate(rcs):
        if rc == -6:
            raise Corruption("block checksum mismatch (fused scan)")
        if rc == -8:
            raise Corruption("block decode/decompress failed (fused scan)")
        if rc != infos[i][0]:
            # Capacity/entry-count disagreement with the properties, codec
            # fallback, or a dict frame: use the compatible path.
            return None
    parts = []
    for i, (ne, rk, rv) in enumerate(infos):
        n_base, k_base, v_base = bases[i]
        parts.append(ColumnarKV(
            key_buf[k_base:k_base + rk],
            key_offs[n_base:n_base + ne] - np.int32(k_base),
            key_lens[n_base:n_base + ne],
            val_buf[v_base:v_base + rv],
            val_offs[n_base:n_base + ne] - np.int32(v_base),
            val_lens[n_base:n_base + ne],
        ))
    return kv, parts


def scan_table_columnar(reader, ref_values: bool = True) -> ColumnarKV:
    """Whole-file bulk scan through the native block decoder. Uncompressed
    files decode in ONE native call over the raw file bytes — values
    REFERENCED into the file image (tpulsm_scan_blocks_refvals: the image
    stays alive as val_buf, saving the per-entry value memcpy), keys
    copied; compressed files fall back to per-block decompression +
    decode. `ref_values=False` forces the value-copying twin (parity
    tests). A ZipTable or a SingleFastTable goes through its own
    decoders (the readers' `scan_columnar`)."""
    lib = native.lib()
    if lib is None:
        raise NotSupported("native library unavailable")
    if getattr(reader, "entry_plane", None):
        return _scan_entry_ranged_columnar(reader)
    if not hasattr(reader, "new_index_iterator"):
        raise NotSupported("bulk columnar scan requires the block, the zip "
                           "or the single_fast format")
    raw, block_offs, block_lens, handles = _file_scan_prologue(reader)
    if raw is None:
        return ColumnarKV(
            np.zeros(0, np.uint8), np.zeros(0, np.int32), np.zeros(0, np.int32),
            np.zeros(0, np.uint8), np.zeros(0, np.int32), np.zeros(0, np.int32),
        )

    if ref_values:
        kv = _refvals_decode(lib, raw, block_offs, block_lens,
                             reader.opts.verify_checksums)
        if kv is not None:
            return kv

    # Bulk path: all blocks in one native call over the raw image.
    kv = _bulk_decode(lib, raw, block_offs, block_lens,
                      reader.opts.verify_checksums)
    if kv is not None:
        return kv

    # Compressed file. Fast path: ONE native call inflates every block in
    # parallel (snappy/zstd dlopen'd in C++) into a synthetic uncompressed
    # image, then the same single-call bulk decode as above — zero
    # per-block Python. Dictionary-compressed and exotic codecs fall to
    # the threaded Python inflate below.
    cdict = getattr(reader, "_compression_dict", b"") or b""
    verify = reader.opts.verify_checksums
    if not cdict and hasattr(lib, "tpulsm_inflate_blocks"):
        rawb = np.frombuffer(bytes(raw), dtype=np.uint8)
        out_cap = 4 * int(block_lens.sum()) + 5 * len(handles) + 4096
        out_offs = np.empty(len(handles), dtype=np.int64)
        out_lens = np.empty(len(handles), dtype=np.int64)
        for _ in range(4):
            out = np.empty(out_cap, dtype=np.uint8)
            rc = lib.tpulsm_inflate_blocks(
                native.np_u8p(rawb), len(rawb),
                native.np_i64p(block_offs), native.np_i64p(block_lens),
                len(handles), 1 if verify else 0,
                native.np_u8p(out), out_cap,
                native.np_i64p(out_offs), native.np_i64p(out_lens),
            )
            if rc == -2:
                out_cap *= 4
                continue
            break
        if rc == -6:
            raise Corruption("block checksum mismatch (native inflate)")
        if rc == -3:
            raise Corruption("block decompression failed (native inflate)")
        if rc > 0 or (rc == 0 and not handles):
            kv = _bulk_decode(lib, out[: int(rc)], out_offs,
                              out_lens, False)
            if kv is not None:
                return kv
        # rc == -1: codec unavailable/dict frame — Python fallback below.
    mv = memoryview(raw)

    def _inflate(handle):
        end = handle.offset + handle.size
        payload = bytes(mv[handle.offset: end])
        ctype = raw[end]
        if verify:
            from toplingdb_tpu.utils import crc32c as _crc

            stored = _crc.unmask(int.from_bytes(raw[end + 1: end + 5],
                                                "little"))
            if stored != _crc.value(payload + bytes([ctype])):
                raise Corruption(
                    f"block checksum mismatch at {handle.offset}")
        return fmt.decompress(payload, ctype, cdict)

    if len(handles) > 8:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(8) as ex:
            blocks = list(ex.map(_inflate, handles))
    else:
        blocks = [_inflate(h) for h in handles]
    trailer = b"\x00" * 5  # type=NO_COMPRESSION + dummy CRC (verify off)
    synth = trailer.join(blocks) + trailer if blocks else b""
    lens = np.array([len(b) for b in blocks], dtype=np.int64)
    offs = np.concatenate([[0], np.cumsum(lens + 5)[:-1]]).astype(np.int64) \
        if blocks else np.zeros(0, np.int64)
    kv = _bulk_decode(lib, synth, offs, lens, False)
    if kv is None:
        raise Corruption("decompressed blocks failed native bulk decode")
    return kv


def _scan_entry_ranged_columnar(reader) -> ColumnarKV:
    """A whole ZipTable or SingleFastTable through its native decoders
    (the reader's `scan_columnar` over every entry): dense keys from
    offset 0; values as the decoded groups hold them (zip) or where they
    lie in the resident image (single_fast)."""
    if reader.n and not reader.scan_native_ready():
        raise NotSupported(f"{reader.entry_plane} scan plane unavailable")
    kb, ko, kl, vb, vo, vl = reader.scan_columnar(0, reader.n)
    if len(kb) > 0x7FFFFF00 or len(vb) > 0x7FFFFF00:
        raise NotSupported("input exceeds the int32 columnar budget")
    return ColumnarKV(kb, ko.astype(np.int32), kl.astype(np.int32),
                      vb, vo.astype(np.int32), vl.astype(np.int32))


def _refvals_decode(lib, raw, block_offs, block_lens, verify):
    """Values-referenced whole-file scan (tpulsm_scan_blocks_refvals): keys
    decode into a dense buffer; val_offs point INTO the raw file image,
    which becomes val_buf zero-copy. Returns None when ineligible (symbol
    missing, compressed blocks, int32 budget, long keys) — the caller then
    uses the value-copying path, which is also the authority on whether a
    block is actually corrupt."""
    if not hasattr(lib, "tpulsm_scan_blocks_refvals"):
        return None
    rawb = np.frombuffer(bytes(raw), dtype=np.uint8) \
        if not isinstance(raw, np.ndarray) else raw
    file_size = len(rawb)
    if file_size > 0x7FFFFF00:
        return None  # val offsets must fit the int32 columnar budget
    data_bytes = int(block_lens.sum())
    key_cap = 4 * data_bytes + 4096
    max_e = data_bytes // 3 + 64
    while True:
        key_out = np.empty(key_cap, dtype=np.uint8)
        key_offs = np.empty(max_e, dtype=np.int32)
        key_lens = np.empty(max_e, dtype=np.int32)
        val_offs = np.empty(max_e, dtype=np.int32)
        val_lens = np.empty(max_e, dtype=np.int32)
        rc = lib.tpulsm_scan_blocks_refvals(
            native.np_u8p(rawb), file_size,
            native.np_i64p(block_offs), native.np_i64p(block_lens),
            len(block_offs), 1 if verify else 0,
            native.np_u8p(key_out), key_cap,
            native.np_i32p(key_offs), native.np_i32p(key_lens),
            native.np_i32p(val_offs), native.np_i32p(val_lens), max_e,
            0, 0,
        )
        if rc == -2:
            key_cap *= 4
            continue
        if rc == -4:
            max_e *= 4
            continue
        if rc == -6:
            raise Corruption("block checksum mismatch (refvals scan)")
        if rc < 0:
            # -5 compressed, -7 offset budget, -8 long-key/corrupt: let the
            # copying path decide (it supports what this one doesn't and
            # raises the proper error for real corruption).
            return None
        n = int(rc)
        key_used = int(key_offs[n - 1] + key_lens[n - 1]) if n else 0
        return ColumnarKV(
            key_out[:key_used].copy(), key_offs[:n].copy(),
            key_lens[:n].copy(),
            rawb, val_offs[:n].copy(), val_lens[:n].copy(),
        )


def _bulk_decode(lib, raw, block_offs, block_lens, verify):
    """One native call decoding every (uncompressed) block of a file image
    into a dense ColumnarKV. Returns None when a block is compressed (the
    caller inflates and retries over a synthetic image)."""
    file_size = len(raw)
    praw = raw.tobytes() if isinstance(raw, np.ndarray) else bytes(raw)
    data_bytes = int(block_lens.sum())
    key_cap = 4 * data_bytes + 4096
    val_cap = data_bytes + 4096
    max_e = data_bytes // 3 + 64
    while True:
        key_out = np.empty(key_cap, dtype=np.uint8)
        val_out = np.empty(val_cap, dtype=np.uint8)
        key_offs = np.empty(max_e, dtype=np.int32)
        key_lens = np.empty(max_e, dtype=np.int32)
        val_offs = np.empty(max_e, dtype=np.int32)
        val_lens = np.empty(max_e, dtype=np.int32)
        rc = lib.tpulsm_decode_blocks(
            praw, file_size,
            native.np_i64p(block_offs), native.np_i64p(block_lens),
            len(block_offs), 1 if verify else 0,
            native.np_u8p(key_out), key_cap,
            native.np_u8p(val_out), val_cap,
            native.np_i32p(key_offs), native.np_i32p(key_lens),
            native.np_i32p(val_offs), native.np_i32p(val_lens), max_e,
        )
        if rc == -2:
            key_cap *= 4
            continue
        if rc == -3:
            val_cap *= 4
            continue
        if rc == -4:
            max_e *= 4
            continue
        if rc == -5:
            return None  # compressed blocks present
        if rc == -6:
            raise Corruption("block checksum mismatch (native bulk scan)")
        if rc == -7:
            raise NotSupported("input too large for native columnar path")
        if rc < 0:
            raise Corruption(f"native bulk decode failed rc={rc}")
        n = int(rc)
        key_used = int(key_offs[n - 1] + key_lens[n - 1]) if n else 0
        val_used = int(val_offs[n - 1] + val_lens[n - 1]) if n else 0
        return ColumnarKV(
            key_out[:key_used].copy(), key_offs[:n].copy(), key_lens[:n].copy(),
            val_out[:val_used].copy(), val_offs[:n].copy(), val_lens[:n].copy(),
        )


class _ColumnarSST:
    """Framing state for ONE output file of the columnar writer (index,
    props, meta blocks, footer) — the TableBuilder-equivalent file shell."""

    def __init__(self, env, dbname, fnum, icmp, options, creation_time,
                 column_family=(0, "default"), pool=None):
        from toplingdb_tpu.db import filename as _fn

        self.fnum = fnum
        self.path = _fn.table_file_name(dbname, fnum)
        self.w = env.new_writable_file(self.path)
        self._icmp = icmp
        self._options = options
        # Compressed output: blocks compress on `pool` threads (the codecs
        # release the GIL) and write in order; ZSTD dictionary training
        # buffers the first train_budget() of raw blocks, as in
        # TableBuilder (reference parallel compression + dict,
        # block_based_table_builder.cc:818-825, util/compression.h:1435).
        self._pool = pool
        self._copts = getattr(options, "compression_opts", None) \
            or CompressionOptions()
        self._dict: bytes | None = (
            b"" if (options.compression == fmt.ZSTD_COMPRESSION
                    and self._copts.max_dict_bytes > 0) else None
        )
        self._dict_samples: list = []
        self._dict_bytes = 0
        self._pending: list = []  # (future|tuple, raw_len, first, last, n)
        self.index_block = BlockBuilder(options.index_restart_interval)
        self.props = TableProperties(
            comparator_name=icmp.user_comparator.name(),
            filter_policy_name=(
                options.filter_policy.name() if options.filter_policy else ""
            ),
            compression_name=str(options.compression),
            column_family_id=column_family[0],
            column_family_name=column_family[1],
            creation_time=creation_time,
            smallest_seqno=dbformat.MAX_SEQUENCE_NUMBER,
        )
        self.pending_last_key: bytes | None = None
        self.pending_handle = None
        self.first_key: bytes | None = None
        self.last_key: bytes | None = None
        self.num_entries = 0

    def _account_block(self, handle, raw_len: int, block_first: bytes,
                       block_last: bytes, n_entries: int) -> None:
        """Index/props bookkeeping shared by the per-block and bulk paths —
        one implementation so the two can't diverge byte-wise."""
        if self.first_key is None:
            self.first_key = block_first
        if self.pending_last_key is not None:
            sep = self._icmp.find_shortest_separator(
                self.pending_last_key, block_first
            )
            self.index_block.add(sep, self.pending_handle.encode())
        self.pending_handle = handle
        self.pending_last_key = block_last
        self.props.data_size += raw_len
        self.props.num_data_blocks += 1
        self.last_key = block_last
        self.num_entries += n_entries

    def pending_bytes(self) -> int:
        """Raw bytes buffered for dict training / in the compress queue —
        counted into the output-cut size check so it can't lag."""
        return self._dict_bytes + sum(p[1] for p in self._pending)

    def add_block(self, raw: bytes, block_first: bytes, block_last: bytes,
                  n_entries: int) -> None:
        if self._dict == b"":
            self._dict_samples.append((raw, block_first, block_last,
                                       n_entries))
            self._dict_bytes += len(raw)
            if self._dict_bytes >= self._copts.train_budget():
                self._train_dict_and_flush()
            return
        if self._pool is not None \
                and self._options.compression != fmt.NO_COMPRESSION:
            fut = self._pool.submit(
                fmt.compress_for_block, raw, self._options.compression,
                self._copts.level, self._dict or b"",
            )
            self._pending.append((fut, len(raw), block_first, block_last,
                                  n_entries))
            self._drain(wait=False)
            return
        handle = fmt.write_block(self.w, raw, self._options.compression,
                                 self._copts.level, self._dict or b"")
        self._account_block(handle, len(raw), block_first, block_last,
                            n_entries)

    def _train_dict_and_flush(self) -> None:
        from toplingdb_tpu.utils import codecs

        self._dict = codecs.zstd_train_dictionary(
            [r for r, _f, _l, _n in self._dict_samples],
            self._copts.max_dict_bytes,
        )
        if self._dict == b"":
            # Training failed (ZDICT needs enough distinct samples). b"" is
            # the 'training pending' sentinel, so leaving it would make the
            # replay below re-buffer forever; disable the dict instead.
            self._dict = None
        samples, self._dict_samples, self._dict_bytes = \
            self._dict_samples, [], 0
        for raw, first, last, n in samples:
            self.add_block(raw, first, last, n)

    def _drain(self, wait: bool) -> None:
        while self._pending and (wait or self._pending[0][0].done()):
            fut, raw_len, first, last, n = self._pending.pop(0)
            payload, out_type = fut.result()
            h = fmt.write_compressed_block(self.w, payload, out_type)
            self._account_block(h, raw_len, first, last, n)

    def add_framed_section_arrays(self, section, counts, plens, rawlens,
                                  nb: int, start_pos: int,
                                  entry_key_fn) -> None:
        """Bulk form of add_framed_section that DEFERS index building to
        one native call at finish: per-block metadata is kept as numpy
        arrays (no per-block Python at all); only the file's first/last
        keys are materialized here (two entry_key calls per section)."""
        base = self.w.file_size()
        if self.first_key is None:
            self.first_key = entry_key_fn(start_pos)
        cnts = counts[:nb].astype(np.int64, copy=True)
        pls = plens[:nb].astype(np.int64, copy=True)
        if not hasattr(self, "_nat_sections"):
            self._nat_sections = []
        self._nat_sections.append((start_pos, cnts, pls, base))
        self.props.data_size += int(rawlens[:nb].sum())
        self.props.num_data_blocks += nb
        total = int(cnts.sum())
        self.num_entries += total
        self.last_key = entry_key_fn(start_pos + total - 1)
        self.w.append(section)

    def _native_index_raw(self, lib, kv, order, trailer_override) -> bytes:
        """Build this file's whole index block in one native call from the
        deferred section metadata (tpulsm_build_index_block)."""
        pos_parts, cnt_parts, off_parts, plen_parts = [], [], [], []
        for start_pos, cnts, pls, base in self._nat_sections:
            cum = np.concatenate(([0], np.cumsum(cnts)[:-1]))
            pos_parts.append(start_pos + cum)
            cnt_parts.append(cnts)
            offcum = np.concatenate(
                ([0], np.cumsum(pls + fmt.BLOCK_TRAILER_SIZE)[:-1]))
            off_parts.append(base + offcum)
            plen_parts.append(pls)
        bpos = np.ascontiguousarray(np.concatenate(pos_parts))
        bcnt = np.ascontiguousarray(np.concatenate(cnt_parts))
        boff = np.ascontiguousarray(np.concatenate(off_parts))
        bpl = np.ascontiguousarray(np.concatenate(plen_parts))
        nb = len(bpos)
        cap = 64 * nb + 8192
        out_len = np.zeros(1, dtype=np.int64)
        while True:
            out = np.empty(cap, dtype=np.uint8)
            rc = lib.tpulsm_build_index_block(
                native.np_u8p(kv.key_buf), native.np_i32p(kv.key_offs),
                native.np_i32p(kv.key_lens), native.np_i64p(trailer_override),
                native.np_i32p(order),
                native.np_i64p(bpos), native.np_i64p(bcnt),
                native.np_i64p(boff), native.np_i64p(bpl),
                nb, self._options.index_restart_interval,
                native.np_u8p(out), cap, native.np_i64p(out_len),
            )
            if rc == -2:
                cap *= 4
                continue
            if rc != nb:
                raise NotSupported(f"native index build failed rc={rc}")
            return out[: int(out_len[0])].tobytes()

    def add_framed_section(self, section: bytes, blocks) -> None:
        """Bulk form of add_block: `section` is a pre-framed run of blocks
        (payload + type byte + crc trailer, exactly what write_block emits;
        payloads may be compressed) and `blocks` yields
        (payload_len, raw_len, first_key, last_key, n_entries) per block in
        file order. One append for the whole run."""
        offset = self.w.file_size()
        for payload_len, raw_len, block_first, block_last, n_entries \
                in blocks:
            self._account_block(fmt.BlockHandle(offset, payload_len),
                                raw_len, block_first, block_last,
                                n_entries)
            offset += payload_len + fmt.BLOCK_TRAILER_SIZE
        self.w.append(section)

    def finish(self, lib, kv, sel, vtypes, seqs, tombstones):
        """Write meta blocks + footer; `sel` = the original-index selection
        of this file's entries (stats/bloom are vectorized over it)."""
        with telemetry.span("sst.finish_file", file=self.fnum):
            out = self._finish_blocks(lib, kv, sel, vtypes, seqs,
                                      tombstones)
        with telemetry.span("sst.sync_close", file=self.fnum,
                            bytes=self.w.file_size()):
            self.w.flush()
            self.w.sync()
            self.w.close()
        return out

    def _finish_blocks(self, lib, kv, sel, vtypes, seqs, tombstones):
        """Everything of finish() but the fsync: pending data blocks,
        filter, range-del, dictionary, index, properties, metaindex,
        footer."""
        if self._dict == b"":
            self._train_dict_and_flush()  # small file: train from the lot
        self._drain(wait=True)
        icmp = self._icmp
        options = self._options
        props = self.props
        n = len(sel)
        nat_sections = getattr(self, "_nat_sections", None)
        if nat_sections and self.pending_last_key is not None:
            # Per-block and deferred-index entries would interleave out of
            # order; this cannot happen on the section path — refuse.
            raise NotSupported("mixed index modes in one output file")
        if self.pending_last_key is not None:
            succ = icmp.find_short_successor(self.pending_last_key)
            self.index_block.add(succ, self.pending_handle.encode())
        props.num_entries = n
        props.raw_key_size = int(kv.key_lens[sel].sum()) if n else 0
        props.raw_value_size = int(kv.val_lens[sel].sum()) if n else 0
        vt = vtypes[sel] if n else vtypes[:0]
        props.num_deletions = int(np.count_nonzero(
            (vt == int(dbformat.ValueType.DELETION))
            | (vt == int(dbformat.ValueType.SINGLE_DELETION))
        ))
        props.num_merge_operands = int(np.count_nonzero(
            vt == int(dbformat.ValueType.MERGE)
        ))
        sq = seqs[sel] if n else seqs[:0]
        props.smallest_seqno = int(sq.min()) if n else 0
        props.largest_seqno = int(sq.max()) if n else 0

        meta_entries = []
        metaindex = BlockBuilder(restart_interval=1)
        if options.filter_policy and options.whole_key_filtering and n:
            from toplingdb_tpu.table.filter import build_filter_block_native

            fdata = build_filter_block_native(
                lib, options.filter_policy, kv.key_buf,
                kv.key_offs[sel], (kv.key_lens[sel] - 8), n)
            fh = fmt.write_block(self.w, fdata, fmt.NO_COMPRESSION)
            props.filter_size = len(fdata)
            meta_entries.append((METAINDEX_FILTER, fh))

        smallest = self.first_key
        largest = self.last_key
        if tombstones:
            rdb = BlockBuilder(restart_interval=1)
            for frag in tombstones:
                b, e = frag.to_table_entry()
                rdb.add(b, e)
                props.num_range_deletions += 1
                if smallest is None or icmp.compare(b, smallest) < 0:
                    smallest = b
                end_ikey = dbformat.make_internal_key(
                    e, dbformat.MAX_SEQUENCE_NUMBER, dbformat.VALUE_TYPE_FOR_SEEK
                )
                if largest is None or icmp.compare(end_ikey, largest) > 0:
                    largest = end_ikey
                props.smallest_seqno = min(props.smallest_seqno, frag.seq)
                props.largest_seqno = max(props.largest_seqno, frag.seq)
            rh = fmt.write_block(self.w, rdb.finish(), fmt.NO_COMPRESSION)
            meta_entries.append((METAINDEX_RANGE_DEL, rh))

        if self._dict:
            dh = fmt.write_block(self.w, self._dict, fmt.NO_COMPRESSION)
            meta_entries.append((METAINDEX_COMPRESSION_DICT, dh))

        if nat_sections:
            iraw = self._native_index_raw(lib, kv, self._idx_order,
                                          self._idx_trailer)
        else:
            iraw = self.index_block.finish()
        props.index_size = len(iraw)
        pblock = props.encode_block()
        ph = fmt.write_block(self.w, pblock, fmt.NO_COMPRESSION)
        meta_entries.append((METAINDEX_PROPERTIES, ph))
        for name, handle in sorted(meta_entries):
            metaindex.add(name, handle.encode())
        mih = fmt.write_block(self.w, metaindex.finish(), fmt.NO_COMPRESSION)
        ih = fmt.write_block(self.w, iraw, options.compression)
        self.w.append(fmt.Footer(mih, ih).encode())
        return props, smallest, largest


def write_tables_columnar(env, dbname, new_file_number, icmp, options,
                          kv: ColumnarKV, order: np.ndarray,
                          trailer_override: np.ndarray, vtypes: np.ndarray,
                          seqs: np.ndarray, tombstones, creation_time: int,
                          max_output_file_size: int = 2 ** 62,
                          column_family=(0, "default"), stats=None):
    """Build output SSTs from `kv` entries in `order`, byte-identical to
    TableBuilder fed the same stream through build_outputs — including the
    output-cutting rule (cut at a user-key boundary once the file's written
    bytes reach max_output_file_size; reference
    CompactionOutputs::ShouldStopBefore). Cutting is disabled while range
    tombstones survive, matching the per-entry path. trailer_override[i]
    (per ORIGINAL entry index) >= 0 replaces the 8-byte key trailer (seqno
    zeroing). Returns a list of (fnum, path, props, smallest, largest, sel)
    where sel is the original-index selection written to that file.
    On any failure every partial output is deleted before re-raising.

    `order` may also be an ITERATOR of int32 chunks (the device-shard
    pipeline: shard s's survivors stream into SSTs while shard s+1 is still
    computing/downloading). Chunks must be key-range-ordered with no user
    key spanning a chunk boundary, and the caller may update
    trailer_override/seqs rows for a chunk any time before yielding it.

    `options.format == "single_fast"` is handed, arguments and all, to the
    format's own writer (table/single_fast.py::write_tables_sft_columnar:
    no blocks to cut, so it appends each chunk as it arrives); `stats`
    (CompactionStats) is for that writer's `sft_build_usec`."""
    lib = native.lib()
    if lib is None:
        raise NotSupported("native library unavailable")
    if getattr(options, "format", "block") == "single_fast":
        from toplingdb_tpu.table.single_fast import write_tables_sft_columnar

        return write_tables_sft_columnar(
            env, dbname, new_file_number, icmp, options, kv, order,
            trailer_override, vtypes, seqs, tombstones, creation_time,
            max_output_file_size=max_output_file_size,
            column_family=column_family, stats=stats)
    # The writer's set-up: buffers sized from the inputs, the first
    # output file created.
    setup = telemetry.span("sst.open")
    if isinstance(order, np.ndarray):
        # Whole array up front: no copy, no withhold/rebuild of the final
        # block (exhausted from the start).
        chunks = iter(())
        order = np.ascontiguousarray(order, dtype=np.int32)
        start_filled = len(order)
        start_exhausted = True
    else:
        chunks = iter(order)
        # Survivor count unknown until the last chunk arrives; kv.n bounds it.
        order = np.empty(kv.n, dtype=np.int32)
        start_filled = 0
        start_exhausted = False
        # Streaming callers mutate trailer_override/seqs rows right before
        # yielding each chunk; a dtype/layout conversion here would COPY and
        # silently sever that aliasing, so demand the exact form instead.
        if (trailer_override.dtype != np.int64
                or not trailer_override.flags.c_contiguous):
            raise NotSupported(
                "streamed order requires a C-contiguous int64 "
                "trailer_override (mutations must alias the writer's view)"
            )
    trailer_override = np.ascontiguousarray(trailer_override, dtype=np.int64)

    max_entry = int(kv.key_lens.max() if kv.n else 0) + int(
        kv.val_lens.max() if kv.n else 0
    )
    if not start_exhausted:
        # Streamed (pipelined) callers hand over PREALLOCATED kv buffers
        # that reader threads are still filling: the length arrays may
        # hold uninitialized garbage here, so any size derived from them
        # is only a capacity GUESS. Clamp it to a sane window — the
        # rc==-2 grow-and-retry loops below make small guesses correct,
        # and a negative/absurd garbage max must never turn into a
        # negative np.empty (a heap-state-dependent crash).
        max_entry = min(max(max_entry, 0), 4 << 20)
    out_cap = options.block_size * 2 + max_entry + 8192
    out_buf = np.empty(out_cap, dtype=np.uint8)
    out_len = np.zeros(1, dtype=np.int64)

    def entry_key(pos: int) -> bytes:
        e = int(order[pos])
        k = kv.ikey(e)
        t = int(trailer_override[e])
        if t >= 0:
            k = k[:-8] + t.to_bytes(8, "little")
        return k

    def same_user_key(pos_a: int, pos_b: int) -> bool:
        a, b = int(order[pos_a]), int(order[pos_b])
        la, lb = int(kv.key_lens[a]) - 8, int(kv.key_lens[b]) - 8
        if la != lb:
            return False
        oa, ob = int(kv.key_offs[a]), int(kv.key_offs[b])
        return bool(np.array_equal(kv.key_buf[oa:oa + la],
                                   kv.key_buf[ob:ob + lb]))

    # Hoist ctypes pointer conversions out of the per-block loop.
    p_kbuf = native.np_u8p(kv.key_buf)
    p_koff = native.np_i32p(kv.key_offs)
    p_klen = native.np_i32p(kv.key_lens)
    p_vbuf = native.np_u8p(kv.val_buf)
    p_voff = native.np_i32p(kv.val_offs)
    p_vlen = native.np_i32p(kv.val_lens)
    p_tro = native.np_i64p(trailer_override)
    p_order = native.np_i32p(order)
    p_outlen = native.np_i64p(out_len)
    p_out = native.np_u8p(out_buf)

    can_cut = not tombstones  # single output while tombstones survive

    # Bulk framing: emit a whole RUN of framed blocks per native call
    # (payload + type byte + crc trailer, byte-identical to write_block)
    # instead of one block per call — the per-block Python loop dominates
    # the write side at bench scale. Uncompressed output and snappy/zstd
    # (dict-less) both run natively; a stale .so degrades per-block.
    copts0 = getattr(options, "compression_opts", None)
    sec_ctype = 0
    if options.compression == fmt.NO_COMPRESSION:
        use_section = hasattr(lib, "tpulsm_build_data_section")
    elif (options.compression in (fmt.SNAPPY_COMPRESSION,
                                  fmt.ZSTD_COMPRESSION)
          and not (copts0 is not None and copts0.max_dict_bytes > 0)
          and hasattr(lib, "tpulsm_build_data_section_c")):
        use_section = True
        sec_ctype = options.compression
    else:
        use_section = False
    if use_section and kv.n:
        # Upper bound over ALL entries (the survivor set streams in): the
        # buffers' own sizes. Not the sum of the length arrays: under
        # streamed callers the readers are still filling those (see the
        # max_entry clamp above), fresh pages read 0, and a section buffer
        # of 64 KB then costs a hundred native calls a chunk where two do
        # (PERF.md §6, PR 25: `sst.build_data` +3.8-5.9 s a job).
        sec_bytes = len(kv.key_buf) + len(kv.val_buf)
        # Each native call emits at most ~_SECTION_RUN_BYTES (stopping a run
        # early is free: the next call continues the same file), so the
        # section buffer and the per-call copy stay bounded no matter how
        # large the compaction or the output-file budget is.
        sec_cap = min(sec_bytes + sec_bytes // 4,
                      _SECTION_RUN_BYTES + out_cap) + (1 << 16)
        sec_buf = np.empty(sec_cap, dtype=np.uint8)
        max_blocks = sec_cap // max(1, options.block_size) + 1024
        sec_counts = np.empty(max_blocks, dtype=np.int64)
        sec_plens = np.empty(max_blocks, dtype=np.int64)
        sec_rawlens = np.empty(max_blocks, dtype=np.int64)
        sec_len = np.zeros(1, dtype=np.int64)
        p_sec = native.np_u8p(sec_buf)
        p_counts = native.np_i64p(sec_counts)
        p_plens = native.np_i64p(sec_plens)
        p_rawlens = native.np_i64p(sec_rawlens)
        p_seclen = native.np_i64p(sec_len)
        sec_level = (copts0.level if copts0 is not None
                     and copts0.level is not None else -(2 ** 31))

    use_nat_index = use_section and hasattr(lib, "tpulsm_build_index_block")

    pool = None
    if (options.compression != fmt.NO_COMPRESSION
            and getattr(options, "compression_parallel_threads", 1) > 1):
        from concurrent.futures import ThreadPoolExecutor

        pool = ThreadPoolExecutor(
            max_workers=options.compression_parallel_threads)

    results = []
    cur: _ColumnarSST | None = None
    lo = 0
    start = 0
    filled = start_filled      # rows of `order` received so far
    exhausted = start_exhausted
    # One `pipeline.encode_write` span a consumed chunk (the whole order
    # when it came as one array); waiting for the next chunk is the
    # feeder's span, not this one.
    n_chunks = 0
    ew = telemetry.NOOP_SPAN
    try:
        cur = _ColumnarSST(env, dbname, new_file_number(), icmp, options,
                           creation_time, column_family, pool)
        setup.finish()
        if start_exhausted:
            ew = telemetry.span("pipeline.encode_write", chunk=0)
        need_fetch = False
        while True:
            if start >= filled or need_fetch:
                need_fetch = False
                if not exhausted:
                    ew.finish()
                    nxt = next(chunks, None)
                    ew = telemetry.span("pipeline.encode_write",
                                        chunk=n_chunks)
                    n_chunks += 1
                    if nxt is None:
                        exhausted = True
                    else:
                        nxt = np.ascontiguousarray(nxt, dtype=np.int32)
                        order[filled:filled + len(nxt)] = nxt
                        filled += len(nxt)
                    continue
                if start >= filled:
                    break
            limit = filled
            if (can_cut and cur.num_entries
                    and cur.w.file_size() + cur.pending_bytes()
                    >= max_output_file_size):
                if not same_user_key(start, start - 1):
                    # Cut HERE (the per-entry path's pre-add check).
                    sel = order[lo:start]
                    results.append((cur.fnum, cur.path) + cur.finish(
                        lib, kv, sel, vtypes, seqs, []
                    ) + (sel,))
                    cur = _ColumnarSST(env, dbname, new_file_number(), icmp,
                                       options, creation_time, column_family,
                                       pool)
                    lo = start
                else:
                    # Same user key spans the boundary: all its versions stay
                    # in this file; bound the block at the end of the run so
                    # the cut re-check happens there.
                    j = start
                    while j < filled and same_user_key(j, j - 1):
                        j += 1
                    limit = j
            if use_section:
                # Native block build + compress of one run of blocks, its
                # copy out of the section buffer and its append to the file.
                with telemetry.span("sst.build_data", file=cur.fnum):
                    base_size = cur.w.file_size()
                    budget = base_size + _SECTION_RUN_BYTES
                    if can_cut and max_output_file_size < budget:
                        budget = max_output_file_size
                    if sec_ctype:
                        rc = lib.tpulsm_build_data_section_c(
                            p_kbuf, p_koff, p_klen, p_vbuf, p_voff, p_vlen,
                            p_tro, p_order, start, limit,
                            options.block_size, options.restart_interval,
                            sec_ctype, sec_level,
                            base_size, budget,
                            p_counts, p_plens, p_rawlens, max_blocks,
                            p_sec, sec_cap, p_seclen,
                        )
                        if rc == -9:
                            # codec .so unavailable: per-block Python framing
                            use_section = False
                            sec_ctype = 0
                            continue
                    else:
                        rc = lib.tpulsm_build_data_section(
                            p_kbuf, p_koff, p_klen, p_vbuf, p_voff, p_vlen,
                            p_tro, p_order, start, limit,
                            options.block_size, options.restart_interval,
                            base_size, budget,
                            p_counts, p_plens, max_blocks,
                            p_sec, sec_cap, p_seclen,
                        )
                        sec_rawlens[:max(0, int(rc))] = \
                            sec_plens[:max(0, int(rc))] if rc > 0 else 0
                    if rc == -2:
                        sec_cap *= 4
                        sec_buf = np.empty(sec_cap, dtype=np.uint8)
                        p_sec = native.np_u8p(sec_buf)
                        continue
                    if rc == -3 or rc == -8:
                        raise NotSupported(
                            f"native block build unsupported input rc={rc}"
                        )
                    if rc <= 0:
                        raise Corruption(f"native section build failed rc={rc}")
                    nb = int(rc)
                    sec_total = int(sec_len[0])
                    pos = start + sum(int(sec_counts[b]) for b in range(nb))
                    if not exhausted and pos == filled:
                        # The final block ended at the chunk boundary — it may
                        # have been starved, not full. Withhold it until more
                        # data arrives so block layout matches the
                        # whole-array build byte-for-byte.
                        last_cnt = int(sec_counts[nb - 1])
                        nb -= 1
                        pos -= last_cnt
                        sec_total -= int(sec_plens[nb]) + fmt.BLOCK_TRAILER_SIZE
                        need_fetch = True
                        if nb == 0:
                            continue
                    section = sec_buf[:sec_total].tobytes()
                    if use_nat_index:
                        # Index entries defer to ONE native call at finish —
                        # zero per-block Python on the section path.
                        cur._idx_order = order
                        cur._idx_trailer = trailer_override
                        cur.add_framed_section_arrays(
                            section, sec_counts, sec_plens, sec_rawlens, nb,
                            start, entry_key)
                    else:
                        blocks = []
                        bpos = start
                        for b in range(nb):
                            cnt = int(sec_counts[b])
                            blocks.append((int(sec_plens[b]),
                                           int(sec_rawlens[b]),
                                           entry_key(bpos),
                                           entry_key(bpos + cnt - 1), cnt))
                            bpos += cnt
                        cur.add_framed_section(section, blocks)
                    start = pos
                    continue
            rc = lib.tpulsm_build_block(
                p_kbuf, p_koff, p_klen, p_vbuf, p_voff, p_vlen, p_tro,
                p_order, start, limit,
                options.block_size, options.restart_interval,
                p_out, out_cap, p_outlen,
            )
            if rc == -2:
                out_cap *= 4
                out_buf = np.empty(out_cap, dtype=np.uint8)
                p_out = native.np_u8p(out_buf)
                continue
            if rc == -3 or rc == -8:
                # Key too long for the native stack buffer / restart table
                # full: the per-entry path handles these.
                raise NotSupported(
                    f"native block build unsupported input rc={rc}"
                )
            if rc <= 0:
                raise Corruption(f"native block build failed rc={rc}")
            if not exhausted and start + int(rc) == filled:
                # Possibly starved at the chunk boundary: rebuild this block
                # once more data arrives (see the section path above).
                need_fetch = True
                continue
            raw = out_buf[: int(out_len[0])].tobytes()
            cur.add_block(raw, entry_key(start),
                          entry_key(start + int(rc) - 1), int(rc))
            start += int(rc)
        sel = order[lo:filled]
        results.append((cur.fnum, cur.path) + cur.finish(
            lib, kv, sel, vtypes, seqs, tombstones
        ) + (sel,))
        cur = None
        return results
    except BaseException:
        if cur is not None:
            cur.w.close()
            try:
                env.delete_file(cur.path)
            except Exception as e:
                _errors.swallow(reason="sst-abort-cleanup", exc=e)
        for r in results:
            try:
                env.delete_file(r[1])
            except Exception as e:
                _errors.swallow(reason="sst-abort-cleanup", exc=e)
        raise
    finally:
        ew.finish()
        if pool is not None:
            pool.shutdown()
