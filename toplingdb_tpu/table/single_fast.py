"""SingleFastTable: the flat, all-in-RAM SST format for hot levels.

The analogue of the reference's Topling SingleFastTable (the L0/L1 format of
the absent topling-sst submodule; README.md:50 claims it as a headline) and
of PlainTable (table/plain/): no blocks, no prefix compression — entries are
a flat [varint klen | varint vlen | ikey | value] region, the index is a raw
fixed32 offset array, and the reader holds the whole file in memory, so a
point lookup is a pure binary search (no per-block linear scan) and a scan
is a linear decode. Shares the bloom filter / properties / range-del meta
blocks and the footer shape with the block format; dispatched by footer
magic (table/factory.py — the adaptive-table mechanism).

The device data plane reads and writes the format without per-entry
Python: a reader bounds and scans ENTRY RANGES (`split_candidates`,
`entry_lower_bound`, `scan_columnar` / `scan_into`: one native pass over
the resident image, ops/pipeline.py plans a file by them), and
`write_tables_sft_columnar` builds files from columnar buffers and a
survivor order, a chunk at a time, byte-identical to the builder.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from toplingdb_tpu.db import dbformat
from toplingdb_tpu.db.dbformat import InternalKeyComparator, ValueType
from toplingdb_tpu.table import format as fmt
from toplingdb_tpu.table.block import BlockBuilder, BlockIter
from toplingdb_tpu.table.builder import (
    METAINDEX_FILTER,
    METAINDEX_PROPERTIES,
    METAINDEX_RANGE_DEL,
    TableOptions,
)
from toplingdb_tpu.table.filter import filter_policy_from_name
from toplingdb_tpu.table.properties import TableProperties
from toplingdb_tpu.utils import coding, crc32c
from toplingdb_tpu.utils import errors as _errors
from toplingdb_tpu.utils import telemetry as _tele
from toplingdb_tpu.utils.status import Corruption, NotSupported

METAINDEX_DATA_CRC = b"tpulsm.sf.data_crc"
METAINDEX_HASH_INDEX = b"tpulsm.sf.hash_index"


class SingleFastTableBuilder:
    """Same surface as TableBuilder (build_outputs/flush compatible)."""

    FOOTER_MAGIC = fmt.SINGLE_FAST_MAGIC

    def __init__(self, wfile, icmp: InternalKeyComparator,
                 options: TableOptions | None = None,
                 column_family_id: int = 0, column_family_name: str = "",
                 creation_time: int = 0):
        self.opts = options or TableOptions()
        self._w = wfile
        self._icmp = icmp
        self._buf = bytearray()
        self._offsets: list[int] = []
        self._filter_keys: list[bytes] = []
        self._range_del_block = BlockBuilder(restart_interval=1)
        self.props = _new_props(icmp, self.opts, column_family_id,
                                column_family_name, creation_time)
        self._last_key: bytes | None = None
        self._smallest: bytes | None = None
        self._largest: bytes | None = None
        self._finished = False
        self._last_filter_prefix: bytes | None = None
        self._collectors = [
            f.create() for f in self.opts.properties_collector_factories
        ]
        self.need_compaction = False
        self._unsorted: list[tuple[bytes, bytes]] = []  # auto_sort buffer
        self._unsorted_bytes = 0

    @property
    def num_entries(self) -> int:
        return self.props.num_entries + self.props.num_range_deletions

    def file_size(self) -> int:
        # _unsorted_bytes: output-cutting must see buffered auto_sort adds.
        return self._w.file_size() + len(self._buf) + self._unsorted_bytes

    @property
    def smallest_key(self) -> bytes | None:
        return self._smallest

    @property
    def largest_key(self) -> bytes | None:
        return self._largest

    def _track_bounds(self, ikey: bytes) -> None:
        if self._smallest is None or self._icmp.compare(ikey, self._smallest) < 0:
            self._smallest = ikey
        if self._largest is None or self._icmp.compare(ikey, self._largest) > 0:
            self._largest = ikey
        seq = dbformat.extract_seqno(ikey)
        self.props.smallest_seqno = min(self.props.smallest_seqno, seq)
        self.props.largest_seqno = max(self.props.largest_seqno, seq)

    def add(self, ikey: bytes, value: bytes) -> None:
        assert not self._finished
        if self.opts.auto_sort:
            # VecAutoSortTable mode: buffer now, sort at finish.
            self._unsorted.append((ikey, value))
            self._unsorted_bytes += len(ikey) + len(value) + 10
            return
        self._add_sorted(ikey, value)

    def _add_sorted(self, ikey: bytes, value: bytes) -> None:
        if self._last_key is not None:
            assert self._icmp.compare(self._last_key, ikey) < 0
        if len(self._buf) + len(ikey) + len(value) + 10 > 0xFFFFFF00:
            # Offsets are fixed32: refuse before appending (no torn region)
            # rather than overflow into a corrupt index at finish().
            from toplingdb_tpu.utils.status import NotSupported

            raise NotSupported(
                "single_fast table data region exceeds 4GiB; use the block "
                "format or a smaller max_output_file_size"
            )
        self._offsets.append(len(self._buf))
        self._buf += coding.encode_varint32(len(ikey))
        self._buf += coding.encode_varint32(len(value))
        self._buf += ikey
        self._buf += value
        self._last_key = ikey
        self._track_bounds(ikey)
        uk, seq_, t = dbformat.split_internal_key(ikey)
        if self.opts.filter_policy:
            if self.opts.whole_key_filtering:
                self._filter_keys.append(uk)
            pe = getattr(self.opts, "prefix_extractor", None)
            if pe is not None and pe.in_domain(uk):
                p = pe.transform(uk)
                if p != self._last_filter_prefix:
                    self._filter_keys.append(p)
                    self._last_filter_prefix = p
        for c in self._collectors:
            c.add_user_key(uk, value, t, seq_, len(self._buf))
        self.props.num_entries += 1
        self.props.raw_key_size += len(ikey)
        self.props.raw_value_size += len(value)
        if t in (ValueType.DELETION, ValueType.SINGLE_DELETION):
            self.props.num_deletions += 1
        elif t == ValueType.MERGE:
            self.props.num_merge_operands += 1

    def add_tombstone(self, begin_ikey: bytes, end_user_key: bytes) -> None:
        assert not self._finished
        self._range_del_block.add(begin_ikey, end_user_key)
        self.props.num_range_deletions += 1
        self._track_bounds(begin_ikey)
        end_ikey = dbformat.make_internal_key(
            end_user_key, dbformat.MAX_SEQUENCE_NUMBER,
            dbformat.VALUE_TYPE_FOR_SEEK,
        )
        if self._largest is None or self._icmp.compare(end_ikey, self._largest) > 0:
            self._largest = end_ikey

    def _entry_user_key(self, i: int) -> bytes:
        off = self._offsets[i]
        klen, o = coding.decode_varint32(self._buf, off)
        _, o = coding.decode_varint32(self._buf, o)
        return bytes(self._buf[o: o + klen - 8])

    def _hash_index_block(self) -> tuple[bytes, bytes] | None:
        """(metaindex name, raw block bytes) of the point-lookup index, or
        None. Subclass hook — the cuckoo format swaps in its own table."""
        if not (self.opts.hash_index and self._offsets
                and self._icmp.user_comparator.name()
                == dbformat.BYTEWISE.name()):
            # Bytewise comparator only: the hash dedups/matches by BYTE
            # equality, which must coincide with comparator equality.
            return None
        # O(1) point-lookup bucket array (the PlainTable prefix-hash role,
        # reference table/plain/): open-addressed xxh64 buckets at <=0.7
        # load, each holding 1 + the ordinal of the NEWEST version of one
        # user key.
        n = len(self._offsets)
        nb = 1
        while nb < (n * 10) // 7 + 1:
            nb <<= 1
        buckets = np.zeros(nb, dtype="<u4")
        mask = nb - 1
        prev_uk = None
        for i in range(n):
            uk = self._entry_user_key(i)
            if uk == prev_uk:
                continue  # hash maps to the first (newest) version
            prev_uk = uk
            h = crc32c.xxh64(uk) & mask
            while buckets[h]:
                h = (h + 1) & mask
            buckets[h] = i + 1
        return METAINDEX_HASH_INDEX, buckets.tobytes()

    def finish(self) -> TableProperties:
        assert not self._finished
        if self.opts.auto_sort and self._unsorted:
            # Reverse + STABLE sort: among exact-duplicate internal keys the
            # latest add comes first, so dedup keeps last-write-wins.
            ents = sorted(reversed(self._unsorted),
                          key=lambda kv: self._icmp.sort_key(kv[0]))
            self._unsorted = []
            self._unsorted_bytes = 0
            prev = None
            for k, v in ents:
                if prev is not None and self._icmp.compare(prev, k) == 0:
                    continue  # older duplicate
                self._add_sorted(k, v)
                prev = k
        for c in self._collectors:
            self.props.user_collected.update(c.finish())
            if c.need_compact():
                self.need_compaction = True
        data = bytes(self._buf)
        self._w.append(data)  # flat data region at offset 0, unframed
        fdata = None
        if self.opts.filter_policy and self._filter_keys:
            fdata = self.opts.filter_policy.create_filter(self._filter_keys)
        _write_tail(
            self._w, self.props, len(data), crc32c.value(data),
            np.asarray(self._offsets, dtype="<u4").tobytes(), fdata,
            self._hash_index_block(),
            None if self._range_del_block.empty()
            else self._range_del_block.finish(), self.FOOTER_MAGIC)
        self._w.flush()
        self._finished = True
        return self.props


def _new_props(icmp, opts, cf_id: int, cf_name: str,
               creation_time: int) -> TableProperties:
    """The properties a SingleFastTable starts from (the builder and the
    columnar writer both: one place, so the two cannot drift)."""
    return TableProperties(
        comparator_name=icmp.user_comparator.name(),
        filter_policy_name=(
            opts.filter_policy.name() if opts.filter_policy else ""
        ),
        compression_name="single_fast",
        prefix_extractor_name=(
            opts.prefix_extractor.name()
            if getattr(opts, "prefix_extractor", None) else ""
        ),
        column_family_id=cf_id,
        column_family_name=cf_name,
        creation_time=creation_time,
        smallest_seqno=dbformat.MAX_SEQUENCE_NUMBER,
        whole_key_filtering=1 if opts.whole_key_filtering else 0,
    )


def _write_tail(w, props, data_len: int, data_crc: int, iraw: bytes,
                fdata: bytes | None, hash_block, rd_raw: bytes | None,
                magic: int) -> None:
    """Everything of a SingleFastTable behind its data region, which `w`
    already holds: the region's checksum block, filter, hash index,
    range-del block, properties, metaindex, the raw fixed32 offset array
    as the "index block", footer. `hash_block` is (metaindex name, raw
    bytes) or None."""
    props.data_size = data_len
    props.num_data_blocks = 1
    metaindex = BlockBuilder(restart_interval=1)
    meta_entries = []
    # Whole-region checksum (entries have no per-block trailers).
    ch = fmt.write_block(w, coding.encode_fixed32(crc32c.mask(data_crc)),
                         fmt.NO_COMPRESSION)
    meta_entries.append((METAINDEX_DATA_CRC, ch))
    if fdata is not None:
        fh = fmt.write_block(w, fdata, fmt.NO_COMPRESSION)
        props.filter_size = len(fdata)
        meta_entries.append((METAINDEX_FILTER, fh))
    if hash_block is not None:
        name, hdata = hash_block
        hh = fmt.write_block(w, hdata, fmt.NO_COMPRESSION)
        meta_entries.append((name, hh))
    if rd_raw is not None:
        rh = fmt.write_block(w, rd_raw, fmt.NO_COMPRESSION)
        meta_entries.append((METAINDEX_RANGE_DEL, rh))
    props.index_size = len(iraw)
    ph = fmt.write_block(w, props.encode_block(), fmt.NO_COMPRESSION)
    meta_entries.append((METAINDEX_PROPERTIES, ph))
    for name, handle in sorted(meta_entries):
        metaindex.add(name, handle.encode())
    mih = fmt.write_block(w, metaindex.finish(), fmt.NO_COMPRESSION)
    ih = fmt.write_block(w, iraw, fmt.NO_COMPRESSION)
    w.append(fmt.Footer(mih, ih, magic=magic).encode())


class SingleFastTableReader:
    """Same surface as TableReader. The whole file is resident in memory."""

    FOOTER_MAGIC = fmt.SINGLE_FAST_MAGIC
    # The family of spans and counters its entry-range scans are booked
    # under on the device data plane (`pipeline.sft_scan`, `sft_*`); None
    # on a reader the plane does not plan by entry ranges.
    entry_plane: str | None = "sft"

    def __init__(self, rfile, icmp: InternalKeyComparator,
                 options: TableOptions | None = None, block_cache=None,
                 cache_key_prefix: bytes = b""):
        self.opts = options or TableOptions()
        self._icmp = icmp
        size = rfile.size()
        self._data = rfile.read(0, size)
        rfile.close()
        self.footer = fmt.Footer.decode(self._data, self.FOOTER_MAGIC)
        iraw = fmt.read_block(_Mem(self._data), self.footer.index_handle,
                              self.opts.verify_checksums)
        self._offsets = np.frombuffer(iraw, dtype="<u4")
        meta = fmt.read_block(_Mem(self._data), self.footer.metaindex_handle,
                              self.opts.verify_checksums)
        mit = BlockIter(meta, dbformat.BYTEWISE.compare)
        mit.seek_to_first()
        self._meta_handles = {
            k: fmt.BlockHandle.decode_exact(v) for k, v in mit.entries()
        }
        self.properties = TableProperties()
        ph = self._meta_handles.get(METAINDEX_PROPERTIES)
        if ph is not None:
            self.properties = TableProperties.decode_block(
                fmt.read_block(_Mem(self._data), ph, self.opts.verify_checksums)
            )
        if self.opts.verify_checksums:
            ch = self._meta_handles.get(METAINDEX_DATA_CRC)
            if ch is not None:
                stored = crc32c.unmask(coding.decode_fixed32(
                    fmt.read_block(_Mem(self._data), ch, True), 0
                ))
                data_len = self.properties.data_size
                if crc32c.value(self._data[:data_len]) != stored:
                    raise Corruption("single_fast data region checksum mismatch")
        self._filter_data = None
        self._filter_policy = None
        fh = self._meta_handles.get(METAINDEX_FILTER)
        if fh is not None:
            self._filter_data = fmt.read_block(
                _Mem(self._data), fh, self.opts.verify_checksums
            )
            self._filter_policy = filter_policy_from_name(
                self.properties.filter_policy_name
            )
        self._range_del_cache = None
        rh = self._meta_handles.get(METAINDEX_RANGE_DEL)
        self._range_del_data = (
            fmt.read_block(_Mem(self._data), rh, self.opts.verify_checksums)
            if rh is not None else None
        )
        self.n = len(self._offsets)
        from toplingdb_tpu.utils.slice_transform import resolve_file_extractor

        # Resolved once: the hot probe path must not rebuild the extractor.
        self._resolved_pe = resolve_file_extractor(
            getattr(self.opts, "prefix_extractor", None),
            self.properties.prefix_extractor_name,
        )
        self._load_hash_index()

    def _load_hash_index(self) -> None:
        self._hash_buckets = None
        hh = self._meta_handles.get(METAINDEX_HASH_INDEX)
        if hh is not None:
            self._hash_buckets = np.frombuffer(
                fmt.read_block(_Mem(self._data), hh,
                               self.opts.verify_checksums),
                dtype="<u4",
            )
        self.has_hash_index = self._hash_buckets is not None

    # -- entry decode ---------------------------------------------------

    def _entry(self, i: int) -> tuple[bytes, bytes]:
        off = int(self._offsets[i])
        klen, off = coding.decode_varint32(self._data, off)
        vlen, off = coding.decode_varint32(self._data, off)
        k = self._data[off : off + klen]
        v = self._data[off + klen : off + klen + vlen]
        return k, v

    def _lower_bound(self, target: bytes) -> int:
        lo, hi = 0, self.n
        cmp = self._icmp.compare
        while lo < hi:
            mid = (lo + hi) // 2
            if cmp(self._entry(mid)[0], target) < 0:
                lo = mid + 1
            else:
                hi = mid
        return lo

    # -- TableReader surface -------------------------------------------

    def close(self) -> None:
        pass

    def key_may_match(self, user_key: bytes) -> bool:
        from toplingdb_tpu.table.filter import filter_probe

        return filter_probe(
            self._filter_policy, self._filter_data,
            bool(self.properties.whole_key_filtering),
            self._resolved_pe, user_key,
        )

    def hash_probe(self, user_key: bytes) -> int | None:
        """O(1) lookup: ordinal of the NEWEST version of user_key, or None
        when the key is definitively absent from this file. Only meaningful
        when has_hash_index (bytewise-comparator files only)."""
        buckets = self._hash_buckets
        if buckets is None:
            return None
        mask = len(buckets) - 1
        h = crc32c.xxh64(user_key) & mask
        for _ in range(len(buckets)):  # bounded: corrupt blocks can't hang
            v = int(buckets[h])
            if v == 0:
                return None
            i = v - 1
            if i >= self.n:
                raise Corruption("single_fast hash index bucket out of range")
            k = self._entry(i)[0]
            if k[:-8] == user_key:
                return i
            h = (h + 1) & mask
        raise Corruption("single_fast hash index has no empty buckets")

    def new_iterator(self) -> "SingleFastIterator":
        return SingleFastIterator(self)

    def range_del_entries(self):
        if self._range_del_data is None:
            return []
        if self._range_del_cache is None:
            it = BlockIter(self._range_del_data, self._icmp.compare)
            it.seek_to_first()
            self._range_del_cache = list(it.entries())
        return self._range_del_cache

    def approximate_offset_of(self, ikey: bytes) -> int:
        i = self._lower_bound(ikey)
        return int(self._offsets[i]) if i < self.n else self.properties.data_size

    def anchors(self, max_anchors: int = 32):
        if self.n == 0:
            return []
        step = max(1, self.n // max_anchors)
        return [self._entry(i)[0] for i in range(0, self.n, step)][:max_anchors]

    # --- entry-range surface (the device data plane) -------------------

    def split_candidates(self, block_size: int) -> list[bytes]:
        """User keys to cut a compaction's key-range shards at
        (ops/pipeline.py::_build_plan): the user key of every `step`-th
        entry, read through the offset array, one for about `block_size`
        raw key and value bytes, so that they weigh among a block file's
        index separators as one data block each."""
        if not self.n:
            return []
        raw = self.properties.raw_key_size + self.properties.raw_value_size
        step = max(1, (block_size * self.n) // max(1, raw))
        at = np.arange(step - 1, self.n, step)
        img = np.frombuffer(self._data, dtype=np.uint8)
        offs = self._offsets[at].astype(np.int64)
        klen = img[offs] if len(at) else img[:0]
        if (len(at) and int(klen.max()) < 0x80 and int(klen.min()) >= 8
                and int(img[offs + 1].max()) < 0x80
                and int(klen.min()) == int(klen.max())):
            # One key length under one-byte varints (the common file): the
            # user keys lie 2 bytes behind each offset, gathered at once.
            ukl = int(klen[0]) - 8
            flat = img[(offs + 2)[:, None] + np.arange(ukl)].tobytes()
            return [flat[i:i + ukl] for i in range(0, len(flat), ukl)] \
                if ukl else [b""] * len(at)
        return [self._entry(int(i))[0][:-8] for i in at]

    def entry_lower_bound(self, target: bytes) -> int:
        """First entry index whose internal key >= target (n past end)."""
        return self._lower_bound(target)

    def scan_native_ready(self) -> bool:
        """True when scan_columnar / scan_into can serve the scan plane."""
        from toplingdb_tpu import native

        return getattr(native.lib(), "tpulsm_sft_scan", None) is not None

    def scan_into(self, e0: int, e1: int, kv, row0: int, k0: int,
                  v0: int | None, k_cap: int, v_cap: int) -> tuple[int, int]:
        """Decode entries [e0, e1) into the columnar buffers `kv` in ONE
        GIL-free native pass over the resident image: rows from `row0`,
        key bytes from `k0`, value bytes from `v0`, at most `k_cap` and
        `v_cap` of them (NotSupported beyond) — or, with `v0` None,
        values REFERENCED where they lie in the image (`kv.val_buf` must
        then be the image). Returns the (key, value) bytes the range
        holds. The region was verified against its checksum at open."""
        import ctypes

        from toplingdb_tpu import native

        used = np.zeros(2, dtype=np.int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        img = np.frombuffer(self._data, dtype=np.uint8)

        def rows(a):
            return ctypes.cast(a.ctypes.data + 4 * row0, i32p)

        rc = native.lib().tpulsm_sft_scan(
            native.np_u8p(img), self.properties.data_size,
            self._offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            self.n, e0, e1,
            ctypes.cast(kv.key_buf.ctypes.data + k0, u8p), k_cap,
            None if v0 is None
            else ctypes.cast(kv.val_buf.ctypes.data + v0, u8p), v_cap,
            rows(kv.key_offs), rows(kv.key_lens), rows(kv.val_offs),
            rows(kv.val_lens), k0, v0 or 0, native.np_i64p(used))
        if rc == -2:
            raise NotSupported("single_fast scan: the range outgrows its "
                               "buffers (or their int32 offsets)")
        if rc != e1 - e0:
            raise Corruption(f"single_fast scan failed (rc={rc})")
        return int(used[0]), int(used[1])

    def scan_columnar(self, e0: int, e1: int):
        """Entries [e0, e1) as columnar slabs, the ZipTableReader's shape:
        (key_buf, key_offs, key_lens, val_buf, val_offs, val_lens). Keys
        are copied out dense; the value slab IS the resident image, the
        offsets point into it (no value is copied)."""
        from toplingdb_tpu.ops.columnar_io import ColumnarKV

        e0 = max(0, int(e0))
        e1 = max(e0, min(self.n, int(e1)))
        cnt = e1 - e0
        lo = int(self._offsets[e0]) if cnt else 0
        hi = (int(self._offsets[e1]) if e1 < self.n
              else self.properties.data_size) if cnt else 0
        kv = ColumnarKV(
            np.empty(max(1, hi - lo), dtype=np.uint8),
            np.empty(cnt, dtype=np.int32), np.empty(cnt, dtype=np.int32),
            np.frombuffer(self._data, dtype=np.uint8),
            np.empty(cnt, dtype=np.int32), np.empty(cnt, dtype=np.int32))
        nk = (self.scan_into(e0, e1, kv, 0, 0, None, hi - lo, 0)[0]
              if cnt else 0)
        return (kv.key_buf[:nk], kv.key_offs, kv.key_lens, kv.val_buf,
                kv.val_offs, kv.val_lens)


class _Mem:
    """RandomAccessFile view over an in-memory bytes object."""

    def __init__(self, data: bytes):
        self._d = data

    def read(self, offset: int, n: int) -> bytes:
        return self._d[offset : offset + n]

    def size(self) -> int:
        return len(self._d)


class SingleFastIterator:
    def __init__(self, r: SingleFastTableReader):
        self._r = r
        self._i = r.n  # invalid

    def valid(self) -> bool:
        return 0 <= self._i < self._r.n

    def key(self) -> bytes:
        return self._r._entry(self._i)[0]

    def value(self) -> bytes:
        return self._r._entry(self._i)[1]

    def seek_to_first(self) -> None:
        self._i = 0

    def seek_to_last(self) -> None:
        self._i = self._r.n - 1

    def seek(self, target: bytes) -> None:
        self._i = self._r._lower_bound(target)

    def seek_ordinal(self, i: int) -> None:
        """Position directly at entry ordinal i (hash_probe fast path)."""
        self._i = i

    def seek_for_prev(self, target: bytes) -> None:
        i = self._r._lower_bound(target)
        if i < self._r.n and self._r._icmp.compare(
            self._r._entry(i)[0], target
        ) == 0:
            self._i = i
        else:
            self._i = i - 1

    def next(self) -> None:
        assert self.valid()
        self._i += 1

    def prev(self) -> None:
        assert self.valid()
        self._i -= 1

    def entries(self):
        while self.valid():
            yield self.key(), self.value()
            self.next()


# Soft budget of one native append: bounds the run buffer and the copy
# handed to the file, whatever the chunk's or the output file's size.
_APPEND_RUN_BYTES = 8 << 20


class _SftOutput:
    """One output file of the columnar writer: the writable file with the
    region appended so far, its running checksum and its offset runs."""

    __slots__ = ("fnum", "path", "w", "lo", "region", "crc", "offs")

    def __init__(self, env, dbname, fnum, lo: int):
        from toplingdb_tpu.db import filename as _fn

        self.fnum = fnum
        self.path = _fn.table_file_name(dbname, fnum)
        self.w = env.new_writable_file(self.path)
        self.lo = lo          # position in `order` of the file's first row
        self.region = 0
        self.crc = np.zeros(1, dtype=np.uint32)
        self.offs: list[np.ndarray] = []


def write_tables_sft_columnar(env, dbname, new_file_number, icmp, options,
                              kv, order, trailer_override, vtypes, seqs,
                              tombstones, creation_time: int,
                              max_output_file_size: int = 2 ** 62,
                              column_family=(0, "default"), stats=None):
    """SingleFastTable emission from columnar buffers and a survivor order:
    the single_fast counterpart of ops/columnar_io.py::write_tables_columnar
    (which hands this format here), same arguments and same result tuples,
    byte-identical to SingleFastTableBuilder fed the same stream through
    build_outputs, output cutting included (parity-tested).

    It STREAMS: `order` may be an iterator of chunks (the pipeline's feed;
    no user key spans a chunk boundary, and the caller may patch
    trailer_override / seqs rows of a chunk until it yields it), and each
    chunk is appended to the open file as it arrives, by one native call a
    run of at most ~8 MB (`sst.sft_append`: region bytes, offsets, the
    running checksum) — the format needs nothing of the whole file before
    `sst.sft_finish` (checksum block, filter, hash index, range-del block,
    properties, metaindex, offset array, footer, sync). Each consumed
    chunk is the span `pipeline.encode_write` that holds them (the last
    file's finish has one of its own). `stats` (CompactionStats)
    gets the two spans' wall as `sft_build_usec`. Prefix extractors and
    property collectors take the per-entry path (NotSupported)."""
    from toplingdb_tpu import native
    from toplingdb_tpu.table.filter import build_filter_block_native

    lib = native.lib()
    if getattr(lib, "tpulsm_sft_append", None) is None:
        raise NotSupported("native single_fast builder unavailable")
    if (getattr(options, "prefix_extractor", None) is not None
            or getattr(options, "properties_collector_factories", None)
            or getattr(options, "auto_sort", False)):
        raise NotSupported("single_fast columnar writer: prefix extractors, "
                           "collectors and auto_sort use the per-entry path")
    if isinstance(order, np.ndarray):
        chunks = iter((order,))
    else:
        chunks = iter(order)
        if (trailer_override.dtype != np.int64
                or not trailer_override.flags.c_contiguous):
            raise NotSupported(
                "streamed order requires a C-contiguous int64 "
                "trailer_override (mutations must alias the writer's view)")
    trailer_override = np.ascontiguousarray(trailer_override, dtype=np.int64)
    order = np.empty(kv.n, dtype=np.int32)  # the survivors, as they arrive
    can_cut = not tombstones  # single output while tombstones survive
    max_size = max_output_file_size if can_cut else 2 ** 62
    run = np.empty(_APPEND_RUN_BYTES, dtype=np.uint8)
    run_len = np.zeros(2, dtype=np.int64)  # bytes written, cut flag
    p_keys = (native.np_u8p(kv.key_buf), native.np_i32p(kv.key_offs),
              native.np_i32p(kv.key_lens))
    p_vals = (native.np_u8p(kv.val_buf), native.np_i32p(kv.val_offs),
              native.np_i32p(kv.val_lens))
    u32p = native.ctypes.POINTER(native.ctypes.c_uint32)
    build_s = [0.0]
    results = []
    cur: _SftOutput | None = None

    @contextlib.contextmanager
    def building(name: str, out: _SftOutput):
        """A span of the build, its wall booked into `sft_build_usec`."""
        t0 = time.time()
        try:
            with _tele.span(name, file=out.fnum) as sp:
                yield sp
        finally:
            build_s[0] += time.time() - t0

    def finish(out: _SftOutput, hi: int, file_tombs) -> None:
        """`sst.sft_finish`: everything behind the region, then sync."""
        sel = order[out.lo:hi]
        n = len(sel)
        props = _new_props(icmp, options, column_family[0],
                           column_family[1], creation_time)
        props.num_entries = n
        smallest = largest = None
        fdata = hash_block = None
        if n:
            props.raw_key_size = int(kv.key_lens[sel].sum(dtype=np.int64))
            props.raw_value_size = int(kv.val_lens[sel].sum(dtype=np.int64))
            vt = vtypes[sel]
            props.num_deletions = int(np.count_nonzero(
                (vt == int(ValueType.DELETION))
                | (vt == int(ValueType.SINGLE_DELETION))))
            props.num_merge_operands = int(np.count_nonzero(
                vt == int(ValueType.MERGE)))
            sq = seqs[sel]
            props.smallest_seqno = int(sq.min())
            props.largest_seqno = int(sq.max())

            def ikey(e):
                k = kv.ikey(e)
                t = int(trailer_override[e])
                return k if t < 0 else k[:-8] + t.to_bytes(8, "little")

            smallest, largest = ikey(int(sel[0])), ikey(int(sel[-1]))
            if options.filter_policy and options.whole_key_filtering:
                fdata = build_filter_block_native(
                    lib, options.filter_policy, kv.key_buf,
                    kv.key_offs[sel], kv.key_lens[sel] - 8, n)
            if options.hash_index:
                nb = 1
                while nb < (n * 10) // 7 + 1:
                    nb <<= 1
                buckets = np.zeros(nb, dtype="<u4")
                if lib.tpulsm_sft_hash_index(
                        *p_keys, native.np_i32p(sel), n,
                        buckets.ctypes.data_as(u32p), nb) < 0:
                    raise Corruption("single_fast hash index build failed")
                hash_block = (METAINDEX_HASH_INDEX, buckets.tobytes())
        rd_raw = None
        if file_tombs:
            rdb = BlockBuilder(restart_interval=1)
            for frag in file_tombs:
                b, e = frag.to_table_entry()
                rdb.add(b, e)
                props.num_range_deletions += 1
                if smallest is None or icmp.compare(b, smallest) < 0:
                    smallest = b
                end_ikey = dbformat.make_internal_key(
                    e, dbformat.MAX_SEQUENCE_NUMBER,
                    dbformat.VALUE_TYPE_FOR_SEEK)
                if largest is None or icmp.compare(end_ikey, largest) > 0:
                    largest = end_ikey
                props.smallest_seqno = min(props.smallest_seqno, frag.seq)
                props.largest_seqno = max(props.largest_seqno, frag.seq)
            rd_raw = rdb.finish()
        iraw = (np.concatenate(out.offs) if out.offs
                else np.zeros(0, dtype="<u4")).astype("<u4").tobytes()
        _write_tail(out.w, props, out.region, int(out.crc[0]), iraw, fdata,
                    hash_block, rd_raw, SingleFastTableBuilder.FOOTER_MAGIC)
        out.w.flush()
        out.w.sync()
        out.w.close()
        results.append((out.fnum, out.path, props, smallest, largest, sel))

    filled = 0
    n_chunks = 0
    # One `pipeline.encode_write` span a consumed chunk, and one more for
    # the last file's finish; waiting for the next chunk is the feeder's
    # span, not this one (as in write_tables_columnar).
    ew = _tele.NOOP_SPAN
    try:
        while True:
            ew.finish()
            chunk = next(chunks, None)
            ew = _tele.span("pipeline.encode_write", chunk=n_chunks)
            n_chunks += 1
            if chunk is None:
                break
            chunk = np.asarray(chunk, dtype=np.int32)
            start = filled
            order[start:start + len(chunk)] = chunk
            filled += len(chunk)
            while start < filled:
                if cur is None:
                    cur = _SftOutput(env, dbname, new_file_number(), start)
                with building("sst.sft_append", cur) as sp:
                    offs = np.empty(filled - start, dtype=np.uint32)
                    rc = lib.tpulsm_sft_append(
                        *p_keys, *p_vals, native.np_i64p(trailer_override),
                        native.np_i32p(order), start, filled, cur.lo,
                        cur.region, max_size, native.np_u8p(run), len(run),
                        native.np_i64p(run_len), offs.ctypes.data_as(u32p),
                        cur.crc.ctypes.data_as(u32p))
                    if rc == -2:  # one entry larger than the run buffer
                        run = np.empty(2 * len(run), dtype=np.uint8)
                        continue
                    if rc == -7:
                        raise NotSupported(
                            "single_fast table data region exceeds 4GiB; "
                            "use the block format or a smaller "
                            "max_output_file_size")
                    if rc < 0:
                        raise Corruption(
                            f"native single_fast build failed rc={rc}")
                    nbytes = int(run_len[0])
                    if rc:
                        cur.w.append(run[:nbytes].tobytes())
                        cur.offs.append(offs[:rc])
                        cur.region += nbytes
                        start += int(rc)
                    sp.tag(rows=int(rc), nbytes=nbytes)
                if run_len[1]:
                    # The cut rule stopped the run: the next row begins
                    # the next file.
                    with building("sst.sft_finish", cur):
                        finish(cur, start, [])
                    cur = None
        if cur is None and tombstones:
            cur = _SftOutput(env, dbname, new_file_number(), filled)
        if cur is not None:
            with building("sst.sft_finish", cur):
                finish(cur, filled, tombstones)
            cur = None
        if stats is not None:
            stats.sft_build_usec += int(build_s[0] * 1e6)
        return results
    except BaseException:
        if cur is not None:
            cur.w.close()
        for path in ([cur.path] if cur is not None else []) + [
                r[1] for r in results]:
            try:
                env.delete_file(path)
            except Exception as e:
                _errors.swallow(reason="sst-abort-cleanup", exc=e)
        raise
    finally:
        ew.finish()
