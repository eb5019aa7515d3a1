"""Loader for the native C++ library.

Builds `_tpulsm_native.so` from the C++ sources on first import (cached by
mtime) and exposes the C ABI via ctypes. Falls back gracefully: callers check
`lib()` for None and use pure-Python paths.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

from toplingdb_tpu.utils import concurrency as ccy
from toplingdb_tpu.utils import errors as _errors

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "tpulsm_native.cc")
# TPULSM_NATIVE_SANITIZE=asan|undefined builds (and loads) a separate
# sanitized .so — slower, instrumented, used by tests/test_sanitize_native
# to replay the fuzz corpus under ASan/UBSan without disturbing the
# regular artifact. For asan, run python under
# LD_PRELOAD=$(g++ -print-file-name=libasan.so).
_SANITIZE = os.environ.get("TPULSM_NATIVE_SANITIZE", "").strip().lower()
_SAN_FLAGS = {
    "asan": ["-fsanitize=address"],
    "address": ["-fsanitize=address"],
    "undefined": ["-fsanitize=undefined",
                  "-fno-sanitize-recover=undefined"],
    "ubsan": ["-fsanitize=undefined", "-fno-sanitize-recover=undefined"],
}
if _SANITIZE and _SANITIZE in _SAN_FLAGS:
    _SO = os.path.join(_DIR, f"_tpulsm_native.{_SANITIZE}.so")
else:
    _SANITIZE = ""
    _SO = os.path.join(_DIR, "_tpulsm_native.so")

_lock = ccy.Lock("native._lock")
_lib: ctypes.CDLL | None = None
_tried = False

# Must match TPULSM_ABI_VERSION in tpulsm_native.cc. The loader refuses a
# .so reporting a different version: mtime staleness alone cannot catch a
# restored backup or a clock-skewed rebuild.
_ABI_VERSION = 2


def _compile(src: str, so: str, extra_flags: list[str]) -> bool:
    """Shared compile-to-tmp-then-swap build step (per-pid tmp name: two
    processes may race the first build)."""
    tmp = f"{so}.{os.getpid()}.tmp"
    opt = ["-O1", "-g"] if _SANITIZE else ["-O3"]
    cmd = ["g++", *opt, "-shared", "-fPIC", *extra_flags,
           *_SAN_FLAGS.get(_SANITIZE, []),
           "-o", tmp, src, "-ldl"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
        return True
    except (subprocess.SubprocessError, OSError):
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


def _stale(so: str, src: str) -> bool:
    try:
        return not os.path.exists(so) or (
            os.path.getmtime(so) < os.path.getmtime(src))
    except OSError:
        return True


def _build() -> bool:
    return _compile(_SRC, _SO, ["-std=c++17", "-pthread"])


def lib() -> ctypes.CDLL | None:
    """Returns the loaded native library, building it if needed; None if
    the toolchain is unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if _stale(_SO, _SRC) and not _build():
            return None
        try:
            l = ctypes.CDLL(_SO)
        except OSError:
            return None
        try:
            l.tpulsm_abi_version.restype = ctypes.c_int32
            l.tpulsm_abi_version.argtypes = []
            abi_ok = l.tpulsm_abi_version() == _ABI_VERSION
        except AttributeError:
            abi_ok = False  # artifact predates the handshake symbol
        if not abi_ok:
            # mtime lied (restored backup / clock skew): one forced
            # rebuild, then give up rather than run a drifted ABI.
            if not _build():
                return None
            l = ctypes.CDLL(_SO)
            l.tpulsm_abi_version.restype = ctypes.c_int32
            l.tpulsm_abi_version.argtypes = []
            if l.tpulsm_abi_version() != _ABI_VERSION:
                return None
        l.tpulsm_crc32c_extend.restype = ctypes.c_uint32
        l.tpulsm_crc32c_extend.argtypes = [
            ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t,
        ]
        l.tpulsm_xxh64.restype = ctypes.c_uint64
        l.tpulsm_xxh64.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint64,
        ]
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        l.tpulsm_decode_block.restype = ctypes.c_int64
        l.tpulsm_decode_block.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,            # block, len
            u8p, ctypes.c_int64,                        # key_out, cap
            u8p, ctypes.c_int64,                        # val_out, cap
            i32p, i32p, i32p, i32p, ctypes.c_int64,     # offs/lens, max_entries
        ]
        l.tpulsm_build_block.restype = ctypes.c_int64
        l.tpulsm_build_block.argtypes = [
            u8p, i32p, i32p,                            # key buf/offs/lens
            u8p, i32p, i32p,                            # val buf/offs/lens
            i64p,                                       # trailer_override
            i32p, ctypes.c_int64, ctypes.c_int64,       # order, start, n_total
            ctypes.c_int64, ctypes.c_int64,             # block_size, restart_int
            u8p, ctypes.c_int64, i64p,                  # out, cap, out_len
        ]
        l.tpulsm_decode_blocks.restype = ctypes.c_int64
        l.tpulsm_decode_blocks.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,            # file buf, len
            i64p, i64p, ctypes.c_int64,                 # block offs/lens, n
            ctypes.c_int32,                             # verify_crc
            u8p, ctypes.c_int64, u8p, ctypes.c_int64,   # key/val out + caps
            i32p, i32p, i32p, i32p, ctypes.c_int64,
        ]
        l.tpulsm_bloom_build.restype = None
        l.tpulsm_bloom_build.argtypes = [
            u8p, i32p, i32p, ctypes.c_int64,
            ctypes.c_uint64, ctypes.c_uint32, u8p,
        ]
        try:
            l.tpulsm_bloom_build_blocked.restype = None
            l.tpulsm_bloom_build_blocked.argtypes = [
                u8p, i32p, i32p, ctypes.c_int64,
                ctypes.c_uint64, ctypes.c_uint32, u8p,
            ]
        except AttributeError:
            pass
        try:
            # A stale .so may predate this symbol; degrade to the numpy
            # sort twin instead of breaking every native caller.
            l.tpulsm_sort_entries.restype = ctypes.c_int32
            l.tpulsm_sort_entries.argtypes = [
                u8p, i64p, i64p, ctypes.c_int64,        # key buf/offs/lens, n
                i32p, u8p,                              # order_out, new_key_out
                ctypes.POINTER(ctypes.c_uint64),        # packed_out (nullable)
            ]
            l.tpulsm_build_data_section.restype = ctypes.c_int64
            l.tpulsm_build_data_section.argtypes = [
                u8p, i32p, i32p,                        # key buf/offs/lens
                u8p, i32p, i32p,                        # val buf/offs/lens
                i64p,                                   # trailer_override
                i32p, ctypes.c_int64, ctypes.c_int64,   # order, start, limit
                ctypes.c_int64, ctypes.c_int64,         # block_size, restart_int
                ctypes.c_int64, ctypes.c_int64,         # base_size, max_size
                i64p, i64p, ctypes.c_int64,             # counts, plens, max_blocks
                u8p, ctypes.c_int64, i64p,              # out, cap, out_len
            ]
        except AttributeError:
            pass
        try:
            # Batch memtable insert on the GIL-RELEASING handle: the whole
            # loop runs without the GIL (the skiplist insert is lock-free),
            # so concurrent writer threads scale past the interpreter lock.
            u64p = ctypes.POINTER(ctypes.c_uint64)
            l.tpulsm_skiplist_insert_batch.restype = ctypes.c_int64
            l.tpulsm_skiplist_insert_batch.argtypes = [
                ctypes.c_void_p, u8p, i64p, i32p, u64p,
                u8p, i64p, i32p, ctypes.c_int64,
            ]
        except AttributeError:
            pass
        try:
            # Compressed section builder: build + compress + frame whole
            # runs of blocks in one call (snappy/zstd dlopen'd).
            l.tpulsm_build_data_section_c.restype = ctypes.c_int64
            l.tpulsm_build_data_section_c.argtypes = [
                u8p, i32p, i32p, u8p, i32p, i32p, i64p, i32p,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int64,
                i64p, i64p, i64p, ctypes.c_int64,
                u8p, ctypes.c_int64, i64p,
            ]
        except AttributeError:
            pass
        try:
            # In-block point seek (restart bsearch + linear scan in C):
            # the BlockIter.seek hot path of every Get.
            l.tpulsm_block_seek.restype = ctypes.c_int32
            l.tpulsm_block_seek.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p,
                ctypes.c_int32, u8p, ctypes.c_int32, i32p,
            ]
        except AttributeError:
            pass
        try:
            # Bulk block inflate (snappy/zstd dlopen'd in C++): one
            # GIL-free, multi-threaded call per compressed SST scan.
            l.tpulsm_inflate_blocks.restype = ctypes.c_int64
            l.tpulsm_inflate_blocks.argtypes = [
                u8p, ctypes.c_int64, i64p, i64p, ctypes.c_int64,
                ctypes.c_int32, u8p, ctypes.c_int64, i64p, i64p,
            ]
        except AttributeError:
            pass
        try:
            # WriteBatch wire-image insert: parse + insert natively, one
            # GIL-free call per batch (no per-record Python/numpy at all).
            l.tpulsm_skiplist_insert_wb.restype = ctypes.c_int64
            l.tpulsm_skiplist_insert_wb.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_uint64, i64p,
            ]
        except AttributeError:
            pass
        try:
            # Fused verify+insert for protected batches: re-hash every
            # record against the carried vector, insert only if ALL match.
            _u64p = ctypes.POINTER(ctypes.c_uint64)
            l.tpulsm_skiplist_insert_wb_prot.restype = ctypes.c_int64
            l.tpulsm_skiplist_insert_wb_prot.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_uint64, _u64p, ctypes.c_int64, ctypes.c_int32, i64p,
            ]
        except AttributeError:
            pass
        try:
            # Per-entry protection over a WriteBatch wire image: one call
            # computes every counted record's checksum (utils/protection
            # bit-compatible) — the protected write path's hot loop.
            u64p = ctypes.POINTER(ctypes.c_uint64)
            l.tpulsm_wb_protect.restype = ctypes.c_int64
            l.tpulsm_wb_protect.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, u64p, ctypes.c_int64,
            ]
            # XOR-aggregate protection over a columnar export (flush's
            # memtable->SST handoff check without per-entry Python).
            l.tpulsm_columnar_protect.restype = ctypes.c_int64
            l.tpulsm_columnar_protect.argtypes = [
                u8p, i32p, i32p, u8p, i32p, i32p, i32p,
                ctypes.c_int64, ctypes.c_int32, u64p,
            ]
        except AttributeError:
            pass
        try:
            # Fused group-commit write plane: validate + protect-verify a
            # whole write group, frame the merged WAL record gather-style,
            # write it to the WAL's fd and apply every record to the
            # memtable rep — one GIL-free call per group
            # (db.py _native_group_commit).
            u64p = ctypes.POINTER(ctypes.c_uint64)
            l.tpulsm_wb_group_commit.restype = ctypes.c_int64
            l.tpulsm_wb_group_commit.argtypes = [
                ctypes.c_void_p, ctypes.c_int32,            # mem, mem_kind
                ctypes.POINTER(ctypes.c_char_p), i64p,      # reps, lens
                ctypes.c_int64, ctypes.c_uint64,            # n_batches, seq
                u64p, ctypes.c_int64, ctypes.c_int32,       # prots, n, pb
                ctypes.c_int32,                             # mode
                ctypes.c_int64, ctypes.c_int64,             # blk_off, log_no
                u8p, ctypes.c_int64,                        # wal out, cap
                ctypes.c_int32, i64p,                       # wal fd, out
            ]
        except AttributeError:
            pass
        try:
            # Host k-way merge of presorted runs (separate block: a stale
            # .so missing THIS symbol must not void older registrations).
            l.tpulsm_merge_runs.restype = ctypes.c_int32
            l.tpulsm_merge_runs.argtypes = [
                u8p, i64p, i64p, ctypes.c_int64,
                i64p, ctypes.c_int32,                   # run_starts, n_runs
                i32p, u8p, ctypes.POINTER(ctypes.c_uint64),
            ]
        except AttributeError:
            pass
        try:
            # Whole-file index block build (separators + BlockHandle
            # entries in C) for the columnar writer's section path.
            l.tpulsm_build_index_block.restype = ctypes.c_int64
            l.tpulsm_build_index_block.argtypes = [
                u8p, i32p, i32p, i64p, i32p,
                i64p, i64p, i64p, i64p,                 # pos/cnt/offs/plens
                ctypes.c_int64, ctypes.c_int64,         # n_blocks, restart
                u8p, ctypes.c_int64, i64p,              # out, cap, out_len
            ]
        except AttributeError:
            pass
        try:
            # Fused whole-file scan (inflate + decode + absolute offsets)
            # into caller-provided slices of a shared columnar buffer.
            l.tpulsm_scan_blocks.restype = ctypes.c_int64
            l.tpulsm_scan_blocks.argtypes = [
                u8p, ctypes.c_int64,                    # file buf, len
                i64p, i64p, ctypes.c_int64,             # block offs/lens, n
                ctypes.c_int32,                         # verify_crc
                u8p, ctypes.c_int64, u8p, ctypes.c_int64,  # key/val out+caps
                i32p, i32p, i32p, i32p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64,         # key_base, val_base
            ]
        except AttributeError:
            pass
        try:
            # Keys-copied / values-REFERENCED whole-file scan: val offsets
            # point into the (uncompressed) file image the caller keeps
            # alive as val_buf — no per-entry value memcpy.
            l.tpulsm_scan_blocks_refvals.restype = ctypes.c_int64
            l.tpulsm_scan_blocks_refvals.argtypes = [
                u8p, ctypes.c_int64,                    # file buf, len
                i64p, i64p, ctypes.c_int64,             # block offs/lens, n
                ctypes.c_int32,                         # verify_crc
                u8p, ctypes.c_int64,                    # key out + cap
                i32p, i32p, i32p, i32p, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64,  # key_base, val_image_base
            ]
        except AttributeError:
            pass
        try:
            # Fused k-way merge + MVCC GC: ONE pass over presorted runs,
            # survivors only — replaces merge + numpy mask passes.
            l.tpulsm_merge_gc_runs.restype = ctypes.c_int64
            l.tpulsm_merge_gc_runs.argtypes = [
                u8p, i64p, i64p, ctypes.c_int64,
                i64p, ctypes.c_int32,                   # run_starts, n_runs
                ctypes.POINTER(ctypes.c_uint64), ctypes.c_int32,  # snaps
                ctypes.POINTER(ctypes.c_uint64),        # cover (nullable)
                ctypes.c_int32,                         # bottommost
                i32p, u8p, u8p,                         # order/zero/cx out
                ctypes.POINTER(ctypes.c_uint64),        # packed_out
                i32p,                                   # has_complex_out
            ]
        except AttributeError:
            pass
        try:
            # Ordered whole-memtable export into columnar buffers: the
            # memtable half of the columnar flush fast path.
            u64p = ctypes.POINTER(ctypes.c_uint64)
            l.tpulsm_skiplist_export.restype = ctypes.c_int64
            l.tpulsm_skiplist_export.argtypes = [
                ctypes.c_void_p, u8p, i64p, i32p, u64p, i32p,
                u8p, i64p, i32p, ctypes.c_int64, i64p,
            ]
        except AttributeError:
            pass
        try:
            # Trie rep (CSPP role) GIL-released entry points.
            u64p = ctypes.POINTER(ctypes.c_uint64)
            l.tpulsm_trie_insert_batch.restype = ctypes.c_int64
            l.tpulsm_trie_insert_batch.argtypes = [
                ctypes.c_void_p, u8p, i64p, i32p, u64p,
                u8p, i64p, i32p, ctypes.c_int64,
            ]
            l.tpulsm_trie_insert_wb.restype = ctypes.c_int64
            l.tpulsm_trie_insert_wb.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_uint64, i64p,
            ]
            l.tpulsm_trie_insert_wb_prot.restype = ctypes.c_int64
            l.tpulsm_trie_insert_wb_prot.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                ctypes.c_uint64, u64p, ctypes.c_int64, ctypes.c_int32, i64p,
            ]
            l.tpulsm_trie_export.restype = ctypes.c_int64
            l.tpulsm_trie_export.argtypes = [
                ctypes.c_void_p, u8p, i64p, i32p, u64p, i32p,
                u8p, i64p, i32p, ctypes.c_int64, i64p,
            ]
        except AttributeError:
            pass
        try:
            # Native point-read engine: table/version handles + the whole
            # GetImpl chain in one GIL-released call.
            l.tpulsm_table_handle_new.restype = ctypes.c_void_p
            l.tpulsm_table_handle_new.argtypes = [
                ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32,
                u8p, ctypes.c_int64, u8p, ctypes.c_int64,
                u8p, ctypes.c_int32, u8p, ctypes.c_int32,
            ]
            l.tpulsm_table_handle_free.restype = None
            l.tpulsm_table_handle_free.argtypes = [ctypes.c_void_p]
            l.tpulsm_version_handle_new.restype = ctypes.c_void_p
            l.tpulsm_version_handle_new.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int32,
                i32p, ctypes.c_int32,
            ]
            l.tpulsm_version_handle_free.restype = None
            l.tpulsm_version_handle_free.argtypes = [ctypes.c_void_p]
            l.tpulsm_block_cache_config.restype = None
            l.tpulsm_block_cache_config.argtypes = [ctypes.c_int64, i64p]
            l.tpulsm_db_get.restype = ctypes.c_int32
            l.tpulsm_db_get.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
                ctypes.c_uint64, u8p, ctypes.c_int32, i32p, i32p, i64p,
            ]
            l.tpulsm_db_get_kinds.restype = ctypes.c_int32
            l.tpulsm_db_get_kinds.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), i32p, ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
                ctypes.c_uint64, u8p, ctypes.c_int32, i32p, i32p, i64p,
            ]
            l.tpulsm_getctx_new.restype = ctypes.c_void_p
            l.tpulsm_getctx_new.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int32,
                ctypes.c_void_p, ctypes.c_int64,
            ]
            l.tpulsm_getctx_free.restype = None
            l.tpulsm_getctx_free.argtypes = [ctypes.c_void_p]
            l.tpulsm_getctx_out.restype = ctypes.c_void_p
            l.tpulsm_getctx_out.argtypes = [ctypes.c_void_p]
            l.tpulsm_getctx_val.restype = ctypes.c_void_p
            l.tpulsm_getctx_val.argtypes = [ctypes.c_void_p]
            l.tpulsm_getctx_set_mem_kind.restype = None
            l.tpulsm_getctx_set_mem_kind.argtypes = [
                ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32,
            ]
            l.tpulsm_getctx_get.restype = ctypes.c_int32
            l.tpulsm_getctx_get.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int32,
                ctypes.c_uint64,
            ]
            i8p = ctypes.POINTER(ctypes.c_int8)
            l.tpulsm_getctx_multiget.restype = ctypes.c_int32
            l.tpulsm_getctx_multiget.argtypes = [
                ctypes.c_void_p, u8p, i64p, i32p, ctypes.c_int64,
                ctypes.c_uint64, i8p, i64p, i64p, u8p, ctypes.c_int64,
                i64p, i64p,
            ]
        except AttributeError:
            pass
        try:
            # Zip-table data plane: batched builder kernels (bit-identical
            # to the Python encoders in table/zip_table.py), the columnar
            # key/value-group decoders, and the zip Get handle.
            l.tpulsm_zip_newkey.restype = ctypes.c_int64
            l.tpulsm_zip_newkey.argtypes = [
                u8p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int32,
                u8p,
            ]
            l.tpulsm_zip_encode_keys.restype = ctypes.c_int64
            l.tpulsm_zip_encode_keys.argtypes = [
                u8p, ctypes.c_int64, i64p, ctypes.c_int64, ctypes.c_int32,
                i64p, ctypes.c_int32, ctypes.c_int32, u8p, u8p,
                ctypes.c_int64, u8p,
            ]
            l.tpulsm_zip_encode_values.restype = ctypes.c_int64
            l.tpulsm_zip_encode_values.argtypes = [
                u8p, ctypes.c_int64, i64p, i64p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                u8p, ctypes.c_int64, u8p, ctypes.c_int64,
                u8p, u8p, i64p,
            ]
            l.tpulsm_zip_train_dict.restype = ctypes.c_int64
            l.tpulsm_zip_train_dict.argtypes = [
                u8p, ctypes.c_int64, i64p, i64p, ctypes.c_int64,
                ctypes.c_int32, ctypes.c_int32, u8p, ctypes.c_int64,
            ]
            l.tpulsm_zip_decode_keys.restype = ctypes.c_int64
            l.tpulsm_zip_decode_keys.argtypes = [
                u8p, ctypes.c_int64, ctypes.c_int32, u8p, ctypes.c_int64,
                u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int64, ctypes.c_int64, u8p, ctypes.c_int64, i64p,
                i64p, ctypes.c_int64,
            ]
            l.tpulsm_zip_group_decode.restype = ctypes.c_int64
            l.tpulsm_zip_group_decode.argtypes = [
                u8p, ctypes.c_int64, u8p, ctypes.c_int64, u8p,
                ctypes.c_int64, u8p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, i64p, u8p, ctypes.c_int64,
            ]
            l.tpulsm_zip_table_handle_new.restype = ctypes.c_void_p
            l.tpulsm_zip_table_handle_new.argtypes = [
                ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32,
                ctypes.c_int32, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int32, u8p, ctypes.c_int64, u8p, ctypes.c_int64,
                u8p, ctypes.c_int64, u8p, ctypes.c_int64, u8p,
                ctypes.c_int64, u8p, ctypes.c_int64, u8p, ctypes.c_int64,
                u8p, ctypes.c_int64, u8p, ctypes.c_int64, u8p,
                ctypes.c_int32, u8p, ctypes.c_int32,
            ]
        except AttributeError:
            pass
        try:
            # SingleFastTable data plane (table/single_fast.py): the
            # entry-range scan into columnar slots, the region builder and
            # the hash index of the columnar writer.
            u32p = ctypes.POINTER(ctypes.c_uint32)
            l.tpulsm_sft_scan.restype = ctypes.c_int64
            l.tpulsm_sft_scan.argtypes = [
                u8p, ctypes.c_int64, u32p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, u8p, ctypes.c_int64, u8p, ctypes.c_int64,
                i32p, i32p, i32p, i32p, ctypes.c_int64, ctypes.c_int64,
                i64p,
            ]
            l.tpulsm_sft_append.restype = ctypes.c_int64
            l.tpulsm_sft_append.argtypes = [
                u8p, i32p, i32p, u8p, i32p, i32p, i64p, i32p,
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_int64, u8p, ctypes.c_int64, i64p,
                u32p, u32p,
            ]
            l.tpulsm_sft_hash_index.restype = ctypes.c_int64
            l.tpulsm_sft_hash_index.argtypes = [
                u8p, i32p, i32p, i32p, ctypes.c_int64, u32p, ctypes.c_int64,
            ]
        except AttributeError:
            pass
        _lib = l
        return _lib


_pylib: "ctypes.PyDLL | None" = None


def pylib() -> "ctypes.PyDLL | None":
    """GIL-holding handle for the skiplist memtable: calls do NOT release the
    GIL, so single-writer mutation is safe against lockless Python readers."""
    global _pylib
    if _pylib is not None:
        return _pylib
    if lib() is None:  # ensures the .so is built
        return None
    l = ctypes.PyDLL(_SO)
    vp = ctypes.c_void_p
    u8p = ctypes.POINTER(ctypes.c_uint8)
    l.tpulsm_skiplist_new.restype = vp
    l.tpulsm_skiplist_new.argtypes = []
    l.tpulsm_skiplist_free.restype = None
    l.tpulsm_skiplist_free.argtypes = [vp]
    l.tpulsm_skiplist_insert.restype = ctypes.c_int32
    l.tpulsm_skiplist_insert.argtypes = [
        vp, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_uint32,
    ]
    l.tpulsm_skiplist_count.restype = ctypes.c_int64
    l.tpulsm_skiplist_count.argtypes = [vp]
    l.tpulsm_skiplist_memory.restype = ctypes.c_int64
    l.tpulsm_skiplist_memory.argtypes = [vp]
    l.tpulsm_skiplist_seek_ge.restype = vp
    l.tpulsm_skiplist_seek_ge.argtypes = [
        vp, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64]
    l.tpulsm_skiplist_seek_lt.restype = vp
    l.tpulsm_skiplist_seek_lt.argtypes = [
        vp, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64]
    l.tpulsm_skiplist_first.restype = vp
    l.tpulsm_skiplist_first.argtypes = [vp]
    l.tpulsm_skiplist_last.restype = vp
    l.tpulsm_skiplist_last.argtypes = [vp]
    l.tpulsm_skiplist_next.restype = vp
    l.tpulsm_skiplist_next.argtypes = [vp]
    l.tpulsm_skiplist_node.restype = None
    l.tpulsm_skiplist_node.argtypes = [
        vp, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_uint32),
    ]
    try:
        # Trie memtable rep (the CSPP role) — same shape of surface.
        l.tpulsm_trie_new.restype = vp
        l.tpulsm_trie_new.argtypes = []
        l.tpulsm_trie_free.restype = None
        l.tpulsm_trie_free.argtypes = [vp]
        l.tpulsm_trie_insert.restype = ctypes.c_int32
        l.tpulsm_trie_insert.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint32,
        ]
        l.tpulsm_trie_count.restype = ctypes.c_int64
        l.tpulsm_trie_count.argtypes = [vp]
        l.tpulsm_trie_memory.restype = ctypes.c_int64
        l.tpulsm_trie_memory.argtypes = [vp]
        l.tpulsm_trie_seek_ge.restype = vp
        l.tpulsm_trie_seek_ge.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64]
        l.tpulsm_trie_seek_lt.restype = vp
        l.tpulsm_trie_seek_lt.argtypes = [
            vp, ctypes.c_char_p, ctypes.c_uint32, ctypes.c_uint64]
        l.tpulsm_trie_first.restype = vp
        l.tpulsm_trie_first.argtypes = [vp]
        l.tpulsm_trie_last.restype = vp
        l.tpulsm_trie_last.argtypes = [vp]
        l.tpulsm_trie_next.restype = vp
        l.tpulsm_trie_next.argtypes = [vp, vp]
        l.tpulsm_trie_ver.restype = None
        l.tpulsm_trie_ver.argtypes = [
            vp, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_uint32),
        ]
    except AttributeError:
        pass
    _pylib = l
    return _pylib


_FASTGET_SRC = os.path.join(_DIR, "fastget.c")
_fastget_mod = None
_fastget_tried = False


def _fastget_so_path() -> str:
    # The interpreter's cache tag rides in the filename so an extension
    # built under an older CPython ABI is never dlopen'd after an
    # interpreter upgrade (layout mismatches can segfault past any
    # except clause).
    import sys as _sys

    tag = getattr(_sys.implementation, "cache_tag", "py") or "py"
    if _SANITIZE:
        tag = f"{tag}.{_SANITIZE}"  # keep the sanitized artifact separate
    return os.path.join(_DIR, f"tpulsm_fastget.{tag}.so")


def fastmultiget():
    """The C-extension whole-batch MultiGet (list-of-bytes in, list out),
    or None when unavailable."""
    if fastget() is None:
        return None
    return getattr(_fastget_mod, "multiget", None)


def wb_put_values():
    """The C-extension deferred put encoder (fastget.c wb_put_values), or
    None when unavailable: WriteBatch then encodes in Python."""
    if fastget() is None:
        return None
    return getattr(_fastget_mod, "wb_put_values", None)


def fastget():
    """The C-extension fast path for tpulsm_getctx_get (fastget.c), or
    None when unavailable (missing Python headers / toolchain): callers
    keep the ctypes path. Returns the bound module's `get` callable."""
    global _fastget_mod, _fastget_tried
    if _fastget_mod is not None:
        return _fastget_mod.get
    if _fastget_tried:
        return None
    if lib() is None:  # resolve the native .so FIRST (it takes _lock too)
        return None
    with _lock:
        if _fastget_mod is not None:
            return _fastget_mod.get
        if _fastget_tried:
            return None
        _fastget_tried = True
        so = _fastget_so_path()
        if _stale(so, _FASTGET_SRC):
            import sysconfig

            inc = sysconfig.get_paths().get("include")
            if not inc or not os.path.exists(
                    os.path.join(inc, "Python.h")):
                return None
            if not _compile(_FASTGET_SRC, so, [f"-I{inc}", "-O2"]):
                return None
        try:
            import importlib.machinery
            import importlib.util

            loader = importlib.machinery.ExtensionFileLoader(
                "tpulsm_fastget", so)
            spec = importlib.util.spec_from_loader("tpulsm_fastget", loader)
            mod = importlib.util.module_from_spec(spec)
            loader.exec_module(mod)
            mod.bind(_SO)
            _fastget_mod = mod
            return mod.get
        except Exception as e:
            _errors.swallow(reason="fastget-bind-fallback", exc=e)
            return None


def np_u8p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def np_i32p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def np_i64p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
