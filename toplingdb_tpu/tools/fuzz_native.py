"""Greybox fuzz harness for the native parser surface.

The reference ships libFuzzer targets (fuzz/db_fuzzer.cc,
fuzz/db_map_fuzzer.cc, fuzz/sst_file_writer_fuzzer.cc); this is the
equivalent harness for our native C++ surface without compiler
instrumentation (atheris/libFuzzer are not in the image): structure-aware
MUTATION of valid inputs plus FEEDBACK-DRIVEN corpus growth — a mutant
that produces a previously-unseen outcome signature (return code, decoded
count bucket, error class) joins the corpus and is mutated further, the
greybox loop's novelty search over observable behavior. Differential
checks cross-validate native accept/reject decisions against the Python
twins, so semantic divergence (not just crashes) is a failure.

Targets:
  wb       WriteBatch wire-image insert (skiplist + trie native parsers)
  block    single data-block decode (tpulsm_decode_block vs Python Block)
  scan     whole-SST fused scan (tpulsm_scan_blocks)
  manifest MANIFEST/VersionEdit recovery
  abi      contract-driven shapes: argument lists are generated from the
           parsed C signatures + the §2.10.2 buffer-pairing table
           (tools/check_native_abi), so every parser-surface export is
           driven with correctly-paired caps and hostile content/indices

Usage: python -m toplingdb_tpu.tools.fuzz_native --target wb --runs 5000
       [--corpus DIR] [--seed N]
Exit code 0 = no findings; 1 = a finding was written to the corpus dir.
"""

from __future__ import annotations

import argparse
import hashlib
import os
import random
import sys
from toplingdb_tpu.utils import errors as _errors


def _mutate(rng: random.Random, data: bytes, max_ops: int = 4) -> bytes:
    """Byte-level structure-agnostic mutations (bit flips, splices,
    truncations, varint-ish small-int overwrites, duplications)."""
    b = bytearray(data)
    for _ in range(rng.randrange(1, max_ops + 1)):
        if not b:
            b = bytearray(rng.randbytes(rng.randrange(1, 64)))
            continue
        op = rng.randrange(6)
        i = rng.randrange(len(b))
        if op == 0:
            b[i] ^= 1 << rng.randrange(8)
        elif op == 1:
            b[i] = rng.randrange(256)
        elif op == 2:  # truncate tail
            del b[i:]
        elif op == 3:  # splice a random window elsewhere
            j = rng.randrange(len(b))
            w = rng.randrange(1, 16)
            b[i:i] = b[j:j + w]
        elif op == 4:  # small-integer overwrite (length fields)
            b[i] = rng.choice((0, 1, 0x7F, 0x80, 0xFF))
        else:  # duplicate tail
            b += b[i:i + rng.randrange(1, 32)]
    return bytes(b)


class Corpus:
    """Signature-novelty corpus: inputs keyed by outcome signature."""

    def __init__(self, path: str | None):
        self.path = path
        self.items: list[bytes] = []
        self.signatures: set = set()
        if path:
            os.makedirs(path, exist_ok=True)
            for n in sorted(os.listdir(path)):
                try:
                    self.items.append(
                        open(os.path.join(path, n), "rb").read())
                except OSError:
                    pass

    def maybe_add(self, data: bytes, signature) -> bool:
        if signature in self.signatures:
            return False
        self.signatures.add(signature)
        self.items.append(data)
        if self.path:
            h = hashlib.sha1(data).hexdigest()[:16]
            with open(os.path.join(self.path, f"c-{h}"), "wb") as f:
                f.write(data)
        return True

    def pick(self, rng: random.Random, seeds: list[bytes]) -> bytes:
        pool = self.items if (self.items and rng.random() < 0.7) else seeds
        return rng.choice(pool)


# -- targets ----------------------------------------------------------------

def _wb_seeds(rng):
    from toplingdb_tpu.db.write_batch import WriteBatch

    seeds = []
    for shape in range(4):
        wb = WriteBatch()
        for i in range(rng.randrange(1, 24)):
            k = b"k%04d" % rng.randrange(200)
            if shape == 0:
                wb.put(k, b"v" * rng.randrange(0, 40))
            elif shape == 1:
                wb.delete(k)
            elif shape == 2:
                wb.merge(k, b"m%d" % i)
            else:
                wb.put_entity(k, b"\x00WCE1\x01\x00\x02vv")
        seeds.append(wb.data())
    # A long run: the skiplist sorts it and searches it in groups
    # (SkipList::insert_run), keys of every length around its 8-byte prefix.
    wb = WriteBatch()
    for i in range(rng.randrange(40, 600)):
        k = (b"k%04d" % rng.randrange(200))[:rng.randrange(0, 7)] \
            + b"\0" * rng.randrange(0, 12)
        if i % 5 == 0:
            wb.delete(k)
        else:
            wb.put(k, b"v" * rng.randrange(0, 200))
    seeds.append(wb.data())
    # db_bench's batch: 500 records, which the fused plane's apply shares
    # between the calling thread and the apply pool's worker.
    wb = WriteBatch()
    for i in range(500):
        k = b"%08d" % rng.randrange(10**8)
        (wb.delete(k) if i % 11 == 0 else wb.put(k, rng.randbytes(20)))
    seeds.append(wb.data())
    return seeds


def group_commit_insert(rep, data: bytes, first_seq: int, out=None):
    """The fused plane's validate + insert (mode 2) of one wire image into
    `rep`: the record count, or None when the plane refuses the image (or
    is not there). `out`, when given, is the plane's `c_int64 * 11` out
    array (out[10]: the records the apply pool's worker inserted)."""
    import ctypes

    from toplingdb_tpu import native

    fn = getattr(native.lib(), "tpulsm_wb_group_commit", None)
    if fn is None:
        return None
    if out is None:
        out = (ctypes.c_int64 * 11)()
    rc = fn(rep._h, rep._nget_mem_kind, (ctypes.c_char_p * 1)(data),
            (ctypes.c_int64 * 1)(len(data)), 1, first_seq, None, 0, 0, 2, 0,
            -1, None, 0, -1, out)
    return rc if rc >= 0 else None


def fuzz_wb(rng, runs, corpus: Corpus):
    from toplingdb_tpu.db.memtable import NativeSkipListRep, NativeTrieRep
    from toplingdb_tpu.db.write_batch import WriteBatch
    from toplingdb_tpu.utils.status import Corruption

    seeds = _wb_seeds(rng)
    findings = 0
    for it in range(runs):
        data = _mutate(rng, corpus.pick(rng, seeds))
        rep = NativeSkipListRep() if it % 2 else NativeTrieRep()
        before = len(rep)
        r = rep.insert_wb(data, 1000)
        if r is None:
            # Native rejected (or unsupported): rejection must be CLEAN.
            if len(rep) != before:
                print(f"FINDING[wb]: rejected batch mutated the rep "
                      f"({before} -> {len(rep)})")
                corpus.maybe_add(data, ("FINDING", it))
                findings += 1
            sig = ("rej",)
        else:
            count = r[0]
            # Differential: if the native wire parser ACCEPTED, the
            # Python decode must ALSO accept, with the same record count
            # (a python-side raise on natively-valid bytes IS the
            # divergence class this harness exists to catch).
            try:
                py_count = sum(1 for _ in WriteBatch(data).entries_cf())
            except Corruption:
                py_count = "corruption"
            except Exception as e:  # noqa: BLE001
                py_count = type(e).__name__
            if py_count != count:
                print(f"FINDING[wb]: native applied {count} records, "
                      f"python says {py_count!r}")
                corpus.maybe_add(data, ("FINDING", it))
                findings += 1
            # What went in reads back in the list's order, a row a record
            # (a batch gives every record a sequence of its own).
            rows = [skey for skey, _ in rep.iter_all()]
            if rows != sorted(rows) or len(set(rows)) != count \
                    or len(rep) != count:
                print(f"FINDING[wb]: {count} records read back as "
                      f"{len(rows)} rows, sorted: {rows == sorted(rows)}")
                corpus.maybe_add(data, ("FINDING", it))
                findings += 1
            sig = ("ok", min(count, 8))
        # The fused plane parses the same image with a loop of its own:
        # it takes what insert_wb takes, and a refusal inserts nothing.
        twin = type(rep)()
        g = group_commit_insert(twin, data, 1000)
        if (g, len(twin)) != ((None, 0) if r is None else (r[0], len(rep))):
            print(f"FINDING[wb]: insert_wb {r and r[0]!r} / {len(rep)} rows,"
                  f" the fused plane {g!r} / {len(twin)} rows")
            corpus.maybe_add(data, ("FINDING", it))
            findings += 1
        corpus.maybe_add(data, sig)
    return findings


def _block_seeds(rng):
    from toplingdb_tpu.table.block import BlockBuilder

    seeds = []
    for interval in (1, 4, 16):
        bb = BlockBuilder(interval)
        for i in range(rng.randrange(2, 40)):
            bb.add(b"key%05d" % i + b"\x01" * 8, b"val%d" % i)
        seeds.append(bb.finish())
    return seeds


def fuzz_block(rng, runs, corpus: Corpus):
    import numpy as np

    from toplingdb_tpu import native

    lib = native.lib()
    seeds = _block_seeds(rng)
    key_out = np.empty(1 << 20, np.uint8)
    val_out = np.empty(1 << 20, np.uint8)
    ko = np.empty(1 << 16, np.int32)
    kl = np.empty(1 << 16, np.int32)
    vo = np.empty(1 << 16, np.int32)
    vl = np.empty(1 << 16, np.int32)
    findings = 0
    for it in range(runs):
        data = _mutate(rng, corpus.pick(rng, seeds))
        buf = np.frombuffer(data, np.uint8)
        rc = lib.tpulsm_decode_block(
            buf.tobytes(), len(buf),
            native.np_u8p(key_out), len(key_out),
            native.np_u8p(val_out), len(val_out),
            native.np_i32p(ko), native.np_i32p(kl),
            native.np_i32p(vo), native.np_i32p(vl), 1 << 16,
        )
        if rc >= 0:
            # Differential: Python block iterator over the same bytes must
            # decode the same entry count (or reject).
            try:
                from toplingdb_tpu.table.block import BlockIter

                bi = BlockIter(data, None)
                bi.seek_to_first()
                py_n = sum(1 for _ in bi.entries())
            except Exception as e:
                _errors.swallow(reason="py-decoder-refused", exc=e)
                py_n = None
            if py_n is not None and py_n != rc:
                print(f"FINDING[block]: native decoded {rc}, python {py_n}")
                corpus.maybe_add(data, ("FINDING", it))
                findings += 1
        corpus.maybe_add(data, ("rc", max(-9, min(int(rc), 8))))
    return findings


def fuzz_scan(rng, runs, corpus: Corpus):
    import numpy as np

    from toplingdb_tpu import native
    from toplingdb_tpu.db.dbformat import (
        InternalKeyComparator,
        ValueType,
        make_internal_key,
    )
    from toplingdb_tpu.env import MemEnv
    from toplingdb_tpu.table import format as fmt
    from toplingdb_tpu.table.builder import TableBuilder, TableOptions
    from toplingdb_tpu.table.reader import TableReader

    lib = native.lib()
    icmp = InternalKeyComparator()
    env = MemEnv()
    seeds = []
    for comp in (0, fmt.SNAPPY_COMPRESSION):
        w = env.new_writable_file("/f.sst")
        tb = TableBuilder(w, icmp, TableOptions(block_size=512,
                                                compression=comp))
        for i in range(300):
            tb.add(make_internal_key(b"k%05d" % i, i + 1, ValueType.VALUE),
                   b"v%04d" % i)
        tb.finish()
        w.close()
        seeds.append(bytes(env.read_file("/f.sst")))

    # Handles come from the REAL footer of the seed; mutants reuse them so
    # the scan sees plausible-but-corrupt block spans.
    r = TableReader(env.new_random_access_file("/f.sst"), icmp,
                    TableOptions())
    idx = r.new_index_iterator()
    idx.seek_to_first()
    handles = [fmt.BlockHandle.decode_exact(e) for _, e in idx.entries()]
    b_offs = np.array([h.offset for h in handles], np.int64)
    b_lens = np.array([h.size for h in handles], np.int64)
    key_out = np.empty(1 << 20, np.uint8)
    val_out = np.empty(1 << 20, np.uint8)
    ko = np.empty(1 << 16, np.int32)
    kl = np.empty(1 << 16, np.int32)
    vo = np.empty(1 << 16, np.int32)
    vl = np.empty(1 << 16, np.int32)
    findings = 0
    for it in range(runs):
        data = _mutate(rng, corpus.pick(rng, seeds))
        buf = np.frombuffer(data, np.uint8)
        rc = lib.tpulsm_scan_blocks(
            native.np_u8p(buf), len(buf),
            native.np_i64p(b_offs), native.np_i64p(b_lens), len(handles),
            1,  # verify_crc on: corrupt payloads must be CAUGHT
            native.np_u8p(key_out), len(key_out),
            native.np_u8p(val_out), len(val_out),
            native.np_i32p(ko), native.np_i32p(kl),
            native.np_i32p(vo), native.np_i32p(vl), 1 << 16, 0, 0,
        )
        if rc < -8 or rc > 1 << 16:
            print(f"FINDING[scan]: out-of-contract rc {rc}")
            corpus.maybe_add(data, ("FINDING", it))
            findings += 1
        corpus.maybe_add(data, ("rc", max(-9, min(int(rc), 4))))
    return findings


def fuzz_manifest(rng, runs, corpus: Corpus):
    import tempfile

    from toplingdb_tpu.db.db import DB
    from toplingdb_tpu.options import Options
    from toplingdb_tpu.utils.status import Corruption, IOError_

    # Seed: a real MANIFEST from a tiny DB.
    d = tempfile.mkdtemp(prefix="fz_mf_")
    db = DB.open(d, Options(create_if_missing=True))
    for i in range(200):
        db.put(b"k%04d" % i, b"v")
    db.flush()
    db.close()
    findings = 0
    cur = open(os.path.join(d, "CURRENT")).read().strip()
    seed = open(os.path.join(d, cur), "rb").read()
    for it in range(runs):
        # Re-read CURRENT every round: a successful open ROLLS the
        # manifest and repoints CURRENT — mutating the stale file would
        # silently stop exercising the parser.
        cur = open(os.path.join(d, "CURRENT")).read().strip()
        mpath = os.path.join(d, cur)
        data = _mutate(rng, corpus.pick(rng, [seed]))
        open(mpath, "wb").write(data)
        try:
            db = DB.open(d, Options())
            db.close()
            sig = ("open-ok",)
        except (Corruption, IOError_, ValueError, KeyError) as e:
            sig = ("err", type(e).__name__)
        except Exception as e:  # noqa: BLE001
            print(f"FINDING[manifest]: unexpected {type(e).__name__}: "
                  f"{str(e)[:120]}")
            corpus.maybe_add(data, ("FINDING", it))
            findings += 1
            sig = ("unexpected", type(e).__name__)
        corpus.maybe_add(data, sig)
    open(mpath, "wb").write(seed)
    import shutil

    shutil.rmtree(d, ignore_errors=True)
    return findings


# -- contract-driven shapes (tools/check_native_abi) ------------------------

# Parser-surface exports: every pointer they take is paired with an
# explicit length/cap and the C side bounds-checks untrusted indices
# against them, so contract-shaped hostile inputs are safe to run
# in-process. Producer-surface exports (builders, memtables) trust their
# offs/lens arrays by design and are excluded.
ABI_FUZZ_SYMS = (
    "tpulsm_crc32c_extend", "tpulsm_xxh64", "tpulsm_wb_protect",
    "tpulsm_block_seek", "tpulsm_decode_block", "tpulsm_decode_blocks",
    "tpulsm_inflate_blocks", "tpulsm_scan_blocks",
    "tpulsm_scan_blocks_refvals",
    # Zip data plane: every kernel validates its full input surface
    # (section length floors, offs/lens bounds, entry/group windows)
    # before touching a byte, so hostile contract-shaped input is safe.
    "tpulsm_zip_newkey", "tpulsm_zip_encode_keys",
    "tpulsm_zip_encode_values", "tpulsm_zip_decode_keys",
    "tpulsm_zip_group_decode", "tpulsm_zip_table_handle_new",
    "tpulsm_zip_train_dict",
    # SingleFastTable entry-range scan: the image and its offset array
    # come from a file, every offset and varint is checked against them.
    "tpulsm_sft_scan",
)

_BLOB_NAMES = ("data", "block", "file_buf", "rep", "target",
               "key_buf", "val_buf", "kmeta", "vblob")


def _cdiv(a: int, b: int) -> int:
    return (max(a, 0) + max(b, 1) - 1) // max(b, 1)


# §2.10.2 `:!` exemptions fall in two classes: opaque handles the fuzzer
# cannot mint (symbol stays unfuzzable), and derived capacities the
# callee recomputes from its scalar parameters. This table sizes the
# second class — worst case, so an under-allocation can never masquerade
# as a kernel bug — from the same scalars the argument list carries.
_DERIVED_ELEMS = {
    ("tpulsm_zip_encode_keys", "meta_out"): lambda v: 4 * max(v["n"], 1),
    ("tpulsm_zip_encode_keys", "gso_out"):
        lambda v: 4 * _cdiv(v["n"], v["group"]),
    ("tpulsm_zip_encode_values", "go_out"):
        lambda v: 4 * (_cdiv(v["n"], v["vg"]) + 1),
    ("tpulsm_zip_encode_values", "flags_out"):
        lambda v: _cdiv(_cdiv(v["n"], v["vg"]), 8),
    ("tpulsm_zip_decode_keys", "key_offs"): lambda v: v["e1"] - v["e0"],
    ("tpulsm_zip_decode_keys", "key_lens"): lambda v: v["e1"] - v["e0"],
    ("tpulsm_zip_group_decode", "raw_offs"):
        lambda v: v["g1"] - v["g0"] + 1,
    **{("tpulsm_sft_scan", a): lambda v: v["e1"] - v["e0"]
       for a in ("key_offs", "key_lens", "val_offs", "val_lens")},
}

# Ranges for scalars whose default 0..3 draw would pin a kernel in its
# reject path (e.g. zip klen < 8 is always -3): wide enough to cross the
# accept/reject boundary in both directions.
_SCALAR_HINTS = {
    "klen": (6, 72), "uklen": (0, 64), "group": (0, 33), "vg": (0, 33),
    "meta16": (0, 2), "lens32": (0, 2), "n": (0, 513), "e0": (-2, 64),
    "e1": (-2, 64), "g0": (-2, 8), "g1": (-2, 8), "key_base": (0, 4),
    "compress": (0, 2), "level": (0, 9), "max_dict_bytes": (0, 1025),
}


def load_abi_contract(repo_root: str | None = None):
    """Parse the three sources of truth the ABI checker cross-validates
    (C signatures, ctypes bindings, §2.10.2 table) and return
    (sigs, bindings, rows). Raises if any of them fails to parse — a
    fuzz run on a drifted contract would test the wrong shapes."""
    from toplingdb_tpu.tools import check_native_abi as abi

    root = repo_root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    nat = os.path.join(root, "toplingdb_tpu", "native")
    sigs, v1 = abi.parse_c_signatures(os.path.join(nat, "tpulsm_native.cc"))
    bindings, v2 = abi.parse_ctypes_bindings(os.path.join(nat, "__init__.py"))
    rows, v3 = abi.parse_contract_table(os.path.join(root, "ARCHITECTURE.md"))
    if v1 or v2 or v3:
        raise RuntimeError("ABI contract failed to parse: "
                           + "; ".join(v1 + v2 + v3))
    return sigs, bindings, rows


def shapes_from_contract(rng, sym, sigs, bindings, rows, data=b""):
    """Build one concrete ctypes argument list for `sym` from the parsed
    contract: the §2.10.2 row says which integer parameter sizes each
    buffer, the C signature says constness/element width, and the binding
    token says the exact ctypes value to construct. `data` feeds the
    primary input blob so the corpus loop drives the parser; index arrays
    get values straddling the valid range (including negatives) to hit
    the bounds-check paths. Returns (args, keepalive) or None when the
    symbol takes opaque handles (`:!`) the fuzzer cannot mint."""
    import ctypes

    import numpy as np

    _, params = sigs[sym]
    specs = rows[sym][2]
    argtoks = bindings[sym]["argtypes"]
    if any(s == "!" and (sym, p) not in _DERIVED_ELEMS
           for p, s in specs.items()):
        return None  # true opaque handles: not mintable from bytes
    ptr_ct = {"POINTER(c_uint8)": (np.uint8, ctypes.c_uint8),
              "POINTER(c_int8)": (np.int8, ctypes.c_int8),
              "POINTER(c_int32)": (np.int32, ctypes.c_int32),
              "POINTER(c_uint32)": (np.uint32, ctypes.c_uint32),
              "POINTER(c_int64)": (np.int64, ctypes.c_int64),
              "POINTER(c_uint64)": (np.uint64, ctypes.c_uint64)}
    # Element count for every sizing parameter: the primary blob's length
    # param carries len(data); other counts stay small so out-buffers are
    # bounded and count-indexed loops terminate quickly.
    blob = next((n for _, n in params if n in specs
                 and n in _BLOB_NAMES), None)
    sized: dict[str, int] = {}
    for pname, spec in specs.items():
        if spec.isdigit() or spec == "!":
            continue
        sized[spec] = (len(data) if pname == blob
                       else sized.get(spec, rng.randrange(0, 257)))
    # Scalars draw before buffers so derived-capacity outputs (zip group
    # counts, entry windows) can size themselves from the same values.
    scalars: dict[str, int] = {}
    for _, pname in params:
        if pname in specs:
            continue
        if pname in sized:
            scalars[pname] = sized[pname]
        else:
            lo, hi = _SCALAR_HINTS.get(pname, (0, 4))
            scalars[pname] = rng.randrange(lo, hi)
    args, keepalive = [], []
    for (ctype, pname), tok in zip(params, argtoks):
        if pname not in specs:  # scalar: a chosen size, or a flag/seed
            args.append(scalars[pname])
            continue
        spec = specs[pname]
        derive = _DERIVED_ELEMS.get((sym, pname))
        if derive is not None:
            n = derive(scalars)
        elif spec.isdigit():
            n = int(spec)
        else:
            n = sized[spec]
        if tok == "c_char_p":
            raw = (data if pname == blob
                   else rng.randbytes(n))[:n].ljust(n, b"\x00")
            keepalive.append(raw)
            args.append(raw)
            continue
        dt, ct = ptr_ct[tok]
        if not ctype.startswith("const"):
            arr = np.zeros(max(n, 1), dt)  # out-buffer sized to its cap
        elif dt is np.uint8:
            raw = (data if pname == blob else rng.randbytes(n))
            arr = np.frombuffer(raw[:n].ljust(n, b"\x00"), dt).copy()
        else:
            # Untrusted index/length array: straddle the valid range.
            hi = max(len(data), 2)
            # (an unsigned array takes the negatives wrapped: far out)
            arr = np.array([rng.randrange(-4, 2 * hi)
                            for _ in range(max(n, 1))], np.int64).astype(dt)
        keepalive.append(arr)
        args.append(ctypes.cast(arr.ctypes.data, ctypes.POINTER(ct)))
    return args, keepalive


def fuzz_abi(rng, runs, corpus: Corpus):
    from toplingdb_tpu import native

    lib = native.lib()
    sigs, bindings, rows = load_abi_contract()
    syms = [s for s in ABI_FUZZ_SYMS
            if s in sigs and s in bindings and s in rows
            and hasattr(lib, s)]
    if not syms:
        print("fuzz[abi]: no contract symbols available (native lib "
              "missing?)")
        return 0
    seeds = _block_seeds(rng) + [rng.randbytes(256)]
    findings = 0
    for it in range(runs):
        sym = syms[it % len(syms)]
        data = _mutate(rng, corpus.pick(rng, seeds))
        shaped = shapes_from_contract(rng, sym, sigs, bindings, rows, data)
        if shaped is None:
            continue
        args, keepalive = shaped
        rc = getattr(lib, sym)(*args)
        if sigs[sym][0] == "void*" and rc:
            # Minted handles (zip table ctor) borrow the keepalive
            # buffers: free before they go away, and never leak.
            import ctypes

            lib.tpulsm_table_handle_free(ctypes.c_void_p(rc))
            rc = 1  # signature: handle minted vs refused, not the address
        del keepalive
        signed = sigs[sym][0] in ("int32_t", "int64_t")
        if signed and rc < -16:
            # Error codes are small negative ints; anything below the
            # contract band means a length/count escaped as a status.
            print(f"FINDING[abi]: {sym} returned out-of-contract rc {rc}")
            corpus.maybe_add(data, ("FINDING", it))
            findings += 1
        sig = (sym, max(-16, min(int(rc), 8)) if signed
               else "h%d" % bool(rc))
        corpus.maybe_add(data, sig)
    return findings


TARGETS = {"wb": fuzz_wb, "block": fuzz_block, "scan": fuzz_scan,
           "manifest": fuzz_manifest, "abi": fuzz_abi}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--target", choices=sorted(TARGETS) + ["all"],
                    default="all")
    ap.add_argument("--runs", type=int, default=2000)
    ap.add_argument("--corpus", default=None,
                    help="persist + reuse interesting inputs here")
    ap.add_argument("--seed", type=int, default=1234)
    args = ap.parse_args(argv)
    total = 0
    names = sorted(TARGETS) if args.target == "all" else [args.target]
    for name in names:
        rng = random.Random(args.seed)
        corpus = Corpus(os.path.join(args.corpus, name)
                        if args.corpus else None)
        f = TARGETS[name](rng, args.runs, corpus)
        print(f"fuzz[{name}]: {args.runs} runs, "
              f"{len(corpus.signatures)} signatures, {f} findings")
        total += f
    return 1 if total else 0


if __name__ == "__main__":
    sys.exit(main())
