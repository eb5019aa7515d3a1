"""Integrity plane tests (ISSUE 5): per-entry protection info, whole-file
checksums in the MANIFEST, the IntegrityScrubber, and the corruption soak
— flip bits on the read path under concurrent load with protection on and
assert every corruption is DETECTED (error or quarantine), zero wrong
bytes are ever served, and scrub+repair+resume returns the DB to byte
parity with an uncorrupted twin."""

import json
import os
import random
import shutil
import tempfile
import threading
import urllib.request

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.env import PosixEnv
from toplingdb_tpu.env.fault_injection import FaultInjectionEnv
from toplingdb_tpu.options import Options
from toplingdb_tpu.utils import protection as prot
from toplingdb_tpu.utils import statistics as st
from toplingdb_tpu.utils.status import Corruption, InvalidArgument


def dump(db, cf=None):
    it = db.new_iterator(cf=cf) if cf is not None else db.new_iterator()
    it.seek_to_first()
    out = []
    while it.valid():
        out.append((it.key(), it.value()))
        it.next()
    return out


def fill(db, n, seed=0, vrep=10):
    rng = random.Random(seed)
    for i in range(n):
        k = b"k%06d" % i
        v = (b"v%05d." % rng.randrange(10**5)) * vrep
        db.put(k, v)
    return n


# ===========================================================================
# Protection primitives (utils/protection.py)
# ===========================================================================


def test_protect_entry_component_sensitivity():
    base = prot.protect_entry(1, b"key", b"value", cf=0)
    assert prot.protect_entry(1, b"kez", b"value", cf=0) != base
    assert prot.protect_entry(1, b"key", b"valuf", cf=0) != base
    assert prot.protect_entry(2, b"key", b"value", cf=0) != base
    assert prot.protect_entry(1, b"key", b"value", cf=1) != base
    # Deterministic (no per-process salt: checksums cross process hops).
    assert prot.protect_entry(1, b"key", b"value", cf=0) == base


def test_strip_cf_swaps_only_the_cf_component():
    full = prot.protect_entry(1, b"k", b"v", cf=7)
    assert prot.strip_cf(full, 7) == prot.protect_entry(1, b"k", b"v", cf=0)
    assert prot.strip_cf(full, 0) == full


def test_truncate_widths():
    cs = prot.protect_entry(1, b"a", b"b")
    for nb in (1, 2, 4):
        assert prot.truncate(cs, nb) == cs & ((1 << (8 * nb)) - 1)
    assert prot.truncate(cs, 8) == cs


def test_check_protection_bytes_rejects_odd_widths():
    for bad in (3, 5, 16, -1):
        with pytest.raises(InvalidArgument):
            prot.check_protection_bytes(bad)
    for ok in prot.VALID_PROTECTION_BYTES:
        prot.check_protection_bytes(ok)


# ===========================================================================
# WriteBatch / memtable handoffs
# ===========================================================================


def test_write_batch_detects_tampered_rep():
    from toplingdb_tpu.db.write_batch import WriteBatch

    b = WriteBatch(protection_bytes_per_key=8)
    b.put(b"alpha", b"one")
    b.put(b"beta", b"two")
    b.verify_protection()  # clean batch passes
    # Flip one byte of a value inside the wire rep: the next verification
    # (explicit, or the memtable-insert handoff) must refuse the batch.
    raw = bytearray(b._rep)
    raw[raw.index(b"two")] ^= 0x40
    b._rep = raw
    with pytest.raises(Corruption):
        b.verify_protection()
    from toplingdb_tpu.db.dbformat import InternalKeyComparator
    from toplingdb_tpu.db.memtable import MemTable

    mem = MemTable(InternalKeyComparator(), protection_bytes=8)
    with pytest.raises(Corruption):
        b.insert_into(mem, sequence=1)


def test_wire_loaded_batch_attach_protection():
    from toplingdb_tpu.db.write_batch import WriteBatch

    src = WriteBatch()
    src.put(b"x", b"1")
    src.delete(b"y")
    loaded = WriteBatch(src.data(), protection_bytes_per_key=4)
    loaded.verify_protection()
    assert loaded._prot is not None and len(loaded._prot) == 2


def test_flush_detects_memtable_corruption(tmp_path):
    d = str(tmp_path / "db")
    db = DB.open(d, Options(protection_bytes_per_key=8))
    try:
        for i in range(50):
            db.put(b"k%03d" % i, b"v%03d" % i)
        # Simulate the rep silently changing an entry under the recorded
        # checksum: the memtable->flush handoff must refuse to emit.
        mem = db._cfs[0].mem
        pmap = mem.protection_map()
        assert len(pmap) == 50  # wire-path checksums materialize here
        skey = next(iter(pmap))
        pmap[skey] ^= 1
        with pytest.raises(Corruption):
            db.flush()
    finally:
        try:
            db.close()  # close re-flushes and hits the same mismatch
        except Corruption:
            pass


# ===========================================================================
# Whole-file checksums (utils/file_checksum.py + MANIFEST)
# ===========================================================================


def test_file_checksum_generators():
    from toplingdb_tpu.utils.file_checksum import (
        Crc32cFileChecksumGen,
        FileChecksumGenFactory,
        Xxh64FileChecksumGen,
    )

    g1, g2 = Crc32cFileChecksumGen(), Crc32cFileChecksumGen()
    g1.update(b"hello world")
    g2.update(b"hello ")
    g2.update(b"world")
    assert g1.finalize() == g2.finalize()  # crc32c streams chunk-agnostic

    x1, x2 = Xxh64FileChecksumGen(), Xxh64FileChecksumGen()
    x1.update(b"ab")
    x1.update(b"c")
    x2.update(b"abc")
    # The xxh combinator chains per-chunk digests — framing-sensitive by
    # design; compute_file_checksum always feeds fixed-size chunks.
    assert x1.finalize() != x2.finalize()

    with pytest.raises(InvalidArgument):
        FileChecksumGenFactory("sha0")
    with pytest.raises(InvalidArgument):
        FileChecksumGenFactory().create("nope")
    assert FileChecksumGenFactory().names() == ["crc32c", "xxh64"]


def test_file_meta_checksum_manifest_roundtrip():
    from toplingdb_tpu.db.version_edit import FileMetaData

    m = FileMetaData(7, 123, b"a\x00" * 5, b"z\x00" * 5, 1, 9,
                     file_checksum=b"\xde\xad\xbe\xef",
                     file_checksum_func_name="crc32c")
    dec, _ = FileMetaData.decode(m.encode(extended=True), 0, extended=True)
    assert dec.file_checksum == b"\xde\xad\xbe\xef"
    assert dec.file_checksum_func_name == "crc32c"
    assert dec.quarantined is False  # in-memory only, never persisted
    # Plain (non-extended) encoding still round-trips without checksums.
    dec2, _ = FileMetaData.decode(m.encode(extended=False), 0,
                                  extended=False)
    assert dec2.file_checksum == b""


@pytest.mark.parametrize("func", ["crc32c", "xxh64"])
def test_checksums_recorded_and_survive_reopen(tmp_path, func):
    d = str(tmp_path / "db")
    db = DB.open(d, Options(protection_bytes_per_key=8, file_checksum=func,
                            write_buffer_size=16 * 1024))
    fill(db, 1500, seed=1)
    db.flush()
    db.wait_for_compactions()
    res = db.verify_file_checksums()
    assert res["files_verified"] >= 1 and res["files_skipped"] == 0
    db.close()

    db2 = DB.open(d, Options(file_checksum=func))
    try:
        res2 = db2.verify_file_checksums()
        assert res2["files_verified"] == res["files_verified"]
        metas = [f for cf_id in db2.versions.column_families
                 for _, f in db2.versions.cf_current(cf_id).all_files()]
        assert metas and all(m.file_checksum_func_name == func
                             for m in metas)
    finally:
        db2.close()

    # Offline (no DB open): the MANIFEST alone yields the digests.
    from toplingdb_tpu.utils.file_checksum import (
        manifest_file_checksums,
        verify_dir_file_checksums,
    )

    rec = manifest_file_checksums(d)
    assert rec and all(name == func for name, _ in rec.values())
    offline = verify_dir_file_checksums(d)
    assert offline["files_verified"] == res["files_verified"]


def _corrupt_table_file(dbdir, skip=None):
    """Flip one byte mid-file in the first (or first non-skipped) live
    SST; returns (path, original_bytes)."""
    ssts = sorted(f for f in os.listdir(dbdir) if f.endswith(".sst")
                  and f != skip)
    path = os.path.join(dbdir, ssts[0])
    orig = open(path, "rb").read()
    buf = bytearray(orig)
    buf[len(buf) // 2] ^= 0x01
    with open(path, "wb") as f:
        f.write(buf)
    return path, orig


def test_verify_file_checksums_detects_on_disk_corruption(tmp_path):
    d = str(tmp_path / "db")
    db = DB.open(d, Options(write_buffer_size=16 * 1024))
    try:
        fill(db, 1200, seed=2)
        db.flush()
        db.wait_for_compactions()
        _corrupt_table_file(d)
        with pytest.raises(Corruption, match="file checksum mismatch"):
            db.verify_file_checksums()
    finally:
        db.close()


# ===========================================================================
# IntegrityScrubber: detect, quarantine, repair, resume
# ===========================================================================


def test_scrubber_quarantine_repair_resume(tmp_path):
    from toplingdb_tpu.utils.listener import EventListener
    from toplingdb_tpu.utils.statistics import Statistics

    events = []

    class L(EventListener):
        def on_corruption_detected(self, db, info):
            events.append(info)

    d = str(tmp_path / "db")
    stats = Statistics()
    db = DB.open(d, Options(protection_bytes_per_key=8,
                            write_buffer_size=16 * 1024,
                            statistics=stats, listeners=[L()],
                            disable_auto_compactions=True))
    try:
        fill(db, 1500, seed=3)
        db.flush()
        expected = dump(db)
        path, orig = _corrupt_table_file(d)
        bad_num = int(os.path.basename(path).split(".")[0])

        rep = db.scrub()
        assert [c["file_number"] for c in rep["corruptions"]] == [bad_num]
        assert rep["quarantined"] == [bad_num]
        assert bad_num in db._quarantined
        assert events and events[0].file_number == bad_num
        assert events[0].recorded_checksum
        t = stats.tickers()
        assert t[st.INTEGRITY_CORRUPTIONS_DETECTED] == 1
        assert t[st.INTEGRITY_SCRUB_PASSES] >= 1
        assert stats.get_histogram(st.SCRUB_LATENCY_MICROS).count >= 1

        # The latch is HARD (resumable after repair), not FATAL: writes
        # fail now, resume() is allowed once the scrub is clean again.
        with pytest.raises(Exception):
            db.put(b"blocked", b"x")

        # Quarantine excludes the file from every compaction pick.
        from toplingdb_tpu.compaction.picker import LeveledCompactionPicker

        picker = LeveledCompactionPicker(db.options, db.icmp)
        c = picker.pick_compaction(db.versions.cf_current(0))
        assert c is None or all(
            f.number != bad_num
            for f in c.inputs + c.output_level_inputs)

        # Operator restores the bytes; a clean re-scrub lifts quarantine.
        with open(path, "wb") as f:
            f.write(orig)
        rep2 = db.scrub()
        assert not rep2["corruptions"] and rep2["repaired"] == [bad_num]
        assert bad_num not in db._quarantined
        db.resume()
        db.put(b"resumed", b"yes")
        assert db.get(b"resumed") == b"yes"
        assert dump(db) == expected + [(b"resumed", b"yes")]
    finally:
        db.close()


def test_background_scrubber_thread_runs_passes(tmp_path):
    import time

    d = str(tmp_path / "db")
    db = DB.open(d, Options(protection_bytes_per_key=8,
                            integrity_scrub_period_sec=1,
                            integrity_scrub_bytes_per_sec=0))
    try:
        fill(db, 300, seed=4)
        db.flush()
        assert db._integrity_scrubber is not None
        deadline = time.time() + 10
        while (db._integrity_scrubber.passes == 0
               and time.time() < deadline):
            time.sleep(0.05)
        assert db._integrity_scrubber.passes >= 1
        assert db.scrub_status()["running"]
    finally:
        db.close()


def test_verify_checksum_sweeps_blob_files(tmp_path):
    d = str(tmp_path / "db")
    db = DB.open(d, Options(enable_blob_files=True, min_blob_size=64,
                            write_buffer_size=1 << 20))
    try:
        for i in range(200):
            db.put(b"b%03d" % i, b"B%03d" % i * 40)  # > min_blob_size
        db.flush()
        db.verify_checksum()  # clean sweep incl. blob records
        blobs = [f for f in os.listdir(d) if f.endswith(".blob")]
        assert blobs
        path = os.path.join(d, blobs[0])
        buf = bytearray(open(path, "rb").read())
        buf[len(buf) // 2] ^= 0x10
        with open(path, "wb") as f:
            f.write(buf)
        with pytest.raises(Corruption):
            db.verify_checksum()
    finally:
        db.close()


# ===========================================================================
# Read-side corruption injection (env/fault_injection.py)
# ===========================================================================


def test_corrupt_read_is_deterministic_and_targeted(tmp_path):
    base = PosixEnv()
    fe = FaultInjectionEnv(base)
    p_sst = str(tmp_path / "000001.sst")
    p_log = str(tmp_path / "000002.log")
    payload = bytes(range(256)) * 64
    for p in (p_sst, p_log):
        with open(p, "wb") as f:
            f.write(payload)
    fe.corrupt_reads(pattern="*.sst", rate=1e-2, seed=42)

    def read_all(path):
        f = fe.new_random_access_file(path)
        try:
            return f.read(0, len(payload))
        finally:
            f.close()

    a, b = read_all(p_sst), read_all(p_sst)
    assert a == b  # seeded: the same read corrupts identically
    assert a != payload
    assert fe.corruptions_injected
    assert read_all(p_log) == payload  # pattern-targeted: logs untouched
    fe.clear_corrupt_reads()
    assert read_all(p_sst) == payload  # disk was never touched


def test_corrupted_wal_reads_fail_recovery_not_serve_garbage(tmp_path):
    d = str(tmp_path / "db")
    db = DB.open(d, Options(protection_bytes_per_key=8))
    for i in range(2000):
        db.put(b"w%04d" % i, b"v%04d" % i * 8)
    db.flush_wal(sync=True)
    # Simulate a crash: snapshot the live dir (WAL still holds every
    # write), then recover from the copy.
    crashed = str(tmp_path / "crashed")
    shutil.copytree(d, crashed)
    db.close()

    fe = FaultInjectionEnv(PosixEnv())
    fe.corrupt_reads(pattern="*.log", rate=1e-3, seed=9)
    with pytest.raises(Corruption):
        DB.open(crashed, Options(protection_bytes_per_key=8), env=fe)
    assert fe.corruptions_injected  # the injector really hit the WAL
    # Uncorrupted recovery from the same image replays everything.
    db2 = DB.open(crashed, Options(protection_bytes_per_key=8))
    try:
        assert db2.get(b"w0007") == b"v0007" * 8
        assert db2.get(b"w1999") == b"v1999" * 8
    finally:
        db2.close()


# ===========================================================================
# The corruption soak (acceptance criterion, CI-scaled)
# ===========================================================================


def test_corruption_soak_zero_wrong_bytes_and_twin_parity(tmp_path):
    """Concurrent read/write/flush/compaction with seeded read-side bit
    flips at 1e-5/byte across SST+blob reads, protection_bytes_per_key=8:
    every served read must be correct-or-error (never silently wrong),
    and after clearing faults + scrub + resume the DB must be
    byte-identical to an uncorrupted twin fed the same ops."""
    rng = random.Random(1234)
    ops = []
    for i in range(4000):
        k = b"s%05d" % rng.randrange(1500)
        if rng.random() < 0.12:
            ops.append(("del", k, None))
        else:
            ops.append(("put", k, b"V%07d." % rng.randrange(10**7) * 6))

    def build(dbdir, env=None):
        opts = Options(protection_bytes_per_key=8,
                       write_buffer_size=24 * 1024,
                       level0_file_num_compaction_trigger=3,
                       enable_blob_files=True, min_blob_size=40)
        return (DB.open(dbdir, opts, env=env) if env is not None
                else DB.open(dbdir, opts))

    fe = FaultInjectionEnv(PosixEnv())
    dbdir = str(tmp_path / "db")
    holder = {"db": build(dbdir, env=fe)}
    twin = build(str(tmp_path / "twin"))
    model = {}
    wrong = []
    detected = [0]
    stop = threading.Event()

    gen = [0]  # recovery generation: reads racing a swap aren't "wrong"

    def recover():
        """An injected-corruption hit may have latched the bg error
        (compaction-found corruption is even UNRECOVERABLE): resume when
        allowed, else reopen — the DISK is intact, only reads lied."""
        try:
            holder["db"].resume()
            return
        except Exception:
            pass
        gen[0] += 1
        old = holder["db"]
        try:
            # Acknowledged writes must survive the reopen even if close()
            # dies mid-flush on another injected fault.
            old.flush_wal(sync=True)
        except Exception:
            pass
        try:
            old.close()
        except Exception:
            pass
        holder["db"] = build(dbdir, env=fe)

    pending = {}  # key -> value of the op the writer is mid-applying

    def reader():
        r = random.Random(99)
        while not stop.is_set():
            k = b"s%05d" % r.randrange(1500)
            g0 = gen[0]
            exp = model.get(k)  # racy: only flag definite corruption
            p0 = pending.get(k)
            try:
                got = holder["db"].get(k)
            except Corruption:
                detected[0] += 1
                continue
            except Exception:
                continue  # latched/closed mid-recovery: not wrong bytes
            if (exp is not None and got is not None and got != exp
                    and got != model.get(k) and got != p0
                    and got != pending.get(k) and gen[0] == g0):
                wrong.append((k, got))

    t = threading.Thread(target=reader)
    t.start()
    try:
        for i, (op, k, v) in enumerate(ops):
            # The op counts as acknowledged only once it SUCCEEDS on the
            # faulted DB; then the twin/model apply it (retries are
            # idempotent: same key, same value). `pending` lets the
            # reader tell the in-flight new value from corruption.
            if op == "put":
                pending[k] = v
            for _attempt in range(10):
                try:
                    if op == "put":
                        holder["db"].put(k, v)
                    else:
                        holder["db"].delete(k)
                    break
                except Exception:
                    detected[0] += 1
                    recover()
            else:
                raise AssertionError("op never recovered")
            if op == "put":
                twin.put(k, v)
                model[k] = v
                pending.pop(k, None)
            else:
                twin.delete(k)
                model.pop(k, None)
            if i == len(ops) // 3:
                # Faults arm only after some SSTs exist to read through.
                # transient=True: a retried read draws fresh randomness
                # (bus-flip model), so recovery can make progress while
                # detection still fires at 1e-5/byte.
                fe.corrupt_reads(pattern="*.sst", rate=1e-5, seed=77,
                                 transient=True)
                fe.corrupt_reads(pattern="*.blob", rate=1e-5, seed=78,
                                 transient=True)
    finally:
        stop.set()
        t.join()

    assert not wrong, wrong[:3]  # ZERO corrupted bytes ever served
    # The injector really fired (otherwise the soak proved nothing).
    assert fe.corruptions_injected

    fe.clear_corrupt_reads()
    recover()
    db = holder["db"]
    db.wait_for_compactions()
    rep = db.scrub()
    assert not rep["corruptions"]  # disk was never damaged, reads were
    try:
        db.resume()
    except Exception:
        pass
    twin.wait_for_compactions()
    assert dump(db) == dump(twin)  # byte parity with the control run
    for d2 in (db, twin):
        d2.close()


@pytest.mark.parametrize("plane", ["pipeline", "TPULSM_ITER_CHUNK"])
def test_protected_parity_with_data_planes(tmp_path, monkeypatch, plane):
    """Protection-on runs through the pipelined compaction plane and the
    chunked scan plane must produce byte-identical results to the
    protection-off serial twin (the handoff checks must be pure
    verification, never a behavior change)."""
    from toplingdb_tpu.compaction.executor import (
        DeviceCompactionExecutorFactory,
    )

    pipelined = []
    if plane == "pipeline":
        from test_compaction_pipeline import (
            _enable_small_pipeline,
            _spy_pipeline,
        )

        _enable_small_pipeline(monkeypatch)  # four shards a job
        pipelined = _spy_pipeline(monkeypatch)

    def scan_plane(value):
        if plane != "pipeline":
            monkeypatch.setenv(plane, value)

    scan_plane("1")

    def build(dbdir, pb, device):
        db = DB.open(dbdir, Options(
            protection_bytes_per_key=pb, write_buffer_size=24 * 1024,
            level0_file_num_compaction_trigger=3,
            compaction_executor_factory=DeviceCompactionExecutorFactory(
                device="cpu-jax", allow_fallback=False) if device else None))
        rng = random.Random(5)
        for i in range(3000):
            db.put(b"p%05d" % rng.randrange(1200),
                   b"val%06d" % rng.randrange(10**6) * 4)
        db.flush()
        db.compact_range()
        return db

    db_p = build(str(tmp_path / "prot"), 8, plane == "pipeline")
    assert bool(pipelined) == (plane == "pipeline")
    scan_plane("0")
    db_o = build(str(tmp_path / "off"), 0, False)
    try:
        scan_plane("1")
        got = dump(db_p)
        scan_plane("0")
        want = dump(db_o)
        assert got == want
        res = db_p.verify_file_checksums()
        assert res["files_verified"] >= 1
    finally:
        db_p.close()
        db_o.close()


def test_scan_plane_emission_verification_catches_tampering(tmp_path,
                                                            monkeypatch):
    """White-box: served bytes that re-hash to a checksum absent from the
    source-side bank must raise at chunk emission — and an empty bank
    (nothing was ever decoded) must refuse everything."""
    from toplingdb_tpu.utils.statistics import Statistics

    monkeypatch.setenv("TPULSM_ITER_CHUNK", "1")
    d = str(tmp_path / "db")
    stats = Statistics()
    db = DB.open(d, Options(protection_bytes_per_key=8,
                            write_buffer_size=16 * 1024,
                            statistics=stats))
    try:
        fill(db, 2000, seed=6)
        db.flush()
        it = db.new_iterator()
        plane = getattr(it, "_plane", None)
        if plane is None:
            pytest.skip("scan plane ineligible in this configuration")
        assert plane._prot_bank is not None
        it.seek_to_first()
        n = 0
        while it.valid():
            n += 1
            it.next()
        assert n == 2000  # clean protected chunked scan

        # The emission check itself: a (key, value) whose checksum was
        # never banked — i.e. bytes that match no decoded source row —
        # is a Corruption and bumps the mismatch ticker.
        with pytest.raises(Corruption, match="protection mismatch"):
            plane._verify_emission(b"fabricated-key", b"fabricated-value")
        assert stats.tickers()[st.INTEGRITY_PROTECTION_MISMATCHES] >= 1
        # A banked row passes.
        uk = b"k000000"
        v = db.get(uk)
        plane._verify_emission(uk, v)
    finally:
        db.close()


# ===========================================================================
# Propagation guards: checkpoint + import
# ===========================================================================


def test_checkpoint_refuses_to_propagate_corruption(tmp_path):
    from toplingdb_tpu.utilities.checkpoint import create_checkpoint

    d = str(tmp_path / "db")
    db = DB.open(d, Options(write_buffer_size=16 * 1024,
                            disable_auto_compactions=True))
    try:
        fill(db, 1200, seed=7)
        db.flush()
        create_checkpoint(db, str(tmp_path / "ck_good"))
        from toplingdb_tpu.utils.file_checksum import (
            verify_dir_file_checksums,
        )

        good = verify_dir_file_checksums(str(tmp_path / "ck_good"))
        assert good["files_verified"] >= 1

        _corrupt_table_file(d)
        with pytest.raises(Corruption):
            create_checkpoint(db, str(tmp_path / "ck_bad"))
    finally:
        db.close()


def test_import_verifies_exported_file_checksums(tmp_path):
    from toplingdb_tpu.db.import_column_family_job import (
        export_column_family,
        import_column_family,
    )

    src = DB.open(str(tmp_path / "src"), Options(write_buffer_size=1 << 20))
    cf = src.create_column_family("payload")
    for i in range(400):
        src.put(b"i%04d" % i, b"v%04d" % i * 6, cf=cf)
    src.flush()
    exp_dir = str(tmp_path / "export")
    meta = export_column_family(src, cf, exp_dir)
    assert all(f.file_checksum for f in meta.files)  # digests ride along
    src.close()

    # Clean import re-verifies and succeeds.
    dst = DB.open(str(tmp_path / "dst1"), Options())
    try:
        h = import_column_family(dst, "payload", exp_dir)
        assert dst.get(b"i0007", cf=h) == b"v0007" * 6
    finally:
        dst.close()

    # A tampered exported file must be refused at import time.
    sst = [f for f in os.listdir(exp_dir) if f.endswith(".sst")][0]
    p = os.path.join(exp_dir, sst)
    buf = bytearray(open(p, "rb").read())
    buf[len(buf) // 2] ^= 0x04
    with open(p, "wb") as f:
        f.write(buf)
    dst2 = DB.open(str(tmp_path / "dst2"), Options())
    try:
        with pytest.raises(Corruption):
            import_column_family(dst2, "payload", exp_dir)
    finally:
        dst2.close()


# ===========================================================================
# Tooling + HTTP view
# ===========================================================================


def test_ldb_and_sst_dump_integrity_commands(tmp_path, capsys):
    from toplingdb_tpu.tools.ldb import main as ldb_main
    from toplingdb_tpu.tools.sst_dump import main as sst_main

    d = str(tmp_path / "db")
    db = DB.open(d, Options(write_buffer_size=16 * 1024,
                            disable_auto_compactions=True))
    fill(db, 1200, seed=8)
    db.flush()
    db.close()

    assert ldb_main(["--db", d, "verify_file_checksums"]) == 0
    assert "verified" in capsys.readouterr().out
    assert ldb_main(["--db", d, "scrub", "--report"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["files_scanned"] >= 1 and not rep["corruptions"]

    sst = sorted(f for f in os.listdir(d) if f.endswith(".sst"))[0]
    sst_path = os.path.join(d, sst)
    assert sst_main(["--file", sst_path, "--verify-file-checksum"]) == 0
    assert "OK" in capsys.readouterr().out

    # Corrupt the file: every tool must now refuse it.
    path, _ = _corrupt_table_file(d)
    assert sst_main(["--file", path, "--verify-file-checksum"]) == 1
    capsys.readouterr()
    assert ldb_main(["--db", d, "scrub"]) == 1
    assert "quarantined" in capsys.readouterr().out


def test_http_integrity_view_and_scrub_trigger(tmp_path):
    from toplingdb_tpu.utils.config import SidePluginRepo

    repo = SidePluginRepo()
    db = repo.open_db({"path": str(tmp_path / "db"),
                       "options": {"create_if_missing": True,
                                   "protection_bytes_per_key": 8,
                                   "write_buffer_size": 16384}},
                      name="main")
    port = repo.start_http()
    base = f"http://127.0.0.1:{port}"
    try:
        fill(db, 600, seed=9)
        db.flush()
        req = urllib.request.Request(f"{base}/scrub/main", data=b"{}",
                                     method="POST")
        with urllib.request.urlopen(req) as r:
            body = json.loads(r.read())
        assert body["ok"] and body["report"]["files_scanned"] >= 1
        with urllib.request.urlopen(f"{base}/integrity/main") as r:
            view = json.loads(r.read())
        assert view["protection_bytes_per_key"] == 8
        assert view["passes"] >= 1
        assert view["quarantined_files"] == []
    finally:
        repo.stop_http()
        db.close()
