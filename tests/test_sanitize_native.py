"""ASan/UBSan replay of the native fuzz corpus (satellite of the
concurrency-correctness plane): rebuild tpulsm_native.cc with
TPULSM_NATIVE_SANITIZE set and drive the same budgeted fuzz targets
through the instrumented .so in a subprocess. A sanitizer report aborts
the child, so a clean exit IS the assertion.

ASan must be loaded before libc allocates, hence the LD_PRELOAD of
libasan in the child environment (the parent process stays
uninstrumented). Skips when the toolchain or the runtime library is
missing.
"""

import os
import shutil
import subprocess
import sys

import pytest

from toplingdb_tpu import native

pytestmark = [pytest.mark.slow,
              pytest.mark.skipif(native.lib() is None,
                                 reason="native library unavailable")]

_CHILD = r"""
import random
from toplingdb_tpu import native
from toplingdb_tpu.tools import fuzz_native as fz

assert native._SANITIZE == {mode!r}, "sanitize mode did not take"
assert native.lib() is not None, "sanitized .so failed to build/load"
rng = random.Random(1234)
total = 0
for target, runs in (("wb", 120), ("block", 120), ("scan", 60),
                     ("manifest", 10)):
    corpus = fz.Corpus({corpus_dir!r} + "/" + target)
    total += fz.TARGETS[target](rng, runs, corpus)
assert total == 0, f"{{total}} finding(s) under sanitizer"
print("SANITIZED_REPLAY_OK")
"""


# Four writers hand the skiplist overlapping runs (insert_wb releases the
# GIL: their sorts, interleaved searches and CAS splices truly overlap)
# while two readers iterate and seek; a fifth commits 500-record groups
# through the fused plane, whose apply shares each group's sorted run with
# the apply pool's worker (on a multi-core run the worker must take some);
# afterwards the list holds the union.
_CONCURRENT_CHILD = r"""
import ctypes, os, struct, threading
from toplingdb_tpu import native
from toplingdb_tpu.db.memtable import NativeSkipListRep
from toplingdb_tpu.db.write_batch import WriteBatch
from toplingdb_tpu.tools import fuzz_native as fz

assert native._SANITIZE == {mode!r}, "sanitize mode did not take"
assert native.lib() is not None, "sanitized .so failed to build/load"
MAXP = 2**64 - 1
rep = NativeSkipListRep()
WRITERS, BATCHES, PER = 4, 25, 200
want, images = [], []
for w in range(WRITERS):
    for b in range(BATCHES):
        first = 1 + (w * BATCHES + b) * PER
        wb = WriteBatch()
        for i in range(PER):
            n = (w * 7919 + b * 104729 + i * 31) % 1500   # overlapping keys
            k = struct.pack(">Q", n) + (b"tail" if n % 3 == 0 else b"")
            t = 0 if i % 11 == 0 else 1
            v = b"" if t == 0 else b"w%d-%d-%d" % (w, b, i)
            (wb.delete(k) if t == 0 else wb.put(k, v))
            want.append(((k, MAXP - ((first + i) << 8 | t)), v))
        images.append((w, wb.data(), first))
GROUP = 500
groups = []
for g in range(10):
    first = 1 + WRITERS * BATCHES * PER + g * GROUP
    wb = WriteBatch()
    for i in range(GROUP):
        k = struct.pack(">Q", (g * 6151 + i * 389) % 1500) + b"g"
        wb.put(k, b"g%d-%d" % (g, i))
        want.append(((k, MAXP - ((first + i) << 8 | 1)), b"g%d-%d" % (g, i)))
    groups.append((wb.data(), first))
done = threading.Event()
errors, by_worker = [], []

def writer(w):
    try:
        if w == WRITERS:  # the fused plane's two-thread apply
            out = (ctypes.c_int64 * 11)()
            for data, first in groups:
                assert fz.group_commit_insert(rep, data, first, out) == GROUP
                by_worker.append(out[10])
            return
        for ww, data, first in images:
            if ww == w:
                assert rep.insert_wb(data, first)[0] == PER
    except BaseException as e:  # noqa: BLE001
        errors.append(repr(e))

def reader():
    try:
        while not done.is_set():
            rows = [skey for skey, _ in rep.iter_all()]
            assert rows == sorted(rows)
            for skey in rows[::97]:
                assert rep.entry_at(rep.pos_seek_ge(skey))[0] == skey
                lt = rep.pos_seek_lt(skey)
                assert lt is None or rep.entry_at(lt)[0] < skey
    except BaseException as e:  # noqa: BLE001
        errors.append(repr(e))

ws = [threading.Thread(target=writer, args=(w,)) for w in range(WRITERS + 1)]
rs = [threading.Thread(target=reader) for _ in range(2)]
for t in rs + ws:
    t.start()
for t in ws:
    t.join()
done.set()
for t in rs:
    t.join()
assert not errors, errors
assert list(rep.iter_all()) == sorted(want)
assert len(rep) == WRITERS * BATCHES * PER + len(groups) * GROUP
assert (sum(by_worker) > 0) == (len(os.sched_getaffinity(0)) >= 2), by_worker
print("SANITIZED_REPLAY_OK")
"""


# The deferred put encoder (fastget.c wb_put_values) under the sanitizer:
# the wire-image parity corpus of tests/test_write_batch.py, then single
# settles whose PyByteArray_Resize carries the image across 2^16 and 2^20
# bytes, each against the Python encode of the same records.
_ENCODER_CHILD = r"""
import random, sys
import pytest
from toplingdb_tpu import native
from toplingdb_tpu.db import write_batch as wbm
from toplingdb_tpu.db.write_batch import WriteBatch

assert native._SANITIZE == {mode!r}, "sanitize mode did not take"
fn = native.wb_put_values()
assert native.lib() is not None and fn is not None, \
    "sanitized .so failed to build/load"
sys.path.insert(0, "tests")
import test_write_batch as twb

for pb in (0, 8):
    for seed in range(4):
        with pytest.MonkeyPatch.context() as mp:
            twb.test_deferred_put_wire_image_parity(mp, "native", pb, seed)

rng = random.Random(7)


def image(pairs, enc, split):
    wbm._enc_fn = enc
    b = WriteBatch()
    for i, (k, v) in enumerate(pairs):
        if i == split:
            b.data_size()  # settle: the next one grows from here
        b.put(k, v)
    return b.data()


for edge in (1 << 16, 1 << 20):
    pairs, size = [], 12
    while size < edge + 4096:
        k = rng.randbytes(rng.randrange(1, 40))
        v = rng.randbytes(rng.choice((0, 20, 127, 128, 3000, 40000)))
        pairs.append((k, v))
        size += 1 + 5 + len(k) + len(v)
    for split in (0, len(pairs) // 2, len(pairs) - 1):
        assert image(pairs, fn, split) == image(pairs, None, split)
print("SANITIZED_REPLAY_OK")
"""


def _libasan() -> str | None:
    gxx = shutil.which("g++")
    if gxx is None:
        return None
    try:
        out = subprocess.run(
            [gxx, "-print-file-name=libasan.so"], capture_output=True,
            text=True, timeout=30).stdout.strip()
    except (subprocess.SubprocessError, OSError):
        return None
    return out if out and os.path.sep in out and os.path.exists(out) \
        else None


def _replay(mode: str, env_extra: dict, tmp_path, child=_CHILD) -> None:
    env = dict(os.environ)
    env["TPULSM_NATIVE_SANITIZE"] = mode
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    src = child.format(mode=mode, corpus_dir=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True,
        timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if proc.returncode != 0 and "failed to build/load" in \
            (proc.stdout + proc.stderr):
        pytest.skip(f"{mode}-instrumented build unavailable")
    assert proc.returncode == 0, (
        f"sanitized replay died (rc={proc.returncode})\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    assert "SANITIZED_REPLAY_OK" in proc.stdout


def test_fuzz_corpus_replay_asan(tmp_path):
    lib = _libasan()
    if lib is None:
        pytest.skip("libasan not found")
    _replay("asan", {
        "LD_PRELOAD": lib,
        # ctypes dlopens the .so after interpreter start; leak reports of
        # interpreter-lifetime allocations are noise here.
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1",
    }, tmp_path)


def test_fuzz_corpus_replay_ubsan(tmp_path):
    _replay("undefined", {"UBSAN_OPTIONS": "halt_on_error=1"}, tmp_path)


@pytest.mark.parametrize("mode", ["asan", "undefined"])
def test_concurrent_run_inserts_under_sanitizer(mode, tmp_path):
    if mode == "asan":
        lib = _libasan()
        if lib is None:
            pytest.skip("libasan not found")
        extra = {"LD_PRELOAD": lib,
                 "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1"}
    else:
        extra = {"UBSAN_OPTIONS": "halt_on_error=1"}
    _replay(mode, extra, tmp_path, child=_CONCURRENT_CHILD)


@pytest.mark.parametrize("mode", ["asan", "undefined"])
def test_deferred_put_encoder_under_sanitizer(mode, tmp_path):
    if mode == "asan":
        lib = _libasan()
        if lib is None:
            pytest.skip("libasan not found")
        extra = {"LD_PRELOAD": lib,
                 "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1"}
    else:
        extra = {"UBSAN_OPTIONS": "halt_on_error=1"}
    _replay(mode, extra, tmp_path, child=_ENCODER_CHILD)
