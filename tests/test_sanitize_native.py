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
# while two readers iterate and seek; afterwards the list holds the union.
_CONCURRENT_CHILD = r"""
import struct, threading
from toplingdb_tpu import native
from toplingdb_tpu.db.memtable import NativeSkipListRep
from toplingdb_tpu.db.write_batch import WriteBatch

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
done = threading.Event()
errors = []

def writer(w):
    try:
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

ws = [threading.Thread(target=writer, args=(w,)) for w in range(WRITERS)]
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
assert len(rep) == WRITERS * BATCHES * PER
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
