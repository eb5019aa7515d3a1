"""CompactFiles / SuggestCompactRange / PromoteL0 (reference db.h manual
compaction APIs) and RemapEnv (env/fs_remap.cc role)."""

import pytest

from toplingdb_tpu.db.db import DB
from toplingdb_tpu.options import Options
from toplingdb_tpu.utils.status import Busy, InvalidArgument


def _db_with_l0_files(tmp_path, n_files=3, overlap=True):
    db = DB.open(str(tmp_path / "db"), Options(
        level0_file_num_compaction_trigger=100,  # no auto compaction
    ))
    for i in range(n_files):
        lo = 0 if overlap else i * 100
        for j in range(lo, lo + 100):
            db.put(b"key%06d" % j, b"f%d-%d" % (i, j))
        db.flush()
    return db


def test_compact_files(tmp_path):
    db = _db_with_l0_files(tmp_path)
    version = db.versions.cf_current(0)
    nums = [f.number for f in version.files[0]]
    assert len(nums) == 3
    db.compact_files(nums, output_level=2)
    version = db.versions.cf_current(0)
    assert not version.files[0]
    assert version.files[2]
    for j in range(100):
        assert db.get(b"key%06d" % j) == b"f2-%d" % j  # newest file wins
    with pytest.raises(InvalidArgument):
        db.compact_files([999999], output_level=2)  # not live
    db.close()


def test_compact_files_level_validation(tmp_path):
    """Reference SanitizeCompactionInputFilesForAllLevels
    (compaction_picker.cc:908) EXPANDS a partial input set: at L0 every
    file older than the newest listed file comes along; overlapping
    output-level files are pulled in automatically."""
    db = _db_with_l0_files(tmp_path)
    version = db.versions.cf_current(0)
    nums = [f.number for f in version.files[0]]  # newest-first
    # The OLDEST L0 file alone: nothing older to pull in — moves by itself,
    # newer overlapping runs legally stay above it.
    db.compact_files(nums[-1:], output_level=1)
    version = db.versions.cf_current(0)
    assert len(version.files[0]) == 2 and len(version.files[1]) == 1
    # The NEWEST remaining L0 file: the older overlapping L0 file AND the
    # overlapping L1 file are auto-included (else reads would find stale
    # data above the moved output).
    db.compact_files([version.files[0][0].number], output_level=1)
    version = db.versions.cf_current(0)
    assert not version.files[0] and version.files[1]
    for j in range(100):
        assert db.get(b"key%06d" % j) == b"f2-%d" % j  # newest still wins
    # compacting upward is rejected
    with pytest.raises(InvalidArgument):
        db.compact_files([version.files[1][0].number], output_level=0)
    db.close()


def test_suggest_compact_range(tmp_path):
    db = _db_with_l0_files(tmp_path, overlap=False)
    marked = db.suggest_compact_range(b"key000150", b"key000250")
    version = db.versions.cf_current(0)
    flagged = [f for _, f in version.all_files() if f.marked_for_compaction]
    assert marked == len(flagged) and 1 <= marked <= 2
    # idempotent
    assert db.suggest_compact_range(b"key000150", b"key000250") == 0
    db.close()


def test_promote_l0(tmp_path):
    db = _db_with_l0_files(tmp_path, overlap=False)  # disjoint L0 files
    db.promote_l0(target_level=2)
    version = db.versions.cf_current(0)
    assert not version.files[0] and len(version.files[2]) == 3
    for j in range(250, 260):
        assert db.get(b"key%06d" % j) == b"f2-%d" % j
    db.close()
    # survives reopen (metadata-only move went through the MANIFEST)
    db = DB.open(str(tmp_path / "db"), Options())
    assert db.get(b"key000000") == b"f0-0"
    db.close()


def test_promote_l0_rejects_overlap(tmp_path):
    db = _db_with_l0_files(tmp_path, overlap=True)
    with pytest.raises(InvalidArgument):
        db.promote_l0()
    db.close()


def test_remap_env(tmp_path):
    from toplingdb_tpu.env import default_env
    from toplingdb_tpu.env.remap import RemapEnv

    real = str(tmp_path / "real")
    env = RemapEnv(default_env(), {"/virtual/db": real,
                                   "/virtual/db/sub": str(tmp_path / "sub")})
    env.create_dir("/virtual/db")
    env.write_file("/virtual/db/x.txt", b"hello", sync=True)
    assert (tmp_path / "real" / "x.txt").read_bytes() == b"hello"
    assert env.read_file("/virtual/db/x.txt") == b"hello"
    assert env.file_exists("/virtual/db/x.txt")
    assert env.get_file_size("/virtual/db/x.txt") == 5
    # longest prefix wins
    env.create_dir("/virtual/db/sub")
    env.write_file("/virtual/db/sub/y.txt", b"yy")
    assert (tmp_path / "sub" / "y.txt").read_bytes() == b"yy"
    # unmapped paths pass through
    p = str(tmp_path / "plain.txt")
    env.write_file(p, b"p")
    assert env.read_file(p) == b"p"
    env.rename_file("/virtual/db/x.txt", "/virtual/db/z.txt")
    assert env.get_children("/virtual/db") == ["z.txt"]
    # a whole DB works through the remap
    db = DB.open("/virtual/db2", Options(),
                 env=RemapEnv(default_env(), {"/virtual/db2":
                                              str(tmp_path / "db2")}))
    db.put(b"k", b"v")
    db.flush()
    db.close()
    assert (tmp_path / "db2").is_dir()
    db = DB.open("/virtual/db2", Options(),
                 env=RemapEnv(default_env(), {"/virtual/db2":
                                              str(tmp_path / "db2")}))
    assert db.get(b"k") == b"v"
    db.close()
