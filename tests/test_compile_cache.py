"""The persistent compile cache has one setter, and it can be placed from
outside: JAX_COMPILATION_CACHE_DIR wins; unset, it is <checkout>/.jax_cache
(ops/device_runtime.py). Checked in child processes, because the setting is
made when the device path is first imported."""

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRINT_CACHE_DIR = (
    "import toplingdb_tpu.ops.compaction_kernels, jax; "
    "print(jax.config.jax_compilation_cache_dir); "
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)")


def _cache_dir_in_child(env_value):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run([sys.executable, "-c", _PRINT_CACHE_DIR], cwd=REPO,
                         env=env, capture_output=True, timeout=120)
    assert out.returncode == 0, out.stderr.decode()
    return out.stdout.decode().split()


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    where = str(tmp_path / "some" / "dir")
    assert _cache_dir_in_child(where)[0] == where


def test_cache_dir_defaults_to_the_checkout():
    path, min_secs = _cache_dir_in_child(None)
    assert path == os.path.join(REPO, ".jax_cache")
    # Every program is kept, so a fresh worker compiles nothing.
    assert float(min_secs) == 0.0


def test_nothing_else_in_the_tree_sets_a_cache_directory():
    pat = re.compile(r"compilation_cache|JAX_COMPILATION_CACHE_DIR|"
                     r"\.jax_cache")
    allowed = {os.path.join("toplingdb_tpu", "ops", "device_runtime.py")}
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("tests", "__pycache__", "chiprun_out")]
        for f in files:
            if not f.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(root, f), REPO)
            with open(os.path.join(root, f), encoding="utf-8") as fh:
                if rel not in allowed and pat.search(fh.read()):
                    hits.append(rel)
    assert hits == []
