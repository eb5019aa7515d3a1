"""The flat C API (toplingdb_tpu/bindings/c — the reference's db/c.cc role):
compile the shared lib + demo with the system toolchain and drive the full
open/put/get/delete/flush/reopen cycle from C."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CDIR = os.path.join(ROOT, "toplingdb_tpu", "bindings", "c")



def _build_lib_and_env(tmp_path, demo_src, demo_name):
    """Build libtpulsm_c.so once per call + the given demo; returns
    (demo_path, env) — shared by every C-binding test so the compile
    flags cannot diverge between them."""
    demo = str(tmp_path / demo_name)
    subprocess.run(
        f"g++ -shared -fPIC -O2 tpulsm_c.c -o libtpulsm_c.so "
        f"$(python3-config --includes) $(python3-config --ldflags --embed)",
        shell=True, cwd=CDIR, check=True,
    )
    subprocess.run(
        f"gcc -O2 {demo_src} -o {demo} -I{CDIR} -L{CDIR} -ltpulsm_c "
        f"-Wl,-rpath,{CDIR}",
        shell=True, cwd=CDIR, check=True,
    )
    env = dict(os.environ)
    # The embedded interpreter needs the repo on PYTHONPATH; the C caller
    # never imports jax.
    env["PYTHONPATH"] = ROOT
    return demo, env

@pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("gcc") is None
    or shutil.which("python3-config") is None,
    reason="C toolchain unavailable",
)
def test_c_binding_end_to_end(tmp_path):
    demo, env = _build_lib_and_env(tmp_path, "demo.c", "demo")
    out = subprocess.run(
        [demo, str(tmp_path / "cdb")], env=env, capture_output=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr.decode()
    assert b"C-API-OK" in out.stdout
    assert os.path.exists(os.path.join(CDIR, "libtpulsm_c.so"))


@pytest.mark.skipif(
    shutil.which("g++") is None or shutil.which("gcc") is None
    or shutil.which("python3-config") is None,
    reason="C toolchain unavailable",
)
def test_c_repo_open_from_json_and_http(tmp_path):
    """SidePluginRepo through the C ABI: open-from-JSON-config, write/read,
    HTTP introspection (/dbs), close-all — the reference's
    SidePluginRepo.java open-from-config flow."""
    demo, env = _build_lib_and_env(tmp_path, "repo_demo.c", "repo_demo")
    out = subprocess.run(
        [demo, str(tmp_path / "repodb")], env=env, capture_output=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr.decode()
    assert b"REPO-C-API-OK" in out.stdout
