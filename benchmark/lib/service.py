"""The one child that owns the chip: a dcompact service, and how the
harness talks to it. Copied from chip_smoke.py's `Service` (PERF.md lists
the original). The harness itself never imports JAX."""

from __future__ import annotations

import json
import os
import select
import subprocess
import sys
import time
import urllib.error
import urllib.request

LIB = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(LIB))  # the checkout

STOCK = ["-m", "toplingdb_tpu.compaction.dcompact_service"]


class Service:
    """`launcher` is the stock entry point (argv after the interpreter) or
    one of the benchmark's own launchers, which take the same options and
    also obey lines on stdin (see span_service.py). `workers` is the
    configuration's `service.workers`: jobs the service runs at once."""

    def __init__(self, launcher: list[str], device: str, chips: int,
                 workers: int, workdir: str, env: dict):
        self.log = os.path.join(workdir, "service.log")
        cmd = [sys.executable, *launcher, "--device", device,
               "--port", "0", "--host", "127.0.0.1",
               "--workers", str(workers)]
        if chips > 1:
            cmd += ["--chips", str(chips)]
        self._logf = open(self.log, "wb")
        env = dict(env, PYTHONPATH=os.pathsep.join(
            [ROOT] + [p for p in [env.get("PYTHONPATH")] if p]))
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=self._logf)
        self.url = ""

    def _readline(self, timeout: float) -> bytes:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if select.select([self.proc.stdout], [], [], 0.25)[0]:
                line = self.proc.stdout.readline()
                if line:
                    return line
            if self.proc.poll() is not None:
                break
        raise RuntimeError(
            f"the service said nothing (exit code {self.proc.poll()}): "
            f"{self.last_words()}")

    def wait_listening(self, timeout: float = 900.0) -> dict:
        """Blocks until the child prints "listening" (it has then checked
        the device against JAX); returns /health."""
        deadline = time.time() + timeout
        while time.time() < deadline:
            line = self._readline(deadline - time.time())
            if b"listening on" in line:
                port = int(line.split(b"listening on ")[1]
                           .split()[0].rsplit(b":", 1)[1])
                self.url = f"http://127.0.0.1:{port}"
                return self.get("/health")
        raise RuntimeError("the service did not come up: " + self.last_words())

    def command(self, line: str, timeout: float = 300.0) -> dict:
        """One line to a benchmark launcher's stdin; its one-line reply."""
        self.proc.stdin.write(line.encode() + b"\n")
        self.proc.stdin.flush()
        reply = json.loads(self._readline(timeout))
        if not reply.get("ok"):
            raise RuntimeError(f"launcher refused {line!r}: {reply}")
        return reply

    def last_words(self) -> str:
        self._logf.flush()
        with open(self.log, "rb") as f:
            return f.read()[-1500:].decode("utf-8", "replace").strip()

    def said(self, prefix: bytes) -> list[str]:
        """The lines of the child's stderr that start with `prefix`."""
        with open(self.log, "rb") as f:
            return [line.decode("utf-8", "replace").rstrip()
                    for line in f if line.startswith(prefix)]

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(self.url + path, timeout=30) as r:
            return json.loads(r.read())

    def post_job(self, job_dir: str, timeout: float = 900.0) -> dict:
        req = urllib.request.Request(
            self.url + "/dcompact",
            data=json.dumps({"job_dir": job_dir}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            raise RuntimeError(
                f"job failed on the service: {e.read()[:800]!r}; "
                f"service log: {self.last_words()}") from e

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for f in (self.proc.stdin, self.proc.stdout, self._logf):
            try:
                f.close()
            except OSError:
                pass
