"""dcompact worker service: HTTP job submission over shared storage.

The transport shape of the reference's distributed compaction (curl control
plane + NFS data plane; CompactionExecutorFactory::JobUrl,
compaction_executor.h:146,177 in /root/reference): a worker host runs ONE
`DcompactWorkerService` process, which owns every chip of that host —
nothing binds a process to one chip of several, and a chip belongs to one
process at a time, so a four-chip host runs one service with `--chips 4`,
never four services. The DB side's `HttpCompactionExecutor` POSTs
{"job_dir": ...} to /dcompact and waits for CompactionResults; the DB
process itself stays off JAX. Bulk data (input SSTs, output SSTs,
params/results JSON) moves through the shared filesystem, exactly like
the reference's NFS/S3 exchange.

Worker:  python -m toplingdb_tpu.compaction.dcompact_service --port 8080 \
             [--device tpu] [--workers 1] [--chips N]

`--device tpu` is the TPU or an error: the service checks what JAX reports
before it prints "listening" and exits non-zero on a mismatch; /health and
/stats carry JAX's own platform, device kind and device count.

Pod-level packing (`--chips N`): the worker host owns N chips; each chip
is a failure domain behind its own circuit breaker (PR 1's
WorkerHealthRegistry reused with "chip:<i>" keys). A job runs on the chips
its grant names and on no other: `--workers W` jobs run at once, and the
pool grants each `N // W` healthy free chips, the idle ones in rotation.
`--chips 4 --workers 4` is the fan-out of the reference's pod (one
CompactionJob a chip, four at once); `--chips 4 --workers 1` meshes one
job's key-range shards over the four (ops/mesh_compaction.py). The granted
`jax.Device`s go down the call chain as an argument (`worker.run_job(...,
devices=)`), never through the environment, so jobs side by side cannot
see each other's grant. A wedged chip drops out of later grants (fewer
chips, down to the backend's default device when every breaker is open)
instead of stalling the queue. Per-chip queue depths, hold times and job
counts ride /metrics and /stats; `/stats` also carries the time integral
of the jobs holding a grant and the wait for one, for window deltas.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

from toplingdb_tpu.utils import concurrency as ccy
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from toplingdb_tpu.compaction.executor import (
    CompactionExecutorFactory,
    SubprocessCompactionExecutor,
)
from toplingdb_tpu.utils.status import InvalidArgument, IOError_


class ChipPool:
    """Chip grants for one worker host. `admit()` waits for `per_job`
    healthy free chips (fewer when fewer are healthy: demotion) and takes
    the free ones in rotation, so jobs that arrive one at a time still
    spread over every chip; a wait that times out takes whatever subset is
    free NOW, so a dead device degrades throughput, never progress. Chip
    health is the SAME breaker machinery the DB side uses for worker URLs,
    keyed "chip:<i>", so record_failure/record_success from finished jobs
    open and re-close chips exactly like remote workers.

    Counters, cumulative in µs of the monotonic clock and brought up to
    date at every grant, release and read: `jobs_running_usec` (the time
    integral of the number of jobs holding a grant), `grant_wait_usec`
    (admit's waits), per chip `chip_held_usec` and `chip_jobs`. With
    one-chip grants the chips' hold times add up to `jobs_running_usec`."""

    def __init__(self, chips: int, per_job: int | None = None, policy=None):
        from toplingdb_tpu.compaction.resilience import (
            DcompactOptions, WorkerHealthRegistry,
        )

        self.chips = ["chip:%d" % i for i in range(max(1, chips))]
        self.per_job = (len(self.chips) if per_job is None
                        else max(1, min(per_job, len(self.chips))))
        self.health = WorkerHealthRegistry(policy or DcompactOptions())
        self._cv = ccy.Condition("dcompact_service.ChipPool._cv")
        # Chips held by a job; a chip's /metrics queue depth (a waiting
        # job targets no chip, so it is 0 or 1).
        self._busy: set[str] = set()
        self._turn = 0       # where the next tie between idle chips starts
        self._holders = 0    # jobs holding a grant
        self._t_us = _now_us()
        self.jobs_running_usec = 0
        self.grant_wait_usec = 0
        self.chip_held_usec = {c: 0 for c in self.chips}
        self.chip_jobs = {c: 0 for c in self.chips}

    def index(self, chip: str) -> int:
        return self.chips.index(chip)

    def _healthy(self) -> list[str]:
        return [c for c in self.chips if self.health.breaker(c).allow()]

    def _advance(self) -> int:
        """Bring the integrals up to now (under the pool lock)."""
        now = _now_us()
        dt = now - self._t_us
        self._t_us = now
        self.jobs_running_usec += self._holders * dt
        for c in self._busy:
            self.chip_held_usec[c] += dt
        return now

    def admit(self, want: int | None = None,
              timeout: float = 30.0) -> list[str]:
        """Block until `want` (default `per_job`) healthy chips are free;
        returns the granted chip list (smaller when fewer are healthy, or
        when the wait timed out), or [] when no healthy chip is free (the
        caller runs on the backend's default device)."""
        want = self.per_job if want is None else max(1, want)
        t0 = _now_us()
        deadline = time.monotonic() + max(0.0, timeout)
        n = len(self.chips)
        with self._cv:
            while True:
                healthy = self._healthy()
                free = sorted(
                    (c for c in healthy if c not in self._busy),
                    key=lambda c: (self.index(c) - self._turn) % n)
                if len(free) >= min(want, len(healthy)):
                    grant = free[:want]
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    grant = free[:want]  # what is free NOW, maybe nothing
                    break
                self._cv.wait(min(0.05, remaining))
            now = self._advance()
            self.grant_wait_usec += now - t0
            if grant:
                self._holders += 1
                self._turn = (self.index(grant[-1]) + 1) % n
            for c in grant:
                self._busy.add(c)
                self.chip_jobs[c] += 1
            return grant

    def release(self, grant: list[str], ok: bool = True,
                failed_chips=()) -> None:
        with self._cv:
            self._advance()
            if grant:
                self._holders -= 1
            for c in grant:
                self._busy.discard(c)
            self._cv.notify_all()
        # Health updates OUTSIDE the pool lock: the registry/breaker locks
        # rank below (after) the pool's in the §2.10.1 order, but release
        # has no reason to nest them.
        for c in grant:
            if ok and c not in failed_chips:
                self.health.record_success(c)
            else:
                self.health.record_failure(c)

    def queue_depths(self) -> dict[str, int]:
        with self._cv:
            return {c: int(c in self._busy) for c in self.chips}

    def counters(self) -> dict:
        """The cumulative counters, up to date, for /stats."""
        with self._cv:
            self._advance()
            return {"jobs_running_usec": self.jobs_running_usec,
                    "grant_wait_usec": self.grant_wait_usec,
                    "chip_held_usec": [self.chip_held_usec[c]
                                       for c in self.chips],
                    "chip_jobs": [self.chip_jobs[c] for c in self.chips]}

    def snapshot(self) -> dict:
        with self._cv:
            self._advance()
            busy = set(self._busy)
            held = dict(self.chip_held_usec)
            jobs = dict(self.chip_jobs)
        health = self.health.snapshot()
        return {
            c: {"queue_depth": int(c in busy), "busy": c in busy,
                "state": health.get(c, {}).get("state", "closed"),
                "held_usec": held[c], "jobs": jobs[c]}
            for c in self.chips
        }


def _now_us() -> int:
    return time.monotonic_ns() // 1000


JOB_SUM_COUNTERS = (
    "merge_operand_rows", "merge_groups", "merge_rows_folded",
    "merge_fold_usec", "tombstone_fragments", "tombstone_cover_usec",
    "zip_input_files", "zip_input_rows", "zip_scan_usec", "zip_output_files",
    "zip_output_bytes", "zip_output_raw_bytes", "zip_encode_usec",
    "zip_dict_train_usec", "sft_input_files", "sft_input_rows",
    "sft_scan_usec", "sft_output_files", "sft_output_rows",
    "sft_output_bytes", "sft_build_usec", "varlen_shards",
    "varlen_shard_rows", "varlen_key_bytes", "varlen_pad_bytes")


class DcompactWorkerService:
    """Hosts job execution: POST /dcompact {"job_dir": ...} → runs the job
    in-process (owning the chip), returns the results JSON. GET /stats for
    introspection; GET /traces and /traces/<trace_id> serve the last jobs'
    spans (every request is recorded, `self.tracer`'s ring bounds them)
    the way a DB's SidePluginRepo serves its traces."""

    def __init__(self, device: str = "cpu", max_workers: int = 1,
                 chips: int = 0):
        self.device = device
        # What JAX reports (platform/kind/count) once the requested device
        # has been checked against it; None for the per-entry "cpu"
        # service, which never imports JAX.
        self.jax_devices = None
        if device != "cpu":
            from toplingdb_tpu.ops import device_runtime

            device_runtime.require_device(device)
            self.jax_devices = device_runtime.describe_devices()
            if chips > self.jax_devices["count"]:
                raise InvalidArgument(
                    f"--chips {chips} but JAX sees "
                    f"{self.jax_devices['count']} device(s)")
        self._sem = threading.Semaphore(max_workers)
        self._server: ThreadingHTTPServer | None = None
        self._counter_mu = ccy.Lock("dcompact_service.DcompactWorkerService._counter_mu")
        self.jobs_done = 0
        self.jobs_failed = 0
        self.jobs_left_pipeline = 0  # ran, but not on the pipelined plane
        # Sums over the jobs done of the planes' merge and range-tombstone
        # counters (CompactionStats): operand rows met, groups and rows
        # folded, the fold's and the covers' wall.
        self.job_sums = dict.fromkeys(JOB_SUM_COUNTERS, 0)
        from toplingdb_tpu.utils import telemetry

        self.tracer = telemetry.Tracer(proc="dcompact-worker", ring=256)
        # Pod-level packing: chips > 0 builds the per-chip grant pool, each
        # job granted chips // workers of them; 0 is the one-chip host
        # (every job on the backend's default device).
        self.pool = (ChipPool(chips, per_job=chips // max(1, max_workers))
                     if chips > 0 else None)
        # chip:<i> of the pool is JAX's device i (None: a host job runs no
        # device program).
        self._chip_devices = None
        if self.pool is not None and device != "cpu":
            import jax

            from toplingdb_tpu.ops import compaction_kernels

            self._chip_devices = jax.devices()[:len(self.pool.chips)]
            # The chips share each program: the first dispatch of one
            # compiles it for every chip at once, not chip after chip.
            compaction_kernels.share_programs(self._chip_devices)

    def _run_job(self, job_dir: str) -> None:
        """Run one job, on the chips of its grant when the host has a pool:
        the span `dcompact.grant` is the wait for them, the granted
        `jax.Device`s go to `worker.run_job` as its argument, and the
        outcome feeds the chip breakers. Outputs are the same bytes on any
        chip and beside any other job."""
        from toplingdb_tpu.compaction import worker
        from toplingdb_tpu.utils import telemetry

        if self.pool is None:
            worker.run_job(job_dir)
            return
        with telemetry.span("dcompact.grant") as sp:
            grant = self.pool.admit()
            chips = [self.pool.index(c) for c in grant]
            sp.tag(chips=",".join(map(str, chips)))
        devices = None
        if self._chip_devices is not None and chips:
            devices = [self._chip_devices[i] for i in chips]
        ok = False
        try:
            worker.run_job(job_dir, devices=devices)
            ok = True
        finally:
            self.pool.release(grant, ok=ok)

    def _count(self, ok: bool, left_pipeline: bool = False,
               stats: dict | None = None) -> None:
        with self._counter_mu:
            if ok:
                self.jobs_done += 1
            else:
                self.jobs_failed += 1
            if left_pipeline:
                self.jobs_left_pipeline += 1
            for k in JOB_SUM_COUNTERS:
                self.job_sums[k] += int((stats or {}).get(k) or 0)

    def start(self, port: int = 0, host: str = "127.0.0.1") -> int:
        svc = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, code: int, body: dict):
                data = json.dumps(body).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path in ("/stats", "/stats?threads=1"):
                    body = {
                        "device": svc.device, "jax": svc.jax_devices,
                        "jobs_done": svc.jobs_done,
                        "jobs_failed": svc.jobs_failed,
                        "jobs_left_pipeline": svc.jobs_left_pipeline,
                        **svc.job_sums,
                    }
                    if svc.jax_devices is not None:
                        from toplingdb_tpu.ops import device_runtime

                        body["device_memory"] = \
                            device_runtime.device_memory()
                    if svc.pool is not None:
                        body["chips"] = svc.pool.snapshot()
                        body.update(svc.pool.counters())
                    if self.path != "/stats":
                        # Every thread of this process by CPU and run-queue
                        # wait: a /proc walk, so only when asked for.
                        from toplingdb_tpu.utils import telemetry

                        body["threads"] = telemetry.thread_times()
                    self._reply(200, body)
                elif self.path == "/traces":
                    self._reply(200, {
                        "tracer": svc.tracer.status(),
                        "traces": [t.summary()
                                   for t in svc.tracer.finished()]})
                elif self.path.startswith("/traces/"):
                    doc = svc.tracer.chrome_trace(
                        self.path[len("/traces/"):])
                    if doc is None:
                        self._reply(404, {"error": "no such trace"})
                    else:
                        self._reply(200, doc)
                elif self.path == "/health":
                    # Liveness probe for the DB-side health registry /
                    # half-open breaker checks; tools/fleet_health.py
                    # maps this bare shape onto its health-doc format.
                    self._reply(200, {"ok": True, "device": svc.device,
                                      "jax": svc.jax_devices})
                elif self.path == "/metrics":
                    # Minimal Prometheus exposition so the worker shows
                    # up on the same scrape config as the DB repos.
                    lines = []
                    for metric, v in (("dcompact_jobs_done",
                                       svc.jobs_done),
                                      ("dcompact_jobs_failed",
                                       svc.jobs_failed)):
                        m = f"tpulsm_{metric}"
                        lines.append(f"# TYPE {m} gauge")
                        lines.append(
                            f'{m}{{device="{svc.device}"}} {v}')
                    if svc.pool is not None:
                        snap = svc.pool.snapshot()
                        for metric, val in (
                            ("dcompact_chip_queue_depth",
                             lambda s: s["queue_depth"]),
                            ("dcompact_chip_busy",
                             lambda s: int(s["busy"])),
                            ("dcompact_chip_wedged",
                             lambda s: int(s["state"] != "closed")),
                            ("dcompact_chip_held_usec",
                             lambda s: s["held_usec"]),
                            ("dcompact_chip_jobs", lambda s: s["jobs"]),
                        ):
                            m = f"tpulsm_{metric}"
                            lines.append(f"# TYPE {m} gauge")
                            for chip, s in snap.items():
                                lines.append(
                                    f'{m}{{chip="{chip}"}} {val(s)}')
                    data = ("\n".join(lines) + "\n").encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                else:
                    self._reply(404, {"error": "not found"})

            def do_POST(self):
                if self.path != "/dcompact":
                    self._reply(404, {"error": "not found"})
                    return
                # The submitter's wall on this side, from the request
                # read to the reply written; what lies in it outside
                # `dcompact.worker` is this boundary's overhead. It adopts
                # the submitter's trace when the header carries one.
                hdr = self.headers.get("X-Tpulsm-Trace")
                try:
                    ctx = json.loads(hdr) if hdr else None
                except ValueError:
                    ctx = None
                if not isinstance(ctx, dict):
                    ctx = None
                with svc.tracer.start_from(ctx, "dcompact.request") \
                        as request:
                    self._run_request(request, ctx)

            def _run_request(self, request, ctx):
                n = int(self.headers.get("Content-Length", 0))
                try:
                    req = json.loads(self.rfile.read(n))
                    job_dir = req["job_dir"]
                    request.tag(job_dir=os.path.basename(job_dir))
                    with svc._sem:  # --workers jobs at a time
                        os.makedirs(job_dir, exist_ok=True)
                        # The worker owns the device: override the submitted
                        # params' device with this service's.
                        ppath = os.path.join(job_dir, "params.json")
                        with open(ppath) as pf:
                            params = json.load(pf)
                        dirty = False
                        if params.get("device") != svc.device:
                            params["device"] = svc.device
                            dirty = True
                        if ctx is not None and not params.get("trace"):
                            # Header-carried trace context (cross-host
                            # deployments where the submitter wrote params
                            # before sampling): fold into the job.
                            params["trace"] = ctx
                            dirty = True
                        if dirty:
                            with open(ppath, "w") as pf:
                                json.dump(params, pf, indent=1)
                        svc._run_job(job_dir)
                    with open(f"{job_dir}/results.json") as f:
                        results = json.load(f)
                    svc._count(ok=True, left_pipeline=bool(
                        results.get("stats", {}).get("pipeline_exit")),
                        stats=results.get("stats"))
                    self._reply(200, results)
                except Exception as e:  # job failure → structured error
                    svc._count(ok=False)
                    request.tag(error=repr(e)[:200])
                    self._reply(500, {"status": f"{type(e).__name__}: {e}",
                                      "output_files": [], "stats": {}})

        self._server = ThreadingHTTPServer((host, port), Handler)
        ccy.spawn("dcompact-http", self._server.serve_forever, owner=self,
                  stop=self.stop)
        return self._server.server_address[1]

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server = None
        if self._chip_devices is not None:
            from toplingdb_tpu.ops import compaction_kernels

            compaction_kernels.share_programs(())


class HttpCompactionExecutorFactory(CompactionExecutorFactory):
    """DB-side factory: jobs go to worker URLs round-robin through a
    per-URL circuit breaker (compaction/resilience.py): consecutive
    failures open a worker's breaker, picks skip open circuits, and a
    half-open probe re-admits a recovered worker. new_executor returns
    None when EVERY circuit is open — the retry driver then falls back to
    local without paying a remote timeout. Falls back to local on any
    transport/worker error (scheduler policy)."""

    def __init__(self, worker_urls: list[str], device: str = "cpu",
                 allow_fallback: bool = True, min_input_bytes: int = 0,
                 job_root: str | None = None, timeout: float | None = None,
                 policy=None, fault_injector=None):
        from toplingdb_tpu.compaction.resilience import (
            DcompactOptions, WorkerHealthRegistry,
        )

        self.worker_urls = list(worker_urls)
        self.device = device
        self._allow_fallback = allow_fallback
        self.min_input_bytes = min_input_bytes
        self.job_root = job_root
        self.policy = policy or DcompactOptions()
        # Legacy knob: an explicit timeout overrides the policy's
        # per-attempt transport timeout.
        self.timeout = timeout if timeout is not None \
            else self.policy.attempt_timeout
        self.health = WorkerHealthRegistry(self.policy)
        self.fault_injector = fault_injector

    def should_run_local(self, compaction) -> bool:
        return compaction.total_input_bytes() < self.min_input_bytes

    def allow_fallback_to_local(self) -> bool:
        return self._allow_fallback

    def job_url(self, job_id: int, attempt: int) -> str:
        return self.worker_urls[(job_id + attempt) % len(self.worker_urls)]

    def new_executor(self, compaction):
        url = self.health.pick(self.worker_urls)
        if url is None:
            return None  # every circuit open: caller skips to local

        def spawn(job_dir: str, device: str) -> None:
            headers = {"Content-Type": "application/json"}
            try:
                # Cross-process trace propagation rides the control plane
                # as a header (the params.json copy serves non-HTTP
                # transports); the worker service folds it back into the
                # job's params before running.
                import os as _os

                with open(_os.path.join(job_dir, "params.json")) as pf:
                    ctx = json.load(pf).get("trace")
                if ctx:
                    headers["X-Tpulsm-Trace"] = json.dumps(ctx)
            except (OSError, ValueError):
                pass
            req = urllib.request.Request(
                url + "/dcompact",
                data=json.dumps({"job_dir": job_dir}).encode(),
                headers=headers,
                method="POST",
            )
            try:
                with urllib.request.urlopen(req, timeout=self.timeout) as r:
                    if r.status != 200:
                        raise IOError_(f"worker {url} HTTP {r.status}")
                    r.read()  # results also land in job_dir/results.json
            except OSError as e:
                # A 500's body says which exception the job died of.
                said = e.read()[:400] if hasattr(e, "read") else b""
                raise IOError_(
                    f"dcompact POST to {url} failed: {e} {said!r}") from e

        ex = SubprocessCompactionExecutor(
            self.device, self.job_root, spawn=spawn, policy=self.policy,
            fault_injector=self.fault_injector,
        )
        ex.url = url
        return ex


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--host", default="0.0.0.0",
                    help="bind address (cross-host deployments need non-loopback)")
    ap.add_argument("--device", default="cpu")
    ap.add_argument("--workers", type=int, default=1,
                    help="jobs run at once; with --chips each is granted "
                         "chips // workers chips")
    ap.add_argument("--chips", type=int, default=0,
                    help="chips this host owns; >0 grants each job its own "
                         "chips (one a job at --workers N, all of them "
                         "meshed at --workers 1)")
    args = ap.parse_args(argv)
    svc = DcompactWorkerService(args.device, args.workers,
                                chips=args.chips)
    port = svc.start(args.port, args.host)
    print(f"dcompact worker listening on {args.host}:{port} "
          f"(device={svc.device}, chips={args.chips})", flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        svc.stop()
    return 0


if __name__ == "__main__":
    main()
