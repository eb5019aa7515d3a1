"""`--trace 1` launcher: the dcompact service exactly as its own `main()`
builds it, plus the profiler.

Adds only: `jax.profiler` start/stop around the window, one
`jax.profiler.TraceAnnotation` around each `worker.run_job` (so every idle
gap of the device can be named by what the host was doing), the reduction
of the trace, which only the process that holds the chip can take, and a
line on stderr with the reason when a job leaves the pipelined data plane
(the program drops the reason; lines that start with "[traced]" reach the
harness's own stderr). It obeys one-line commands on stdin and answers each with one JSON
line on stdout:

  trace-start <dir>          start the profiler, mark the window's opening
  trace-stop <summary.json> [events.json]
                             mark its close, stop, reduce, write the summary
                             (and, for tests/data, the events it was made of)
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import threading
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import trace_reduce  # noqa: E402


def build_service(argv):
    """The service as `dcompact_service.main()` builds it; returns it
    started, with the "listening" line printed."""
    from toplingdb_tpu.compaction.dcompact_service import DcompactWorkerService

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--device", default="cpu")
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--chips", type=int, default=0)
    args, rest = ap.parse_known_args(argv)
    svc = DcompactWorkerService(args.device, args.workers, chips=args.chips)
    port = svc.start(args.port, args.host)
    print(f"dcompact worker listening on {args.host}:{port} "
          f"(device={svc.device}, chips={args.chips})", flush=True)
    return svc, rest


def annotate_jobs() -> None:
    import jax

    from toplingdb_tpu.compaction import worker

    run_job = worker.run_job

    def traced_run_job(job_dir):
        with jax.profiler.TraceAnnotation(trace_reduce.JOB):
            try:
                return run_job(job_dir)
            except Exception:
                # The service answers 500 and keeps no record of why.
                traceback.print_exc()
                raise

    worker.run_job = traced_run_job  # the service looks it up per job

    from toplingdb_tpu.ops import pipeline
    from toplingdb_tpu.utils.status import NotSupported

    run_pipelined = pipeline.run_pipelined

    def telling_run_pipelined(*args, **kw):
        try:
            return run_pipelined(*args, **kw)
        except (pipeline.PipelineIneligible, NotSupported) as e:
            # Caught without a word by ops/device_compaction.py, which then
            # takes the serial path.
            print(f"[traced] a job left the pipeline: {type(e).__name__}: "
                  f"{e}", file=sys.stderr, flush=True)
            raise

    pipeline.run_pipelined = telling_run_pipelined  # looked up per job


def serve_commands(handlers: dict) -> None:
    for line in sys.stdin:
        words = line.split()
        if not words:
            continue
        try:
            reply = handlers[words[0]](*words[1:]) or {}
            reply["ok"] = True
        except Exception as e:  # the harness raises on ok: false
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        print(json.dumps(reply), flush=True)
    threading.Event().wait()  # stdin closed: serve until terminated


def main(argv=None) -> int:
    build_service(sys.argv[1:] if argv is None else argv)
    import jax

    annotate_jobs()
    state = {}

    def trace_start(trace_dir):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # annotations only: a small trace
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        state["dir"] = trace_dir
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_OPEN):
            pass

    def trace_stop(summary_path, events_path=""):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_CLOSE):
            pass
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(
            state["dir"], "plugins", "profile", "*", "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file, found {files}")
        events = trace_reduce.xplane_events(files[0])
        if events_path:
            with open(events_path, "w") as f:
                json.dump(events, f)
        with open(summary_path, "w") as f:
            json.dump(trace_reduce.reduce(events), f)

    serve_commands({"trace-start": trace_start, "trace-stop": trace_stop})
    return 0


if __name__ == "__main__":
    sys.exit(main())
