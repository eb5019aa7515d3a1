"""The `--trace 1` launcher: the dcompact service exactly as its own `main()`
builds it, plus the profiler — and nothing patched.

The program records its own spans (`worker.run_job` opens `dcompact.worker`
for every job, the stages below it are real spans) and mirrors them into the
profiler's trace, so this launcher adds only the profiler session, the
window's two `bench:` marks and the reduction (`span_reduce`), which only the
process that holds the chip can take. Why a job left the pipelined data
plane is in the job's own `pipeline_exit` and the service's
`jobs_left_pipeline`. It obeys one-line commands on stdin and answers each
with one JSON line on stdout:

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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import span_reduce  # noqa: E402
from lib.trace_reduce import WINDOW_CLOSE, WINDOW_OPEN  # noqa: E402

PRINTED = ("window_s", "job_s", "jobs_seen", "busy_s", "in_job_idle_s",
           "unattributed_s", "gap_totals_s", "idle_by_place", "device_ops",
           "span_self_s", "slow_jobs", "h2d_s", "d2h_wait_s", "h2d_bytes",
           "d2h_bytes")


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

    state = {}

    def trace_start(trace_dir):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # annotations only: a small trace
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        state["dir"] = trace_dir
        with jax.profiler.TraceAnnotation(WINDOW_OPEN):
            pass

    def trace_stop(summary_path, events_path=""):
        with jax.profiler.TraceAnnotation(WINDOW_CLOSE):
            pass
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(
            state["dir"], "plugins", "profile", "*", "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one xplane file, found {files}")
        events = span_reduce.xplane_events(files[0])
        if events_path:
            with open(events_path, "w") as f:
                json.dump(events, f)
        summary = span_reduce.reduce(events)
        with open(summary_path, "w") as f:
            json.dump(summary, f)
        # run.py prints the ten largest idle gaps and device operations;
        # the rest of what the builder reads reaches the harness's stderr
        # by the "[traced]" prefix.
        print("[traced] span_summary " + json.dumps(
            {k: summary[k] for k in PRINTED}), file=sys.stderr, flush=True)

    serve_commands({"trace-start": trace_start, "trace-stop": trace_stop})
    return 0


if __name__ == "__main__":
    sys.exit(main())
