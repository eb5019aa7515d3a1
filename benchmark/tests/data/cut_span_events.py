"""Cut a kept events file of a `--trace 1` run (run.py --keep-events) to its
first whole jobs (from the window's opening to 50 ms past the last of
them), and store it with what `span_reduce.reduce` and, of the same
events, the plain `trace_reduce.reduce` make of it: tests/data/span_events.json
(its numbers: span_events.md)."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from lib import span_reduce as sr  # noqa: E402
from lib import trace_reduce as tr  # noqa: E402

JOBS = 2
EXPECTED = ("window_s", "busy_s", "busy_in_jobs_chip_s", "jobs_seen", "job_s",
            "devices", "in_job_idle_s", "unattributed_s", "h2d_s",
            "d2h_wait_s", "h2d_bytes", "d2h_bytes")
PLAIN = ("window_s", "busy_s", "busy_in_jobs_chip_s", "jobs_seen", "job_s",
         "devices", "idle_gaps")         # of the plain half, trace_reduce


def main(path: str) -> None:
    with open(path) as f:
        ev = json.load(f)
    host = sorted(ev["host"], key=lambda e: e[1])
    start = min(e[1] for e in host if e[0] == tr.WINDOW_OPEN)
    jobs = [e for e in host if e[0] == sr.WORKER and e[1] >= start][:JOBS]
    end = jobs[-1][1] + jobs[-1][2] + 50_000_000      # 50 ms past the last
    cut = {"host": [[tr.WINDOW_OPEN, start, 10, 0, {}]]
           + [e for e in host if e[0].startswith(sr.SPAN_PREFIXES)
              and e[1] >= start and e[1] + e[2] <= end]
           + [[tr.WINDOW_CLOSE, end - 10, 10, 0, {}]],
           "device_ops": {
               dev: [[tr._short(n), s, d, scope] for n, s, d, scope in ops
                     if s >= start and s + d <= end]
               for dev, ops in ev["device_ops"].items()},
           "op_stats": ev.get("op_stats", {})}
    summary = sr.reduce(cut)
    expected = {k: summary[k] for k in EXPECTED}
    plain = tr.reduce(sr.as_trace_reduce_events(cut))
    out = os.path.join(HERE, "span_events.json")
    with open(out, "w") as f:
        json.dump({"events": cut, "expected": expected,
                   "expected_plain": {k: plain[k] for k in PLAIN}}, f)
    print(json.dumps({k: v for k, v in summary.items()
                      if k not in ("jobs", "device_ops_by_hlo")}, indent=1))
    print(os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
