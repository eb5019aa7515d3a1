"""Cut a kept events file (run.py --keep-events) to its first jobs and store
it with what `reduce` makes of it: tests/data/trace_events.json."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from lib import trace_reduce as tr  # noqa: E402

JOBS = 2


def main(path: str) -> None:
    with open(path) as f:
        ev = json.load(f)
    host = sorted(ev["host"], key=lambda e: e[1])
    jobs = [e for e in host if e[0] == tr.JOB][:JOBS]
    end = jobs[-1][1] + jobs[-1][2] + 50_000_000      # 50 ms past the last
    start = min(e[1] for e in host if e[0] == tr.WINDOW_OPEN)
    cut = {"host": [[tr.WINDOW_OPEN, start, 10]] + jobs
           + [[tr.WINDOW_CLOSE, end - 10, 10]],
           "device_ops": {
               dev: [[tr._short(n), s, d] for n, s, d in ops if s + d <= end]
               for dev, ops in ev["device_ops"].items()}}
    summary = tr.reduce(cut)
    expected = {k: summary[k] for k in (
        "window_s", "busy_s", "busy_in_jobs_chip_s", "jobs_seen", "job_s",
        "devices")}
    with open(os.path.join(HERE, "trace_events.json"), "w") as f:
        json.dump({"events": cut, "expected": expected}, f)
    print(expected, os.path.getsize(os.path.join(HERE, "trace_events.json")))


if __name__ == "__main__":
    main(sys.argv[1])
