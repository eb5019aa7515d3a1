"""From the events of a profiler trace to numbers: device busy and idle
share, device time inside the jobs, the top device operations, and the idle
gaps by where in a job they lie.

The plain half of the reduction: it knows a window, job intervals and
device operations, and nothing of the program's stages. `span_reduce`
(which also turns an .xplane.pb into the lists read here) starts from its
summary and names every idle second by the program's span; the tests hold
the two against each other.

  device operations  {device: [[name, start_ns, dur_ns], ...]}
  host events        [[name, start_ns, dur_ns], ...]: the launcher's two
                     window marks, and the program's own `dcompact.worker`
                     span around each job (nothing is patched in).
"""

from __future__ import annotations

WINDOW_OPEN = "bench:window_open"
WINDOW_CLOSE = "bench:window_close"
JOB = "dcompact.worker"  # the program's span around worker.run_job
TOP = 10


def _merge(intervals):
    """Sorted union of [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        elif b > a:
            out.append([a, b])
    return out


def _clip(merged, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in merged
            if b > lo and a < hi]


def _total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def reduce(events: dict) -> dict:
    """The summary of one traced window; times in seconds. Shares and
    means are over the devices that ran at least one operation plus, where
    the trace names more device planes, those too (an idle chip counts)."""
    host = events["host"]
    opens = [s for n, s, d in host if n == WINDOW_OPEN]
    closes = [s + d for n, s, d in host if n == WINDOW_CLOSE]
    if not opens or not closes:
        raise ValueError("the trace lacks the window's annotations")
    w0, w1 = min(opens), max(closes)
    jobs = _merge([s, s + d] for n, s, d in host if n == JOB)
    jobs = _clip(jobs, w0, w1)
    out = {"window_s": (w1 - w0) / 1e9, "jobs_seen": len(jobs),
           "job_s": _total(jobs) / 1e9, "devices": len(events["device_ops"])}
    by_name: dict[str, float] = {}
    busy_by_dev, in_jobs_by_dev = {}, {}
    gaps = {"no_job": 0.0, "job: before first op": 0.0,
            "job: between ops": 0.0, "job: after last op": 0.0,
            "job: no op": 0.0}
    for dev, ops in sorted(events["device_ops"].items()):
        busy = _clip(_merge([s, s + d] for _n, s, d in ops), w0, w1)
        for n, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                by_name[n] = by_name.get(n, 0.0) + (b - a) / 1e9
        busy_by_dev[dev] = _total(busy) / 1e9
        in_jobs = 0.0
        for a, b in jobs:
            inside = _clip(busy, a, b)
            t = _total(inside)
            in_jobs += t
            if not inside:
                gaps["job: no op"] += (b - a) / 1e9
                continue
            first, last = inside[0][0], inside[-1][1]
            gaps["job: before first op"] += (first - a) / 1e9
            gaps["job: after last op"] += (b - last) / 1e9
            gaps["job: between ops"] += (last - first - t) / 1e9
        in_jobs_by_dev[dev] = in_jobs / 1e9
        gaps["no_job"] += ((w1 - w0) - _total(jobs)
                           - (_total(busy) - in_jobs)) / 1e9
    n_dev = max(1, len(busy_by_dev))
    out["busy_by_device_s"] = busy_by_dev
    out["busy_s"] = sum(busy_by_dev.values()) / n_dev
    out["busy_in_jobs_chip_s"] = sum(in_jobs_by_dev.values())
    out["device_ops"] = [
        [_short(n), s] for n, s in
        sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_gaps"] = [
        [n, s / n_dev] for n, s in
        sorted(gaps.items(), key=lambda kv: -kv[1]) if s > 0][:TOP]
    return out


def _short(name: str) -> str:
    """An operation's name as the trace has it is a line of HLO: keep its
    head, which names the fusion and its result shape."""
    return " ".join(name.split())[:96]
