"""From a profiler trace that holds the program's own spans to numbers: what
`trace_reduce.reduce` gives, under the same keys, plus every idle second of
the device under the name of the program span that was open, and every
device operation under the name of its scope in the program.

The program's spans reach the trace by `utils/telemetry.py`'s mirror
(`ops/device_runtime.py` installs `jax.profiler.TraceAnnotation`), so they
lie in the host plane on the device's clock, one line a thread. Nothing here
patches the program: the job intervals are its `dcompact.worker` spans.

Two stages, so that the arithmetic can be tested on a recorded trace without
JAX: `xplane_events` (needs jax.profiler.ProfileData; run by the process
that holds the chip) turns an .xplane.pb into plain lists, and `reduce`
turns those into the summary the metric readers read. In a CPU rehearsal
the XLA:CPU client's threads stand in as device 0 (their numbers are never
reported as a device's).

  device operations  [name, start_ns, dur_ns, scope]: `scope` is the step of
                     the program the operation belongs to, read from the
                     event's stats (the scope path `jax.named_scope` left in
                     the HLO's op_name), a kernel's own name (`gc_rows`), or
                     the head of the HLO line where the event has neither.
  host events        [name, start_ns, dur_ns, line, tags]: the program's
                     spans (names under `dcompact.`, `compaction.`,
                     `pipeline.`, `sst.`, `runtime.`, `zip.`) and the
                     launcher's two `bench:` window marks; `line` numbers
                     the thread.
  op_stats           {HLO name: the stats of its first event}: kept with the
                     events for reading one trace by hand; `reduce` does not
                     read it.

How an idle interval of the device gets its name (`idle_gaps`): the
innermost span open on the compute thread (the thread that feeds the
device: its spans are the `pipeline.*` of COMPUTE below), else the innermost
stage span open on the job's own thread; inside a job with neither it is
`unattributed`; outside a job it is `dcompact.request` while a request is
open, else `no_request`. Intervals are split where a span starts or ends.
"""

from __future__ import annotations

import bisect
import statistics

from lib import trace_reduce
from lib.trace_reduce import (JOB, TOP, WINDOW_CLOSE, WINDOW_OPEN, _clip,
                              _merge, _short, _total)

REQUEST = "dcompact.request"
WORKER = JOB
SPAN_PREFIXES = ("dcompact.", "compaction.", "pipeline.", "sst.", "runtime.",
                 "zip.")
# Spans of the thread that feeds the device (ops/pipeline.py's compute
# thread; in the serial program the job's own thread does the same work
# under the same names).
COMPUTE = ("pipeline.wait_scan", "pipeline.chunk_prepare", "pipeline.upload",
           "pipeline.dispatch", "pipeline.merge_gc", "pipeline.unpack",
           "pipeline.wait_writer")
KERNELS = ("gc_rows",)  # Pallas kernels keep their own names
UNATTRIBUTED = "unattributed"
NO_REQUEST = "no_request"
HEAD, BETWEEN, TAIL, NO_OP = ("job: before first op", "job: between ops",
                              "job: after last op", "job: no op")
SLOW_FACTOR = 1.5


def scope_of(name: str, stats: dict) -> str:
    """The step of the program a device operation belongs to: a kernel's
    name, else the first component of the op_name path that is not a
    `jit(...)` frame, else the head of the HLO line."""
    paths = [v for v in stats.values() if isinstance(v, str) and "/" in v
             and "jit(" in v]
    for text in [name] + paths:
        for kernel in KERNELS:
            if kernel in text:
                return kernel
    for path in paths:
        for part in path.split("/"):
            if part and not part.startswith(("jit(", "pjit", "jvp(",
                                             "transpose(", "vmap(")):
                return part
    return _short(name)


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            else:
                size = {1: 8, 5: 4}[kind]
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def metadata_stats(path: str) -> dict:
    """{plane name: {event name: {stat name: value}}} of the device planes:
    the stats an XLA operation's *metadata* carries (its op_name path among
    them). `ProfileData` shows an event's own stats only, so this reads the
    .xplane.pb's wire format for just that: XSpace.planes = 1; XPlane.name =
    2, .event_metadata = 4, .stat_metadata = 5 (maps: key = 1, value = 2);
    XEventMetadata.name = 2, .display_name = 4, .stats = 5; XStatMetadata
    .id = 1, .name = 2; XStat.metadata_id = 1, double = 2, uint64 = 3,
    int64 = 4, str = 5, bytes = 6, ref = 7 (tsl's xplane.proto). Lines and
    their events are skipped, not parsed."""
    import struct

    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode("utf-8", "replace")
            elif pf == 4:
                events.append(dict(_fields(value)).get(2, b""))
            elif pf == 5:
                meta = dict(_fields(dict(_fields(value)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(
                    meta.get(2, b"")).decode("utf-8", "replace")
        if not name.startswith("/device:"):
            continue
        table = out[name] = {}
        for event in events:
            names, stats = [], {}
            for ef, value in _fields(event):
                if ef in (2, 4):
                    names.append(bytes(value).decode("utf-8", "replace"))
                elif ef == 5:
                    stat = dict(_fields(value))
                    key = stat_names.get(stat.get(1, 0), str(stat.get(1)))
                    if 5 in stat:
                        stats[key] = bytes(stat[5]).decode("utf-8", "replace")
                    elif 7 in stat:
                        stats[key] = stat_names.get(stat[7], "")
                    elif 2 in stat:
                        stats[key] = struct.unpack("<d", stat[2])[0]
                    elif 3 in stat or 4 in stat:
                        stats[key] = stat.get(3, stat.get(4))
            for n in names:
                table[n] = stats
    return out


def xplane_events(path: str) -> dict:
    from jax.profiler import ProfileData

    meta = metadata_stats(path)

    data = ProfileData.from_file(path)
    device_ops: dict[str, list] = {}
    host, cpu_standin = [], []
    op_stats: dict[str, dict] = {}  # what one event of each HLO name carries
    n_line = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = device_ops.setdefault(plane.name, [])
                    of_name = meta.get(plane.name, {})
                    for e in line.events:
                        stats = {**of_name.get(e.name, {}), **dict(e.stats)}
                        op_stats.setdefault(_short(e.name), {
                            k: v for k, v in stats.items()
                            if isinstance(v, (str, int, float))})
                        ops.append([e.name, e.start_ns, e.duration_ns,
                                    scope_of(e.name, stats)])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                n_line += 1
                standin = line.name.startswith(("tf_XLAPjRtCpuClient",
                                                "tf_XLAEigen"))
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES + ("bench:",)):
                        host.append([e.name, e.start_ns, e.duration_ns,
                                     n_line, dict(e.stats)])
                    elif standin and e.duration_ns > 0 \
                            and not e.name.startswith("end: "):
                        cpu_standin.append(
                            [e.name, e.start_ns, e.duration_ns,
                             scope_of(e.name, dict(e.stats))])
    if not device_ops and cpu_standin:
        device_ops["/host:CPU (XLA:CPU threads, a rehearsal)"] = cpu_standin
    return {"device_ops": device_ops, "host": host, "op_stats": op_stats}


def as_trace_reduce_events(events: dict) -> dict:
    """The same events as `trace_reduce.reduce` takes them: names and
    times only, of the window's marks and the jobs."""
    return {
        "device_ops": {dev: [op[:3] for op in ops]
                       for dev, ops in events["device_ops"].items()},
        "host": [e[:3] for e in events["host"]
                 if e[0] in (WORKER, WINDOW_OPEN, WINDOW_CLOSE)]}


def flatten(spans):
    """One thread's spans (properly nested) as disjoint segments
    [start, end, innermost name, outermost name], in time order."""
    out = []
    stack = []  # [end, name, outermost]

    def emit(a, b):
        if b > a and stack:
            out.append([a, b, stack[-1][1], stack[-1][2]])

    cursor = 0
    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            emit(cursor, stack[-1][0])
            cursor = max(cursor, stack.pop()[0])
        emit(cursor, start)
        cursor = max(cursor, start)
        if stack:  # a child never outlasts its parent
            end = min(end, stack[-1][0])
        stack.append([end, name, stack[0][2] if stack else name])
    while stack:
        emit(cursor, stack[-1][0])
        cursor = max(cursor, stack.pop()[0])
    return out


class Timeline:
    """Disjoint named segments in time order, cut out of intervals."""

    def __init__(self, segments):
        self.segs = sorted(segments)
        self.starts = [s[0] for s in self.segs]

    def cut(self, a, b):
        """([start, end, name] covered pieces of [a, b), the uncovered
        rest as intervals)."""
        covered, rest = [], []
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        cursor = a
        while i < len(self.segs) and self.segs[i][0] < b:
            s, e, name = self.segs[i][:3]
            lo, hi = max(s, cursor), min(e, b)
            if hi > lo:
                if lo > cursor:
                    rest.append([cursor, lo])
                covered.append([lo, hi, name])
                cursor = hi
            i += 1
        if b > cursor:
            rest.append([cursor, b])
        return covered, rest


def _complement(merged, lo, hi):
    out, cursor = [], lo
    for a, b in merged:
        if a > cursor:
            out.append([cursor, a])
        cursor = max(cursor, b)
    if hi > cursor:
        out.append([cursor, hi])
    return out


def _add(table, key, ns):
    table[key] = table.get(key, 0.0) + ns / 1e9


def _top(table, scale=1.0, n=TOP):
    return [[k, v * scale] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1]) if v > 0][:n]


def reduce(events: dict) -> dict:
    """The summary of one traced window; times in seconds."""
    out = trace_reduce.reduce(as_trace_reduce_events(events))
    host = events["host"]
    w0 = min(s for n, s, d, *_ in host if n == WINDOW_OPEN)
    w1 = max(s + d for n, s, d, *_ in host if n == WINDOW_CLOSE)
    spans = [e for e in host if e[0].startswith(SPAN_PREFIXES)]
    workers = sorted((e for e in spans if e[0] == WORKER), key=lambda e: e[1])
    jobs = _clip(_merge([e[1], e[1] + e[2]] for e in workers), w0, w1)
    requests = _clip(_merge([e[1], e[1] + e[2]] for e in spans
                            if e[0] == REQUEST), w0, w1)

    # -- each thread's innermost span, by the role of the thread ---------
    by_line: dict = {}
    for e in spans:
        by_line.setdefault(e[3], []).append(e[:3])
    segments = [seg for line in by_line.values() for seg in flatten(line)]
    feeder = Timeline(s for s in segments if s[3] in COMPUTE)
    stages = Timeline(s for s in segments if s[3] in (REQUEST, WORKER)
                      and s[2] not in (REQUEST, WORKER))

    # -- the device's idle time, by span and by where in the job ---------
    n_dev = max(1, len(events["device_ops"]))
    by_span: dict = {}
    by_place = {HEAD: {}, BETWEEN: {}, TAIL: {}, NO_OP: {}}
    place_total = {HEAD: 0.0, BETWEEN: 0.0, TAIL: 0.0, NO_OP: 0.0,
                   "no_job": 0.0}
    by_scope: dict = {}

    def name_idle(a, b, place):
        covered, rest = feeder.cut(a, b)
        for lo, hi in rest:
            more, still = stages.cut(lo, hi)
            covered += more
            covered += [[x, y, UNATTRIBUTED] for x, y in still]
        for lo, hi, name in covered:
            _add(by_span, name, hi - lo)
            _add(by_place[place], name, hi - lo)
        _add(place_total, place, b - a)

    for _dev, ops in sorted(events["device_ops"].items()):
        busy = _clip(_merge([s, s + d] for _n, s, d, *_ in ops), w0, w1)
        for _n, s, d, scope in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                _add(by_scope, scope, b - a)
        for ja, jb in jobs:
            inside = _clip(busy, ja, jb)
            if not inside:
                name_idle(ja, jb, NO_OP)
                continue
            first, last = inside[0][0], inside[-1][1]
            for a, b in _complement(inside, ja, jb):
                name_idle(a, b, HEAD if b <= first else
                          TAIL if a >= last else BETWEEN)
        busy_or_job = _merge([list(i) for i in busy] + [list(j) for j in jobs])
        for a, b in _complement(busy_or_job, w0, w1):
            _add(place_total, "no_job", b - a)
            for lo, hi in _clip(requests, a, b):
                _add(by_span, REQUEST, hi - lo)
            _add(by_span, NO_REQUEST,
                 (b - a) - _total(_clip(requests, a, b)))

    in_job_idle = sum(place_total[p] for p in (HEAD, BETWEEN, TAIL, NO_OP))
    out["idle_gaps"] = _top(by_span, 1.0 / n_dev)
    out["idle_by_place"] = {p: _top(t, 1.0 / n_dev, n=64)
                            for p, t in by_place.items() if t}
    out["gap_totals_s"] = {p: v / n_dev for p, v in place_total.items()}
    out["in_job_idle_s"] = in_job_idle / n_dev
    out["unattributed_s"] = by_span.get(UNATTRIBUTED, 0.0) / n_dev
    out["device_ops_by_hlo"] = out["device_ops"]
    out["device_ops"] = _top(by_scope)
    out["device_s_by_scope"] = dict(_top(by_scope, n=64))

    # -- the host's own time: self time by span, over the window, a job --
    self_s: dict = {}
    for a, b, name, _outer in segments:
        lo, hi = max(a, w0), min(b, w1)
        if hi > lo:
            _add(self_s, name, hi - lo)
    out["span_self_s"] = dict(_top(self_s, n=64))
    job_rows = []
    for e in workers:
        a, b = max(e[1], w0), min(e[1] + e[2], w1)
        if b <= a:
            continue
        own: dict = {}
        for sa, sb, name, _outer in segments:
            lo, hi = max(sa, a), min(sb, b)
            if hi > lo:
                _add(own, name, hi - lo)
        job_rows.append({
            "start_s": (a - w0) / 1e9, "wall_s": (b - a) / 1e9,
            "rows": int(e[4].get("input_records", 0)),
            "pipelined": bool(e[4].get("pipelined", True)),
            "whole": e[1] >= w0 and e[1] + e[2] <= w1, "self_s": own})
    out["jobs"] = [{k: v for k, v in j.items() if k != "self_s"}
                   for j in job_rows]
    out["slow_jobs"] = slow_jobs(job_rows)

    # -- transfers, as the host sees them ---------------------------------
    def in_window(name):
        return [e for e in spans if e[0] == name
                and e[1] >= w0 and e[1] + e[2] <= w1]

    ups, waits = in_window("pipeline.upload"), in_window("pipeline.merge_gc")
    out["h2d_s"] = sum(e[2] for e in ups) / 1e9
    out["d2h_wait_s"] = sum(e[2] for e in waits) / 1e9
    out["h2d_bytes"] = sum(int(e[4].get("h2d_bytes", 0)) for e in ups)
    out["d2h_bytes"] = sum(int(e[4].get("d2h_bytes", 0)) for e in waits)
    return out


def slow_jobs(job_rows):
    """Of the jobs that lie whole in the window: those over SLOW_FACTOR
    times the median wall of their input-row count, each with the spans
    whose self time grew over the median of the other jobs of that count
    (`runtime.gc_pause` is one of them when the collector did it)."""
    groups: dict = {}
    for j in job_rows:
        if j["whole"]:
            groups.setdefault(j["rows"], []).append(j)
    out = []
    for rows, group in sorted(groups.items()):
        if len(group) < 2:
            continue
        median = statistics.median(j["wall_s"] for j in group)
        for j in group:
            if j["wall_s"] <= SLOW_FACTOR * median:
                continue
            others = [o for o in group if o is not j]
            grew = {}
            for name, s in j["self_s"].items():
                usual = statistics.median(
                    o["self_s"].get(name, 0.0) for o in others)
                if s - usual > 0.01:
                    grew[name] = s - usual
            out.append({"start_s": j["start_s"], "rows": rows,
                        "wall_s": j["wall_s"], "median_wall_s": median,
                        "grew": _top(grew)})
    return out
