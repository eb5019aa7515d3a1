#!/usr/bin/env python3
"""benchmark/run.py — one cell, once, in a fresh process.

  python3 benchmark/run.py --workload <config>.<mix> --seed <n>
                           --seconds <s> --trace <0|1>

This process is the client and, in a served cell, the DB. It never imports
JAX (asserted). The chip belongs to one child: with `--trace 0` the stock
`python -m toplingdb_tpu.compaction.dcompact_service --device tpu`, with
`--trace 1` the benchmark's `lib/span_service.py`, which is that service
plus the profiler and nothing patched. Without a TPU (or with fewer chips
than the cell asks for) it says what JAX found, prints no result and exits
non-zero.
`--rehearse-cpu` drives a cell at a tiny size on XLA:CPU for the tests: its
line says platform cpu and `correct` is false whatever was compared.

Everything about one configuration, one traffic mix or one per-layer metric
sits in a file of its own that is found by the name in BENCHMARK.json:
configs/<config>.json, traffic/<mix>.json (its "kind" names the driver
traffic/kinds/<kind>.py), metrics/<name>.json (its "reader" names
metrics/readers/<reader>.py). README.md has the window rule.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from lib import service as service_mod  # noqa: E402

EXIT_NO_DEVICE = 3
EXIT_REHEARSAL = 4  # a rehearsal's line is never a pass


def log(msg: str) -> None:
    print(f"[bench {time.time() - T_PROCESS:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """What a traffic kind's driver gets: the cell's files, the service, a
    scratch directory, and the window's two edges. The driver fills
    `facts` (what the metric readers read), `compared` (each number beside
    its limit) and returns the end-to-end numbers."""

    def __init__(self, args, cell, config, traffic, workdir):
        self.args = args
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.workdir = workdir
        self.seed = args.seed
        self.seconds = args.seconds
        self.rehearsal = args.rehearse_cpu is not None
        self.scale = args.rehearse_cpu if self.rehearsal else 1.0
        self.device = "cpu-jax" if self.rehearsal else "tpu"
        self.svc = None
        self.dev = None
        self.facts: dict = {"rehearsal": self.rehearsal}
        self.compared: dict = {}   # name -> [value, limit]; value <= limit
        self.attempted = 0
        self.failed = 0
        self.setup_s = None
        self.memory_peak_bytes = 0
        self.trace_summary = None

    # -- the chip's one owner ------------------------------------------
    def start_service(self) -> None:
        env = dict(os.environ)
        if self.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
            if self.cell["chips"] > 1:
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "") +
                    f" --xla_force_host_platform_device_count="
                    f"{self.cell['chips']}").strip()
        if self.args.launcher:
            launcher = [os.path.join(HERE, "lib", self.args.launcher),
                        *self.args.launcher_arg]
        elif self.args.trace:
            launcher = [os.path.join(HERE, "lib", "span_service.py")]
        else:
            launcher = service_mod.STOCK
        self.svc = service_mod.Service(
            launcher, self.device, self.cell["chips"],
            self.config["service"]["workers"], self.workdir, env)

    def wait_service(self) -> None:
        try:
            health = self.svc.wait_listening()
        except RuntimeError as e:  # it checks the device before it listens
            raise NoDevice(str(e)) from e
        self.dev = health["jax"]
        want = "cpu" if self.rehearsal else "tpu"
        if self.dev["platform"] != want or \
                self.dev["count"] < self.cell["chips"]:
            raise NoDevice(
                f"the cell needs {self.cell['chips']} {want} chip(s); JAX "
                f"reports {self.dev}")
        log(f"service on {self.dev}")

    # -- the window's edges --------------------------------------------
    def window_open(self) -> float:
        os.sync()  # set-up's dirty pages are written back in set-up
        if self.args.trace:
            self.svc.command(
                "trace-start " + os.path.join(self.workdir, "trace"))
        now = time.time()
        self.setup_s = now - T_PROCESS
        log(f"window opens; set-up took {self.setup_s:.2f}s")
        return now

    def window_close(self) -> None:
        """Call at the closing work boundary, before anything is freed."""
        if self.args.trace:
            path = os.path.join(self.workdir, "trace_summary.json")
            cmd = "trace-stop " + path
            if self.args.keep_events:
                cmd += " " + os.path.abspath(self.args.keep_events)
            self.svc.command(cmd, timeout=240.0)
            self.trace_summary = load_json(path)
            self.facts["trace"] = self.trace_summary
        mem = self.svc.get("/stats").get("device_memory") or []
        self.memory_peak_bytes = max(
            (m["peak_bytes_in_use"] for m in mem), default=0)
        log("window closed")

    def compare(self, name: str, value, limit=0) -> None:
        self.compared[name] = [value, limit]


class NoDevice(Exception):
    pass


def load_bench(held: str = "") -> dict:
    """BENCHMARK.json, and with `held` the entries of held/<held>.json too:
    a cell that was taken out and waits there, for the tests and the
    builder's chip runs. The driver's command never names one."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    if held:
        entries = load_json(os.path.join(HERE, "held", held + ".json"))
        for section, more in entries["entries"].items():
            bench[section] = bench[section] + more
    return bench


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in bench['workloads']]}")


def metrics_of(bench: dict, cell: dict, section: str, reported_e2e=None):
    """The metrics of one section that this cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported_e2e:
            out.append(m)
    return out


def read_per_layer(bench, cell, facts, e2e_names) -> dict:
    out = {}
    for m in metrics_of(bench, cell, "per_layer", e2e_names):
        spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"))
        reader = load_module(os.path.join(
            HERE, "metrics", "readers", spec["reader"] + ".py"))
        value = reader.read(facts, **spec.get("args", {}))
        if value is not None:  # nothing to read: the metric is left out
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse-cpu", type=float, nargs="?", const=0.02,
                    default=None, metavar="SCALE",
                    help="tests only: XLA:CPU at SCALE of the cell's size; "
                         "never correct")
    ap.add_argument("--launcher", default="", help=argparse.SUPPRESS)
    ap.add_argument("--launcher-arg", action="append", default=[],
                    help=argparse.SUPPRESS)
    ap.add_argument("--keep-events", default="", help=argparse.SUPPRESS)
    ap.add_argument("--held", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    bench = load_bench(args.held)
    cell = find_cell(bench, args.workload)
    config = load_json(os.path.join(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"])))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    kind = load_module(os.path.join(HERE, "traffic", "kinds",
                                    traffic["kind"] + ".py"))

    workdir = tempfile.mkdtemp(prefix="bench_")  # under TMPDIR
    run = Run(args, cell, config, traffic, workdir)
    e2e = None
    try:
        try:
            run.start_service()
            e2e = kind.drive(run)
        except Exception:
            if run.svc is not None and not isinstance(
                    sys.exc_info()[1], NoDevice):
                log("the run failed; the service's last words: "
                    + run.svc.last_words())
            raise
        finally:
            if run.svc is not None:
                run.svc.stop()
                for line in run.svc.said(b"[traced]"):
                    log(line)
            shutil.rmtree(workdir, ignore_errors=True)
    except NoDevice as e:
        print(f"no result: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    run.compare("harness_imported_jax", int("jax" in sys.modules))

    e2e["setup_s"] = run.setup_s
    within = all(v is not None and v <= lim
                 for v, lim in run.compared.values())
    correct = bool(within and run.failed == 0 and not run.rehearsal
                   and run.compared)
    e2e_specs = metrics_of(bench, cell, "end_to_end")
    if args.trace:
        metrics = read_per_layer(bench, cell, run.facts,
                                 {m["name"] for m in e2e_specs})
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in e2e_specs}
    device = {"platform": run.dev["platform"], "kind": run.dev["kind"],
              "count": run.dev["count"],
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        line["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
        log(f"device busy by chip: {run.trace_summary['busy_by_device_s']}")
    for note in run.facts.get("notes", []):
        log(note)
    log(f"harness peak RSS (the pre-encoded stream, the DB, the checks): "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    line["compared"] = run.compared  # each number beside its limit: last
    for name, (value, limit) in run.compared.items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return EXIT_REHEARSAL if run.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
