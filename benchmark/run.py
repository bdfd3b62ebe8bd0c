#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data, found by the names in `BENCHMARK.json`:

* the cell names a configuration (`configs/<config>.json`: the deployment,
  its sizes and guarantees) and a traffic mix (`traffic/<traffic>.json`:
  parameters, and the traffic `kind` that reads them);
* a traffic kind is `kinds/<kind>.py` with `generate / install / run /
  verify`;
* a per-layer metric is `layer_metrics/<name>.py` with `read(run)`; one
  that finds nothing to read returns None and is left out. A metric that
  every cell has (`compiles_in_window`) is entered once per cell, because
  an entry names the one end-to-end metric it moves, as
  `<anything>_compiles_in_window`: where no file has the whole name, the
  reader is the file named by the longest tail of it, so a new cell brings
  an entry and no file.

The run starts master + volume server A (owns the chip) + B as separate
processes (cluster.py), makes the data from `--seed`, warms the cell's
shapes, measures for `--seconds`, checks the answers, stops every daemon
and prints one JSON line. `--trace 0` reports the cell's end-to-end
metrics, `--trace 1` its per-layer metrics (A traced by the JAX profiler).

Without a TPU the run fails. `--rehearse` (tests only) runs A on the CPU
backend at the sizes of the configuration's `rehearsal` group and reports
every device metric as not measured.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

import numpy as np  # noqa: E402

from benchmark import xplane  # noqa: E402
from benchmark.cluster import Cluster, Failed, check, compile_cache_dir  # noqa: E402


def say(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def load_module(directory: str, name: str):
    """`<directory>/<name>.py`, found by the name alone."""
    path = os.path.join(directory, f"{name}.py")
    check(os.path.isfile(path), f"no {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{os.path.basename(directory)}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(directory: str, name: str):
    """The reader of the per-layer metric `name`: the file of that name,
    else of the longest tail of it after a `_` (`seal_device_idle_share`
    is read by `device_idle_share.py`)."""
    parts = name.split("_")
    for i in range(len(parts)):
        tail = "_".join(parts[i:])
        if os.path.isfile(os.path.join(directory, f"{tail}.py")):
            return load_module(directory, tail)
    raise Failed(f"no reader for {name} in {directory}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Run:
    """One run of one cell: what the traffic kind works with, and what
    the per-layer readers read afterwards."""

    def __init__(self, bench: dict, base: str, cell: dict, args):
        self.base = base
        self.cell = cell
        config = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
        with open(os.path.join(CHECKOUT, config["file"])) as f:
            self.config = json.load(f)
        with open(os.path.join(base, "traffic",
                               f"{cell['traffic']}.json")) as f:
            self.traffic = json.load(f)
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = args.rehearse
        if self.rehearsal:  # tiny sizes, for the tests
            self.config.update(self.config.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))
        self.rng = np.random.default_rng([self.seed, 2])
        self.cluster: "Cluster | None" = None
        self.root = ""
        self.stage = ""
        # filled by the run
        self.ops: "list[dict]" = []        # timed operations, in order
        self.phases: "list[list]" = []     # [t0, t1, label], time.time()
        self.samples: dict = {}            # whatever else the kind keeps
        self.window: "tuple[float, float]" = (0.0, 0.0)
        self.seq0 = 0                      # A's event seq at window start
        self.journal: "list[dict]" = []    # A's events since then
        self.metrics0 = self.metrics1 = ""  # A's /metrics text
        self.cache0: "set[str]" = set()
        self.cache1: "set[str]" = set()
        self.device: dict = {}
        self.trace_dir = ""
        self.trace_t0 = 0.0
        self.trace_window: "tuple[float, float] | None" = None
        self.trace_seconds = float(self.traffic.get("trace_seconds", 10))
        self.trace_after = float(self.traffic.get("trace_after_s", 0))
        self.traced: "dict | None" = None  # xplane.reduce_planes' result
        self._trace_lock = threading.Lock()
        self._timers: "list[threading.Timer]" = []

    # -- for the kinds -------------------------------------------------------
    @contextlib.contextmanager
    def phase(self, label: str):
        """Name what the harness does meanwhile, for the idle gaps."""
        t0 = time.time()
        try:
            yield
        finally:
            self.phases.append([t0, time.time(), label])

    def past_end(self) -> bool:
        """No timed operation starts after the window's end."""
        return time.monotonic() >= self.window[1]

    def op_done(self, op: dict) -> None:
        """Record one timed operation; at this boundary a traced run ends
        its trace once `trace_seconds` have passed, so that the trace
        holds whole operations only."""
        self.ops.append(op)
        self.phases.append([op["t0"], op["t1"], op["label"]])
        if (self.trace_window is None and self.trace_t0
                and self.traffic.get("trace_stop") != "timer"
                and time.time() - self.trace_t0 >= self.trace_seconds):
            self.stop_trace()

    def events(self, etype: str) -> "list[dict]":
        """A's journal entries of `etype` since the window began."""
        return [e["attrs"] for e in self.journal
                if e["type"].startswith(etype)]

    # -- tracing ---------------------------------------------------------------
    def _after(self, seconds: float, call) -> None:
        timer = threading.Timer(seconds, call)
        self._timers.append(timer)
        timer.start()

    def start_trace(self) -> None:
        """The trace starts `trace_after_s` into the window (0: with it)
        and ends at the first operation boundary `trace_seconds` later.
        A kind whose operations flood the trace (a scan's every step is
        an event) asks for `trace_stop: timer`: it is traced for
        `trace_seconds` wherever its operations stand."""
        self.trace_dir = os.path.join(self.root, "trace")
        if self.trace_after > 0:
            self._after(self.trace_after, self._begin_trace)
        else:
            self._begin_trace()

    def _begin_trace(self) -> None:
        with self._trace_lock:
            self.cluster.control(f"trace_start {self.trace_dir}")
            self.trace_t0 = time.time()
        if self.traffic.get("trace_stop") == "timer":
            self._after(self.trace_seconds, self.stop_trace)

    def stop_trace(self) -> None:
        for timer in self._timers:  # a start still to come comes first
            if timer is not threading.current_thread():
                timer.join()
        with self._trace_lock:
            if self.trace_window is None:
                t1 = time.time()
                self.cluster.control("trace_stop", timeout=300)
                self.trace_window = (self.trace_t0, t1)

    def traced_ops(self) -> "list[dict]":
        """The operations that ran wholly inside the traced window."""
        if self.trace_window is None:
            return []
        t0, t1 = self.trace_window
        return [op for op in self.ops if op["t0"] >= t0 and op["t1"] <= t1]


def cache_entries() -> "set[str]":
    try:
        return set(os.listdir(compile_cache_dir()))
    except FileNotFoundError:
        return set()


def reduce_trace(run: Run) -> None:
    """The traced window's reduction, by a process told to stay on the
    CPU (the parent never imports jax)."""
    r = subprocess.run(
        [sys.executable, "-m", "benchmark.xplane", run.trace_dir],
        cwd=CHECKOUT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    check(r.returncode == 0, f"trace reduction failed:\n{r.stderr[-2000:]}")
    run.traced = json.loads(r.stdout.splitlines()[-1])


def measure(run: Run, kind, bench: dict) -> dict:
    """Set up, warm, measure, verify; returns the result line's dict."""
    t_start = time.monotonic()
    cell, cl = run.cell, run.cluster
    cl.spawn()
    with run.phase("generate"):
        kind.generate(run)
    cl.wait_ready()
    run.device = cl.device(cell["chips"])
    with run.phase("install+warm"):
        kind.install(run)
    setup_s = time.monotonic() - t_start
    say(f"set-up {setup_s:.1f}s; measuring for {run.seconds:.0f}s")

    run.seq0 = cl.last_seq(cl.a_url)
    run.metrics0 = cl.metrics_text(cl.a_url)
    run.cache0 = cache_entries()
    if run.trace:
        run.start_trace()
    t0 = time.monotonic()
    run.window = (t0, t0 + run.seconds)
    result = kind.run(run)
    run.cache1 = cache_entries()
    run.metrics1 = cl.metrics_text(cl.a_url)
    run.journal = cl.events(cl.a_url, since=run.seq0)
    if run.trace:
        # a cell whose traffic leaves the device alone drives it once,
        # after the window and in traced runs only: the driver refuses a
        # trace in which no operation ran on the device
        if hasattr(kind, "probe") and run.trace_window is None:
            with run.phase("device probe"):
                kind.probe(run)
        run.stop_trace()
    with run.phase("verify"):
        correct = bool(kind.verify(run)) and result["failed"] == 0
    memory = cl.control("memory")["memory_peak_bytes"]
    cl.stop()

    device = {**run.device, "memory_peak_bytes": memory}
    # a kind may compute more than the cell reports end to end (a median
    # beside a tail): BENCHMARK.json says which are the cell's
    end_to_end = [m for m in bench["end_to_end"] if applies(m, cell["name"])]
    values = {**result["metrics"], "setup_s": setup_s}
    missing = [m["name"] for m in end_to_end if m["name"] not in values]
    check(not missing, f"the cell did not produce {missing}")
    values = {m["name"]: values[m["name"]] for m in end_to_end}
    if run.trace:
        if not run.rehearsal:
            reduce_trace(run)
            check(run.traced.get("devices", 0) > 0 and
                  run.traced["busy_s"] > 0,
                  "the trace holds no operation run on the device")
            device["busy_s"] = run.traced["busy_s"]
            device["window_s"] = run.trace_window[1] - run.trace_window[0]
        wanted = [m for m in bench["per_layer"]
                  if applies(m, cell["name"]) and m["moves"] in values]
        readers = os.path.join(run.base, "layer_metrics")
        values = {m["name"]: load_reader(readers, m["name"]).read(run)
                  for m in wanted}
    else:
        wanted = end_to_end
    units = {m["name"]: m["unit"] for m in wanted}
    if run.rehearsal:  # a CPU run gives counts, never a time or a rate
        values = {m["name"]: (values.get(m["name"])
                              if m["source"] == "program_counter"
                              else "not measured")
                  for m in wanted if values.get(m["name"]) is not None}
    line = {
        "correct": correct,
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": {k: {"value": values[k], "unit": units[k]}
                    for k in units if values.get(k) is not None},
        "device": device,
    }
    if run.trace and run.traced:
        t0w = run.trace_window[0]
        phases = [[a - t0w, b - t0w, label] for a, b, label in run.phases]
        # jaxlib times events from the start of the profiling session:
        # the stretch before the first event is idle too
        first = run.traced["t0_ns"] / 1e9
        found = [[0.0, first]] + [[first + at, seconds]
                                  for at, seconds in run.traced["gaps"]]
        line["breakdown"] = {
            "device_ops": run.traced["device_ops"],
            "idle_gaps": xplane.label_gaps(found, phases)[:xplane.TOP]}
    return line


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: tiny sizes on the CPU backend, device "
                         "metrics not measured")
    ap.add_argument("--base", default=HERE,
                    help="directory holding traffic/, kinds/ and "
                         "layer_metrics/ (tests point it at a copy)")
    ap.add_argument("--benchmark", default=os.path.join(CHECKOUT,
                                                        "BENCHMARK.json"))
    args = ap.parse_args(argv)
    # a run cut from outside still stops its daemons and removes its data
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    root = ""
    run = None
    try:
        # the program under test has to be in the checkout
        check(os.path.isdir(os.path.join(CHECKOUT, "seaweedfs_tpu")),
              f"no seaweedfs_tpu/ beside {HERE}: nothing to measure")
        with open(args.benchmark) as f:
            bench = json.load(f)
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        check(cell is not None, f"no workload {args.workload!r}")
        run = Run(bench, args.base, cell, args)
        kind = load_module(os.path.join(args.base, "kinds"),
                           run.traffic["kind"])
        # run data: a new directory under TMPDIR, removed at exit
        root = run.root = tempfile.mkdtemp(prefix="weedtpu_bench_")
        run.stage = os.path.join(root, "stage")
        os.makedirs(run.stage)
        run.cluster = Cluster(root, int(run.config["volume_limit_mb"]),
                              rehearsal=args.rehearse)
        line = measure(run, kind, bench)
    except Exception as e:  # noqa: BLE001 — any failure: no result line
        print(f"benchmark: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        if run is not None and run.cluster is not None:
            if not isinstance(e, Failed):
                import traceback
                traceback.print_exc()
            for name in run.cluster.procs:
                print(f"--- last lines of {name}.log ---\n"
                      f"{run.cluster.log_tail(name, 25)}", file=sys.stderr)
        return 1
    finally:
        if run is not None and run.cluster is not None:
            run.cluster.stop()
        if root:
            shutil.rmtree(root, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
