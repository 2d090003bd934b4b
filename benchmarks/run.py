"""One run of one cell of BENCHMARK.json.

    python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: loads the cell's configuration, traffic mix and
driver by name from files of their own (see README.md), lets the driver
set up and warm only this cell's shapes, measures for `--seconds`, checks
what the timed path produced against the plain reference, and prints one
JSON object as the last line of standard output.  With `--trace 0` the
metrics are the cell's end-to-end metrics; with `--trace 1` the last
`trace_seconds` of the run go under the profiler and the metrics are the
cell's per-layer metrics, each from a reader of its own under metrics/.

Exits non-zero and prints no result where jax finds no TPU or fewer
chips than the cell asks for.  `--rehearse` runs the cell's tiny
`rehearse` sizes on whatever platform there is (the CPU, in the sandbox)
to find wrong paths before chip time is spent; its line names the true
platform and is never a measurement.
"""

import argparse
import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

_T_IMPORT = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _process_age_s():
    """Seconds since this process started, by the kernel's record of it;
    since this module was imported where /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmarks/<kind>/<name>.py, loaded by path: names carry dots and
    dashes that an import statement could not."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric):
    """The reader of a per-layer metric: metrics/<metric>.py, or the
    family's file metrics/<name before the first dot>.py."""
    for name in (metric, metric.split(".", 1)[0]):
        if os.path.exists(os.path.join(HERE, "metrics", name + ".py")):
            return load_module("metrics", name)
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def cell_metrics(bench, section, workload, reports):
    """The metrics of `section` that this cell reports: those that list
    it, and those that list no cells and move a metric it reports."""
    out = []
    for m in bench[section]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif section == "end_to_end":
            if m["name"] in reports:
                out.append(m)
        elif m["moves"] in reports:
            out.append(m)
    return out


class CompileLog:
    """What jax itself reports about compilation (copied from
    chip_smoke.py): backend-compile seconds (a persistent-cache hit
    costs its retrieval), tracing and lowering seconds (no cache saves
    those), compile requests and cache hits."""

    _TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.compiles = 0
        self.backend_s = 0.0
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == self._BACKEND:
            self.compiles += 1
            self.backend_s += secs
        elif event in self._TRACE_LOWER:
            self.trace_lower_s += secs

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def mark(self):
        return {"compiles": self.compiles, "backend_compile_s": self.backend_s,
                "trace_and_lower_s": self.trace_lower_s,
                "cache_hits": self.cache_hits}


class Run:
    """What a driver and the readers share: the cell's data, the clock,
    spans, the profiler, the device."""

    def __init__(self, args, cell, jax):
        self.jax = jax
        self.workload = cell["name"]
        self.chips = cell["chips"]
        self.seed, self.seconds = args.seed, args.seconds
        self.trace_on, self.rehearse = bool(args.trace), args.rehearse
        self.control = bool(args.control)
        self.cfg = load_json("configs", cell["config"] + ".json")
        self.job = load_json("traffic", cell["traffic"] + ".json")
        if self.rehearse:
            self.cfg.update(self.cfg.get("rehearse", {}))
            self.job.update(self.job.get("rehearse", {}))
            # sizes of the configuration that this mix's rehearsal needs
            self.cfg.update(self.job.get("rehearse_config", {}))
        self.config = load_module("configs", cell["config"])
        limits = load_json("limits", cell["name"] + ".json")
        self.limits = dict(limits["limits"])
        if self.rehearse:
            self.limits.update(limits.get("rehearse_limits", {}))
        self.peaks = load_json("peaks.json")
        self.out_dir = os.path.join(
            args.out, self.workload, f"seed{self.seed}-trace{args.trace}")
        os.makedirs(self.out_dir, exist_ok=True)
        self.compile_log = CompileLog(jax)
        self.clock = time.perf_counter
        self.setup_s = None
        self.at_window = None     # compile log when the window opened
        self.at_close = None
        self.trace = None         # reduce_trace()'s result, traced runs
        self.memory_stats = None  # the first chip's, when the peak was read
        self.result = None        # the driver's

    @property
    def devices(self):
        return self.jax.devices()[:self.chips]

    @contextlib.contextmanager
    def span(self, name):
        """A host span: in a traced stretch it goes into the profiler's
        trace, on the device events' clock; otherwise it costs nothing
        to speak of."""
        with self.jax.profiler.TraceAnnotation("bench:" + name):
            yield

    def open_window(self):
        """Set-up ends here: everything before is `setup_s`."""
        self.setup_s = _process_age_s()
        self.at_window = self.compile_log.mark()
        return self.clock()

    def close_window(self):
        self.at_close = self.compile_log.mark()

    def traced(self, body):
        """Run `body()` under the profiler inside a `bench:window` span
        and keep the reduction.  The trace's files are read and removed."""
        from benchmarks import trace_reduce

        tdir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        # the Python tracer off: it records every call of the host loop
        # and slows what it measures
        options = self.jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        self.jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            with self.jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                body()
        finally:
            self.jax.profiler.stop_trace()
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"the profiler left no trace under {tdir}")
        plain = trace_reduce.load_xplane(max(files, key=os.path.getmtime))
        if os.environ.get("BENCH_KEEP_TRACE"):
            import gzip
            with gzip.open(os.path.join(self.out_dir, "trace.json.gz"),
                           "wt") as f:
                json.dump(plain, f)
        shutil.rmtree(tdir, ignore_errors=True)
        if self.rehearse and not any(
                trace_reduce.DEVICE_PLANE.match(p) for p in plain):
            print("rehearsal: the trace has no TPU plane, so no device "
                  "metric is read", file=sys.stderr)
            return
        self.trace = trace_reduce.reduce_trace(plain)

    def memory_peak(self):
        """Peak bytes held on the fullest of the cell's chips."""
        stats = [d.memory_stats() or {} for d in self.devices]
        self.memory_stats = stats[0]
        # the TPU runtime keeps two disjoint regions: buffers "in use"
        # (arguments, results, what the host holds) and the region it
        # reserves for the running programs' temporaries, and gives a
        # peak for each but none for their sum.  So: the larger of the
        # peak in use (set-up's high-water mark) and, now that the
        # window's programs are resident, in use plus reserved.
        return int(max(max(s.get("peak_bytes_in_use", 0),
                           s.get("bytes_in_use", 0)
                           + s.get("bytes_reserved", 0)) for s in stats))

    def chip_peaks(self):
        """The peaks of the device in use, from peaks.json.  A device that
        is not in the table is an error, not a default; a rehearsal on
        one has no peak, and the readers that need one read nothing."""
        kind = self.jax.devices()[0].device_kind
        if kind not in self.peaks:
            if self.rehearse:
                return None
            raise KeyError(f"peaks.json has no entry for device {kind!r}")
        return self.peaks[kind]

    def write_json(self, name, obj):
        with open(os.path.join(self.out_dir, name), "w") as f:
            json.dump(obj, f)


def _fail(msg, code=3):
    print(f"benchmarks.run: {msg}", file=sys.stderr)
    return code


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out"),
                    help="directory for per-run files (step times, "
                         "traces while they are read)")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control and the planted faults "
                         "(the reference in the program's place, in a "
                         "lower precision or broken) against the same "
                         "limits; for setting limits, never in a check")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform there is; "
                         "never a measurement")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        return _fail(f"no workload {args.workload!r} in BENCHMARK.json; "
                     f"there are {sorted(cells)}", 2)
    cell = cells[args.workload]

    # the program's one compile-cache rule (paddle_tpu/compile_cache.py)
    # puts the cache at <checkout>/.jax_cache unless the caller names a
    # directory; here every program is kept, also the sub-second ones
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    if cell["chips"] > 1 and args.rehearse:
        flag = f"--xla_force_host_platform_device_count={cell['chips']}"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   + flag).strip()
    import paddle_tpu  # noqa: F401  (applies the cache rule before jax starts)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"]}
    if not args.rehearse and (devs[0].platform != "tpu"
                              or len(devs) < cell["chips"]):
        return _fail(f"{args.workload} needs {cell['chips']} TPU chip(s); "
                     f"jax found {len(devs)} x {devs[0].platform} "
                     f"({devs[0].device_kind})")
    if len(devs) < cell["chips"]:
        return _fail(f"rehearsal needs {cell['chips']} devices, "
                     f"found {len(devs)}")

    run = Run(args, cell, jax)
    driver = load_module("drivers", run.job["driver"])
    run.result = result = driver.run(run)

    values = dict(result["values"], setup_s=run.setup_s)
    metrics = {}
    if run.trace_on:
        for m in cell_metrics(bench, "per_layer", run.workload, values):
            value = load_reader(m["name"]).read(run, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, "end_to_end", run.workload, values):
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}

    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    line["notes"] = dict(result.get("notes", {}), workload=run.workload,
                         seed=run.seed, rehearse=run.rehearse,
                         setup_s=run.setup_s,
                         memory_stats=run.memory_stats,
                         compiled_in_window=run.at_close["compiles"]
                         - run.at_window["compiles"])
    # each number compared beside its limit: last on stderr, last in the line
    checks = {name: {"value": v, "limit": lim}
              for name, v, lim in result["checks"]}
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    print(f"correct={line['correct']} " + " ".join(
        f"{n}={c['value']:.6g}(limit {c['limit']:.6g})"
        for n, c in checks.items()), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
