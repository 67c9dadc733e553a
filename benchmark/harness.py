"""What every job kind shares: the manifest, finding files by name, the look
for the chips, host spans, the compile counter, the profiler window and the
result line. Nothing here knows a model, a traffic mix or a metric by name."""

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

EXIT_NO_DEVICE = 3


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    """Import a file under the benchmark by its path (file names follow the
    manifest's names, which may hold ``-`` and ``.``)."""
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, HERE))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


class Spans:
    """Host spans of the benchmark's own, kept in memory: (name, start, end)
    on ``time.perf_counter``."""

    def __init__(self):
        self.rows = []

    def add(self, name, start, end):
        self.rows.append((name, start, end))

    def durations(self, name):
        return [e - s for n, s, e in self.rows if n == name]


class CompileCounter:
    """Counts XLA backend compilations in this process through
    ``jax.monitoring`` (the benchmark's own count: a cache load is also a
    compile request and counts, which is what 'nothing compiles inside the
    window' needs)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1


class Run:
    """One run of one cell: the parsed files, the devices, the clocks."""

    def __init__(self, manifest_path, workload, seed, seconds, trace,
                 rehearse, process_start):
        self.root = os.path.dirname(os.path.abspath(manifest_path))
        self.manifest = load_json(manifest_path)
        cells = {c["name"]: c for c in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit("no cell %r in %s (it has: %s)" % (
                workload, manifest_path, ", ".join(sorted(cells))))
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.cell["config"]]
        self.config = load_json(self.path(self.config_entry["file"]))
        self.traffic = load_json(self.path(
            "benchmark", "traffic", self.cell["traffic"] + ".json"))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        self.process_start = process_start
        self.spans = Spans()
        self.devices = None
        self.compiles = None

    @classmethod
    def from_args(cls, args, process_start):
        return cls(args.manifest, args.workload, args.seed, args.seconds,
                   args.trace, args.rehearse, process_start)

    def path(self, *parts):
        return os.path.join(self.root, *parts)

    # -- the chips ----------------------------------------------------------
    def claim_devices(self):
        """Import JAX, see that the chips the cell asks for are there, start
        the compile counter. Returns the devices this run uses."""
        import jax

        chips = int(self.cell["chips"])
        devices = jax.devices()
        platform = devices[0].platform
        if platform != "tpu" and not self.rehearse:
            sys.stderr.write("benchmark: JAX found no TPU (platform %r); "
                             "no result\n" % platform)
            sys.exit(EXIT_NO_DEVICE)
        if len(devices) < chips:
            sys.stderr.write("benchmark: cell %s asks for %d chips, JAX "
                             "found %d; no result\n"
                             % (self.cell["name"], chips, len(devices)))
            sys.exit(EXIT_NO_DEVICE)
        self.devices = devices[:chips]
        self.devices_answered = time.time()
        self.compiles = CompileCounter()
        return self.devices

    def peaks(self):
        """The published peaks of this run's device kind; an unknown kind is
        an error, never a default."""
        table = load_json(os.path.join(HERE, "peaks.json"))["device_kinds"]
        kind = self.devices[0].device_kind
        if kind not in table:
            raise KeyError("device kind %r is not in benchmark/peaks.json"
                           % kind)
        return table[kind]

    def memory_peak_bytes(self):
        """Peak bytes on the fullest chip: the backend's peak of live
        buffers plus its peak of reserved bytes. On a TPU v5e
        ``peak_bytes_in_use`` counts arguments, outputs and other live
        arrays only; the temporaries of a loaded executable are
        ``bytes_reserved`` (7.31 GB reserved beside the 7.39 GB of
        temporaries the compiler counts for tbase.train.s256; PERF.md §2).
        The two peaks need not fall together, so the sum is an upper
        reading of the footprint, never under it."""
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                       + int(stats.get("peak_bytes_reserved", 0)))
        return peak

    # -- per-layer metrics --------------------------------------------------
    def metric_names(self):
        """Names of the metrics this run's result line carries."""
        name = self.cell["name"]

        def here(metric):
            return "workloads" not in metric or name in metric["workloads"]

        end_to_end = [m["name"] for m in self.manifest["end_to_end"]
                      if here(m)]
        if not self.trace:
            return end_to_end
        # a per-layer metric without a list of cells belongs to every cell
        # that reports the end-to-end metric it moves
        return [m["name"] for m in self.manifest["per_layer"]
                if (name in m["workloads"] if "workloads" in m
                    else m["moves"] in end_to_end)]

    def metric_units(self):
        return {m["name"]: m["unit"] for g in ("end_to_end", "per_layer")
                for m in self.manifest[g]}

    def read_layer_metrics(self, ctx):
        """Call the reader of every per-layer metric this cell reports. A
        reader that finds nothing to read returns None and its metric is
        left out of the line."""
        out = {}
        ctx = dict(ctx, run=self)
        for name in self.metric_names():
            reader = load_module(os.path.join(
                HERE, "layer_metrics", name + ".py"))
            value = reader.read(ctx)
            if value is not None:
                out[name] = float(value)
        return out


def profile(directory, body):
    """Run ``body()`` under ``jax.profiler`` and return the path of the
    xplane file it wrote."""
    import glob

    import jax

    jax.profiler.start_trace(directory)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise RuntimeError("the profiler wrote no xplane under " + directory)
    return found[-1]


def emit(run, result):
    """Print the compared numbers, each beside its limit (on standard
    output with their notes, and as the last lines of standard error), then
    the result line as the last line of standard output, the compared
    numbers under its last key."""
    units = run.metric_units()
    compared = result.get("compared", [])
    for row in compared:
        print("compared %-34s value %.6g  limit %.6g  %s  %s" % (
            row["name"], row["value"], row["limit"],
            "ok" if row["ok"] else "OVER", row.get("note", "")))
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": result.get("memory_peak_bytes", 0)}
    on_chip = device["platform"] == "tpu"
    for key in ("busy_s", "window_s"):
        if result.get(key) is not None and on_chip:
            device[key] = result[key]
    wanted = run.metric_names()
    if not on_chip:
        # a rehearsal keeps what is no device number: set-up's host clocks
        # and the program's own counts
        kept = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run.manifest[g]
                if m["name"] == "setup_s" or m.get("moves") == "setup_s"
                or m["source"] == "program_counter"}
        wanted = [n for n in wanted if n in kept]
    metrics = {name: {"value": value, "unit": units[name]}
               for name, value in result["metrics"].items()
               if name in wanted}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}
    if result.get("breakdown") and on_chip:
        line["breakdown"] = result["breakdown"]
    if run.rehearse:
        line["rehearsal"] = True
    line["compared"] = {row["name"]: {"value": row["value"],
                                      "limit": row["limit"]}
                        for row in compared}
    sys.stdout.flush()
    for row in compared:
        sys.stderr.write("compared %s value %.6g limit %.6g %s\n" % (
            row["name"], row["value"], row["limit"],
            "ok" if row["ok"] else "OVER"))
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
