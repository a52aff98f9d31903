"""What every cell's run shares: the manifest, the look for the chip, the
compile cache and the count of compilations, the device trace, the readers of
per-layer metrics, and the one result line.

A cell is an entry of BENCHMARK.json's `workloads`. Its configuration file
(`configs/<config>.json`) names a driver (`drivers/<driver>.py`), its traffic
file (`traffic/<traffic>.json`) holds the parameters one general generator
reads, and each per-layer metric has a file (`metrics/<name>.json`) naming a
reader (`readers/<reader>.py`). A new cell, configuration, mix or metric is
new files and new entries; nothing here names any of them."""

from __future__ import annotations

import glob
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell with everything its entry names resolved to data."""
    man = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[cell["config"]]
    bench = os.path.join(root, "benchmark")

    def reports(metric: dict, moved: set) -> bool:
        if "workloads" in metric:
            return name in metric["workloads"]
        return metric.get("moves", metric["name"]) in moved

    e2e = [m for m in man["end_to_end"] if reports(m, {m["name"]})]
    names = {m["name"] for m in e2e}
    return {
        "name": name, "chips": int(cell["chips"]), "manifest": man,
        "config_path": os.path.join(root, conf["file"]),
        "config": load_json(root, conf["file"]),
        "traffic_path": os.path.join(bench, "traffic",
                                     cell["traffic"] + ".json"),
        "traffic": load_json(bench, "traffic", cell["traffic"] + ".json"),
        "end_to_end": e2e,
        "per_layer": [m for m in man["per_layer"] if reports(m, names)],
    }


# ------------------------------------------------------------------ the chip
def compile_cache_dir() -> str:
    """`JAX_COMPILATION_CACHE_DIR` when set, else `<checkout>/.jax_cache`:
    the place akka_tpu/utils/compile_cache.py uses, so the program's own
    entry points and the benchmark share entries. A fixed path: the path is
    part of each entry's key."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def open_devices(chips: int, require_chip: bool = True):
    """Initialise JAX, return its first `chips` devices. Without a TPU, or
    with fewer chips than the cell asks for, a measurement is refused."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    # the sub-second programs count too: a run recompiles none of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise BenchError(f"no accelerator: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}, not 'tpu'")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileLog:
    """Counts backend compilations from JAX's own monitoring events. A
    persistent-cache hit is inside the same bracket, so it counts: inside
    the measured window there may be neither."""

    def __init__(self):
        import jax

        self.times = []  # monotonic time of each compile's END
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.times.append((time.monotonic(), secs, kw.get("fun_name")))

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, *_ in self.times if t0 <= t < t1)

    def seconds(self) -> float:
        return sum(t[1] for t in self.times)


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip (the process's high-water mark)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class DeviceTrace:
    """A profiler trace of a stretch inside the window, between two marks
    that the reduction finds again in the trace."""

    def __init__(self, workdir: str):
        self.dir = os.path.join(workdir, "trace")
        self.t_a = self.t_b = None
        self.path = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # TraceMe events only: small, cheap
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_a = self.mark("a")

    @staticmethod
    def mark(label: str) -> float:
        import jax

        with jax.profiler.TraceAnnotation(f"bench.mark.{label}"):
            t = time.monotonic()
            time.sleep(0.0002)
        return t

    def stop(self) -> None:
        import jax

        self.t_b = self.mark("b")
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins/profile/*/*.xplane.pb"))
        if not found:
            raise BenchError("the profiler wrote no .xplane.pb")
        self.path = sorted(found)[-1]

    def reduce(self) -> dict:
        """The traced stretch as numbers: [a, b) in trace ns, busy seconds
        per device, the breakdown, and the loaded trace for the readers."""
        from benchmark import xplane

        trace = xplane.load(self.path)
        marks = xplane.find_marks(trace)
        if "a" not in marks or "b" not in marks:
            raise BenchError("the trace lacks the benchmark's marks")
        if not trace.devices:
            raise BenchError("the trace holds no device plane")
        a, b = marks["a"], marks["b"]
        busy = xplane.device_busy(trace, a, b)
        dev = xplane.fullest(trace, a, b)
        ops = xplane.op_sums(trace, a, b, dev)
        print("device operations by self time, fullest device: "
              + json.dumps(xplane.top(ops, 40)), file=sys.stderr, flush=True)
        return {
            "trace": trace, "a": a, "b": b,
            "busy_s": sum(busy) / len(busy), "window_s": (b - a) / 1e9,
            "fullest": dev,
            "breakdown": {
                "device_ops": xplane.top(ops),
                "idle_gaps": xplane.top(
                    xplane.attribute_gaps(trace, a, b, dev)),
            },
        }


# ------------------------------------------------------------------- a run
class Run:
    """One invocation: what the driver is given and what it hands back."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 t_start: float, require_chip: bool = True, faults=None):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.t_start = bool(trace), t_start
        self.require_chip = require_chip
        self.faults = faults or {}  # test hooks a driver understands
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.devices = None
        self.compiles = None
        self.workdir = None
        self.device_trace = None
        # the driver fills these
        self.t_open = self.t_close = None
        self.end_to_end = {}      # name -> value (without setup_s)
        self.obs = {}             # what the per-layer readers read
        self.attempted = self.failed = 0
        self.compared = {}        # name -> {"value": v, "limit": l}
        self.memory_peak = 0
        self.notes = {}           # set-up parts and the like, for stderr
        self.controls = None      # name -> compared numbers, when asked for

    def note(self, msg: str) -> None:
        print(f"[bench +{time.monotonic() - self.t_start:7.2f}s] {msg}",
              file=sys.stderr, flush=True)


def load_part(root: str, kind: str, name: str):
    """`<root>/benchmark/<kind>/<name>.py` as a module: drivers and readers
    are found by the name the data gives, so a new one is a new file."""
    if os.path.abspath(root) == ROOT:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    path = os.path.join(root, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(run: Run, root: str = ROOT) -> dict:
    """Each per-layer metric of the cell through its own reader. A reader
    that finds nothing returns None and the metric is left out: except a
    `device_trace` metric, which the manifest lists for this cell because
    the trace holds it. Nothing to read there means the program it looks
    for ran under another name, and that is an error, not a silence."""
    out = {}
    for m in run.cell["per_layer"]:
        spec = load_json(root, "benchmark", "metrics", m["name"] + ".json")
        reader = load_part(root, "readers", spec["reader"])
        args = spec.get("args", {})
        value = reader.read(run.obs, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        elif m["source"] == "device_trace":
            raise BenchError(
                f"{m['name']}: reader {spec['reader']!r} with {args} found "
                f"nothing in the device trace of {run.cell['name']!r}")
    return out


def execute(name: str, seed: int, seconds: float, trace: bool,
            t_start: float, require_chip: bool = True,
            root: str = ROOT, faults=None) -> dict:
    """Run one cell and return the result object (the CLI prints it).
    `require_chip=False` is for tests only: they skip the look for a chip
    and drive everything else. `faults`: test hooks a driver understands."""
    cell = load_cell(name, root)
    run = Run(cell, seed, seconds, trace, t_start, require_chip, faults)
    driver = load_part(root, "drivers", cell["config"]["driver"])
    run.devices = open_devices(cell["chips"], require_chip)
    run.compiles = CompileLog()
    run.workdir = tempfile.mkdtemp(prefix="bench_")  # under TMPDIR
    if trace:
        run.device_trace = DeviceTrace(run.workdir)
    try:
        driver.run(run)
        d0 = run.devices[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(run.devices),
                  "memory_peak_bytes": run.memory_peak}
        setup_s = run.t_open - t_start
        result = {}
        if trace:
            red = run.device_trace.reduce()
            run.obs.update(trace=red["trace"], trace_a=red["a"],
                           trace_b=red["b"], trace_fullest=red["fullest"],
                           device_kind=d0.device_kind, config=run.config,
                           traffic=run.traffic, chips=len(run.devices))
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            metrics = read_per_layer(run, root)
            result["breakdown"] = red["breakdown"]
        else:
            units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
            values = dict(run.end_to_end, setup_s=setup_s)
            metrics = {k: {"value": float(values[k]), "unit": units[k]}
                       for k in units}
    finally:
        shutil.rmtree(run.workdir, ignore_errors=True)
    compared = run.compared
    correct = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared.values())
    run.note(f"setup_s {setup_s:.3f} ({run.notes}); compiles "
             f"{len(run.compiles.times)} taking {run.compiles.seconds():.1f} s")
    inside = [t[2] for t in run.compiles.times if run.t_open <= t[0] < run.t_close]
    if inside:
        run.note(f"compiled inside the window: {inside}")
    for k, c in compared.items():
        print(f"compared {k} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    out.update(result)
    out["setup_parts_s"] = run.notes
    if run.controls is not None:
        out["controls"] = run.controls
    out["compared"] = compared
    return out
