"""The benchmark harness: one run of one cell.

A cell is a ``BENCHMARK.json`` workload: a configuration file under
``bench/configs/``, a traffic file ``bench/traffic/<traffic>.json`` whose
``entry`` names its runner in ``bench/cells/``, and the limits of its
correctness numbers in ``bench/limits/<workload>.json``.  Each per-layer
metric is a reader ``bench/metrics/<metric>.py``.  All of them are found by
name, so a new cell, configuration or metric is new files and entries.

A run: set-up (device, weights and data from the seed, the program built,
its first rounds driven through the window's own call and kept for the
check), the measured window, then the check against the plain reference
once the program's state is freed.  It prints the numbers compared on
standard error and, last on standard output, one JSON line.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"


class Refused(SystemExit):
    """The run cannot measure here: exit non-zero with no result line."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    traffic: dict           # the traffic file
    limits: dict            # number -> limit
    per_layer: list[dict]   # the per-layer metric entries that read this cell
    end_to_end: list[dict]  # the end-to-end metric entries this cell reports

    @property
    def reference(self):
        return importlib.import_module(f"bench.reference.{self.config['family']}")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def resolve(workload: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise Refused(f"bench: no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == wl["config"])
    return Cell(
        name=workload,
        chips=int(wl["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "limits" / f"{workload}.json").read_text()),
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
    )


def check_device(chips: int):
    """The TPU devices the cell runs on; refuses anything else."""
    import jax

    devices = jax.devices()
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if devices[0].platform != "tpu":
        raise Refused(f"bench: no TPU (JAX sees {devices[0].platform})")
    if devices[0].device_kind not in peaks:
        raise Refused(f"bench: no peaks for {devices[0].device_kind!r} in bench/peaks.json")
    if len(devices) < chips:
        raise Refused(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def peaks_for(kind: str) -> dict:
    return json.loads((BENCH / "peaks.json").read_text())[kind]


def enable_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (or ``JAX_COMPILATION_CACHE_DIR``), holding every
    program, however quick its compile."""
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclass
class Window:
    """What the measured stretch did, as the runner counted it."""

    t_start: float
    t_end: float
    rounds: int
    attempted: int          # client updates dispatched
    absorbed: int           # client updates the server aggregated
    samples: int            # local training samples the absorbed updates took
    round_s: list[float] = field(default_factory=list)
    spans: dict = field(default_factory=dict)   # span name -> [seconds, ...]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


@dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Cell
    window: Window
    trace: object | None        # bench.trace.Summary
    peaks: dict
    memory_peak_bytes: int
    runner: object


def peak_memory(device) -> int:
    """Peak device memory of the process, ``peak_bytes_in_use``."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


class CompileCounter:
    """Counts backend compilations while registered (a cache hit is none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


def read_per_layer(ctx: Context) -> dict:
    out = {}
    for entry in ctx.cell.per_layer:
        path = BENCH / "metrics" / f"{entry['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{entry['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def make_runner(cell: Cell, seed: int, devices, faults=()):
    entry = importlib.import_module(f"bench.cells.{cell.traffic['entry']}")
    return entry.Runner(cell, seed, devices, faults=faults)


def measure(cell: Cell, seed: int, seconds: float, trace: bool, devices,
            t_process: float, faults=(), peaks: dict | None = None) -> dict:
    """One run: set-up, window, check.  Returns the result object; the
    lines of the numbers compared go to standard error.  ``peaks`` defaults
    to the devices' row of ``peaks.json``."""
    import jax

    from bench import trace as tr

    runner = make_runner(cell, seed, devices, faults)
    runner.setup()
    setup_s = time.perf_counter() - t_process

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            try:
                with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                    win = runner.window(seconds)
            finally:
                jax.profiler.stop_trace()
        else:
            win = runner.window(seconds)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    print(f"[window] {win.rounds} rounds, {win.absorbed}/{win.attempted} client "
          f"updates absorbed in {win.seconds:.3f} s; compiles in the window: "
          f"{counter.count}", file=sys.stderr, flush=True)

    memory_peak = max(peak_memory(d) for d in devices)
    summary = tr.summarize(tr.find_xplane(TRACE_DIR)) if trace else None
    if trace:
        print(f"[trace] Pallas kernel events in the window: {summary.kernels()}",
              file=sys.stderr, flush=True)
        ctx = Context(cell, win, summary,
                      peaks_for(devices[0].device_kind) if peaks is None else peaks,
                      memory_peak, runner)
        metrics = read_per_layer(ctx)
    else:
        metrics = end_to_end(cell, win, setup_s)
    runner.release()

    numbers = runner.check()
    correct = bool(numbers) and all(v <= cell.limits[k] for k, v in numbers.items())
    for k, v in numbers.items():
        print(f"[check] {k} = {v!r} (limit {cell.limits[k]!r})", file=sys.stderr)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.attempted - win.absorbed, "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = summary.window_s()
        result["breakdown"] = summary.breakdown()
    result["checked"] = {k: {"value": v, "limit": cell.limits[k]} for k, v in numbers.items()}
    return result


def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    import numpy as np

    q1, q2, q3 = np.percentile(win.round_s, [25, 50, 75])
    print(f"[rounds] {len(win.round_s)} round spans in the window; quartiles "
          f"{1e3 * q1:.1f} / {1e3 * q2:.1f} / {1e3 * q3:.1f} ms, longest "
          f"{1e3 * max(win.round_s):.1f} ms", file=sys.stderr, flush=True)
    # a metric split by cells (``client_updates_per_s.flower``) is its base's
    values = {
        "client_updates_per_s": lambda: win.absorbed / win.seconds,
        "round_ms_p95": lambda: 1e3 * float(np.percentile(win.round_s, 95)),
        "setup_s": lambda: setup_s,
    }
    return {m["name"]: {"value": values[m["name"].split(".")[0]](), "unit": m["unit"]}
            for m in cell.end_to_end}
