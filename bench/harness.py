"""One run of one cell: set-up, the measured window, the comparison
with the reference, and the result line.

A cell's traffic names a driver (``drivers/<driver>.py``) with four
functions: ``setup(cell)`` builds the program's objects and runs every
shape the window will use once; ``window(cell, seconds, tracer)`` drives
the load and returns a ``Window``; ``check(cell, win)`` compares what
the window produced with the reference; ``control(cell, win)`` puts the
reference in a lower precision in the program's place (calibration
only).  ``release(cell, win)`` drops the program's state before the
reference runs.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from bench import registry, tracing

ROOT = registry.BENCH_DIR.parent
# The engine's jitted stages, by the names their executables carry.
EXECUTABLES = ("_ppa_stage", "_lane_layers", "_broadcast_layers",
               "_network_sums")


def sub_seed(seed: int, *path: int) -> int:
    """A 32-bit seed derived from the run's seed and a path of ints."""
    return int(np.random.SeedSequence([int(seed), *path])
               .generate_state(1)[0])


@dataclass
class Window:
    """What a driver's window returns."""
    metrics: dict                      # end-to-end metric name -> value
    attempted: int
    failed: int
    items: list = field(default_factory=list)   # what ``check`` reads
    lags_s: list = field(default_factory=list)  # load generator lateness
    unanswered: int = 0


class Cell:
    """A cell's configuration, traffic, seed, devices and program
    objects, as the drivers see them."""

    def __init__(self, name: str, config: dict, traffic: dict, seed: int,
                 devices):
        self.name, self.config, self.traffic = name, config, traffic
        self.seed, self.devices = int(seed), list(devices)
        self.space = {k: tuple(v) for k, v in config["space"].items()}
        self.chunk_size = int(config["chunk_size"])
        self.layer_buckets = tuple(config["layer_buckets"])
        self.models = program_models(config)
        self.state: dict = {}
        self.marks: dict = {}     # set-up milestones (name -> perf_counter)
        self._ref_models = None

    def reference_models(self):
        from bench.reference.joint import Model
        if self._ref_models is None:
            self._ref_models = [Model(m) for m in self.config["models"]]
        return self._ref_models

    def best_by_index(self, best: dict) -> dict:
        """A front's per-(model name, PE name) bests keyed by (model
        position, PE code), as the reference keys them."""
        from bench.reference.costmodel import PE_TYPES
        pos = {m.name: i for i, m in enumerate(self.models)}
        return {(pos[m], PE_TYPES.index(pe)):
                (e["macs_per_s_per_mm2"], e["energy_per_mac_pj"])
                for (m, pe), e in best.items()}


def program_models(config: dict):
    """The program's model axis for a configuration: each model's
    workload constructor and arguments, as the configuration names
    them."""
    from repro.core import workloads
    from repro.core.coexplore import model_entry
    return tuple(
        model_entry(getattr(workloads, m["program"]["fn"])(
            **m["program"]["args"]), acc_classes=m.get("acc_classes", False))
        for m in config["models"])


def _compiles() -> int:
    from repro.core import ppa_trace_count, trace_count
    return trace_count() + ppa_trace_count()


class _CompileWatch:
    """Counts XLA backend compiles (any executable) while active."""

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and "backend_compile" in event:
            self.n += 1


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class Readings:
    """What a per-layer metric reads: the program's spans and counters
    from the traced window, the trace reduction, the load generator's
    lateness."""

    def __init__(self, tracer, reduced: dict | None, win: Window):
        reg = tracer.registry
        self.spans = {k: (h.count, h.total)
                      for k, h in reg.histograms.items()}
        self.counters = {k: c.value for k, c in reg.counters.items()}
        self.trace = reduced
        self.lags_s = list(win.lags_s)

    def span_s(self, name: str) -> float:
        return self.spans.get(name, (0, 0.0))[1]

    def span_n(self, name: str) -> int:
        return self.spans.get(name, (0, 0.0))[0]

    @property
    def chunks(self) -> int:
        """Chunks finished in the window (one ``device_wait`` each)."""
        return self.span_n("sweep.device_wait")


def run_cell(spec: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, devices, t_start: float,
             compile_cache: bool = True, marks: dict | None = None
             ) -> tuple[dict, list]:
    """Run one cell; return the result line (a dict) and the compared
    numbers as (name, value, limit) rows.  ``marks`` are the caller's
    set-up milestones (name -> ``perf_counter`` time), reported with the
    cell's own as ``setup_split``: the seconds from each milestone to the
    next."""
    import jax
    marks = dict(marks or {})
    w = registry.workload(spec, cell_name)
    config = registry.config(w["config"])
    traffic = registry.traffic(w["traffic"])
    drv = registry.driver(traffic["driver"])
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    watch = _CompileWatch()
    cell = Cell(cell_name, config, traffic, seed, devices)
    marks["workload_build"] = time.perf_counter()
    cell.marks = marks
    drv.setup(cell)

    tracer = tracing.annotating_tracer() if trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    c0 = _compiles()
    watch.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    if trace:
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=tracing.profile_options())
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            win = drv.window(cell, seconds, tracer)
        jax.profiler.stop_trace()
    else:
        win = drv.window(cell, seconds, tracer)
    watch.on = False
    in_window = _compiles() - c0 + watch.n
    peak = memory_peak(devices)

    reduced = None
    if trace:
        try:
            host = {f"{k}" for k in tracer.registry.histograms}
            reduced = tracing.reduce(tracing.load(trace_dir, host),
                                     EXECUTABLES)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    drv.release(cell, win)
    gc.collect()
    numbers = drv.check(cell, win)
    numbers.add("compiles_in_window", in_window)
    numbers.add("unanswered", win.unanswered)

    metrics = {}
    if trace:
        readings = Readings(tracer, reduced, win)
        for m in registry.cell_metrics(spec, cell_name, "per_layer"):
            v = registry.metric_reader(m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
    else:
        values = dict(win.metrics, setup_s=setup_s)
        for m in registry.cell_metrics(spec, cell_name, "end_to_end"):
            metrics[m["name"]] = dict(value=float(values[m["name"]]),
                                      unit=m["unit"])
    d0 = devices[0]
    device = dict(platform=d0.platform, kind=d0.device_kind,
                  count=len(devices), memory_peak_bytes=peak)
    result = dict(correct=numbers.correct(), attempted=int(win.attempted),
                  failed=int(win.failed), metrics=metrics, device=device)
    if reduced is not None:
        busy = [reduced["busy_s"].get(d.id, 0.0) for d in devices]
        device.update(busy_s=float(np.mean(busy)),
                      window_s=reduced["window_s"])
        result["breakdown"] = dict(device_ops=reduced["device_ops"],
                                   idle_gaps=reduced["idle_gaps"])
    names, times = [*marks, "setup_rest"], [t_start, *marks.values(), t0]
    result["setup_split"] = {n: b - a for n, a, b in
                             zip(names, times, times[1:])}
    lines = numbers.lines()
    result["checks"] = {k: dict(value=v, limit=lim) for k, v, lim in lines}
    return result, lines

