"""The harness: finds a cell by name, runs it once, prints one result line.

Everything that belongs to one item sits in a file of its own, found by the
name that ``BENCHMARK.json`` gives it:

  configs/<config>.json    the configuration as it is run (``file`` in
                           ``BENCHMARK.json``)
  traffic/<traffic>.json   a traffic mix: which driver runs it, its
                           parameters, and the limits of its comparisons
  drivers/<driver>.py      a window driver: ``setup``, ``window``, ``verify``
  metrics/<metric>.py      a per-layer metric: ``read(run) -> float | None``

A run is: set-up (weights and inputs from the seed, compiles, warm-up and,
for training, the steps that the comparison follows), the measured window,
the peak memory, then the comparison with the plain reference.  With
``trace`` the window runs under the profiler and the run reports the cell's
per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable

BENCH_DIR = Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent
#: a traced run measures at most this long: the trace of a longer window
#: is large, and only the per-layer metrics are read from it
TRACE_WINDOW_S = 10.0


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names does not say what a run needs."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[dict]         # metrics this cell reports with trace 0
    per_layer: list[dict]          # metrics this cell reports with trace 1
    bench_dir: Path

    def module(self, kind: str, name: str):
        """Load ``<bench_dir>/<kind>/<name>.py`` (names may hold dots)."""
        path = self.bench_dir / kind / f"{name}.py"
        if not path.is_file():
            raise SpecError(f"{kind[:-1]} {name!r}: no file {path}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        return mod


def _for_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = REPO_DIR) -> Cell:
    """Resolve cell ``name`` from ``<root>/BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"{name}: no configuration {w['config']!r}")
    bench_dir = root / bench["paths"][0]
    traffic_file = bench_dir / "traffic" / f"{w['traffic']}.json"
    if not traffic_file.is_file():
        raise SpecError(f"{name}: no traffic file {traffic_file}")
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"],
        config=json.loads((root / configs[w["config"]]["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads(traffic_file.read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _for_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _for_cell(m, name)],
        bench_dir=bench_dir,
    )


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns."""
    seconds: float                       # wall time of the window
    attempted: int
    failed: int
    end_to_end: dict[str, float]         # by end-to-end metric name
    counts: dict[str, Any]               # what per-layer readers need


@dataclasses.dataclass
class Context:
    cell: Cell
    seed: int
    devices: list
    log: Callable[[str], None]
    seconds: float                       # length of the window to come
    trace: bool = False

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"chipbench.{name}")

    def limit(self, name: str) -> float:
        return float(self.cell.traffic["limits"][name])


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader reads."""
    cell: Cell
    window: Window
    trace: Any                           # trace.Summary of the traced window
    peaks: dict
    chips: int


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class GcPauses:
    """The garbage collector's pauses while it is installed, by generation:
    a pause between two steps leaves the device idle."""

    def __init__(self):
        self.pauses: dict[int, list[float]] = {}
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.setdefault(info["generation"], []).append(
                time.perf_counter() - self._t0)

    @contextlib.contextmanager
    def installed(self):
        gc.callbacks.append(self)
        try:
            yield self
        finally:
            gc.callbacks.remove(self)

    def summary(self) -> str:
        return "; ".join(f"generation {g}: {len(v)} pauses, {sum(v):.4f} s, "
                         f"longest {max(v):.4f} s"
                         for g, v in sorted(self.pauses.items())) or "none"


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices: list, peaks: dict, started: float,
             out_dir: Path, log: Callable[[str], None]) -> dict:
    """Run ``cell`` once on ``devices`` and return its result line.

    ``started`` is the host clock at process start: set-up is counted from
    it to the first timed step.
    """
    import jax
    from chipbench import trace as tracemod

    driver = cell.module("drivers", cell.traffic["driver"])
    if trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    ctx = Context(cell=cell, seed=seed, devices=devices, log=log,
                  seconds=seconds, trace=trace)
    state = driver.setup(ctx)
    # Set-up leaves garbage in reference cycles, which only the collector
    # frees.  Left in place, it coincided with training steps a second or
    # more longer than the rest, though no collection ran in those windows
    # and collecting it frees no device memory; it is freed here, in set-up.
    gc.collect()
    trace_dir = out_dir / "trace"
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the Python tracer would record every Python call of the host loop
        # and slow it; the harness's own spans need only the host tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    setup_s = time.perf_counter() - started
    try:
        with ctx.span("window"), GcPauses().installed() as gc_pauses:
            win = driver.window(state, ctx, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    log(f"garbage collection in the window: {gc_pauses.summary()}")
    memory_peak = _memory_peak(devices)
    checks = driver.verify(state, ctx)
    del state

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result: dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": win.attempted, "failed": win.failed}
    if not trace:
        metrics = {}
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else win.end_to_end.get(m["name"])
            if value is None:
                raise SpecError(f"{cell.name}: the driver measured no {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        summary = tracemod.reduce(tracemod.find_xplane(trace_dir),
                                  window_span="chipbench.window")
        run = Run(cell=cell, window=win, trace=summary, peaks=peaks,
                  chips=len(devices))
        metrics = {}
        for m in cell.per_layer:
            value = cell.module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def check_lines(result: dict) -> list[str]:
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}"
            for name, c in result["checks"].items()]


def main(argv: list[str] | None = None, *, started: float | None = None) -> int:
    import argparse
    import os

    started = time.perf_counter() if started is None else started
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    cell = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from chipbench import flops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: no TPU found (JAX's first device is a "
            f"{devices[0].platform!r} device); the benchmark runs only on a TPU")
        return 2
    if len(devices) < cell.chips:
        log(f"chipbench: {cell.name} needs {cell.chips} chips, "
            f"JAX finds {len(devices)}")
        return 2
    devices = devices[:cell.chips]
    peaks = flops.peaks(devices[0].device_kind)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"chipbench: {cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} on {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); compile cache {cache}")

    out_dir = BENCH_DIR / ".out" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices, peaks=peaks,
                      started=started, out_dir=out_dir, log=log)
    for line in check_lines(result):
        log(line)
    print(json.dumps(result), flush=True)
    return 0
