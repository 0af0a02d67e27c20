"""What every cell shares: finding the cell's files by name, the device gate,
the compile cache and its meter, host spans, the closed-loop window, the
traced window, and the one result line.

A runner (``perfbench/runners/<name>.py``) exposes ``Session(cell, seed,
devices)``. Building it is set-up: weights made on the device, one whole
warm-up step, the correctness checks. Then

- ``session.step()`` runs one whole step and returns a record (a dict with at
  least ``attempted`` and ``failed``),
- ``session.end_to_end(records)`` turns a window's records into end-to-end
  values by metric name,
- ``session.problems`` collects what set-up and the steps find wrong, and
  ``session.finish(records)`` returns what is found after the window,
- ``session.trace_steps`` is how many whole steps a traced window holds.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import shutil
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

SPAN_PREFIX = "pb/"  # host spans carry it into the profiler's trace
WINDOW_SPAN = "window"


@dataclasses.dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with its files read."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]  # the metrics this cell reports
    per_layer: List[Dict[str, Any]]
    root: Path


def metrics_of(entries, cell_name):
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"perfbench: no workload {name!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    w = cells[name]
    config_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    config = json.loads((root / config_file).read_text())
    traffic = json.loads(
        (root / "perfbench" / "traffic" / f"{w['traffic']}.json").read_text())
    if int(config["chips"]) != int(w["chips"]):
        raise SystemExit(
            f"perfbench: cell {name} asks for {w['chips']} chips, its "
            f"configuration is laid out for {config['chips']}")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=metrics_of(bench["end_to_end"], name),
                per_layer=metrics_of(bench["per_layer"], name), root=root)


class CompileMeter:
    """Seconds JAX spent in compile-or-load-from-cache, how many programs,
    and how often its persistent cache hit: JAX's own monitoring events
    (copied from ``chip_smoke.CompileMeter``)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event.endswith("backend_compile_duration"):
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_seconds": round(self.seconds, 2),
                "programs": self.programs, "cache_hits": self.cache_hits}


def span(name: str):
    """A host span around a call into a layer: a ``TraceAnnotation``, so
    that a traced run has it on the device's time line."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def note(**record) -> None:
    """A progress line. Not a result: the result is the last line."""
    print(json.dumps(record), flush=True)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed ``<checkout>/.jax_cache`` (the path is part of
    the key). Every program is kept, also those that compile in under a
    second: a cell has dozens of them and every run is a new process."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or no run at all."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"perfbench: JAX found no TPU (platform "
            f"{devices[0].platform!r}); nothing ran")
    if len(devices) < chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} chips, JAX sees {len(devices)}")
    from agilerl_tpu.ops import pallas_enabled
    from agilerl_tpu.ops.kernel_mode import active_kill_switches

    if active_kill_switches() or not pallas_enabled():
        raise SystemExit(
            f"perfbench: kernels are not on (kill switches "
            f"{active_kill_switches()}); nothing ran")
    return devices[:chips]


def load_peaks(device_kind: str) -> Dict[str, Any]:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SystemExit(
            f"perfbench: no peaks for device kind {device_kind!r} in "
            f"perfbench/peaks.json; add a row with its source")
    return table["devices"][device_kind]


def peak_bytes(devices) -> int:
    """Peak bytes of the fullest device: live arrays at their peak
    (``peak_bytes_in_use``) plus what the runtime reserved for the scratch
    memory of the programs it loaded (``peak_bytes_reserved``). On a TPU v5e
    the first alone leaves a running program's scratch out (PERF.md, PR 22:
    a generation program that plans 9.85 GB of scratch read 0.16 GB in use
    and 9.84 GB reserved). 0 where the backend keeps no memory statistics:
    the CPU of the tests' rehearsal."""
    def peak(device) -> int:
        stats = device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))

    return max(peak(d) for d in devices)


def program_counters() -> Dict[str, float]:
    """The program's registry counters, read and never copied."""
    from agilerl_tpu import observability

    return dict(observability.get_registry().dump()["counters"])


@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read."""

    cell: Cell
    records: List[Dict[str, Any]]  # the window's step records
    counters: Dict[str, float]  # program counters, change over the window
    compiles_in_window: int
    trace: Any  # xplane.Trace of the traced window, host spans included
    peaks: Dict[str, Any]
    peak_bytes: int


def read_layer_metrics(ctx: LayerContext) -> Dict[str, Dict[str, Any]]:
    """One reader module per metric, found by the metric's name. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in ctx.cell.per_layer:
        reader = importlib.import_module(
            f"perfbench.layer_metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _run_window(session, seconds: float) -> List[Dict[str, Any]]:
    """Closed loop: the next step starts when the last one ends; whole steps
    until the window has elapsed."""
    records = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        records.append(session.step())
    return records


def _traced_window(session, trace_dir: Path,
                   trace_layout: Optional[Dict[str, Any]] = None):
    """``session.trace_steps`` whole steps under the profiler; host tracing
    is kept to the spans (no Python tracer), so the device is not starved."""
    import jax

    from perfbench import xplane

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with span(WINDOW_SPAN):
            records = [session.step() for _ in range(session.trace_steps)]
    finally:
        jax.profiler.stop_trace()
    files = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    trace = xplane.load(files[-1], span_prefix=SPAN_PREFIX,
                        window_span=SPAN_PREFIX + WINDOW_SPAN,
                        **(trace_layout or {}))
    return records, trace


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_process: float, gate: Callable = require_tpu,
             peaks: Optional[Dict[str, Any]] = None,
             trace_layout: Optional[Dict[str, Any]] = None) -> str:
    """Set up, warm up, measure, check; returns the result line. ``gate``,
    ``peaks`` and ``trace_layout`` are for the CPU rehearsals of the tests:
    the command never passes them."""
    import jax

    from perfbench import xplane

    cache_dir = enable_compile_cache(cell.root)
    devices = gate(cell.chips)
    if peaks is None:
        peaks = load_peaks(devices[0].device_kind)
    meter = CompileMeter()
    note(perfbench="start", workload=cell.name, seed=seed, trace=trace,
         device_kind=devices[0].device_kind, devices=len(jax.devices()),
         compile_cache=cache_dir)

    runner = importlib.import_module(
        f"perfbench.runners.{cell.config['runner']}")
    session = runner.Session(cell, seed, devices)
    setup_s = time.perf_counter() - t_process
    note(perfbench="set-up done", setup_s=round(setup_s, 2),
         peak_bytes=peak_bytes(devices),
         memory_stats=devices[0].memory_stats(), **meter.snapshot())

    programs_before = meter.programs
    counters_before = program_counters()
    t0 = time.perf_counter()
    if trace:
        records, reduced = _traced_window(
            session, cell.root / ".perfbench_trace" / cell.name, trace_layout)
    else:
        records, reduced = _run_window(session, seconds), None
    t1 = time.perf_counter()
    compiles = meter.programs - programs_before
    counters_after = program_counters()
    counters = {k: v - counters_before.get(k, 0.0)
                for k, v in counters_after.items()}
    problems = list(session.problems) + session.finish(records)
    if compiles:
        problems.append(f"{compiles} programs compiled inside the window")
    attempted = sum(int(r["attempted"]) for r in records)
    failed = sum(int(r["failed"]) for r in records)
    note(perfbench="steps", records=records)
    note(perfbench="window done", steps=len(records),
         window_s=round(t1 - t0, 3), attempted=attempted, failed=failed,
         problems=problems, **meter.snapshot())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": peak_bytes(devices)}
    result: Dict[str, Any] = {"correct": not problems and failed == 0,
                              "attempted": attempted, "failed": failed}
    if trace:
        ctx = LayerContext(
            cell=cell, records=records, counters=counters,
            compiles_in_window=compiles, trace=reduced, peaks=peaks,
            peak_bytes=device["memory_peak_bytes"])
        result["metrics"] = read_layer_metrics(ctx)
        lo, hi = reduced.window
        device["busy_s"] = xplane.busy_seconds(reduced, lo, hi)
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = xplane.breakdown(reduced, lo, hi)
    else:
        values = dict(session.end_to_end(records), setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
    result["device"] = device
    return json.dumps(result)
