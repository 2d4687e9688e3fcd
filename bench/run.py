#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
repository root: its configuration ``bench/configs/<config>.json``, its
traffic ``bench/traffic/<traffic>.json`` (which names its generator), the
cell kind's driver ``bench/<kind>_cell.py``, each per-layer metric's reader
``bench/metrics/<metric>.py`` and each cell's limits
``bench/limits/<workload>.json``.  A later change adds a cell, a traffic
mix or a metric by adding files and entries, without editing this one.

A run sets up (compile cache, state and inputs on the device from
``--seed``, a warm-up of every program the window drives), measures for
``--seconds``, then checks what the timed path produced against a plain
reference that imports nothing of the program.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; its last key, ``checks``, holds every number compared with
its limit, which are also the last lines of standard error.

Without a TPU, with fewer chips than the cell asks for, or on a device
kind that ``bench/peaks.json`` does not list, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

T_START = time.perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")
# JAX's persistent compilation cache: one fixed directory inside the
# checkout, handed to the program through the variable it reads
COMPILE_CACHE = os.path.join(ROOT, ".jax_cache")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class SetupError(RuntimeError):
    """The run cannot start: no accelerator, too few chips, an unknown
    device kind, a cell whose files are missing, or a counter that a
    reader declares and the step does not return."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------


def load_json(*parts: str) -> Any:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise SetupError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise SetupError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def kind(self) -> str:
        return self.config["kind"]


def find_cell(name: str, root: str = ROOT) -> Cell:
    manifest = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    bench = os.path.join(root, "bench")
    config = load_json(bench, "configs", w["config"] + ".json")
    traffic = load_json(bench, "traffic", w["traffic"] + ".json")
    if config["kind"] != traffic["kind"]:
        raise SetupError(f"{name}: a {traffic['kind']} traffic mix on a "
                         f"{config['kind']} configuration")
    limits = load_json(bench, "limits", name + ".json")
    e2e = [m for m in manifest["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e, layer)


def generator(cell: Cell):
    return load_module(os.path.join(BENCH, "traffic",
                                    cell.traffic["generator"] + ".py"),
                       "bench_traffic_" + cell.traffic["generator"])


def reference(cell: Cell):
    return load_module(os.path.join(BENCH, "reference",
                                    cell.config["reference"] + ".py"),
                       "bench_reference_" + cell.config["reference"])


def reader_module(metric: str):
    """A per-layer metric's reader module: ``read(run)``, and optionally
    ``COUNTERS``, the names of scalars of the train step's metrics that it
    reads per window step from ``run.data["counters"]``."""
    return load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_"))


def reader(metric: str) -> Callable:
    return reader_module(metric).read


def step_counters(cell: Cell) -> Tuple[str, ...]:
    """The union of the ``COUNTERS`` that the cell's per-layer readers
    declare, in the manifest's order."""
    return tuple(dict.fromkeys(
        c for m in cell.per_layer
        for c in getattr(reader_module(m["name"]), "COUNTERS", ())))


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def peaks_of(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")
    if kind not in table["devices"]:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json; "
                         f"known: {sorted(table['devices'])}")
    return table["devices"][kind]


def device_check(chips: int, require_tpu: bool = True):
    """The devices the cell runs on, and their peaks."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SetupError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SetupError(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
    peaks = peaks_of(devs[0].device_kind) if require_tpu else None
    return devs, peaks


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_key(seed: int):
    """The run's JAX key: all bits of the seed, not only the low 32."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclass
class Run:
    """What a cell driver gets, and what it fills in."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list = field(default_factory=list)
    peaks: Optional[dict] = None
    # filled by the driver
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    end_to_end: Dict[str, float] = field(default_factory=dict)
    checks: List[Tuple[str, float, float]] = field(default_factory=list)
    memory_peak_bytes: Optional[int] = None
    device_summary: Any = None          # devtrace.DeviceSummary (traced)
    data: Dict[str, Any] = field(default_factory=dict)   # for readers
    compiles: List[float] = field(default_factory=list)  # perf_counter

    def mark_setup_done(self) -> None:
        self.setup_s = time.perf_counter() - T_START

    def compiles_between(self, t0: float, t1: float) -> int:
        """JAX traces and backend compiles that started in [t0, t1]."""
        return sum(t0 <= t <= t1 for t in self.compiles)

    def span(self, name: str):
        """A harness span on the profiler's clock (traced runs only)."""
        if not self.trace:
            return nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextmanager
    def profiled(self):
        """Profile the block when tracing; the trace goes to a fixed
        directory inside the checkout, emptied first."""
        if not self.trace:
            yield
            return
        import jax
        tdir = os.path.join(OUT, "trace", self.cell.name)
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        self.data["trace_dir"] = tdir

    def reduce_trace(self) -> None:
        from bench import devtrace
        path = devtrace.find_xplane(self.data["trace_dir"])
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        tr = devtrace.load_xplane(path)
        window = tr.window("bench.window")
        if window is None:
            raise RuntimeError("no bench.window span in the trace")
        used = [str(d.id) for d in self.devices[:self.cell.chips]]
        devs = [d for d in used if d in tr.devices] or None
        self.device_summary = devtrace.summarize(tr, window, devs)
        self.data["trace"] = tr
        self.data["trace_window"] = window


def passes(value: float, limit: float) -> bool:
    """The check's rule for one compared number: finite, and at most its
    limit."""
    return math.isfinite(value) and value <= limit


def _num(x: float):
    return x if math.isfinite(x) else str(x)


def result_line(run: Run, correct: bool) -> dict:
    import jax
    d0 = run.devices[0] if run.devices else jax.devices()[0]
    metrics: Dict[str, dict] = {}
    if run.trace:
        for m in run.cell.per_layer:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in run.cell.end_to_end:
            if m["name"] in run.end_to_end:
                metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": bool(correct), "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    s = run.device_summary
    if run.trace and s is not None:
        device["busy_s"] = s.busy_ns / 1e9
        device["window_s"] = s.window_ns / 1e9
        out["breakdown"] = {"device_ops": s.top_ops(10),
                            "idle_gaps": s.top_gaps(10)}
    out["checks"] = {name: {"value": _num(v), "limit": lim}
                     for name, v, lim in run.checks}
    return out


def execute(workload: str, seed: int, seconds: float, trace: bool,
            require_tpu: bool = True, cell: Optional[Cell] = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    A test passes ``require_tpu=False`` and a ``cell`` shrunk to a size
    the CPU can hold."""
    cell = cell or find_cell(workload)
    devices, peaks = device_check(cell.chips, require_tpu)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run = Run(cell, seed, seconds, trace, devices[:cell.chips], peaks)
    import jax

    def on_compile(event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            run.compiles.append(time.perf_counter() - duration)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    driver = load_module(os.path.join(BENCH, cell.kind + "_cell.py"),
                         "bench_" + cell.kind + "_cell")
    correct = driver.run(run)
    run.end_to_end.setdefault("setup_s", run.setup_s)
    if trace:
        run.reduce_trace()
    correct = correct and all(passes(v, lim) for _, v, lim in run.checks)
    return result_line(run, correct)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = COMPILE_CACHE
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        out = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except SetupError as e:
        log(f"bench: {e}")
        return 2
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
