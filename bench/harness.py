"""One benchmark run of one cell: registry, set-up, the measured window,
the correctness comparison, the per-layer readers, and the result line.

Everything a cell needs is found by name from its entry in
``BENCHMARK.json``: the configuration's JSON file and the reference
module beside it (``bench/configs/<config>.py``), the traffic mix
(``bench/traffic/<mix>.json``), the driver of the mix's ``kind``
(``bench/kinds/<kind>.py``), its arrival process where it has one
(``bench/arrivals/<process>.py``), the limits of its comparison
(``bench/limits/<cell>.json``) and one reader per per-layer metric
(``bench/metrics/<metric>.py``). Adding a cell adds files and entries;
nothing here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------- registry

def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    root: str                        # the checkout its files came from
    chips: int
    config_name: str
    spec: Dict[str, Any]             # the configuration's JSON
    model: Any                       # bench/configs/<config>.py
    traffic_name: str
    traffic: Dict[str, Any]          # bench/traffic/<mix>.json
    limits: Dict[str, float]         # bench/limits/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", None) in e2e_names if "moves" in metric \
        else True


def load_cell(name: str, root: str = ROOT) -> Cell:
    """A cell of ``BENCHMARK.json`` with everything it names loaded, or
    one of ``bench/pending.json``: cells built and tested on the CPU whose
    chip readings are still to come (the same entries, moved into
    ``BENCHMARK.json`` once measured)."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    pending = os.path.join(root, "bench", "pending.json")
    if os.path.exists(pending):
        more = read_json(pending)
        bench = {k: v + more.get(k, []) if isinstance(v, list) else v
                 for k, v in bench.items()}
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json or "
                       f"bench/pending.json; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg_path = os.path.join(root, cfg["file"])
    model = load_module(os.path.splitext(cfg_path)[0] + ".py",
                        f"bench_config_{len(sys.modules)}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name=name, root=root, chips=int(w["chips"]),
                config_name=w["config"],
                spec=read_json(cfg_path), model=model,
                traffic_name=w["traffic"],
                traffic=read_json(os.path.join(
                    root, "bench", "traffic", f"{w['traffic']}.json")),
                limits=read_json(os.path.join(
                    root, "bench", "limits", f"{name}.json")),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, root: str = ROOT) -> Callable:
    return load_module(os.path.join(root, "bench", "metrics", f"{name}.py"),
                       f"bench_metric_{len(sys.modules)}").read


def driver_class(kind: str, root: str = ROOT):
    """The driver of a traffic ``kind``: ``DRIVER`` of
    ``bench/kinds/<kind>.py``."""
    return load_module(os.path.join(root, "bench", "kinds", f"{kind}.py"),
                       f"bench_kind_{len(sys.modules)}").DRIVER


# ------------------------------------------------------------------ jax

def configure_jax() -> None:
    """The persistent compilation cache at a fixed path in the checkout,
    whatever the host sets, and every program kept in it. Call before
    the first compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Counts XLA compilations and their seconds (JAX monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class Probe:
    """Counts, stamps and annotates the calls the harness makes into the
    program. ``wrap`` replaces a callable with one that counts its calls
    while ``counting`` is set and, when tracing, opens a
    ``bench.<name>`` span around it. Nothing blocks: JAX's asynchronous
    dispatch is left as the program has it."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.counting = False
        self.counts: Dict[str, int] = {}
        self.hooks: Dict[str, Callable] = {}

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def wrap(self, name: str, fn: Callable) -> Callable:
        def wrapped(*args, **kwargs):
            if self.counting:
                self.counts[name] = self.counts.get(name, 0) + 1
            hook = self.hooks.get(name)
            if hook is not None:
                hook(args)
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ------------------------------------------------------------------ run

def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False,
             runtime_hook: Optional[Callable] = None,
             devices=None) -> Dict[str, Any]:
    """Set up, measure, compare and read one run; returns the result
    object (the contract's last line). ``runtime_hook(runtime)`` may
    replace the program's callables (the fault tests break the timed
    path with it); ``control`` adds the control's numbers."""
    import jax

    devices = devices if devices is not None else jax.devices()[:cell.chips]
    kind = cell.traffic["kind"]
    driver = driver_class(kind, cell.root)(cell, seed=seed, seconds=seconds,
                                           devices=devices,
                                           probe=Probe(annotate=trace))
    clock = CompileClock()
    driver.setup(runtime_hook=runtime_hook)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s, {clock.count} compiles "
        f"({clock.seconds:.3f} s)")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    before = clock.count
    try:
        ctx = (jax.profiler.trace(trace_dir) if trace
               else contextlib.nullcontext())
        with ctx:
            window = driver.window(seconds)
        in_window = clock.count - before
        log(f"compiles inside the window: {in_window}")
        reduced = None
        if trace:
            from bench import trace_reduce
            reduced = trace_reduce.reduce_trace(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    peak = memory_peak_bytes(devices)
    checks = driver.check(control=control)
    limits = cell.limits
    correct = verdict(checks, limits, window["failed"])

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        metrics = read_per_layer(cell, {
            "driver": driver, "window": window, "trace": reduced,
            "chips": len(devices),
            "device_kind": dev0.device_kind})
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise KeyError(f"the {kind} driver reports no "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": correct, "attempted": window["attempted"],
           "failed": window["failed"], "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    if control:
        # the control in the program's place, judged as the program is
        out["control"] = checks["control"]
        out["control_correct"] = verdict(checks["control"], limits, 0)
        for k, v in out["control"].items():
            log(f"control {k}: {v!r} (limit {limits.get(k)!r})")
        log(f"control correct: {out['control_correct']}")
    # the numbers compared, each beside its limit: last on stderr and
    # last in the result line
    out["checks"] = {k: {"value": checks.get(k), "limit": limits[k]}
                     for k in limits}
    for k in limits:
        log(f"check {k}: {checks.get(k)!r} (limit {limits[k]!r})")
    return out


def verdict(checks: Dict[str, Any], limits: Dict[str, float],
            failed: int) -> bool:
    """``correct``: nothing failed, and every number compared is at or
    under its limit (a number that is missing or not finite fails)."""
    import math
    return bool(failed == 0 and all(
        isinstance(checks.get(k), float) and math.isfinite(checks[k])
        and checks[k] <= limits[k] for k in limits))


def read_per_layer(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Run each per-layer reader; a reader that finds nothing returns
    None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
