"""The benchmark's driver: resolve a cell by name, check the chip, set up,
measure a window, check the outputs, print one result line.

Everything that belongs to one cell is found by name, so a cell, a
configuration, a traffic mix or a per-layer metric is added with files
and ``BENCHMARK.json`` entries alone:

* ``BENCHMARK.json``: the cell (``workloads``), its configuration entry
  (``configs[].file``) and the metrics that apply to it;
* ``chipbench/traffic/<traffic>.json``: the traffic mix, which names its
  operation;
* ``chipbench/ops/<op>.py``: the driver of that operation (``build``);
* ``chipbench/limits/<cell>.json``: the limit of each number compared;
* ``chipbench/metrics/<metric>.py``: the reader of each per-layer metric
  (``read(ctx)``, returning ``None`` where it finds nothing to read).
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, "traces")
TRACE_SECONDS = 4.0  # longest traced stretch; a few calls of every cell


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module for {name!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_spec(cell: str, root: str = ROOT) -> dict:
    """Everything the named cell needs, read from its files."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"unknown workload {cell!r}; known: {sorted(cells)}")
    work = cells[cell]
    entry = {c["name"]: c for c in bench["configs"]}[work["config"]]
    # an end-to-end metric without a list of cells is every cell's; a
    # per-layer metric always lists the cells in which it finds something
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    return {
        "cell": cell,
        "chips": int(work["chips"]),
        "config": _load_json(os.path.join(root, entry["file"])),
        "traffic": _load_json(os.path.join(HERE, "traffic", f"{work['traffic']}.json")),
        "limits": _load_json(os.path.join(HERE, "limits", f"{cell}.json")),
        "end_to_end": e2e,
        "per_layer": [m for m in bench["per_layer"] if cell in m["workloads"]],
        "peaks": _load_json(os.path.join(HERE, "peaks.json")),
    }


def check_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache():
    """JAX's persistent cache at one fixed path inside the checkout, for
    every program however fast it compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts executables JAX builds or loads, and persistent-cache hits
    and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.compiles = 0
        self.events = {"hits": 0, "misses": 0}
        self._compile_event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._compile_event:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.events["misses"] += 1


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class _GcClock:
    """Seconds the host spent in Python's garbage collector."""

    def __init__(self):
        self.pauses = []
        self._t = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)
            self._t = None


def _window(op, seconds, first_call):
    """Calls back to back until ``seconds`` have passed; each call is
    finished (``block_until_ready``) before the next is prepared.  Returns
    the calls, their end times, and each call's host phases (seconds in
    ``next_input``, ``dispatch`` and ``block``)."""
    import gc

    import jax

    calls, ends, phases = [], [], []
    gc_clock = _GcClock()
    gc.callbacks.append(gc_clock)
    t0 = time.perf_counter()
    try:
        with _span("window"):
            while True:
                i = first_call + len(calls)
                t_a = time.perf_counter()
                with _span("next_input"):
                    inp = op.next_input(i)
                t_b = time.perf_counter()
                with _span("dispatch"):
                    out = op.launch(inp)
                t_c = time.perf_counter()
                with _span("block"):
                    jax.block_until_ready(out)
                t_d = time.perf_counter()
                calls.append(i)
                ends.append(t_d - t0)
                phases.append((t_b - t_a, t_c - t_b, t_d - t_c))
                if ends[-1] >= seconds:
                    return calls, ends, phases, gc_clock.pauses
    finally:
        gc.callbacks.remove(gc_clock)


def _peak_bytes(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _emit(stream, obj):
    print(json.dumps(obj), file=stream, flush=True)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *, t_start: float,
             require_chip: bool = True, out=None, err=None) -> int:
    """One run of one cell; prints the result as the last line of ``out``.

    ``require_chip=False`` skips the look for a TPU (tests drive the rest
    of a run on the CPU that way)."""
    out = out or sys.stdout
    err = err or sys.stderr
    import jax

    devices = check_chips(spec["chips"]) if require_chip else jax.devices()[:spec["chips"]]
    peak = spec["peaks"].get(devices[0].device_kind) if require_chip else None
    if require_chip and peak is None:
        raise KeyError(f"device kind {devices[0].device_kind!r} is not in peaks.json")
    enable_compile_cache()
    counter = CompileCounter()
    try:
        return _run(spec, seed, seconds, trace, t_start, devices, peak, counter, out, err)
    finally:
        counter.close()


def _run(spec, seed, seconds, trace, t_start, devices, peak, counter, out, err):
    import jax

    op_module = _load_module("ops", spec["traffic"]["op"])
    op = op_module.build(spec, seed)
    op.warmup()
    setup_s = time.perf_counter() - t_start
    setup_compiles = counter.compiles
    _emit(out, {"phase": "setup", "setup_s": setup_s, "phases": op.phases,
                "compiles": setup_compiles, "cache": dict(counter.events),
                "cache_dir": CACHE_DIR})

    first = op.calls_made
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        try:
            calls, ends, phases, gc_pauses = _window(op, min(seconds, TRACE_SECONDS),
                                                     first)
        finally:
            jax.profiler.stop_trace()
    else:
        calls, ends, phases, gc_pauses = _window(op, seconds, first)
    window_s = ends[-1]
    window_compiles = counter.compiles - setup_compiles
    memory_peak = _peak_bytes(devices)
    call_s = np.diff(ends, prepend=0.0)
    slowest = int(call_s.argmax())
    _emit(out, {"phase": "window", "calls": len(calls), "window_s": window_s,
                "call_s": {"min": call_s.min(), "median": float(np.median(call_s)),
                           "max": call_s.max(), "slowest": slowest},
                "slowest_call_s": dict(zip(("next_input", "dispatch", "block"),
                                           phases[slowest])),
                "median_call_s": dict(zip(("next_input", "dispatch", "block"),
                                          np.median(phases, axis=0).tolist())),
                "gc_s": {"total": sum(gc_pauses), "max": max(gc_pauses, default=0.0),
                         "count": len(gc_pauses)},
                "compiles": window_compiles, "memory_peak_bytes": memory_peak})

    failed = op.failed_calls(calls)
    counts = op.trace_counts(calls) if trace else {}
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    numbers = op.compare(calls, rng)
    numbers["failed_calls"] = failed
    numbers["window_compiles"] = window_compiles
    limits = dict(spec["limits"], failed_calls=0, window_compiles=0)
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    metrics, breakdown = {}, None
    if trace:
        from chipbench import traces

        reduced = traces.read_xplane(traces.find_xplane(TRACE_DIR))
        with open(os.path.join(TRACE_DIR, "intervals.json"), "w") as f:
            json.dump(reduced.to_json(), f)
        summary = traces.summarize(reduced)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = {"summary": summary, "counts": counts, "peak": peak}
        for m in spec["per_layer"]:
            value = _load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": summary.top_ops, "idle_gaps": summary.gaps}
        _emit(out, {"phase": "trace", "busy_s": summary.busy_s,
                    "window_s": summary.window_s,
                    "device_span_s": summary.device_span_s,
                    "idle_by_span_s": summary.gap_totals,
                    "clock_offset_s": summary.offset_s, "counts": counts})
    else:
        units = op.units_per_call * len(calls)
        for m in spec["end_to_end"]:
            if m["name"] == "setup_s":
                value = setup_s
            elif m["name"] == op.rate_metric:
                value = units / window_s
            else:
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    result = {"correct": correct, "attempted": len(calls), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}", file=err,
              flush=True)
    _emit(out, result)
    return 0


def main(argv=None, t_start=None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    try:
        return run_cell(spec, args.seed, args.seconds, bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
