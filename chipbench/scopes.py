"""Device seconds by the program's own named scopes, from a profiler trace.

The program names its layers with ``jax.named_scope`` (``walk_transition``,
``fleet_sgd``, ``fleet_average``, ``fleet_loss_eval``).  A scope reaches
the compiled program as the HLO metadata ``op_name`` of each instruction,
and ``jax.profiler`` writes the HLO of every program that ran into the
trace's ``/host:metadata`` plane.  So a trace alone says which scope each
device operation belongs to:

1. ``program_scopes`` reads, from the ``.xplane.pb``, each program's
   scope path by instruction, keyed as the trace's ``XLA Modules`` line
   names the program's runs (``jit__fleet_scan(926510353011395910)``: the
   module's name and its program id).
2. ``scope_seconds`` counts the device operations of the traced window
   exactly as ``traces.summarize`` does (the same clock offset, the same
   clipping to the window, the same ``_leaves`` rule), each under the top
   scope of the program run that encloses it, or ``unscoped``.

Metric readers (``chipbench/metrics``) call ``window_scopes``, which reads
the traced window that the run left under ``harness.TRACE_DIR``.  Where the
program names no scope, as before it did, no scope but ``unscoped`` is
found, and the readers return ``None``.
"""
from __future__ import annotations

import bisect
import json
import os
from collections import defaultdict

from chipbench import traces

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
UNSCOPED = "unscoped"
# name-stack components JAX adds for control flow; never a named scope
_STRUCTURE = frozenset({"while", "body", "cond", "closed_call", "pallas_call",
                        "shard_map"})


# --- protobuf wire format, for the few fields read here -------------------

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one serialized message:
    an int for a varint, a memoryview for anything length-delimited."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map int64 ->
# XEventMetadata), stat_metadata = 5 (map int64 -> XStatMetadata);
# XEventMetadata: id = 1, name = 2, stats = 5; XStatMetadata: id = 1,
# name = 2; XStat: metadata_id = 1, bytes_value = 6.

def _metadata_plane(xspace):
    for number, plane in _fields(xspace):
        if number != 1:
            continue
        for field, value in _fields(plane):
            if field == 2:
                if _text(value) == METADATA_PLANE:
                    return plane
                break
    return None


def _map_values(plane, number):
    for field, entry in _fields(plane):
        if field == number:
            for key, value in _fields(entry):
                if key == 2:
                    yield value


def _hlo_protos(plane):
    """``(program id, program name, serialized HloProto)``: the name is the
    one the ``XLA Modules`` line gives the program's runs."""
    stat_names = {}
    for meta in _map_values(plane, 5):
        f = dict(_fields(meta))
        stat_names[f.get(1, 0)] = _text(f.get(2, b""))
    for meta in _map_values(plane, 4):
        pid, name, proto = 0, "", None
        for field, value in _fields(meta):
            if field == 1:
                pid = value
            elif field == 2:
                name = _text(value)
            elif field == 5:
                stat = dict(_fields(value))
                if stat_names.get(stat.get(1)) == HLO_PROTO_STAT and 6 in stat:
                    proto = stat[6]
        if proto is not None:
            yield pid, name, proto


# HloProto.hlo_module = 1; HloModuleProto: name = 1, computations = 3;
# HloComputationProto.instructions = 2; HloInstructionProto: name = 1,
# metadata = 7; OpMetadata.op_name = 2.

def hlo_op_names(hlo_proto) -> tuple[str, dict]:
    """``(module name, {instruction: op_name})`` of a serialized HloProto,
    over every computation of the module (loop bodies and fusions too)."""
    module_name, op_names = "", {}
    for number, module in _fields(hlo_proto):
        if number != 1:
            continue
        for field, value in _fields(module):
            if field == 1:
                module_name = _text(value)
            elif field == 3:
                for kind, inst in _fields(value):
                    if kind != 2:
                        continue
                    name, op_name = None, ""
                    for f, v in _fields(inst):
                        if f == 1:
                            name = _text(v)
                        elif f == 7:
                            op_name = next((_text(x) for k, x in _fields(v) if k == 2), "")
                    if name is not None:
                        op_names[name] = op_name
    return module_name, op_names


def scope_path(op_name: str) -> str:
    """The named scopes of an HLO ``op_name``, outermost first.

    ``jit(_fleet_scan)/while/body/closed_call/walk_transition/jit(_uniform)/add``
    gives ``walk_transition``.  The first component names the program and
    the last the primitive; a component with parentheses is a transform
    (``vmap()``); a nested ``jit(...)`` is a library call, whose inner names
    are not the program's; control flow adds ``while``, ``body`` and the
    like.  Empty where the operation is in no named scope."""
    named = []
    for part in op_name.split("/")[1:-1]:
        if "jit(" in part:
            break
        if "(" in part or part in _STRUCTURE or part.startswith("branch_"):
            continue
        named.append(part)
    return "/".join(named)


def top_scope(path) -> str:
    return (path or "").split("/", 1)[0] or UNSCOPED


def program_scopes(xplane_path: str) -> dict:
    """``{program: {instruction: scope path}}`` of every program whose HLO
    the trace holds, keyed ``<module>(<program id>)`` as the ``XLA Modules``
    line names its runs."""
    with open(xplane_path, "rb") as f:
        plane = _metadata_plane(f.read())
    programs = {}
    if plane is None:
        return programs
    for pid, name, proto in _hlo_protos(plane):
        module, op_names = hlo_op_names(proto)
        programs[name or f"{module}({pid})"] = {
            op: scope_path(op_name) for op, op_name in op_names.items()}
    return programs


def scope_seconds(trace: traces.Trace, programs: dict) -> dict:
    """Device seconds of each top-level scope in the traced window, plus
    ``unscoped``, averaged over the devices: every leaf operation that
    ``traces.summarize`` counts, once, under the scope its instruction has
    in the program run that encloses it."""
    windows = [(s, e) for name, s, e in trace.host if name == traces.WINDOW]
    if len(windows) != 1 or not trace.device:
        return {}
    w0, w1 = windows[0]
    seconds = defaultdict(float)
    for plane, ops in trace.device.items():
        modules = trace.modules.get(plane, [])
        shift = traces.clock_offset(modules, trace.host)
        runs = sorted((s + shift, e + shift, name) for name, s, e in modules)
        starts = [r[0] for r in runs]
        inside = [(n, max(s + shift, w0), min(e + shift, w1)) for n, s, e in ops
                  if e + shift > w0 and s + shift < w1]
        for n, s, e in traces._leaves(inside):
            k = bisect.bisect_right(starts, s) - 1
            table = programs.get(runs[k][2], {}) if k >= 0 and e <= runs[k][1] else {}
            seconds[top_scope(table.get(n))] += e - s
    ndev = len(trace.device)
    return {name: t / ndev * 1e-9 for name, t in seconds.items()}


_cache = {}


def window_scopes(trace_dir: str | None = None) -> dict:
    """``scope_seconds`` of the traced window a run left in ``trace_dir``
    (``harness.TRACE_DIR``): its reduced intervals (``intervals.json``) and
    the programs' HLO in its ``.xplane.pb``.  Empty where there is no
    trace.  Read once per trace, however many readers ask."""
    if trace_dir is None:
        from chipbench import harness

        trace_dir = harness.TRACE_DIR
    try:
        xplane = traces.find_xplane(trace_dir)
        intervals = os.path.join(trace_dir, "intervals.json")
        key = (xplane, os.stat(xplane).st_mtime_ns, os.stat(intervals).st_mtime_ns)
    except (FileNotFoundError, OSError):
        return {}
    if key not in _cache:
        with open(intervals) as f:
            trace = traces.Trace.from_json(json.load(f))
        _cache.clear()
        _cache[key] = scope_seconds(trace, program_scopes(xplane))
    return _cache[key]


def per_step_ms(ctx, scope: str, trace_dir: str | None = None):
    """Device milliseconds of ``scope`` a fleet step, over the traced
    window's ``fleet_steps``; ``None`` where the scope or the count is
    absent."""
    steps = ctx["counts"].get("fleet_steps")
    seconds = window_scopes(trace_dir).get(scope) if steps else None
    if not seconds:
        return None
    return 1e3 * seconds / steps
