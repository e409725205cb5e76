"""From a profiler trace to busy time, idle share and a breakdown.

A trace is reduced in two steps, so that the second can be checked on a
small recorded trace without a chip:

1. ``read_xplane`` pulls three kinds of interval out of the
   ``.xplane.pb`` that ``jax.profiler`` writes: device operations (the
   ``XLA Ops`` line of every TPU plane), device programs (its ``XLA
   Modules`` line) and the benchmark's own host spans
   (``jax.profiler.TraceAnnotation`` names on the host plane).
2. ``summarize`` puts the device's intervals on the host's clock, clips
   them to the traced window (the host span named ``window``) and returns
   the device-busy seconds (the union of the operation intervals,
   averaged over the devices), the top operations by device time, and the
   idle gaps, each named by the host span that overlaps it most.

The two clocks of a TPU trace disagree by a fraction of a millisecond
(on a v5e a program's first operation can be stamped before its own
dispatch began), a good part of the ~2 ms gaps between calls.  So the
device's intervals are shifted by one offset per device: the median over
calls of the time from a program's start to the end of its ``dispatch``
span, the moment the program was handed to the device.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import statistics
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
HOST_SPANS = ("dispatch", "block", "next_input")


@dataclasses.dataclass
class Trace:
    """Intervals in nanoseconds: ``device[plane] = [(op, start, end)]``,
    ``modules[plane] = [(program, start, end)]`` and ``host = [(span,
    start, end)]``.  Operation names are the HLO instruction's name
    (``fusion.63``, ``walk_transition_ragged.8``)."""

    device: dict
    modules: dict
    host: list

    def to_json(self) -> dict:
        return {"device": self.device, "modules": self.modules, "host": self.host}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        def tuples(rows):
            return [tuple(r) for r in rows]

        return cls({k: tuples(v) for k, v in obj["device"].items()},
                   {k: tuples(v) for k, v in obj["modules"].items()},
                   tuples(obj["host"]))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _op_name(text: str) -> str:
    """``%fusion.63 = f32[8192]... fusion(...)`` -> ``fusion.63``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str, span_names=(WINDOW,) + HOST_SPANS) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, modules, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [(_op_name(e.name), e.start_ns, e.end_ns)
                                          for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.end_ns)
                        for line in plane.lines for e in line.events
                        if e.name in span_names)
    device = {k: v for k, v in device.items() if v}
    return Trace(device, {k: modules.get(k, []) for k in device}, host)


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _leaves(ops):
    """Operations that enclose no other operation: a ``while`` op spans
    its whole loop on the same line as the operations of its body."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent, stack = set(), []
    for i in order:
        s, e = ops[i][1], ops[i][2]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            parent.add(stack[-1])
        stack.append(i)
    return [ops[i] for i in range(len(ops)) if i not in parent]


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def clock_offset(modules, host) -> float:
    """Nanoseconds to add to device times to put them on the host clock."""
    dispatch = sorted((s, e) for n, s, e in host if n == "dispatch")
    starts = sorted(s for _, s, _ in modules)
    if not starts or len(starts) != len(dispatch):
        return 0.0
    return statistics.median(d[1] - s for d, s in zip(dispatch, starts))


@dataclasses.dataclass
class Summary:
    busy_s: float  # union of device op intervals in the window, mean over devices
    window_s: float  # length of the traced window
    device_span_s: float  # first op start to last op end in the window, mean
    top_ops: list  # [[op, seconds]] by total device time, at most 10
    gaps: list  # [[host span, seconds]] the longest idle gaps, at most 10
    gap_totals: dict  # host span -> idle seconds in the window
    offset_s: dict  # device plane -> seconds added to put it on the host clock

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(trace: Trace, top: int = 10) -> Summary:
    windows = [(s, e) for name, s, e in trace.host if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one '{WINDOW}' span, found {len(windows)}")
    w0, w1 = windows[0]
    spans = [(n, s, e) for n, s, e in trace.host if n != WINDOW]
    if not trace.device:
        raise ValueError("the trace holds no device operation")
    busy, first_last, op_time, offsets = [], [], defaultdict(float), {}
    gaps, gap_totals = [], defaultdict(float)
    for plane, ops in trace.device.items():
        shift = clock_offset(trace.modules.get(plane, []), trace.host)
        offsets[plane] = shift * 1e-9
        inside = [(n, max(s + shift, w0), min(e + shift, w1)) for n, s, e in ops
                  if e + shift > w0 and s + shift < w1]
        for n, s, e in _leaves(inside):
            op_time[n] += e - s
        merged = _union((s, e) for _, s, e in inside)
        busy.append(sum(e - s for s, e in merged))
        first_last.append(merged[-1][1] - merged[0][0] if merged else 0)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            best = max(spans, key=lambda sp: _overlap(g0, g1, sp[1], sp[2]), default=None)
            name = best[0] if best and _overlap(g0, g1, best[1], best[2]) > 0 else "other"
            gaps.append((name, g1 - g0))
            gap_totals[name] += g1 - g0
    ndev = len(trace.device)
    gaps.sort(key=lambda g: -g[1])
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])
    return Summary(
        busy_s=sum(busy) / ndev * 1e-9,
        window_s=(w1 - w0) * 1e-9,
        device_span_s=sum(first_last) / ndev * 1e-9,
        top_ops=[[n, t / ndev * 1e-9] for n, t in ranked[:top]],
        gaps=[[n, t * 1e-9] for n, t in gaps[:top]],
        gap_totals={n: t / ndev * 1e-9 for n, t in gap_totals.items()},
        offset_s=offsets,
    )
