"""Reduction of a profiler trace (``.xplane.pb``) to the device's busy time,
its busiest operations and its idle gaps, each gap put down to what the
host was doing in it.

Device planes are the ``/device:<KIND>:<n>`` planes; their operations are
the events of the ``XLA Ops`` line.  Host activity is the benchmark's own
``TraceAnnotation`` spans (names starting ``bench.``) on any host thread.
The traced window is the ``bench.traced_steps`` span when there is one,
else the extent of the device's operations.
"""
from __future__ import annotations

import collections
import glob
import os
import re

import jax

HLO_OP = re.compile(r"(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")
DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced_steps"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(merged, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[..] fusion(...)`` -> ``%fusion.12 fusion``."""
    m = HLO_OP.match(text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def self_times(ops, lo, hi):
    """Time of each op inside [lo, hi] less the time of the ops nested in
    it (a ``while`` holds its body's operations)."""
    out = collections.Counter()
    stack = []                               # (end, name) of open ops
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(e, stack[-1][0]) - s
        out[name] += e - s
        stack.append((e, name))
    return out


def reduce(path: str) -> dict:
    """busy_s (mean over the device planes that ran operations), window_s,
    device_ops [[name, self seconds]] and idle_gaps [[host span, seconds]],
    each list the top ten by seconds."""
    data = jax.profiler.ProfileData.from_file(path)
    devices = []      # per plane: list of (start_ns, end_ns, name)
    spans = []        # (start_ns, end_ns, name) of host annotations
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(e.start_ns, e.end_ns, e.name) for line in plane.lines
                   if line.name == OPS_LINE for e in line.events]
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            spans += [(e.start_ns, e.end_ns, e.name) for line in plane.lines
                      for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    if not devices:
        raise ValueError(f"no device operations in {path}")
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for ops in devices for s, _, _ in ops)
        hi = max(e for ops in devices for _, e, _ in ops)
    window_ns = hi - lo

    busy = []
    for ops in devices:
        merged = _clip(_union((s, e) for s, e, _ in ops), lo, hi)
        busy.append(sum(e - s for s, e in merged))

    per_op = collections.Counter()
    for ops in devices:
        named = [(s, e, op_name(n)) for s, e, n in ops]
        for name, ns in self_times(named, lo, hi).items():
            per_op[name] += ns / len(devices)

    merged = _clip(_union((s, e) for s, e, _ in devices[0]), lo, hi)
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    per_gap = collections.Counter()
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = (s + e) / 2
        covering = [(ee - ss, n) for ss, ee, n in inner if ss <= mid <= ee]
        per_gap[min(covering)[1] if covering else "(no bench span)"] += e - s

    def top(counter):
        return [[name, ns / 1e9] for name, ns in counter.most_common(TOP)]

    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": window_ns / 1e9,
            "device_ops": top(per_op), "idle_gaps": top(per_gap)}
