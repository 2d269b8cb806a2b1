"""Extract roofline terms from compiled XLA artifacts.

* ``cost_stats``       — per-device FLOPs / bytes from ``cost_analysis()``.
* ``collective_bytes`` — per-device collective traffic, parsed from the
  optimized HLO text: for each all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute we take the output shape + replica-group
  size and apply standard ring estimates.
* ``roofline_terms``   — the three §Roofline terms in seconds for TPU v5e
  (197 bf16 TFLOP/s, 819 GB/s HBM, ~50 GB/s/link ICI per chip).

NOTE (documented bias): XLA cost analysis counts a while-loop body ONCE, so
scanned-over-layers programs under-report.  The dry-run therefore derives
totals from small UNROLLED probe compiles and affine extrapolation (exact
for homogeneous layer stacks); the full scanned compile is still built to
validate sharding and memory.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s/link (estimate per assignment)

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, num_devices: int) -> Dict[str, float]:
    """Per-device bytes moved over the interconnect, ring estimates."""
    out = Counter()
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "-done" in stripped.split("(")[0]:
            continue
        op = None
        for c in _COLL:
            token = " " + c
            if (token + "(" in stripped or token + "-start(" in stripped):
                op = c
                break
        if op is None:
            continue
        head = stripped.split(" " + op)[0]  # "%x = <output shapes>"
        out_bytes = _shape_bytes(head.split("=", 1)[-1])
        n = _group_size(stripped, num_devices)
        if n <= 1:
            continue
        if op == "all-gather":
            moved = out_bytes * (n - 1) / n
        elif op == "reduce-scatter":
            moved = out_bytes * (n - 1)          # input = out * n
        elif op == "all-reduce":
            moved = 2.0 * out_bytes * (n - 1) / n
        elif op == "all-to-all":
            moved = out_bytes * (n - 1) / n
        else:  # collective-permute
            moved = float(out_bytes)
        out[op] += moved
        out["total"] += moved
    return dict(out)


def cost_stats(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }


def memory_stats(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": float(ma.argument_size_in_bytes),
        "output_bytes": float(ma.output_size_in_bytes),
        "temp_bytes": float(ma.temp_size_in_bytes),
        "alias_bytes": float(ma.alias_size_in_bytes),
        "peak_bytes": float(ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            + ma.temp_size_in_bytes
                            - ma.alias_size_in_bytes),
    }


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll_bytes_per_dev: float) -> Dict[str, float]:
    comp = flops_per_dev / PEAK_FLOPS
    mem = bytes_per_dev / HBM_BW
    coll = coll_bytes_per_dev / ICI_BW
    dominant = max((comp, "compute"), (mem, "memory"), (coll, "collective"))[1]
    total = max(comp, mem, coll)
    return {
        "compute_s": comp,
        "memory_s": mem,
        "collective_s": coll,
        "dominant": dominant,
        "bound_s": total,
        "roofline_fraction": comp / total if total > 0 else 0.0,
    }
