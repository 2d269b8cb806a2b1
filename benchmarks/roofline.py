"""§Roofline report generator: reads the dry-run JSONL artifacts and emits
the per-(arch × shape) roofline table (markdown) with the three terms,
dominant bottleneck, MODEL_FLOPS/HLO_FLOPs ratio, and a what-would-move-it
note per row."""
from __future__ import annotations

import argparse
import json
import os
from collections import OrderedDict

NOTES = {
    ("compute", "train"): "raise arithmetic intensity: fuse HT head, larger "
                          "microbatch, bf16 remat",
    ("compute", "prefill"): "attention-bound: banded/flash kernels, shorter "
                            "effective T via RPC",
    ("compute", "decode"): "batch more concurrent sequences per chip",
    ("memory", "train"): "cut optimizer/grad traffic: int8 moments, fewer "
                         "microbatch weight re-reads",
    ("memory", "prefill"): "KV/activation layout; fuse QKV; wider tiles",
    ("memory", "decode"): "weight-bound: quantize weights / multi-token "
                          "speculation to amortize reads",
    ("collective", "train"): "shrink FSDP all-gathers: replicate small "
                             "weights, overlap with compute, 2D-shard",
    ("collective", "prefill"): "reshard activations less; overlap collectives",
    ("collective", "decode"): "replicate params over idle axes; shrink "
                              "all-reduce payloads",
}


def load(paths):
    recs = OrderedDict()
    for p in paths:
        if not os.path.exists(p):
            continue
        for line in open(p):
            r = json.loads(line)
            recs[(r["arch"], r["shape"], r["mesh"])] = r  # last write wins
    return recs


def fmt_s(x):
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x * 1e3:.1f}ms"
    return f"{x * 1e6:.0f}us"


def table(recs, mesh="single"):
    rows = []
    for (arch, shape, m), r in recs.items():
        if m != mesh or r.get("status") != "ok":
            continue
        rl = r.get("roofline")
        if not rl:
            continue
        kind = ("train" if shape.startswith("train") else
                "prefill" if shape.startswith("prefill") else "decode")
        rows.append({
            "arch": arch, "shape": shape,
            "compute": rl["compute_s"], "memory": rl["memory_s"],
            "collective": rl["collective_s"], "dominant": rl["dominant"],
            "frac": rl["roofline_fraction"],
            "useful": r.get("useful_ratio", float("nan")),
            "note": NOTES.get((rl["dominant"], kind), ""),
            "mem_gib": r.get("memory", {}).get("peak_bytes", 0) / 2**30,
        })
    return rows


def markdown(rows):
    out = ["| arch | shape | compute | memory | collective | dominant | "
           "roofline-frac | useful (6ND/HLO) | peak GiB/dev | move it down by |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {fmt_s(r['compute'])} | "
            f"{fmt_s(r['memory'])} | {fmt_s(r['collective'])} | "
            f"**{r['dominant']}** | {r['frac']:.2f} | {r['useful']:.2f} | "
            f"{r['mem_gib']:.1f} | {r['note']} |")
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="inputs", nargs="*",
                    default=["experiments/dryrun.jsonl"])
    ap.add_argument("--mesh", default="single")
    args = ap.parse_args(argv)
    recs = load(args.inputs)
    rows = table(recs, args.mesh)
    if not rows:
        print("# roofline: no probe records found (run the dry-run with "
              "--probes first)")
        return
    print(markdown(rows))


if __name__ == "__main__":
    main()
