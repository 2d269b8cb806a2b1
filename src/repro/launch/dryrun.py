"""Multi-pod dry-run: prove every (architecture × input shape × mesh) cell
lowers + compiles under the production sharding config, and extract the
roofline terms — with NO real hardware and NO array allocation.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch nemotron-4-340b \
        --shape train_4k --mesh single --probes

Per cell this script:
  1. builds abstract inputs + NamedShardings (launch/step_specs.py),
  2. jit().lower().compile() the REAL program (scan-over-layers, grad
     accumulation) — prints memory_analysis()/cost_analysis(), validating
     the sharding config and the per-device memory fit,
  3. (--probes) compiles small UNROLLED probe variants (1 vs 2 superblocks
     per layer group; 1 vs 2 microbatches) and affinely extrapolates exact
     per-device FLOPs / bytes / collective bytes — XLA's cost analysis
     counts while-loop bodies once, so the scanned compile cannot be used
     for totals directly (see launch/hlo_stats.py),
  4. appends a JSON record to --out (default experiments/dryrun.jsonl).
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder host devices
# and never needs an accelerator, so it also pins itself to the CPU (a
# chip belongs to one process; this one must not take it).  These lines
# must run before ANY other import — jax locks the platform and device
# count on first initialization.
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs import ALL_ARCHS, get_config, SHAPES, shapes_for  # noqa: E402
from repro.configs.shapes import ShapeSpec  # noqa: E402
from repro.launch import hlo_stats  # noqa: E402
from repro.launch.mesh import make_production_mesh, set_ambient_mesh  # noqa: E402
from repro.launch.step_specs import make_cell, rules_for  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.model import model_decl  # noqa: E402
from repro.models.params import count_params  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402


# ----------------------------------------------------------- compile one
def compile_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
                 rules_profile: str = "default", **kw):
    rules = rules_for(shape, profile=rules_profile)
    cell = make_cell(cfg, shape, mesh, rules, **kw)
    set_ambient_mesh(mesh)
    t0 = time.time()
    lowered = jax.jit(
        cell.fn, in_shardings=cell.in_shardings,
        out_shardings=cell.out_shardings,
        donate_argnums=cell.donate or ()).lower(*cell.args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return compiled, {"lower_s": t1 - t0, "compile_s": t2 - t1}


def _measure(compiled, num_devices: int) -> dict:
    txt = compiled.as_text()
    return {
        **hlo_stats.cost_stats(compiled),
        "collectives": hlo_stats.collective_bytes(txt, num_devices),
        "memory": hlo_stats.memory_stats(compiled),
    }


# ------------------------------------------------------------- probe math
def _probe_cfg(cfg: ModelConfig, depths) -> ModelConfig:
    blocks = tuple((pat, d) for (pat, _), d in zip(cfg.blocks, depths))
    return dataclasses.replace(cfg, blocks=blocks, scan_layers=False)


def _probe_shape(shape: ShapeSpec, batch: int) -> ShapeSpec:
    return dataclasses.replace(shape, global_batch=batch)


def probe_extrapolate(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
                      micro_rows: int, num_micro: int, opt_cfg,
                      rules_profile: str = "default") -> dict:
    """Affine probe extrapolation of per-device flops/bytes/collective bytes.

    Model (exact for homogeneous layer groups):
      train:    cost(M, L) = opt_base + Σ_g opt_g·L_g + M·(c_base + Σ_g c_g·L_g)
      pre/dec:  cost(L)    = c_base + Σ_g c_g·L_g
    Probes hold the PER-MICROBATCH row count at the real value (micro_rows)
    and vary (M ∈ {1,2}, depth_g ∈ {1,2}) with everything unrolled, so XLA's
    cost analysis sees every instance.
    """
    nd = mesh.devices.size
    groups = len(cfg.blocks)
    depths1 = [1] * groups
    is_train = shape.kind == "train"

    def run(depths, m=1):
        if is_train:
            kw = dict(opt_cfg=opt_cfg, num_microbatches=m,
                      unroll_microbatches=True)
            s = _probe_shape(shape, micro_rows * m)
        else:
            kw = {}
            s = shape
        comp, _ = compile_cell(_probe_cfg(cfg, depths), s, mesh,
                               rules_profile=rules_profile, **kw)
        meas = _measure(comp, nd)
        return {"flops": meas["flops"], "bytes": meas["bytes"],
                "coll": meas["collectives"].get("total", 0.0)}

    def bump(g):
        d = list(depths1)
        d[g] = 2
        return d

    real_depths = [r for _, r in cfg.blocks]
    pa = run(depths1, m=1)
    s1 = [{k: run(bump(g), m=1)[k] - pa[k] for k in pa} for g in range(groups)]

    total = {}
    if is_train:
        pc = run(depths1, m=2)
        u = {k: pc[k] - pa[k] for k in pa}                  # c_base + Σ c_g
        s2 = [{k: run(bump(g), m=2)[k] - pc[k] for k in pa}
              for g in range(groups)]                        # opt_g + 2 c_g
        for k in pa:
            c_g = [s2[g][k] - s1[g][k] for g in range(groups)]
            opt_g = [s1[g][k] - c_g[g] for g in range(groups)]
            c_base = u[k] - sum(c_g)
            opt_base = pa[k] - sum(opt_g) - u[k]
            total[k] = (opt_base
                        + sum(opt_g[g] * real_depths[g] for g in range(groups))
                        + num_micro * (c_base + sum(
                            c_g[g] * real_depths[g] for g in range(groups))))
    else:
        for k in pa:
            c_g = [s1[g][k] for g in range(groups)]
            c_base = pa[k] - sum(c_g)
            total[k] = c_base + sum(c_g[g] * real_depths[g]
                                    for g in range(groups))
    return total


# ----------------------------------------------------------- model flops
def active_params(cfg: ModelConfig) -> int:
    """Parameters touched per token: total minus non-routed expert weights."""
    total = count_params(model_decl(cfg))
    if cfg.moe is None:
        return total
    m = cfg.moe
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    inactive = 0
    for pattern, repeat in cfg.blocks:
        for kind in pattern:
            if cfg.mlp_of(kind) == "moe":
                inactive += repeat * (m.num_experts - m.top_k) * per_expert
    return total - inactive


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """6·N_active·tokens (train) / 2·N_active·tokens (prefill) /
    2·N_active·batch (decode, per step)."""
    n = active_params(cfg)
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


# ------------------------------------------------------------------ main
def plan_microbatches(cfg: ModelConfig, shape: ShapeSpec, mesh) -> tuple:
    """(micro_rows, num_micro): default 1 row per data shard per microbatch,
    bounded so num_micro >= 1."""
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    micro_rows = max(dp, shape.global_batch // 16)
    micro_rows = min(micro_rows, shape.global_batch)
    num_micro = max(shape.global_batch // micro_rows, 1)
    return micro_rows, num_micro


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             probes: bool, out_path: str,
             rules_profile: str = "default",
             seq_len: int = 0, label: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if seq_len:  # ad-hoc hillclimb cell (e.g. the RPC expected bucket)
        shape = dataclasses.replace(shape, seq_len=seq_len)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    nd = mesh.devices.size
    rec = {"arch": arch, "shape": label or shape_name, "mesh": mesh_name,
           "devices": nd, "status": "ok", "rules": rules_profile}
    try:
        opt_cfg = AdamWConfig(moment_dtype="int8")
        kw = {}
        if shape.kind == "train":
            if rules_profile == "small_model":
                # pure DP: one microbatch, batch over every axis
                micro_rows, num_micro = shape.global_batch, 1
            else:
                micro_rows, num_micro = plan_microbatches(cfg, shape, mesh)
            kw = dict(opt_cfg=opt_cfg, num_microbatches=num_micro)
            rec.update(micro_rows=micro_rows, num_micro=num_micro)
        compiled, times = compile_cell(cfg, shape, mesh,
                                       rules_profile=rules_profile, **kw)
        rec.update(times)
        meas = _measure(compiled, nd)
        rec["memory"] = meas["memory"]
        rec["scan_cost"] = {"flops": meas["flops"], "bytes": meas["bytes"],
                            "coll": meas["collectives"]}
        print(compiled.memory_analysis())
        print(hlo_stats.cost_stats(compiled))
        del compiled

        if probes and mesh_name == "single":
            tot = probe_extrapolate(cfg, shape, mesh,
                                    micro_rows=rec.get("micro_rows", 1),
                                    num_micro=rec.get("num_micro", 1),
                                    opt_cfg=opt_cfg,
                                    rules_profile=rules_profile)
            rec["probe_total_per_dev"] = tot
            mf = model_flops(cfg, shape)
            rec["model_flops_total"] = mf
            rec["hlo_flops_total"] = tot["flops"] * nd
            rec["useful_ratio"] = mf / max(tot["flops"] * nd, 1.0)
            rec["roofline"] = hlo_stats.roofline_terms(
                tot["flops"], tot["bytes"], tot["coll"])
    except Exception as e:  # noqa: BLE001
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    status = rec["status"]
    dom = rec.get("roofline", {}).get("dominant", "-")
    print(f"[{status}] {arch} × {shape_name} × {mesh_name} "
          f"compile={rec.get('compile_s', 0):.1f}s dominant={dom}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--probes", action="store_true",
                    help="run roofline probe compiles (single-pod only)")
    ap.add_argument("--rules", default="default",
                    choices=["default", "small_model"],
                    help="sharding-rule profile (small_model = replicated "
                         "weights, full DP — the sub-1B hillclimb)")
    ap.add_argument("--out", default="experiments/dryrun.jsonl")
    ap.add_argument("--seq-len", type=int, default=0,
                    help="override the shape's seq_len (hillclimb cells)")
    ap.add_argument("--label", default="",
                    help="shape label override for the record")
    args = ap.parse_args()

    archs = ALL_ARCHS if args.arch == "all" else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        cell_shapes = ([s.name for s in shapes_for(cfg)]
                       if args.shape == "all" else [args.shape])
        for shape_name in cell_shapes:
            for mesh_name in meshes:
                rec = run_cell(arch, shape_name, mesh_name,
                               probes=args.probes, out_path=args.out,
                               rules_profile=args.rules,
                               seq_len=args.seq_len, label=args.label)
                n_fail += rec["status"] != "ok"
    print(f"dry-run complete; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
