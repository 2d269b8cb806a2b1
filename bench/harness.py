"""One run of one cell: set-up, the measured window, the optional trace, and
the check of the first steps against the reference.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix; the configuration's ``file``, the mix
``bench/traffic/<traffic>.json``, the limits ``bench/limits/<cell>.json``
and each per-layer metric's reader ``bench/metrics/<metric>.py``.

Run sequence:

1. Set-up (``setup_s``, from process start): the weights from the seed, the
   trainer, ``setup_steps`` training steps (they compile and are the steps
   the reference follows), then the learner step compiled for every packed
   row count in the mix's ``warm_rows`` that those steps did not meet.
2. The window starts at a step boundary and runs whole steps until
   ``seconds`` have passed; it ends when the step in flight ends.  Rates are
   all the work of the window's steps over the whole window.
3. With ``trace``, the profiler records the window's last step (as
   estimated from the set-up steps' duration).
4. After the window the peak memory is read, the program is freed, and the
   reference follows the set-up steps on the same batches.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from bench import flops as F
from bench import reference as R
from bench import traffic as TR
from bench import weights as W
from bench.compile_log import CompileLog
from bench.peaks import peak

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ".bench_trace"
CHECKS = ("logp_gap", "ratio_gap", "loss_gap", "grad_gap", "grad_dir_gap",
          "update_gap", "select_faults", "cut_z", "sample_z")
FAULT_TEMPERATURE = 0.8    # a sampler off by a fifth, for sample_z's reading


# ------------------------------------------------------------------ spec
def load_spec(workload: str, root: Path = ROOT) -> dict:
    """The cell's entry, configuration, mix, limits and metric entries."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    t = traffic["trainer"]
    if t["max_prompt_len"] + t["max_new_tokens"] > cfg[
            "max_position_embeddings"]:
        raise ValueError(f"{workload}: the mix's contexts exceed the "
                         f"configuration's max_position_embeddings")
    return {
        "cell": cell,
        "config": cfg,
        "traffic": traffic,
        "limits": json.loads(
            (root / "bench" / "limits" / f"{workload}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry's settings with every size taken from the file."""
    from repro.configs import get_config
    from repro.models.config import dense_blocks

    base = get_config(cfg["registry_id"])
    mc = dataclasses.replace(
        base, d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        blocks=dense_blocks(cfg["num_hidden_layers"]),
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"])
    if (mc.mlp_kind != "swiglu" or mc.tie_embeddings
            != cfg["tie_word_embeddings"] or mc.moe or mc.mla
            or cfg["hidden_act"] != "silu"):
        raise ValueError(f"{cfg['name']}: the reference covers dense SwiGLU "
                         f"decoders with untied heads only")
    return mc


def trainer_config(traffic: dict, seed32: int):
    from repro.core.grpo import GRPOConfig
    from repro.optim import AdamWConfig
    from repro.rl import NATTrainerConfig, RolloutConfig

    t = traffic["trainer"]
    return NATTrainerConfig(
        env=t["env"], selector=t["selector"],
        selector_kwargs=tuple(sorted(t["selector_kwargs"].items())),
        prompts_per_step=t["prompts_per_step"],
        max_prompt_len=t["max_prompt_len"],
        rollout=RolloutConfig(max_new_tokens=t["max_new_tokens"],
                              temperature=t["temperature"],
                              group_size=t["group_size"],
                              overprovision=t["overprovision"],
                              eos_id=t["eos_id"]),
        num_slots=t["num_slots"],
        rollout_engine=t["rollout_engine"], layout=t["layout"],
        num_buckets=t["num_buckets"], adamw=AdamWConfig(**t["adamw"]),
        grpo=GRPOConfig(**t["grpo"]), seed=seed32)


# --------------------------------------------------------------- capture
def design(traffic: dict) -> tuple:
    """The mix's selector and its minimum cut."""
    t = traffic["trainer"]
    return t["selector"], t["selector_kwargs"].get("min_cut", 0)


class Capture:
    """Wraps the trainer's ``layout.build``: records what each step's
    learner consumed (the first ``keep`` steps in full, for the reference),
    how each step's kept-token mask stands against the selector's design
    (``design``: selector name and minimum cut), and the shapes of the
    batch it built."""

    def __init__(self, layout, keep: int, design: tuple):
        self.steps: list = []
        self.batch_shapes: Optional[dict] = None
        build = layout.build

        def recording_build(batch, **kw):
            with jax.profiler.TraceAnnotation("bench.layout_build"):
                lb = build(batch, **kw)
            keep_mask = np.asarray(kw["keep_mask"], bool)
            rec = {"prompt_lens": np.array(kw["prompt_lens"]),
                   "response_lens": np.array(kw["response_lens"]),
                   "hulls": F.hull_lengths(keep_mask),
                   "selection": R.selection_stats(
                       keep_mask, kw["prompt_lens"], kw["response_lens"],
                       *design),
                   "rows": lb.num_rows}
            if len(self.steps) < keep:
                rec.update(tokens=np.array(batch["tokens"]),
                           old_logp=np.array(batch["old_logp"]),
                           keep_mask=keep_mask)
            if self.batch_shapes is None:
                self.batch_shapes = {k: (np.shape(v), np.asarray(v).dtype)
                                     for k, v in lb.data.items()}
            self.steps.append(rec)
            return lb

        layout.build = recording_build


def _annotate(obj, attr: str, span: str) -> None:
    """Wrap ``obj.attr`` in a profiler span, where the attribute exists."""
    fn = getattr(obj, attr, None)
    if fn is None:
        return

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


def _annotate_selector(trainer) -> None:
    sel = trainer.selector
    if type(sel).__name__ == "EntropySelector":   # called with entropies
        return

    def select(*a, **kw):
        with jax.profiler.TraceAnnotation("bench.select"):
            return sel(*a, **kw)

    trainer.selector = select


# ------------------------------------------------------------------ check
def worst_leaf(prog: dict, ref: dict, leaves) -> float:
    """Largest |norm_prog - norm_ref| over the leaves, each against the
    reference's norm of that leaf or of the median leaf, the larger."""
    leaves = list(leaves)
    med = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in leaves)


def response_mask(step: dict) -> np.ndarray:
    resp = np.zeros(step["tokens"].shape, bool)
    for i, (pl, rl) in enumerate(zip(step["prompt_lens"],
                                     step["response_lens"])):
        resp[i, pl:pl + rl] = True
    return resp


def sample_z(terms: dict, resp, draw: str = "nlp") -> float:
    """|sum(-log p(x) - H)| / sqrt(sum Var) over the response tokens: about
    |N(0, 1)| where the tokens were drawn from p; ``draw="ef"`` reads what a
    sampler at the wrong temperature gives on average."""
    d = float(np.sum((terms[draw] - terms["h"])[resp], dtype=np.float64))
    return abs(d) / math.sqrt(float(np.sum(terms["var"][resp],
                                           dtype=np.float64)))


def ratio_of(learner_logp, step: dict) -> float:
    """Mean of exp(learner logp - behaviour logp) over step's kept
    tokens (the learner's ``ratio_mean``)."""
    kept = response_mask(step) & step["keep_mask"]
    return float(np.mean(np.exp(learner_logp - step["old_logp"])[kept]))


def compare(cap_steps, prog: dict, ref: dict, all_steps=None) -> dict:
    """The numbers that decide ``correct``: the reference against the
    program over the captured steps, and the kept-token masks of
    ``all_steps`` (default the captured ones) against the selector."""
    s1 = cap_steps[0]
    resp = response_mask(s1)
    logp_gap = float(np.max(np.abs(s1["old_logp"] - ref["logp1"])[resp]))
    loss_gap = max(abs(lp - lr) / max(float(np.mean(np.abs(ps))), 1e-30)
                   for lp, lr, ps in zip(prog["loss"], ref["loss"],
                                         ref["per_seq"]))
    g = ref["grad1"]
    med = float(np.median(list(g.values())))
    moved = [k for k in g if g[k] >= 1e-3 * med]
    sel = [s["selection"] for s in (all_steps or cap_steps)]
    return {"logp_gap": logp_gap,
            "ratio_gap": abs(prog["ratio1"] - ratio_of(ref["logp1"], s1)),
            "loss_gap": loss_gap,
            "grad_gap": worst_leaf(prog["grad1"], g, g),
            "grad_dir_gap": max(1.0 - ref["grad1_cos"][k] for k in moved),
            "update_gap": worst_leaf(prog["update"], ref["update"], moved),
            "select_faults": sum(s["faults"] for s in sel),
            "cut_z": R.cut_z(sel),
            "sample_z": sample_z(ref["sampler1"], resp)}


def rewards_of(step: dict, seed: int, p: float) -> np.ndarray:
    return np.array([
        TR.reward(seed, p, step["tokens"][i, pl:pl + rl])
        for i, (pl, rl) in enumerate(zip(step["prompt_lens"],
                                         step["response_lens"]))])


def follow_reference(spec: dict, seed: int, cap_steps, flat0_host,
                     **kw) -> dict:
    """The reference (or a variant of it, through ``kw``) over the captured
    set-up steps."""
    t = spec["traffic"]["trainer"]
    z = W.dims(spec["config"])
    p = spec["traffic"]["reward"]["p"]
    kw.setdefault("temps", (t["temperature"],
                            t["temperature"] * FAULT_TEMPERATURE))
    return R.follow(
        flat0_host, z, cap_steps, t["adamw"], t["selector"],
        t["selector_kwargs"].get("min_cut", 0), t["group_size"],
        t["grpo"]["adv_eps"], [rewards_of(s, seed, p) for s in cap_steps],
        **kw)


# -------------------------------------------------------------------- run
@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers (bench/metrics) read."""
    window: list                 # the program's metrics of each window step
    window_s: float
    compiles_in_window: int
    rollout_flops: list          # per window step
    learner_flops: list
    peak_flops: float
    trace: Optional[dict]


def _load_reader(name: str, root: Path):
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def step_line(m: dict) -> str:
    return (f"{m['time_total']:.3f} s (rollout {m['time_rollout']:.3f} s, "
            f"{m['rollout_decode_steps']} substeps, "
            f"{m['tokens_generated']} tokens; learner {m['time_learn']:.3f} "
            f"s, {m['learner_rows']} rows)")


def setup(spec: dict, seed: int, clog: CompileLog, warm: bool = True,
          n_steps: Optional[int] = None):
    """Build the trainer from the seed and run the set-up steps (the mix's
    ``setup_steps``, or ``n_steps``), then (with ``warm``) compile the
    learner step for the mix's other row counts.  Returns (trainer,
    capture, program readings, host copy of the initial weights, set-up
    step metrics)."""
    from repro.rl import NATGRPOTrainer

    traffic = spec["traffic"]
    s32 = W.seed32(seed)
    mc = model_config(spec["config"])
    gen = TR.Traffic(traffic, seed)
    flat0 = W.make(spec["config"], seed)
    flat0_host = jax.device_get(flat0)
    trainer = NATGRPOTrainer(mc, trainer_config(traffic, s32),
                             params=W.to_program(flat0),
                             budget_fn=gen.budget_fn)
    del flat0
    trainer.env = TR.RewardEnv(seed, traffic["reward"]["p"])
    n_steps = n_steps or traffic["setup_steps"]
    cap = Capture(trainer.layout, keep=n_steps, design=design(traffic))
    learner_step = trainer._train_step
    _annotate(trainer, "_train_step", "bench.learner_step")
    _annotate(trainer, "_roll_next_group", "bench.rollout")
    _annotate(trainer.engine, "drive", "bench.engine_round")
    _annotate_selector(trainer)

    b1 = traffic["trainer"]["adamw"]["b1"]
    steps, losses = [], []
    for k in range(n_steps):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            m = trainer.train_step()
        steps.append(m)
        losses.append(m["loss"])
        say(f"set-up step {k + 1}: {step_line(m)}, loss {m['loss']!r}, "
            f"compiles {clog.n}")
        if k == 0:
            # the optimizer's first moment after one step is (1 - b1) g:
            # its norms, and a host copy for the direction
            m1 = W.from_program(trainer.opt_state["m"])
            grad1 = {n: v / (1 - b1) for n, v in W.leaf_norms(m1).items()}
            m1 = jax.device_get(m1)
    update = W.change_norms(W.from_program(trainer.params), flat0_host)
    prog = {"loss": losses, "grad1": grad1, "m1": m1, "update": update,
            "ratio1": steps[0]["ratio_mean"]}

    lo, hi = traffic["warm_rows"] if warm else (1, 0)
    seen = {s["rows"] for s in cap.steps}
    for rows in range(lo, hi + 1):
        if rows in seen:
            continue
        shapes = {k: jax.ShapeDtypeStruct(
            (rows,) + shp[1:] if len(shp) >= 2 else shp, dt)
            for k, (shp, dt) in cap.batch_shapes.items()}
        learner_step.lower(trainer.params, trainer.opt_state,
                           shapes).compile()
    return trainer, cap, prog, flat0_host, steps


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: Path = ROOT, spec: Optional[dict] = None,
        peak_flops: Optional[float] = None) -> dict:
    """One run; returns the result line's object.  ``spec`` and
    ``peak_flops`` stand in for the cell's files and the device's table
    entry where a test drives the run on the CPU."""
    spec = spec or load_spec(workload, root)
    dev = jax.devices()[0]
    if peak_flops is None:
        peak_flops = peak(dev.device_kind)["bf16_flops"]
    clog = CompileLog()
    trainer, cap, prog, flat0_host, setup_steps = setup(spec, seed, clog)
    n_setup = len(cap.steps)
    est = float(np.mean([m["time_total"] for m in setup_steps[1:]]
                        or [setup_steps[0]["time_total"]]))

    trace_dir = root / TRACE_DIR
    window, raised = [], 0
    traced, span = False, None
    setup_s = time.perf_counter() - t_start
    say(f"set-up: {setup_s:.3f} s, {clog.n} compiles "
        f"({clog.seconds:.1f} s), {clog.cache_hits} cache hits")
    n0 = clog.n
    t0 = time.perf_counter()
    while True:
        if trace and not traced and (time.perf_counter() - t0
                                     >= seconds - est):
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # spans only, no Python calls
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            span = jax.profiler.TraceAnnotation("bench.traced_steps")
            span.__enter__()
            traced = True
        try:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                m = trainer.train_step()
        except Exception:          # a failed step ends the window
            traceback.print_exc()
            raised = 1
            break
        window.append(m)
        say(f"window step {len(window)}: {step_line(m)}")
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    compiles = clog.n - n0
    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    red = None
    if traced:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    say(f"window: {len(window)} steps in {window_s:.3f} s, {compiles} "
        f"compiles, peak {peak_bytes} bytes")

    rec = cap.steps[n_setup:n_setup + len(window)]
    z = W.dims(spec["config"])
    group = spec["traffic"]["trainer"]["group_size"]
    run_rec = RunRecord(
        window=window, window_s=window_s, compiles_in_window=compiles,
        rollout_flops=[F.rollout_flops(z, s["prompt_lens"],
                                       s["response_lens"], group)
                       for s in rec],
        learner_flops=[F.learner_flops(z, s["hulls"]) for s in rec],
        peak_flops=peak_flops, trace=None)
    kept_tokens = sum(int(np.sum(s["response_lens"])) for s in rec)

    trainer.close()
    del trainer
    gc.collect()
    if traced:
        from bench import trace as T
        red = T.reduce(T.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run_rec.trace = red

    cap_steps = cap.steps[:n_setup]
    ref = follow_reference(spec, seed, cap_steps, flat0_host,
                           grad1_other=prog.pop("m1"))
    checks = compare(cap_steps, prog, ref, cap.steps)
    limits = spec["limits"]
    failed = raised + sum(1 for m in window if not math.isfinite(m["loss"]))
    correct = (failed == 0 and len(window) > 0 and all(
        math.isfinite(checks[k]) and checks[k] <= limits[k] for k in limits))

    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            val = _load_reader(m["name"], root)(run_rec)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        e2e = {"rl_tokens_per_s": kept_tokens / window_s,
               "peak_hbm_gb": peak_bytes / 1e9, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    out = {"correct": bool(correct), "attempted": len(window) + raised,
           "failed": failed, "metrics": metrics, "device": device}
    if red is not None:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    for k in CHECKS:
        if k not in limits:
            say(f"reading {k}: {checks[k]!r} (not compared)")
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in CHECKS if k in limits}
    return out
