"""Readings that the limits of ``correct`` are set from, for one cell, in one
process: the program's numbers over many seeds, and on some of them the
control and the planted faults.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --out <file.jsonl> [--steps N]

For every seed: the program's set-up steps (the steps a run checks; with
``--steps``, only the first N), then the reference over them, and the
numbers of ``harness.compare``.  On each control seed, readings of the
same numbers where the reference's place is taken by

* ``control``: the reference computed with float8 matmul operands
  (bench/reference.py), as learner and behaviour policy both;
* ``half_batch``: the reference with half of the batch left out and the
  mean taken over the rest;
* ``altered_token``: the program's behaviour log-probabilities against a
  batch in which one response token of step 1 was changed after it was
  sampled (the reference scores the changed row);
* ``wrong_temperature``: what ``sample_z`` reads, on average, where the
  tokens were drawn at ``harness.FAULT_TEMPERATURE`` times the mix's
  temperature;
* ``cut_early`` and ``not_prefix``: the program's kept-token masks with
  each cut moved halfway back towards the minimum, or with the first kept
  token of each row dropped.

A step that returns its state unchanged reads 1 on ``update_gap`` and on
``grad_dir_gap`` (a zero moment), and a gradient of flipped sign reads 2 on
``grad_dir_gap``, by the measures' definitions; they need no run.  Each
reading is one JSON line.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def altered_row_gap(spec, cap_steps, flat0_host, row: int) -> float:
    """Widest behaviour-logp gap on ``row`` of step 1 once its first
    response token is replaced by the next id in the vocabulary."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import reference as R
    from bench import weights as W

    s1 = cap_steps[0]
    z = W.dims(spec["config"])
    pl, rl = int(s1["prompt_lens"][row]), int(s1["response_lens"][row])
    toks = np.array(s1["tokens"][row:row + 1])
    toks[0, pl] = (toks[0, pl] + 1) % z["v"]
    params = {n: jnp.asarray(v).astype(jnp.float32)
              for n, v in flat0_host.items()}
    logp = np.asarray(jax.jit(lambda p, t: R.token_logp(p, t, z))(
        params, jnp.asarray(toks)))
    return float(np.max(np.abs(s1["old_logp"][row, pl:pl + rl]
                               - logp[0, pl:pl + rl])))


def worst(prog: dict, ref: dict) -> list:
    """The leaf whose norm differs most (for the record): name, program's
    norm, reference's norm."""
    k = max(ref, key=lambda n: abs(prog[n] - ref[n]) / max(ref[n], 1e-30))
    return [k, prog[k], ref[k]]


def planted_selection(steps, design, fault: str) -> list:
    """Selection stats of the captured masks with a fault planted."""
    import numpy as np

    from bench import reference as R

    out = []
    for st in steps:
        keep = np.array(st["keep_mask"], bool)
        for i, (pl, n) in enumerate(zip(st["prompt_lens"],
                                        st["response_lens"])):
            k = int(keep[i].sum())
            if fault == "cut_early":
                lo = min(design[1], int(n))
                keep[i, pl + lo + (k - lo) // 2:] = False
            elif k:
                keep[i, pl] = False
        out.append(R.selection_stats(keep, st["prompt_lens"],
                                     st["response_lens"], *design))
    return out


def calibrate(spec, workload, seeds, control, emit, n_steps=None) -> None:
    """Readings of the program on every seed, and of the control and the
    faults on the seeds in ``control``; ``emit`` takes each record."""
    from bench import harness as H
    from bench import reference as R
    from bench.compile_log import CompileLog

    clog = CompileLog()
    design = H.design(spec["traffic"])
    for seed in seeds:
        t0 = time.perf_counter()
        trainer, cap, prog, flat0_host, _ = H.setup(spec, seed, clog,
                                                    warm=False,
                                                    n_steps=n_steps)
        trainer.close()
        del trainer
        gc.collect()
        steps = cap.steps
        t1 = time.perf_counter()
        ref = H.follow_reference(spec, seed, steps, flat0_host,
                                 grad1_other=prog.pop("m1"),
                                 keep_grad1=seed in control)
        t2 = time.perf_counter()
        emit({"workload": workload, "seed": seed, "kind": "program",
              "rows": [s["rows"] for s in steps], "program_s": t1 - t0,
              "reference_s": t2 - t1, **H.compare(steps, prog, ref),
              "worst": {k: worst(prog[k], ref[k]) for k in ("grad1",
                                                             "update")}})
        if seed not in control:
            continue
        g1 = ref.pop("grad1_host")
        for kind, kw in (("control", {"fp8": True}),
                         ("half_batch", {"rows": list(range(
                             steps[0]["tokens"].shape[0] // 2))})):
            t3 = time.perf_counter()
            try:
                alt = H.follow_reference(spec, seed, steps, flat0_host,
                                         grad1_other=g1, **kw)
            except Exception as e:  # a control that gives no number failed
                emit({"workload": workload, "seed": seed, "kind": kind,
                      "error": repr(e)})
                continue
            # the control is learner and behaviour policy both; the half
            # batch leaves the policy as the reference's
            behaviour = alt["logp1"] if kind == "control" else ref["logp1"]
            alt_steps = [dict(steps[0], old_logp=behaviour)] + steps[1:]
            alt_prog = dict(alt, ratio1=1.0)
            got = H.compare(alt_steps, alt_prog,
                            dict(ref, grad1_cos=alt["grad1_cos"]))
            emit({"workload": workload, "seed": seed, "kind": kind,
                  "seconds": time.perf_counter() - t3,
                  **{k: got[k] for k in ("logp_gap", "ratio_gap",
                                         "loss_gap", "grad_gap",
                                         "grad_dir_gap", "update_gap")}})
        del g1
        emit({"workload": workload, "seed": seed,
              "kind": "altered_token",
              "logp_gap": altered_row_gap(spec, steps, flat0_host, 0)})
        emit({"workload": workload, "seed": seed,
              "kind": "wrong_temperature",
              "sample_z": H.sample_z(ref["sampler1"],
                                     H.response_mask(steps[0]), "ef")})
        for fault in ("cut_early", "not_prefix"):
            sel = planted_selection(steps, design, fault)
            emit({"workload": workload, "seed": seed, "kind": fault,
                  "select_faults": sum(x["faults"] for x in sel),
                  "cut_z": R.cut_z(sel)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args(argv)

    from bench import harness as H
    from bench.run import use_compile_cache

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    use_compile_cache()
    spec = H.load_spec(args.workload, ROOT)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")

    calibrate(spec, args.workload, seeds, control, emit, args.steps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
