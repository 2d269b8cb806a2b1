"""Sizing rehearsal without a chip: compile each cell's learner step and
rollout engine round at the cell's largest shapes for one chip of a
described v5e 2x2 host, and print what the compiler says they need.

    JAX_PLATFORMS=cpu python3 bench/size_check.py [cell ...]

The shapes come from the cell's own files: the learner step at the largest
packed row count of the mix's ``warm_rows``, the engine round as the
trainer builds it.  The round's host-built operands are recorded from one
step of the same traffic on a small model of the same layout on the CPU
(they depend on the traffic, and on the model only through the
vocabulary).  Nothing runs at the real size: only shapes are passed.
"""
from __future__ import annotations

import json
import os
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SMALL = dict(hidden_size=64, intermediate_size=192, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
             vocab_size=257)


def record_round_operands(spec: dict) -> list:
    """Shapes of the engine step's operands after the params and the state,
    from one training step of the cell's traffic on a small model."""
    import jax

    from bench import harness as H
    from bench import traffic as TR
    from bench import weights as W
    from repro.rl import NATGRPOTrainer

    fam = spec["family"]
    cfg = dict(spec["config"], **SMALL)
    gen = TR.Traffic(spec["traffic"], 0)
    tr = NATGRPOTrainer(fam.program_config(cfg),
                        H.trainer_config(spec["traffic"], 0),
                        params=fam.to_program(W.make(fam.layout(cfg), 0)),
                        budget_fn=gen.budget_fn)
    seen = []
    step = tr.engine._step

    def recording_step(params, state, *ops):
        if not seen:
            seen.extend(jax.ShapeDtypeStruct(o.shape, o.dtype) for o in ops)
        return step(params, state, *ops)

    tr.engine._step = recording_step
    cap = H.Capture(tr.layout, keep=0, design=H.design(spec["traffic"]))
    tr.train_step()
    tr.close()
    return seen, cap.batch_shapes


def compile_cell(name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness as H
    from bench import weights as W
    from repro.rl import NATGRPOTrainer
    from repro.rl.learner import make_train_step

    spec = H.load_spec(name)
    ops, batch_shapes = record_round_operands(spec)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one)

    cfg, traffic, fam = spec["config"], spec["traffic"], spec["family"]
    mc = fam.program_config(cfg)
    tcfg = H.trainer_config(traffic, 0)
    flat = {k: sds(shape, W.STORE)
            for k, (shape, _) in fam.layout(cfg).items()}
    params = fam.to_program(flat)

    rows = traffic["warm_rows"][1]
    batch = {k: sds((rows,) + shp[1:] if len(shp) >= 2 else shp, dt)
             for k, (shp, dt) in batch_shapes.items()}
    f32 = {k: sds(v.shape, jnp.float32) for k, v in flat.items()}
    opt = {"m": fam.to_program(f32), "v": fam.to_program(dict(f32)),
           "step": sds((), jnp.int32)}
    step = jax.jit(make_train_step(mc, tcfg.grpo, tcfg.adamw, vocab_chunks=1,
                                   packed=True), donate_argnums=(1,))
    learner = step.lower(params, opt, batch).compile().memory_analysis()

    fake = types.SimpleNamespace(tcfg=tcfg, model_cfg=mc)
    engine = NATGRPOTrainer._build_engine(fake)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(engine._init_state, params, key)
    state = jax.tree.map(lambda a: sds(a.shape, a.dtype), state)
    small_v = SMALL["vocab_size"]
    ops = [sds(o.shape[:-1] + (cfg["vocab_size"],)
               if o.shape and o.shape[-1] == small_v else o.shape, o.dtype)
           for o in ops]
    rnd = engine._step.lower(params, state, *ops).compile().memory_analysis()

    def nbytes(tree):
        return sum(int(a.size) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))

    def fields(m):
        return {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes")}

    lm, rm = fields(learner), fields(rnd)
    learner_peak = (lm["argument_size_in_bytes"] + lm["output_size_in_bytes"]
                    + lm["temp_size_in_bytes"] - lm["alias_size_in_bytes"])
    engine_state = nbytes(state)
    return {"cell": name, "learner_rows": rows,
            "pack_len": batch["tokens"].shape[1],
            "learner_step": lm, "engine_round": rm,
            "params_bytes": nbytes(params), "engine_state_bytes": engine_state,
            # the engine's last state stays resident through the update
            "learner_peak_with_engine_state": learner_peak + engine_state}


def main(argv=None) -> int:
    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in json.loads(
            (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        print(json.dumps(compile_cell(name)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
