"""Table 2 / Figures 1-2 analog: accuracy parity of NAT schemes with
full-token GRPO on a verifiable task, multi-seed with 95% CIs.

Trains the same tiny model with GRPO / URS / Det-Trunc / RPC on modular
arithmetic; reports greedy accuracy, final reward, behaviour entropy, and
mean learner tokens per step.  The paper's claim to reproduce: URS and RPC
within CI of GRPO; Det-Trunc directionally worse / less stable.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import ci95, emit
from repro.models.config import ModelConfig, dense_blocks
from repro.optim import AdamWConfig
from repro.rl import NATGRPOTrainer, NATTrainerConfig, RolloutConfig, VOCAB_SIZE

ALGOS = [
    ("grpo", "full", ()),
    ("urs", "urs", (("p", 0.5),)),
    ("det_trunc", "det_trunc", ()),
    ("rpc", "rpc", (("min_cut", 4),)),
]


def model():
    return ModelConfig(name="q", d_model=128, n_heads=4, n_kv_heads=2,
                       head_dim=32, d_ff=384, vocab_size=VOCAB_SIZE,
                       blocks=dense_blocks(3), seq_parallel=False,
                       remat_policy="none", scan_layers=False)


def run(steps: int = 60, seeds=(0, 1, 2), eval_prompts: int = 48) -> dict:
    print("# bench_quality (Table 2 / Fig 1-2): NAT vs GRPO on mod-arith")
    print(f"{'algo':10s} {'acc@greedy':>16s} {'reward':>14s} "
          f"{'entropy':>13s} {'tokens/step':>12s}")
    out = {}
    for name, sel, kw in ALGOS:
        accs, rewards, ents, toks = [], [], [], []
        t0 = time.perf_counter()
        for seed in seeds:
            tc = NATTrainerConfig(
                selector=sel, selector_kwargs=kw,
                prompts_per_step=8, max_prompt_len=16,
                rollout=RolloutConfig(max_new_tokens=8, group_size=8,
                                      overprovision=1.0),
                adamw=AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=steps),
                grpo=__import__("repro.core.grpo", fromlist=["GRPOConfig"]
                                ).GRPOConfig(clip_eps=0.2),
                bucket_align=8, seed=seed,
                env_kwargs=(("max_val", 9), ("mod", 10)),  # single-digit task
            )
            tr = NATGRPOTrainer(model(), tc)
            hist = tr.run(steps)
            ev = tr.evaluate(eval_prompts)
            accs.append(ev["accuracy"])
            rewards.append(np.mean([m["reward_mean"] for m in hist[-10:]]))
            ents.append(np.mean([m["entropy_behavior"] for m in hist[-10:]]))
            toks.append(np.mean([m["tokens_scored"] for m in hist]))
        dt = time.perf_counter() - t0
        (am, ah), (rm_, rh), (em, eh) = ci95(accs), ci95(rewards), ci95(ents)
        print(f"{name:10s} {am:8.3f}±{ah:<6.3f} {rm_:8.3f}±{rh:<4.3f} "
              f"{em:8.3f}±{eh:<4.3f} {np.mean(toks):11.0f}")
        out[name] = dict(acc=am, acc_ci=ah, reward=rm_, entropy=em,
                         tokens=float(np.mean(toks)))
        emit(f"quality/{name}", dt / (len(seeds) * steps),
             f"acc={am:.3f}+-{ah:.3f};tok={np.mean(toks):.0f}")
    return out


if __name__ == "__main__":
    run()
