"""Real training entry point (the launcher a cluster job would run).

    PYTHONPATH=src python -m repro.launch.train \
        --arch nat-qwen3-8b --preset smoke --selector rpc --steps 50 \
        --ckpt-dir /tmp/nat_ckpt --ckpt-every 10

On this CPU container the ``smoke`` preset (reduced config) actually trains;
the ``full`` preset builds the exact assigned architecture and is what a TPU
job would launch (same code path the dry-run compiles).  Fault tolerance:
periodic async checkpoints (params, optimizer, data cursor, PRNG, step),
SIGTERM triggers a final save, and restart auto-resumes from the latest
checkpoint — onto whatever mesh the restarted job has (elastic restore).
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke
from repro.launch.compile_cache import use_compile_cache
from repro.optim import AdamWConfig
from repro.rl import NATGRPOTrainer, NATTrainerConfig, RolloutConfig
from repro.rl.dist_trainer import make_dist_trainer
from repro.rl.env import VOCAB_SIZE as ENV_VOCAB


def build_model_cfg(arch: str, preset: str):
    cfg = get_smoke(arch) if preset == "smoke" else get_config(arch)
    if preset == "smoke":
        # the RL env has its own tiny vocabulary
        cfg = dataclasses.replace(cfg, vocab_size=max(ENV_VOCAB, 32))
    return cfg


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="nat-qwen3-8b")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--selector", default="rpc",
                    choices=["full", "grpo", "urs", "rpc", "det_trunc", "entropy"])
    ap.add_argument("--min-cut", type=int, default=8)
    ap.add_argument("--urs-p", type=float, default=0.5)
    ap.add_argument("--env", default="mod_arith", choices=["mod_arith", "copy_calc"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--prompts-per-step", type=int, default=8)
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--overprovision", type=float, default=1.25)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--layout", default="",
                    choices=["", "padded", "bucketed", "packed"],
                    help="learner batch layout (core/layout.py, DESIGN.md "
                         "§7); default derives from the selector's repack")
    ap.add_argument("--rollout-engine", default="continuous",
                    choices=["continuous", "paged", "legacy"],
                    help="rollout arena: dense slot rows, paged KV pool "
                         "with group prefix sharing (DESIGN.md §8), or "
                         "the legacy fixed-shape scan")
    ap.add_argument("--fleet", type=int, default=0,
                    help="replicated rollout fleet size (DESIGN.md §12): "
                         "carve the device set into a learner slice plus N "
                         "engine replicas with device-to-device weight "
                         "publication; 0 = single in-process engine")
    ap.add_argument("--disagg", default="", choices=["", "prefill,decode"],
                    help="split each fleet slice into a prefill cell and a "
                         "paged decode arena (requires --rollout-engine "
                         "paged; checked against models/capabilities.py at "
                         "config time)")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="bounded staleness for the overlapped pipeline "
                         "(0 = serial; required 0 for bit-exact parity)")
    ap.add_argument("--fleet-elastic", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="supervise the fleet (DESIGN.md §13): heartbeat "
                         "each actor, reclaim a dead/hung replica's claimed "
                         "group for a token-exact re-roll by a survivor, "
                         "and allow add_replica joins mid-run; "
                         "--no-fleet-elastic dies on first replica failure")
    ap.add_argument("--hang-timeout", type=float, default=300.0,
                    help="seconds a claimed group may sit with no heartbeat "
                         "and no engine progress before the supervisor "
                         "condemns the replica and reclaims its group")
    ap.add_argument("--supervise-interval", type=float, default=0.2,
                    help="supervisor monitor poll period in seconds")
    ap.add_argument("--publish-retries", type=int, default=3,
                    help="bounded attempts for weight publication before "
                         "escalating PublicationError (DESIGN.md §13)")
    ap.add_argument("--placement-retries", type=int, default=3,
                    help="bounded rollout attempts under transient "
                         "PagePoolExhausted before escalating")
    ap.add_argument("--eval-prompts", type=int, default=32)
    args = ap.parse_args(argv)
    use_compile_cache()

    model_cfg = build_model_cfg(args.arch, args.preset)
    sel_kwargs = ()
    if args.selector == "rpc":
        sel_kwargs = (("min_cut", args.min_cut),)
    elif args.selector == "urs":
        sel_kwargs = (("p", args.urs_p),)
    tcfg = NATTrainerConfig(
        env=args.env,
        selector=args.selector,
        selector_kwargs=sel_kwargs,
        prompts_per_step=args.prompts_per_step,
        rollout=RolloutConfig(max_new_tokens=args.max_new,
                              group_size=args.group_size,
                              overprovision=args.overprovision),
        adamw=AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps),
        layout=args.layout,
        rollout_engine=args.rollout_engine,
        max_staleness=args.max_staleness,
        fleet=args.fleet,
        disagg=args.disagg,
        supervise=args.fleet_elastic,
        hang_timeout=args.hang_timeout,
        supervise_interval=args.supervise_interval,
        publish_retries=args.publish_retries,
        placement_retries=args.placement_retries,
        seed=args.seed,
    )
    # config-time capability check happens inside the dist constructor
    # (models/capabilities.py::check_slice_handoff) — a mixer whose state
    # can't hand off across slices fails HERE, not 50 steps in
    if args.fleet or args.disagg or args.max_staleness:
        trainer = make_dist_trainer(model_cfg, tcfg)
    else:
        trainer = NATGRPOTrainer(model_cfg, tcfg)

    # the trainer's own quiesce-checkpoint (DESIGN.md §6) persists params,
    # optimizer, AND the async cursors (learner version, actor key chain,
    # pipeline step): resume is token-exact for this serial trainer, and a
    # clean group boundary for the max_staleness>0 pipeline
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt is not None and ckpt.latest_step() is not None:
        trainer.restore_checkpoint(ckpt)
        print(f"resumed from step {trainer.step_count}")

    def on_sigterm(signum, frame):
        print("SIGTERM received: saving final checkpoint", file=sys.stderr)
        if ckpt is not None:
            trainer.save_checkpoint(ckpt, blocking=True)
        sys.exit(0)

    signal.signal(signal.SIGTERM, on_sigterm)

    while trainer.step_count < args.steps:
        m = trainer.train_step()
        s = trainer.step_count
        if args.log_every and s % args.log_every == 0:
            print(f"step {s:4d} reward={m['reward_mean']:.3f} "
                  f"loss={m['loss']:+.4f} sel={m.get('selected_ratio', 1.0):.2f} "
                  f"grad={m['grad_norm']:.2f} t={m['time_total']:.2f}s")
        if ckpt is not None and s % args.ckpt_every == 0:
            trainer.save_checkpoint(ckpt, blocking=False)

    if ckpt is not None:
        ckpt.wait()
        trainer.save_checkpoint(ckpt, blocking=True)
    ev = trainer.evaluate(args.eval_prompts)
    print(f"final eval: accuracy={ev['accuracy']:.3f} "
          f"mean_resp_len={ev['resp_len']:.1f}")
    trainer.close()
    return trainer


if __name__ == "__main__":
    main()
