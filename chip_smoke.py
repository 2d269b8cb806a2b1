"""Bring-up check of the NAT trainer on a TPU: the main path, once, at the
published widths of Qwen3-8B, on random weights made from a seed.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips

One chip runs two phases:

1. **Paged decode kernel** — ``models/attention.py::paged_decode_attention``
   with ``impl="kernel"`` (the Pallas kernel, compiled) against
   ``impl="ref"`` (the jnp gather path) on one pool, at the model's widths.
2. **Training** — ``NATGRPOTrainer`` (what ``launch/train.py`` builds) with
   the RPC selector, the packed learner layout and the paged rollout engine:
   one warm-up step, then three steps with finite losses.

``--four-chips`` runs only what exists across chips: the serial trainer for
three steps, then a fleet of one at staleness 0 (bit-exact against it), then
a fleet of two at staleness 1 (watermarks, zero host bytes for publication).

Every phase runs in this one process.  The last line of standard output is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
Without a TPU the script refuses to run.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.attention import paged_decode_attention  # noqa: E402
from repro.models.config import dense_blocks  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.rl import NATGRPOTrainer, NATTrainerConfig, RolloutConfig  # noqa: E402
from repro.rl.dist_trainer import make_dist_trainer  # noqa: E402

# Qwen3-8B at its published widths (d_model 4096, 32 query / 8 kv heads of
# 128, SwiGLU d_ff 12288), cut to one chip's share of an 8-chip deployment:
# - depth: 4 of 36 layers (a dense stack repeats with period 1; 4 is the
#   floor for a cut model),
# - vocabulary: 18,992 rows, one shard of the 151,936-row embedding and head
#   under 8-way vocab parallelism (the RL env's 23 token ids fall inside).
DEPTH = 4
VOCAB_SHARD = 151_936 // 8
SEED = 0

# Kernel vs jnp path, as max |difference| over max |reference|.  Both read
# the same bf16 pool; the jnp path rounds its softmax probabilities to bf16
# before the value contraction while the kernel keeps them in f32, so they
# differ by a few bf16 roundings (2^-8 each) in the attention output and the
# bf16 output projection (5e-3 at these widths in interpret mode).  2e-2
# leaves room for those and none for a wrong page, head or mask, which
# moves the output by O(1).
KERNEL_TOL = 2e-2

# Learner over engine probability of the kept tokens, mean of
# exp(logp - old_logp) at staleness 0: the rollout engine's in-flight
# logprobs (paged decode) and the learner's packed teacher-forced forward
# score the same tokens under the same bf16 params by two paths, so the
# mean stays within bf16 noise of 1.  A wrong position, mask or page moves
# the logprobs of a context-dependent model by far more than 5e-2.
RATIO_TOL = 5e-2


class CompileLog:
    """Counts and times XLA compilations (and persistent-cache hits)."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.n, self.seconds, self.cache_hits


def model_config():
    cfg = get_config("nat-qwen3-8b")
    return dataclasses.replace(cfg, blocks=dense_blocks(DEPTH),
                               vocab_size=VOCAB_SHARD)


def trainer_config(**kw) -> NATTrainerConfig:
    # launch/train.py's defaults for the RPC selector, at P=8, G=8
    base = dict(
        selector="rpc", selector_kwargs=(("min_cut", 8),),
        prompts_per_step=8,
        rollout=RolloutConfig(max_new_tokens=64, group_size=8,
                              overprovision=1.25),
        adamw=AdamWConfig(lr=3e-4, warmup_steps=10, total_steps=50),
        layout="packed", rollout_engine="paged", seed=SEED)
    base.update(kw)
    return NATTrainerConfig(**base)


def say(msg: str) -> None:
    print(msg, flush=True)


def devices_of(tree) -> list:
    """Device ids the arrays of ``tree`` live on, read from the arrays."""
    return sorted({d.id for x in jax.tree.leaves(tree)
                   if isinstance(x, jax.Array) for d in x.devices()})


# ------------------------------------------------------------ phase: kernel
def kernel_phase(cfg, tcfg) -> None:
    """Pallas paged decode vs the jnp gather path, one decode step of one
    attention layer, on a pool laid out as the trainer's paged engine
    leaves it: one slot per sample, each group's slots share its prompt
    pages, decode pages are slot-private and start on a fresh page."""
    rng = np.random.default_rng(tcfg.seed)
    d, h, kvh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    group = tcfg.rollout.group_size
    slots = tcfg.prompts_per_step * group
    page_len, prompt_len = tcfg.page_len, tcfg.max_prompt_len
    max_new = tcfg.rollout.max_new_tokens
    prompt_pages = -(-prompt_len // page_len)
    dec_pages = -(-max_new // page_len)
    groups = -(-slots // group)
    num_pages = groups * prompt_pages + slots * dec_pages
    pos_pages = np.full((num_pages, page_len), -1, np.int32)
    tables = np.full((slots, prompt_pages + dec_pages), -1, np.int32)
    for gi in range(groups):
        for j in range(prompt_pages):
            n = min(page_len, prompt_len - j * page_len)
            pos_pages[gi * prompt_pages + j, :n] = np.arange(
                j * page_len, j * page_len + n)
    pos = np.zeros((slots,), np.int32)
    write_page = np.zeros((slots,), np.int32)
    write_off = np.zeros((slots,), np.int32)
    nxt = groups * prompt_pages
    for s in range(slots):
        gi = s // group
        tables[s, :prompt_pages] = gi * prompt_pages + np.arange(prompt_pages)
        done = int(rng.integers(0, max_new))        # tokens already decoded
        for j in range(-(-(done + 1) // page_len)):
            tables[s, prompt_pages + j] = nxt + j
            n = min(page_len, done - j * page_len)
            if n > 0:
                pos_pages[nxt + j, :n] = prompt_len + j * page_len + np.arange(n)
        pos[s] = prompt_len + done                  # the token decoded now
        write_page[s] = nxt + done // page_len
        write_off[s] = done % page_len
        nxt += dec_pages

    ks = jax.random.split(jax.random.PRNGKey(tcfg.seed), 7)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(jnp.bfloat16)

    p = {"wq": w(ks[0], (d, h, dh), d), "wk": w(ks[1], (d, kvh, dh), d),
         "wv": w(ks[2], (d, kvh, dh), d), "wo": w(ks[3], (h, dh, d), h * dh)}
    pool = {"k": w(ks[4], (num_pages, page_len, kvh, dh), 1),
            "v": w(ks[5], (num_pages, page_len, kvh, dh), 1),
            "pos": jnp.asarray(pos_pages)}
    x = w(ks[6], (slots, 1, d), 1)
    args = (p, x, pool, jnp.asarray(pos[:, None]), jnp.asarray(tables),
            jnp.asarray(write_page), jnp.asarray(write_off))

    def run(impl):
        fn = jax.jit(lambda *a: paged_decode_attention(
            *a, rope_theta=cfg.rope_theta, impl=impl))
        out, new_pool = fn(*args)
        return np.asarray(out.astype(jnp.float32)), new_pool

    out_k, pool_k = run("kernel")
    out_r, pool_r = run("ref")
    for name in ("k", "v", "pos"):
        if not np.array_equal(np.asarray(pool_k[name].astype(jnp.float32)),
                              np.asarray(pool_r[name].astype(jnp.float32))):
            raise AssertionError(f"pool write differs between paths: {name}")
    if not np.isfinite(out_k).all():
        raise AssertionError("paged decode kernel output is not finite")
    err = float(np.max(np.abs(out_k - out_r)) / np.max(np.abs(out_r)))
    say(f"paged decode kernel vs jnp path: {slots} slots, {h}/{kvh} heads "
        f"x {dh}, page_len {page_len}: max|diff|/max|ref| = {err!r} "
        f"(tolerance {KERNEL_TOL})")
    if not err <= KERNEL_TOL:
        raise AssertionError(f"paged decode kernel differs from the jnp "
                             f"path: {err} > {KERNEL_TOL}")


# ---------------------------------------------------------- phase: training
def step_line(i, m) -> str:
    return (f"step {i}: {m['time_total']!r} s  loss={m['loss']!r}  "
            f"reward={m['reward_mean']!r}  "
            f"selected_ratio={m.get('selected_ratio', 1.0)!r}  "
            f"ratio_mean={m['ratio_mean']!r}  "
            f"tokens_generated={m['tokens_generated']}  "
            f"learner_rows={m['learner_rows']}x{m['bucket_len']}")


def check_step(i, m) -> None:
    if not np.isfinite(m["loss"]):
        raise AssertionError(f"step {i}: non-finite loss {m['loss']}")
    if m["staleness"] == 0 and not abs(m["ratio_mean"] - 1.0) <= RATIO_TOL:
        raise AssertionError(f"step {i}: learner and engine logprobs "
                             f"disagree, ratio_mean {m['ratio_mean']}")


def train_phase(cfg, tcfg, clog: CompileLog, *, steps: int = 3) -> None:
    n0, s0, h0 = clog.snapshot()
    t0 = time.perf_counter()
    trainer = NATGRPOTrainer(cfg, tcfg)
    nb, sb, hb = clog.snapshot()
    say(f"trainer built in {time.perf_counter() - t0!r} s; compiled "
        f"{nb - n0} programs in {sb - s0!r} s (persistent cache hits "
        f"{hb - h0})")
    try:
        m = trainer.train_step()
        check_step("warm-up", m)
        n1, s1, h1 = clog.snapshot()
        say(f"warm-up step: {m['time_total']!r} s; compiled {n1 - nb} "
            f"programs in {s1 - sb!r} s (persistent cache hits {h1 - hb})")
        say(step_line("warm-up", m))
        times = []
        for i in range(1, steps + 1):
            m = trainer.train_step()
            check_step(i, m)
            times.append(m["time_total"])
            say(step_line(i, m))
        n2, s2, _ = clog.snapshot()
        say(f"steps 1-{steps}: compiled {n2 - n1} programs in {s2 - s1!r} s; "
            f"median step {float(np.median(times))!r} s")
        stats = jax.devices()[0].memory_stats() or {}
        say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")
    finally:
        trainer.close()


# ------------------------------------------------------- phase: four chips
def recording(trainer) -> list:
    """Keep each served group's rollout tokens (learner-side queue pop)."""
    seen = []
    pop = trainer.queue.pop

    def recording_pop(*a, **kw):
        g = pop(*a, **kw)
        seen.append(np.asarray(g.batch.tokens))
        return g

    trainer.queue.pop = recording_pop
    return seen


def roles(trainer) -> str:
    parts = [f"learner params={devices_of(trainer.params)} "
             f"opt={devices_of(trainer.opt_state)}"]
    for eng in getattr(trainer, "fleet_engines", [trainer.engine]):
        name = getattr(eng, "chaos_replica", None) or "engine"
        parts.append(f"{name} params={devices_of(eng._params)} "
                     f"state={devices_of(getattr(eng, 'last_state', None))}")
    return "; ".join(parts)


def first_difference(ref: list, got: list) -> str:
    for i, (a, b) in enumerate(zip(ref, got)):
        if a.shape != b.shape:
            return f"step {i}: token grid {a.shape} vs {b.shape}"
        bad = np.argwhere(a != b)
        if len(bad):
            r, c = bad[0]
            return f"step {i}: first differing token at row {r}, column {c}"
    return "none"


def four_chip_phase(cfg, *, steps: int = 3) -> None:
    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    keys = ("loss", "reward_mean", "resp_len_mean", "tokens_generated",
            "grad_norm")
    serial = NATGRPOTrainer(cfg, trainer_config())
    ref_tokens = recording(serial)
    ref = [serial.train_step() for _ in range(steps)]
    say("serial: " + roles(serial))
    for i, m in enumerate(ref):
        check_step(i, m)
        say("serial " + step_line(i, m))
    ref_params = jax.tree.map(np.asarray, serial.params)
    serial.close()
    del serial
    gc.collect()

    dist = make_dist_trainer(cfg, trainer_config(fleet=1, max_staleness=0))
    try:
        say(f"fleet of 1: topology {dist.topology.describe()}")
        got_tokens = recording(dist)
        got = [dist.train_step() for _ in range(steps)]
        say("fleet of 1: " + roles(dist))
        for i, m in enumerate(got):
            say("fleet1 " + step_line(i, m))
        diffs = [f"step {i} {k}: {a[k]!r} vs {b[k]!r}"
                 for i, (a, b) in enumerate(zip(ref, got)) for k in keys
                 if a[k] != b[k]]
        same_params = all(jax.tree.leaves(jax.tree.map(
            lambda x, y: bool(np.array_equal(x, np.asarray(y))),
            ref_params, dist.params)))
        where = first_difference(ref_tokens, got_tokens)
        say(f"fleet of 1 vs serial: metric diffs {diffs or 'none'}; "
            f"rollout tokens first difference: {where}; "
            f"params bit-exact: {same_params}")
        stats = dist.publication_stats()
        say(f"fleet of 1 publication: host_bytes={stats['host_bytes']} "
            f"publishes={stats['publishes']} "
            f"bytes_published={stats['bytes_published']}")
        if diffs or where != "none" or not same_params:
            raise AssertionError("fleet of 1 is not bit-exact against the "
                                 "serial trainer")
        if stats["host_bytes"] != 0:
            raise AssertionError("publication moved bytes through the host")
    finally:
        dist.close()
    del dist, ref_params
    gc.collect()

    dist = make_dist_trainer(cfg, trainer_config(fleet=2, max_staleness=1))
    try:
        say(f"fleet of 2: topology {dist.topology.describe()}")
        marks = []
        for i in range(steps):
            m = dist.train_step()
            check_step(i, m)
            marks.append(dict(dist.publication_stats()["watermarks"]))
            say(f"fleet2 {step_line(i, m)}  staleness={m['staleness']} "
                f"watermarks={marks[-1]}")
        say("fleet of 2: " + roles(dist))
        stats = dist.publication_stats()
        say(f"fleet of 2 publication: host_bytes={stats['host_bytes']} "
            f"publishes={stats['publishes']} watermarks={stats['watermarks']}")
        if set(stats["watermarks"]) != {"fleet0", "fleet1"}:
            raise AssertionError(f"not every fleet deposited: "
                                 f"{stats['watermarks']}")
        if max(marks[-1].values()) <= max(marks[0].values(), default=-1):
            raise AssertionError(f"watermarks did not advance: {marks}")
        if stats["host_bytes"] != 0:
            raise AssertionError("publication moved bytes through the host")
    finally:
        dist.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip fleet phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    say(f"device: {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}")
    say(f"compile cache: {use_compile_cache()}")
    clog = CompileLog()
    cfg = model_config()
    say(f"model: {cfg.name} widths, {cfg.n_layers} layers, vocab "
        f"{cfg.vocab_size}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(cfg)
    else:
        tcfg = trainer_config()
        kernel_phase(cfg, tcfg)
        train_phase(cfg, tcfg, clog)
    n, s, hits = clog.snapshot()
    say(f"total: {time.perf_counter() - t0!r} s; compiled {n} programs in "
        f"{s!r} s; persistent cache hits {hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
