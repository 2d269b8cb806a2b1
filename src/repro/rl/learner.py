"""The NAT-GRPO learner step: scoring + HT-weighted loss + grads + AdamW.

One code path serves both the CPU trainer (num_microbatches=1, tiny model)
and the production dry-run (gradient accumulation over microbatches, 512-way
mesh) so what we validate hermetically is what we lower at scale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.grpo import GRPOConfig, nat_grpo_loss
from repro.dist.sharding import DEFAULT_RULES
from repro.models.config import ModelConfig
from repro.models.model import score_tokens
from repro.optim.adamw import AdamWConfig, adamw_update

F32 = jnp.float32

# behavior_logp/staleness are optional: the async trainer supplies them so
# stale samples get the truncated-IS correction (core/grpo.py); the serial
# path may omit them (or pass staleness == 0, which is bit-identical)
BATCH_KEYS = ("tokens", "response_mask", "old_logp", "advantages",
              "ht_weights", "orig_lengths", "lengths", "behavior_logp",
              "staleness")

# the packed layout (core/layout.py) swaps the per-row keys: token leaves
# are (num_rows, pack_len), per-response leaves stay (B,), and three id
# planes map packed tokens back — positions (rope), segment_ids (attention
# visibility), resp_ids (loss segment scatter)
PACKED_BATCH_KEYS = ("tokens", "positions", "segment_ids", "resp_ids",
                     "response_mask", "old_logp", "advantages", "ht_weights",
                     "orig_lengths", "behavior_logp", "staleness")

# the paged layout (zero re-prefill scoring, DESIGN.md §11) adds the page
# handoff from the rollout engine's export_learner_pages: the per-layer
# pool pages plus the per-segment block tables and suffix-start positions
PAGED_BATCH_KEYS = PACKED_BATCH_KEYS + ("pool", "block_tables", "seg_start")


def make_loss_fn(model_cfg: ModelConfig, grpo_cfg: GRPOConfig, *,
                 mesh=None, rules=None, vocab_chunks: int = 8,
                 packed: bool = False, paged: bool = False,
                 paged_impl: str = "ref"):
    """Build the learner loss.  ``packed=True`` consumes PACKED_BATCH_KEYS
    batches: scoring runs on the dense packed rows (segment-masked
    attention, original positions) and the HT reduction gathers per-token
    terms back to per-response sums via ``resp_ids`` segment scatter —
    same estimator, fewer scored tokens.

    ``paged=True`` (implies packed rows) consumes PAGED_BATCH_KEYS batches
    from ``core.layout.PagedLayout`` + the engine's
    ``export_learner_pages``: only response suffixes are forwarded, prompt
    KV is read (detached) from the rollout page pool — zero re-prefill
    (DESIGN.md §11).  ``paged_impl`` picks the attention path ("ref" |
    "kernel")."""
    rules = rules or DEFAULT_RULES  # a mesh without rules gets the defaults

    def loss_fn(params, mb: dict):
        # the backward pass's ops carry transpose(jvp(learner.loss))
        with jax.named_scope("learner.loss"):
            return _loss(params, mb)

    def _loss(params, mb: dict):
        if packed or paged:
            pg = {} if not paged else dict(
                paged_prefix=mb["pool"],
                page_tables={"block_tables": mb["block_tables"],
                             "seg_start": mb["seg_start"]},
                paged_impl=paged_impl)
            logp, aux = score_tokens(
                params, model_cfg, mb["tokens"],
                positions=mb["positions"], segment_ids=mb["segment_ids"],
                image_embeds=mb.get("image_embeds"), mesh=mesh, rules=rules,
                vocab_chunks=vocab_chunks, **pg)
            loss, metrics = nat_grpo_loss(
                logp, mb["old_logp"], mb["advantages"], mb["ht_weights"],
                mb["orig_lengths"], grpo_cfg, ref_logp=mb.get("ref_logp"),
                behavior_logp=mb.get("behavior_logp"),
                staleness=mb.get("staleness"),
                segment_ids=mb["resp_ids"],
                num_segments=mb["advantages"].shape[0])
        else:
            logp, aux = score_tokens(
                params, model_cfg, mb["tokens"], lengths=mb["lengths"],
                image_embeds=mb.get("image_embeds"), mesh=mesh, rules=rules,
                vocab_chunks=vocab_chunks)
            loss, metrics = nat_grpo_loss(
                logp, mb["old_logp"], mb["advantages"], mb["ht_weights"],
                mb["orig_lengths"], grpo_cfg, ref_logp=mb.get("ref_logp"),
                behavior_logp=mb.get("behavior_logp"),
                staleness=mb.get("staleness"))
        metrics["moe_aux"] = aux
        return loss + aux, metrics

    return loss_fn


def make_train_step(
    model_cfg: ModelConfig,
    grpo_cfg: GRPOConfig,
    opt_cfg: AdamWConfig,
    *,
    num_microbatches: int = 1,
    mesh=None,
    rules=None,
    vocab_chunks: int = 8,
    unroll_microbatches: bool = False,
    param_shardings=None,
    packed: bool = False,
    paged: bool = False,
    paged_impl: str = "ref",
):
    """Returns learner_step(params, opt_state, batch) -> (params', opt',
    metrics); jitted, its program is named ``jit_learner_step``.

    With num_microbatches > 1 the batch is split on dim 0 and gradients are
    accumulated in fp32 through a lax.scan (sequential microbatches — the
    standard activation-memory/compute trade at large global batch).
    ``unroll_microbatches`` uses a Python loop instead of lax.scan — the
    dry-run's roofline probes need the per-microbatch cost visible in HLO
    (XLA's cost analysis counts a while-loop body once).
    ``param_shardings`` (optional tree of NamedShardings): constrain each
    microbatch gradient to its parameter's sharding so the data-axis psum
    lowers to a reduce-scatter instead of a full all-reduce (§Perf).
    ``packed`` selects the packed-layout loss (PACKED_BATCH_KEYS).  Packed
    batches cannot be split on dim 0 — a packed row holds tokens of several
    responses while the per-response leaves stay (B,) — so with
    ``num_microbatches > 1`` the batch must be microbatched BEFORE packing
    (``core.layout.build_microbatches``: one pack plan per chunk) and the
    train step consumes a TUPLE of per-microbatch packed dicts.  The
    accumulation loop is unrolled — chunks may pack to different
    (rows, pack_len) shapes, which lax.scan cannot carry.
    ``paged=True`` swaps in the zero re-prefill loss (PAGED_BATCH_KEYS;
    see ``make_loss_fn``); the microbatch discipline is the packed one."""
    loss_fn = make_loss_fn(model_cfg, grpo_cfg, mesh=mesh, rules=rules,
                           vocab_chunks=vocab_chunks, packed=packed,
                           paged=paged, paged_impl=paged_impl)
    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def constrain(grads):
        if param_shardings is None:
            return grads
        return jax.tree.map(jax.lax.with_sharding_constraint, grads,
                            param_shardings)

    def packed_accum_step(params, opt_state, batches):
        """Packed gradient accumulation: ``batches`` is a tuple of
        ``num_microbatches`` pre-packed dicts (split on the response axis
        before packing).  Grads and metrics average over chunks exactly as
        the dense scan path does."""
        m = num_microbatches
        if not isinstance(batches, (tuple, list)) or len(batches) != m:
            raise ValueError(
                f"packed train step with num_microbatches={m} takes a "
                f"tuple of {m} pre-packed batch dicts "
                "(core.layout.build_microbatches)")
        g_acc = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
        metrics0 = jax.eval_shape(lambda p, b: loss_fn(p, b)[1], params,
                                  batches[0])
        metric_acc = jax.tree.map(lambda _: jnp.zeros((), F32), metrics0)
        for mb in batches:
            (loss, metrics), g = vg(params, mb)
            g = constrain(g)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(F32) / m,
                                 g_acc, g)
            metrics = {k: v.astype(F32) / m for k, v in metrics.items()}
            metric_acc = jax.tree.map(lambda a, b: a + b, metric_acc,
                                      metrics)
        return g_acc, metric_acc

    def learner_step(params, opt_state, batch: dict):
        m = num_microbatches
        if m == 1:
            (loss, metrics), grads = vg(params, batch)
            grads = constrain(grads)
        elif packed or paged:
            grads, metrics = packed_accum_step(params, opt_state, batch)
        else:
            def split(x):
                return x.reshape((m, x.shape[0] // m) + x.shape[1:])

            mbs = {k: split(v) for k, v in batch.items()}

            def acc(carry, mb):
                g_acc, metric_acc = carry
                (loss, metrics), g = vg(params, mb)
                g = constrain(g)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(F32) / m, g_acc, g)
                metrics = {k: v.astype(F32) / m for k, v in metrics.items()}
                metric_acc = jax.tree.map(lambda a, b: a + b, metric_acc, metrics)
                return (g_acc, metric_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
            mb0 = jax.tree.map(lambda x: x[0], mbs)
            metrics0 = jax.eval_shape(lambda p, b: loss_fn(p, b)[1], params, mb0)
            metric0 = jax.tree.map(lambda _: jnp.zeros((), F32), metrics0)
            if unroll_microbatches:
                carry = (g0, metric0)
                for i in range(m):
                    carry, _ = acc(carry, jax.tree.map(lambda x: x[i], mbs))
                grads, metrics = carry
            else:
                (grads, metrics), _ = jax.lax.scan(acc, (g0, metric0), mbs)

        with jax.named_scope("learner.optimizer"):
            new_params, new_opt, opt_metrics = adamw_update(
                params, grads, opt_state, opt_cfg)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return new_params, new_opt, metrics

    return learner_step


def with_publication(train_step, publisher):
    """Compose a train step with device-to-device weight publication
    (DESIGN.md §12): after each update the new params are snapshotted onto
    every rollout slice via ``dist.publish.WeightPublisher`` — a pure
    ``jax.device_put`` resharding, zero bytes through the host — before
    the step returns.  Publication is async-dispatched device work, so it
    overlaps the host-side metrics fetch that follows in the trainer.

    Epochs auto-increment from the publisher's last epoch; the
    disaggregated trainer maps them 1:1 onto learner versions
    (``rl/dist_trainer.py::DistNATGRPOTrainer._publish``).
    """

    def published_step(params, opt_state, batch, *args, **kwargs):
        new_params, new_opt, metrics = train_step(
            params, opt_state, batch, *args, **kwargs)
        publisher.publish(new_params)
        return new_params, new_opt, metrics

    return published_step
