"""Plain float32 reference of what the timed step computes.

Written from the published equations, imports nothing of the program:

* the model's forward: the configuration's family module (``logits``,
  named by the configuration's ``reference`` key), given every matmul;
* GRPO (NAT paper Eq. 2): advantages (r - mean) / (std + eps) per prompt,
  with the biased std;
* the NAT objective (Eqs. 3, 6, 9) on policy: loss = -mean_i (1 / T_i)
  sum_t w_it A_i rho_it, with rho = exp(logp - stopgrad(logp)) = 1, T_i the
  full response length and w = m / p the Horvitz-Thompson weight: p = 1
  for the full selector; for RPC with minimum cut C, p_t = 1 for t <= C and
  (T - t + 1) / (T - C + 1) after it (t counted from 1);
* AdamW: global-norm clipping, bias-corrected moments, linear warm-up then
  cosine decay to ``end_lr_frac`` of the rate.

All arithmetic is float32 with matmuls at ``Precision.HIGHEST``.  The
parameters are kept in bfloat16 between steps, the type the configuration
states for them (``torch_dtype``): each update is computed in float32 and
rounded to bfloat16, as a bfloat16 checkpoint holds it.

``fp8=True`` is the control: the same computation with every matmul's two
operands (the family's too, through ``mm``) rounded to float8 e4m3 (scaled
per tensor to its largest value), the precision below bfloat16 that a later
change could be tempted to use.

It runs on the device after the program's state is freed, a block of
sequences at a time (each padded only to its own longest, in steps of 128),
with the Adam moments kept on the host between steps.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 8
BLOCK_ALIGN = 128


# ------------------------------------------------------------------ model
def _round8(x):
    """Round to an 8-bit float of 4 exponent and 3 mantissa bits (e4m3),
    scaled per tensor so its largest value maps to the format's largest
    (240).  ``reduce_precision`` and not a cast to float8 and back: the
    compiler may drop a round trip of casts, never this rounding."""
    s = 240.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return jax.lax.reduce_precision(x * s, exponent_bits=4,
                                    mantissa_bits=3) / s


@jax.custom_vjp
def _q8(x):
    """A matmul operand in float8: rounded on the way forward, and its
    gradient rounded on the way back."""
    return _round8(x)


_q8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HI, preferred_element_type=F32)


def _at_tokens(per_pos, tokens=None):
    """(B, T - 1[, V]) per-position values -> (B, T) at each token (0 at
    position 0); with ``tokens``, the entry of the token that follows."""
    if tokens is not None:
        per_pos = jnp.take_along_axis(per_pos, tokens[:, 1:, None],
                                      axis=-1)[..., 0]
    return jnp.concatenate([jnp.zeros_like(per_pos[:, :1]), per_pos], axis=1)


def _logits(family, params, tokens, z, fp8):
    """The family's (B, T - 1, V) logits, every matmul through ``_mm``."""
    return family.logits(params, tokens, z, partial(_mm, fp8=fp8))


def token_logp(family, params, tokens, z, fp8=False):
    """(B, T) log-probability of each token given the ones before it (0 at
    position 0).  ``params``: flat float32 weights (bench/weights.py)."""
    lp = jax.nn.log_softmax(_logits(family, params, tokens, z, fp8),
                            axis=-1)
    return _at_tokens(lp, tokens)


def sampler_terms(logits, tokens, temps):
    """What a sampler at temperature ``temps[0]`` should produce, per token
    (B, T): ``nlp`` = -log p(token) under p = softmax(logits / temps[0]),
    ``h`` the entropy of p and ``var`` the variance of -log p(x) for x ~ p,
    so that sum(nlp - h) / sqrt(sum(var)) over a sound sample is about
    N(0, 1); ``ef`` the mean of -log p(x) for x drawn at ``temps[1]``
    instead (a sampler at the wrong temperature)."""
    lp = jax.nn.log_softmax(logits / temps[0], axis=-1)
    p = jnp.exp(lp)
    h = -jnp.sum(p * lp, -1)
    q = jax.nn.softmax(logits / temps[1], axis=-1)
    return {"nlp": -_at_tokens(lp, tokens), "h": _at_tokens(h),
            "var": _at_tokens(jnp.sum(p * lp * lp, -1) - h * h),
            "ef": _at_tokens(-jnp.sum(q * lp, -1))}


@partial(jax.jit, static_argnames=("family", "zt", "fp8", "temps"),
         donate_argnums=(1,))
def _block(params, acc, tokens, weights, adv, inv_len, scale, family, zt,
           fp8, temps):
    z = dict(zt)

    def loss_fn(pf):
        logits = _logits(family, pf, tokens, z, fp8)
        logp = _at_tokens(jax.nn.log_softmax(logits, axis=-1), tokens)
        rho = jnp.exp(logp - jax.lax.stop_gradient(logp))
        per_seq = jnp.sum(weights * adv[:, None] * rho, axis=1) * inv_len
        terms = sampler_terms(jax.lax.stop_gradient(logits), tokens, temps)
        return -jnp.sum(per_seq) * scale, (logp, per_seq, terms)

    (loss, (logp, per_seq, terms)), g = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    return {k: acc[k] + g[k] for k in acc}, loss, logp, per_seq, terms


@partial(jax.jit, static_argnames=("hp",))
def _adam(p, g, m, v, k, factor, hp):
    hp = dict(hp)
    b1, b2, eps, wd, lr = (hp["b1"], hp["b2"], hp["eps"],
                           hp["weight_decay"], _lr(hp, k))
    g = g * factor
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    kf = k.astype(F32)
    delta = (m / (1 - b1 ** kf)) / (jnp.sqrt(v / (1 - b2 ** kf)) + eps)
    new = p - lr * (delta + wd * p)
    # stored as bfloat16 (8 exponent, 7 mantissa bits); a cast to bfloat16
    # and back is a round trip the compiler may drop, this rounding it keeps
    return jax.lax.reduce_precision(new, exponent_bits=8,
                                    mantissa_bits=7), m, v


def _lr(hp, k):
    s = k.astype(F32)
    warm = jnp.minimum(s / max(hp["warmup_steps"], 1), 1.0)
    prog = jnp.clip((s - hp["warmup_steps"])
                    / max(hp["total_steps"] - hp["warmup_steps"], 1), 0, 1)
    frac = hp["end_lr_frac"] + (1 - hp["end_lr_frac"]) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return hp["lr"] * warm * frac


@jax.jit
def _sq(acc):
    return sum(jnp.sum(jnp.square(v)) for v in acc.values())


# ------------------------------------------------------------ objective
def advantages(rewards, group, eps):
    r = np.asarray(rewards, np.float64).reshape(-1, group)
    mu = r.mean(1, keepdims=True)
    sd = np.sqrt(((r - mu) ** 2).mean(1, keepdims=True))
    return ((r - mu) / (sd + eps)).reshape(-1)


def ht_weights(keep_mask, prompt_lens, response_lens, selector, min_cut):
    """w = m / p on the (B, T) grid from the kept-token mask."""
    keep = np.asarray(keep_mask, bool)
    w = np.zeros(keep.shape, np.float64)
    for i in range(keep.shape[0]):
        pl, n = int(prompt_lens[i]), int(response_lens[i])
        t = np.arange(1, n + 1, dtype=np.float64)
        if selector == "rpc":
            c = min(min_cut, n)
            p = np.where(t <= c, 1.0, (n - t + 1) / max(n - c + 1, 1))
        elif selector in ("full", "grpo"):
            p = np.ones(n)
        else:
            raise ValueError(f"the reference has no design for {selector!r}")
        w[i, pl:pl + n] = keep[i, pl:pl + n] / p
    return w


def selection_stats(keep_mask, prompt_lens, response_lens, selector,
                    min_cut) -> dict:
    """How a step's kept-token mask stands against the selector's design.

    ``faults``: rows whose kept tokens are not one prefix of the response
    that the design allows (RPC: a cut L in {min(C, T) .. T}; full: all T
    tokens), or that keep a token outside the response.  ``kept``, ``mean``
    and ``var``: the kept tokens, and their mean and variance under the
    design (RPC: L ~ Uniform{min(C, T) .. T}, independent across rows)."""
    keep = np.asarray(keep_mask, bool)
    faults, kept, mean, var = 0, 0, 0.0, 0.0
    for i in range(keep.shape[0]):
        pl, n = int(prompt_lens[i]), int(response_lens[i])
        k = int(keep[i, pl:pl + n].sum())
        lo = min(min_cut, n) if selector == "rpc" else n
        prefix = keep[i, pl:pl + k].all() and k == int(keep[i].sum())
        faults += int(not (prefix and lo <= k <= n))
        kept += k
        mean += (lo + n) / 2
        var += ((n - lo + 1) ** 2 - 1) / 12
    return {"faults": faults, "kept": kept, "mean": mean, "var": var}


def cut_z(stats) -> float:
    """The kept tokens of all ``stats`` against the design's mean, in its
    standard deviations (0 where the design fixes them and they match)."""
    d = sum(s["kept"] for s in stats) - sum(s["mean"] for s in stats)
    v = sum(s["var"] for s in stats)
    return abs(d) / math.sqrt(v) if v > 0 else (0.0 if d == 0 else math.inf)


# ------------------------------------------------------------------ steps
def follow(family, flat0, z, batches, hp, selector, min_cut, group,
           adv_eps, rewards, *, fp8=False, rows=None, temps=(1.0, 0.8),
           grad1_other=None, keep_grad1=False) -> dict:
    """Run the reference (or, with ``fp8``, the control) through the
    captured steps.

    flat0    initial weights (flat, bfloat16, host arrays).
    batches  per step: tokens (B, T), prompt_lens, response_lens, keep_mask.
    rewards  per step: (B,) rewards of the kept rows.
    rows     only these rows enter the mean (a planted fault: part of the
             batch left out); default all.
    temps    the mix's sampling temperature and a wrong one, for the
             sampler's terms (``sampler_terms``).
    grad1_other  host arrays (bench.weights names) of another first
             gradient, such as the program's; its per-leaf cosine with this
             one is returned as ``grad1_cos``.
    keep_grad1   also return this first gradient as host arrays.

    Returns per-step losses and per-sequence terms, step 1's token logp
    (B, T) and sampler terms, the first gradient's per-leaf norms after
    clipping and the per-leaf norms of the change over all steps.
    """
    from bench import weights as W

    zt = tuple(sorted(z.items()))
    # bfloat16 values held in float32 arrays: every update is rounded to
    # bfloat16 (_adam), and nothing converts inside the gradient program
    params = {n: jnp.asarray(v).astype(F32) for n, v in flat0.items()}
    moments = None
    out = {"loss": [], "per_seq": []}
    for k, (b, r) in enumerate(zip(batches, rewards), start=1):
        tokens = np.asarray(b["tokens"])
        n_rows = tokens.shape[0]
        use = np.arange(n_rows) if rows is None else np.asarray(rows)
        adv = advantages(r, group, adv_eps)
        w = ht_weights(b["keep_mask"], b["prompt_lens"], b["response_lens"],
                       selector, min_cut)
        inv_len = 1.0 / np.maximum(np.asarray(b["response_lens"], F32), 1.0)
        lens = np.asarray(b["prompt_lens"]) + np.asarray(b["response_lens"])
        order = use[np.argsort(lens[use], kind="stable")]
        acc = {n: jnp.zeros(v.shape, F32) for n, v in params.items()}
        loss = 0.0
        logp = np.zeros(tokens.shape, np.float32)
        terms = {n: np.zeros(tokens.shape, np.float32)
                 for n in ("nlp", "h", "var", "ef")}
        per_seq = np.zeros((n_rows,), np.float64)
        for i in range(0, len(order), BLOCK_ROWS):
            blk = order[i:i + BLOCK_ROWS]
            t = min(tokens.shape[1], -(-int(lens[blk].max()) // BLOCK_ALIGN)
                    * BLOCK_ALIGN)
            pad = BLOCK_ROWS - len(blk)
            sel = np.concatenate([blk, np.repeat(blk[:1], pad)])
            live = np.r_[np.ones(len(blk)), np.zeros(pad)].astype(np.float32)
            acc, l, lp, ps, tm = _block(
                params, acc, jnp.asarray(tokens[sel, :t]),
                jnp.asarray((w[sel, :t] * live[:, None]).astype(np.float32)),
                jnp.asarray(adv[sel].astype(np.float32)),
                jnp.asarray(inv_len[sel]),
                jnp.float32(1.0 / len(use)), family, zt, fp8, tuple(temps))
            loss += float(l)
            lp, ps = np.asarray(lp), np.asarray(ps)
            logp[blk, :t] = lp[:len(blk)]
            per_seq[blk] = ps[:len(blk)]
            for n, v in jax.device_get(tm).items():
                terms[n][blk, :t] = v[:len(blk)]
        out["loss"].append(loss)
        out["per_seq"].append(per_seq[use])
        if k == 1:
            out["logp1"] = logp
            out["sampler1"] = terms
        gnorm = math.sqrt(float(_sq(acc)))
        factor = jnp.float32(min(1.0, hp["clip_norm"] / max(gnorm, 1e-12)))
        if k == 1:
            out["grad1"] = W.leaf_norms(
                {n: acc[n] * factor for n in acc}, family.STACKED)
            if grad1_other is not None:
                out["grad1_cos"] = W.cosines(acc, grad1_other,
                                             family.STACKED)
            if keep_grad1:
                out["grad1_host"] = {n: np.asarray(jax.device_get(
                    acc[n] * factor)) for n in acc}
        if moments is None:
            moments = {n: (np.zeros(v.shape, np.float32),
                           np.zeros(v.shape, np.float32))
                       for n, v in params.items()}
        hpt = tuple(sorted(hp.items()))
        new = {}
        for n in list(params):
            g = acc.pop(n)
            m, v = moments[n]
            p, m, v = _adam(params[n], g, jnp.asarray(m), jnp.asarray(v),
                            jnp.int32(k), factor, hpt)
            del g
            moments[n] = (np.asarray(m), np.asarray(v))
            new[n] = p
        params = new
    out["update"] = W.change_norms(params, flat0, family.STACKED)
    return out
