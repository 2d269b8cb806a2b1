"""jit-able wrapper for the fused HT head: custom_vjp around the Pallas
kernels, saving only (logz,) per token — flash-style — instead of the
(N, V) logits.

``fused_token_logprobs(hidden, w, tokens)`` is a drop-in for the jnp chunked
path in ``repro.models.layers.chunked_token_logprobs`` (flattened (N, D)
layout; entropy is returned but NOT differentiated — it is a metrics-only
quantity in NAT, so its cotangent is dropped by design).
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

from repro.kernels.ht_loss import kernel as K

F32 = jnp.float32


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_token_logprobs(hidden, w, tokens, block_n: int = 256,
                         block_v: int = 512, interpret: bool | None = None):
    """hidden: (N, D), w: (D, V), tokens: (N,) -> (logp (N,), entropy (N,)).

    Gradients flow to ``hidden`` and ``w`` through logp only.
    """
    logp, _, ent = K.fwd_pallas(hidden, w, tokens, block_n=block_n,
                                block_v=block_v, interpret=interpret)
    return logp, ent


def _fwd(hidden, w, tokens, block_n, block_v, interpret):
    logp, logz, ent = K.fwd_pallas(hidden, w, tokens, block_n=block_n,
                                   block_v=block_v, interpret=interpret)
    return (logp, ent), (hidden, w, tokens, logz)


def _bwd(block_n, block_v, interpret, res, cts):
    hidden, w, tokens, logz = res
    g_logp, _g_ent = cts  # entropy cotangent intentionally dropped (metrics)
    g = g_logp.astype(F32)
    dh = K.bwd_dh_pallas(hidden, w, tokens, logz, g, block_n=block_n,
                         block_v=block_v, interpret=interpret)
    dw = K.bwd_dw_pallas(hidden, w, tokens, logz, g, block_n=block_n,
                         block_v=block_v, interpret=interpret)
    return dh, dw, None


fused_token_logprobs.defvjp(_fwd, _bwd)


def fused_score_grid(hidden, w, tokens, *, block_n: int = 128,
                     block_v: int = 512, interpret: bool | None = None):
    """(B, T) grid convenience wrapper: scores tokens[:, 1:] from
    hidden[:, :-1] like ``score_tokens`` and left-pads — returns
    (logp (B, T), entropy (B, T))."""
    b, t = tokens.shape
    h = hidden[:, :-1].reshape(b * (t - 1), -1)
    tg = tokens[:, 1:].reshape(-1)
    n = h.shape[0]
    pad = (-n) % block_n
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        tg = jnp.pad(tg, (0, pad))
    logp, ent = fused_token_logprobs(h, w, tg, block_n, block_v, interpret)
    logp = logp[:n].reshape(b, t - 1)
    ent = ent[:n].reshape(b, t - 1)
    z = jnp.zeros((b, 1), logp.dtype)
    return (jnp.concatenate([z, logp], axis=1),
            jnp.concatenate([z, ent], axis=1))
