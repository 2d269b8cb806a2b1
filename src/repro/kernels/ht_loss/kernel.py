"""Pallas TPU kernels for the fused HT-GRPO loss head.

The learner's memory hot spot is the (N, V) logits tensor (N = B*T tokens,
V up to 262k).  These kernels stream V in VMEM-sized tiles and never
materialize it:

* ``_fwd_kernel``    — logp(target), logsumexp, entropy per token.
* ``_bwd_dh_kernel`` — d(hidden): recomputes softmax tiles from the saved
                       logsumexp (flash-style residual), accumulates
                       dlogits @ W^T across V tiles in VMEM scratch.
* ``_bwd_dw_kernel`` — d(W): grid transposed (V outer, token-block inner) so
                       each dW tile accumulates over token blocks in scratch
                       and is written exactly once.

Grid iteration on TPU is sequential with the LAST axis fastest; scratch
persists across iterations, with @pl.when(first/last) init/finalize — the
same pattern as flash attention.  dtypes: inputs bf16/f32, all accumulation
in f32.  Tile sizes default to (block_n tokens × block_v vocab) with the
full D dimension resident (D ≤ ~8k for the archs that run the RL learner;
the D-tiled extension is a documented TODO).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

F32 = jnp.float32
NEG = -1e30


# ----------------------------------------------------------------- forward
def _fwd_kernel(h_ref, w_ref, tok_ref, logp_ref, logz_ref, ent_ref,
                m_sc, s_sc, tgt_sc, ed_sc, *, block_v: int, num_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        s_sc[...] = jnp.zeros_like(s_sc)
        tgt_sc[...] = jnp.zeros_like(tgt_sc)
        ed_sc[...] = jnp.zeros_like(ed_sc)

    h = h_ref[...].astype(F32)                      # (bn, D)
    w = w_ref[...].astype(F32)                      # (D, bv)
    logits = jax.lax.dot(h, w, precision=jax.lax.Precision.HIGHEST)  # (bn, bv)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, None])
    s_sc[...] = s_sc[...] * corr + jnp.sum(p, axis=-1)
    ed_sc[...] = ed_sc[...] * corr + jnp.sum(p * logits, axis=-1)
    m_sc[...] = m_new

    # target logit if it lands in this vocab tile
    tok = tok_ref[...]                              # (bn,) int32 global ids
    local = tok - vi * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    hit = cols == local[:, None]
    tgt_sc[...] = tgt_sc[...] + jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)

    @pl.when(vi == num_v - 1)
    def _fin():
        logz = m_sc[...] + jnp.log(s_sc[...])
        logz_ref[...] = logz
        logp_ref[...] = tgt_sc[...] - logz
        ent_ref[...] = logz - ed_sc[...] / s_sc[...]


def fwd_pallas(hidden, w, tokens, *, block_n: int = 256, block_v: int = 512,
               interpret: bool | None = None):
    """hidden: (N, D), w: (D, V), tokens: (N,) -> (logp, logz, ent) f32."""
    n, d = hidden.shape
    v = w.shape[1]
    assert n % block_n == 0 and v % block_v == 0, (n, v, block_n, block_v)
    num_n, num_v = n // block_n, v // block_v
    kern = functools.partial(_fwd_kernel, block_v=block_v, num_v=num_v)
    out_shape = [jax.ShapeDtypeStruct((n,), F32)] * 3
    return pl.pallas_call(
        kern,
        grid=(num_n, num_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n,), lambda i, j: (i,)),
        ],
        out_specs=[pl.BlockSpec((block_n,), lambda i, j: (i,))] * 3,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_n,), F32)] * 4,
        interpret=resolve_interpret(interpret),
    )(hidden, w, tokens)


# ------------------------------------------------------------ backward: dh
def _bwd_dh_kernel(h_ref, w_ref, tok_ref, logz_ref, g_ref, dh_ref, acc_sc,
                   *, block_v: int, num_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    h = h_ref[...].astype(F32)
    w = w_ref[...].astype(F32)
    logits = jax.lax.dot(h, w, precision=jax.lax.Precision.HIGHEST)
    p = jnp.exp(logits - logz_ref[...][:, None])     # softmax tile
    tok = tok_ref[...]
    local = tok - vi * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = (cols == local[:, None]).astype(F32)
    dlogits = (onehot - p) * g_ref[...][:, None]     # d logp(target)/d logits
    acc_sc[...] += jax.lax.dot(dlogits, w.T, precision=jax.lax.Precision.HIGHEST)

    @pl.when(vi == num_v - 1)
    def _fin():
        dh_ref[...] = acc_sc[...].astype(dh_ref.dtype)


def bwd_dh_pallas(hidden, w, tokens, logz, g, *, block_n: int = 256,
                  block_v: int = 512, interpret: bool | None = None):
    n, d = hidden.shape
    v = w.shape[1]
    num_n, num_v = n // block_n, v // block_v
    kern = functools.partial(_bwd_dh_kernel, block_v=block_v, num_v=num_v)
    return pl.pallas_call(
        kern,
        grid=(num_n, num_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((block_n,), lambda i, j: (i,)),
            pl.BlockSpec((block_n,), lambda i, j: (i,)),
            pl.BlockSpec((block_n,), lambda i, j: (i,)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), hidden.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, d), F32)],
        interpret=resolve_interpret(interpret),
    )(hidden, w, tokens, logz, g)


# ------------------------------------------------------------ backward: dW
def _bwd_dw_kernel(h_ref, w_ref, tok_ref, logz_ref, g_ref, dw_ref, acc_sc,
                   *, block_v: int, num_n: int):
    vi = pl.program_id(0)
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    h = h_ref[...].astype(F32)
    w = w_ref[...].astype(F32)
    logits = jax.lax.dot(h, w, precision=jax.lax.Precision.HIGHEST)
    p = jnp.exp(logits - logz_ref[...][:, None])
    tok = tok_ref[...]
    local = tok - vi * block_v
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    onehot = (cols == local[:, None]).astype(F32)
    dlogits = (onehot - p) * g_ref[...][:, None]
    acc_sc[...] += jax.lax.dot(h.T, dlogits, precision=jax.lax.Precision.HIGHEST)

    @pl.when(ni == num_n - 1)
    def _fin():
        dw_ref[...] = acc_sc[...].astype(dw_ref.dtype)


def bwd_dw_pallas(hidden, w, tokens, logz, g, *, block_n: int = 256,
                  block_v: int = 512, interpret: bool | None = None):
    n, d = hidden.shape
    v = w.shape[1]
    num_n, num_v = n // block_n, v // block_v
    kern = functools.partial(_bwd_dw_kernel, block_v=block_v, num_n=num_n)
    return pl.pallas_call(
        kern,
        grid=(num_v, num_n),  # V outer so each dW tile finishes before moving on
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
            pl.BlockSpec((block_n,), lambda j, i: (i,)),
        ],
        out_specs=pl.BlockSpec((d, block_v), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((d, v), w.dtype),
        scratch_shapes=[pltpu.VMEM((d, block_v), F32)],
        interpret=resolve_interpret(interpret),
    )(hidden, w, tokens, logz, g)
