"""jit wrappers + custom_vjp for prefix-aware and packed flash attention.

``prefix_flash_attention(q, k, v, cut_lens, window=0)`` — q (B, H, T, D),
k/v (B, KV, T, D), cut_lens (B,) int32.  Residuals are (q, k, v, O, LSE):
activation memory is O(B·H·T·D), never O(T^2).  GQA backward reduces the
per-query-head dk/dv over groups.

``packed_flash_attention(q, k, v, segment_ids)`` — the packed-layout
variant (core/layout.py): segment_ids (B, T) int32 confine attention to
same-segment tokens and drive the block-sparse skip of cross-segment KV
blocks.  Same residual/backward structure.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.prefix_attn import kernel as K


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def prefix_flash_attention(q, k, v, cut_lens, window: int = 0,
                           bq: int = 128, bk: int = 128,
                           interpret: bool | None = None):
    o, _ = K.fwd_pallas(q, k, v, cut_lens, window=window, bq=bq, bk=bk,
                        interpret=interpret)
    return o


def _fwd(q, k, v, cut_lens, window, bq, bk, interpret):
    o, lse = K.fwd_pallas(q, k, v, cut_lens, window=window, bq=bq, bk=bk,
                          interpret=interpret)
    return o, (q, k, v, o, lse, cut_lens)


def _bwd(window, bq, bk, interpret, res, do):
    q, k, v, o, lse, cut_lens = res
    dq, dk_full, dv_full = K.bwd_pallas(q, k, v, o, lse, do, cut_lens,
                                        window=window, bq=bq, bk=bk,
                                        interpret=interpret)
    kvh = k.shape[1]
    b, h, t, d = q.shape
    g = h // kvh
    dk = dk_full.reshape(b, kvh, g, t, d).sum(axis=2).astype(k.dtype)
    dv = dv_full.reshape(b, kvh, g, t, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv, None


prefix_flash_attention.defvjp(_fwd, _bwd)


def attention_bthd(q, k, v, cut_lens, *, window: int = 0, bq: int = 128,
                   bk: int = 128, interpret: bool | None = None):
    """(B, T, H, D)-layout convenience wrapper matching the model's attention
    call sites; transposes around the kernel layout."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = prefix_flash_attention(qt, kt, vt, cut_lens, window, bq, bk, interpret)
    return jnp.swapaxes(o, 1, 2)


# ------------------------------------------------------- packed (segment-id)
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def packed_flash_attention(q, k, v, segment_ids, bq: int = 128,
                           bk: int = 128, interpret: bool | None = None):
    o, _ = K.packed_fwd_pallas(q, k, v, segment_ids, bq=bq, bk=bk,
                               interpret=interpret)
    return o


def _packed_fwd(q, k, v, segment_ids, bq, bk, interpret):
    o, lse = K.packed_fwd_pallas(q, k, v, segment_ids, bq=bq, bk=bk,
                                 interpret=interpret)
    return o, (q, k, v, o, lse, segment_ids)


def _packed_bwd(bq, bk, interpret, res, do):
    q, k, v, o, lse, segment_ids = res
    dq, dk_full, dv_full = K.packed_bwd_pallas(q, k, v, o, lse, do,
                                               segment_ids, bq=bq, bk=bk,
                                               interpret=interpret)
    kvh = k.shape[1]
    b, h, t, d = q.shape
    g = h // kvh
    dk = dk_full.reshape(b, kvh, g, t, d).sum(axis=2).astype(k.dtype)
    dv = dv_full.reshape(b, kvh, g, t, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv, None


packed_flash_attention.defvjp(_packed_fwd, _packed_bwd)


def packed_attention_bthd(q, k, v, segment_ids, *, bq: int = 128,
                          bk: int = 128, interpret: bool | None = None):
    """(B, T, H, D)-layout convenience wrapper for the packed variant."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = packed_flash_attention(qt, kt, vt, segment_ids, bq, bk, interpret)
    return jnp.swapaxes(o, 1, 2)
