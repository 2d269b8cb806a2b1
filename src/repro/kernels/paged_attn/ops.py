"""jit-friendly wrappers for paged attention (decode and prefill).

``paged_attention(q, k_pages, v_pages, pos_pages, block_tables, q_pos)``
takes q in the model's flat-head decode layout ``(S, H, D)`` and handles
the GQA regrouping around the kernel's ``(S, KV, G, D)`` layout: query
head ``h`` reads kv head ``h // (H // KV)`` — the same mapping
``repeat_kv`` realizes on the dense path, without the kv repeat in HBM.
Decode (one token per slot) is never differentiated, so the decode
wrappers carry no custom_vjp.

``paged_prefill_attention`` is the learner's teacher-forcing forward
(DESIGN.md §11) and DOES carry a custom_vjp.  The backward splits by key
partition and stays exact because the forward's (O, LSE) are global over
pool + suffix keys:
  * suffix dq/dk/dv — prefix_attn's packed backward, fed the fused
    (O, LSE),
  * pool dq — the dq-pool kernel, summed into the suffix dq,
  * pool dk/dv — the dkv-pool kernel's per-(segment, page) blocks,
    GQA-reduced and scatter-added through the block table into a
    pool-shaped gradient (GRPO siblings sharing a prompt page sum).
The learner wraps the pool in ``stop_gradient`` (the pool belongs to the
rollout policy), so XLA drops the pool-gradient computation there; the
path exists so the kernel-vs-ref grad parity tests can pin it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.prefix_attn import kernel as _PFX
from repro.kernels.paged_attn import kernel as K

F32 = jnp.float32


def paged_attention(q, k_pages, v_pages, pos_pages, block_tables, q_pos,
                    *, interpret: bool | None = None):
    """q: (S, H, D) flat query heads; k_pages/v_pages: (P, page_len, KV, D);
    pos_pages: (P, page_len); block_tables: (S, M); q_pos: (S,).
    Returns out (S, H, D)."""
    s, h, d = q.shape
    kvh = k_pages.shape[2]
    g = h // kvh
    o = K.paged_decode_pallas(
        q.reshape(s, kvh, g, d), k_pages, v_pages, pos_pages, block_tables,
        q_pos, interpret=interpret)
    return o.reshape(s, h, d)


def paged_mla_attention(q_abs, q_rope, c_pages, kr_pages, pos_pages,
                        block_tables, q_pos, *, scale: float,
                        interpret: bool | None = None):
    """MLA variant: the latent pool is MQA-shaped (no kv-head axis, no GQA
    regrouping) and the value operand IS the latent page, so the kernel's
    output stays in latent rank R — the caller applies W_uv / W_o.
    q_abs: (S, H, R); q_rope: (S, H, Dr); c_pages: (P, page_len, R);
    kr_pages: (P, page_len, Dr).  Returns out (S, H, R)."""
    return K.paged_mla_decode_pallas(
        q_abs, q_rope, c_pages, kr_pages, pos_pages, block_tables, q_pos,
        scale=scale, interpret=interpret)


# ------------------------------------------------------- prefill (custom vjp)
@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def paged_prefill_attention(q, k, v, segment_ids, seg_start, block_tables,
                            k_pages, v_pages, pos_pages, bq=16, bk=16,
                            interpret=None):
    """Fused pool+suffix prefill attention with an exact custom vjp.

    q (R, H, T, D) / k, v (R, KV, T, D): PagedLayout suffix batch;
    segment_ids (R, T); seg_start (S,); block_tables (S, M);
    k/v_pages (P, page_len, KV, D); pos_pages (P, page_len).
    Returns o (R, H, T, D).  Gradients flow to q, k, v AND to the pool
    pages (scatter-added through the block table)."""
    o, _ = K.paged_prefill_fwd_pallas(
        q, k, v, segment_ids, seg_start, block_tables,
        k_pages, v_pages, pos_pages, bq=bq, bk=bk, interpret=interpret)
    return o


def _prefill_fwd(q, k, v, segment_ids, seg_start, block_tables,
                 k_pages, v_pages, pos_pages, bq, bk, interpret):
    o, lse = K.paged_prefill_fwd_pallas(
        q, k, v, segment_ids, seg_start, block_tables,
        k_pages, v_pages, pos_pages, bq=bq, bk=bk, interpret=interpret)
    return o, (q, k, v, o, lse, segment_ids, seg_start, block_tables,
               k_pages, v_pages, pos_pages)


def _prefill_bwd(bq, bk, interpret, res, do):
    (q, k, v, o, lse, segment_ids, seg_start, block_tables,
     k_pages, v_pages, pos_pages) = res
    b, h, t, d = q.shape
    kvh = k.shape[1]
    g = h // kvh

    # suffix partition: the packed backward is exact here because the
    # (o, lse, delta) it consumes are GLOBAL over pool + suffix keys
    dq_sfx, dk_full, dv_full = _PFX.packed_bwd_pallas(
        q, k, v, o, lse, do, segment_ids, bq=bq, bk=bk, interpret=interpret)
    dk = dk_full.reshape(b, kvh, g, t, d).sum(axis=2).astype(k.dtype)
    dv = dv_full.reshape(b, kvh, g, t, d).sum(axis=2).astype(v.dtype)

    # pool partition: dq adds in; dk/dv scatter through the block table
    dq_pool = K.paged_prefill_bwd_dq_pallas(
        q, o, lse, do, segment_ids, seg_start, block_tables,
        k_pages, v_pages, pos_pages, bq=bq, interpret=interpret)
    dq = (dq_sfx.astype(F32) + dq_pool).astype(q.dtype)

    dk_pg, dv_pg = K.paged_prefill_bwd_dkv_pallas(
        q, o, lse, do, segment_ids, seg_start, block_tables,
        k_pages, v_pages, pos_pages, bq=bq, interpret=interpret)
    s_count, nm = block_tables.shape
    plen = pos_pages.shape[1]

    def to_pool(dpg):
        # (S, M, H, pl, d) -> per-kv-head (S, M, pl, KV, d) -> pool scatter
        contrib = jnp.moveaxis(
            dpg.reshape(s_count, nm, kvh, g, plen, d).sum(axis=3), 2, 3)
        valid = block_tables >= 0
        contrib = jnp.where(valid[..., None, None, None], contrib, 0.0)
        return jnp.zeros(k_pages.shape, F32).at[
            jnp.maximum(block_tables, 0).reshape(-1)
        ].add(contrib.reshape(-1, plen, kvh, d))

    dk_pool = to_pool(dk_pg).astype(k_pages.dtype)
    dv_pool = to_pool(dv_pg).astype(v_pages.dtype)
    return dq, dk, dv, None, None, None, dk_pool, dv_pool, None


paged_prefill_attention.defvjp(_prefill_fwd, _prefill_bwd)


def paged_prefill_attention_bthd(q, k, v, segment_ids, seg_start,
                                 block_tables, k_pages, v_pages, pos_pages,
                                 *, bq: int = 16, bk: int = 16,
                                 interpret: bool | None = None):
    """Convenience wrapper taking the model layout q (R, T, H, D) /
    k, v (R, T, KV, D); transposes around the kernel layout (the
    transposes sit outside the custom_vjp and differentiate fine)."""
    o = paged_prefill_attention(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
        segment_ids, seg_start, block_tables, k_pages, v_pages, pos_pages,
        bq, bk, interpret)
    return o.swapaxes(1, 2)
