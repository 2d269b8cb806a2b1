"""Attention mixers: GQA self-attention (full / sliding-window / banded
local), decode attention against a KV cache (including ring buffers for
local layers and sequence-sharded caches for long-context decode), and
cross-attention to frontend embeddings (VLM).

TPU notes (hardware adaptation):
* Dense and sharded GQA (``self_attention``, ``decode_attention``,
  ``cross_attention``) use the kv-repeat scheme — queries keep a flat head
  axis that shards cleanly over the "model" mesh axis even when kv_heads <
  model parallelism.  The paged paths (``paged_decode_attention``,
  ``paged_score_attention``) use grouped-query einsums instead: queries
  reshaped to (KV, G) and scored against the gathered pages as they are,
  so no KV tensor is ever widened to query-head width.  Paged decode runs
  in rollout engines, each a whole replica (``dist/placement.py``), so its
  heads are never sharded over a mesh axis.
* Sliding-window prefill uses an exact two-block banded computation so HLO
  FLOPs reflect the O(T·w) cost instead of a masked O(T^2) einsum.
* The Pallas kernel (repro.kernels.prefix_attn) implements the same math
  with per-sequence cut lengths for RPC's physical forward truncation; this
  module is the jnp reference / SPMD path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope
from repro.models.params import ParamDecl

Array = jax.Array
F32 = jnp.float32
NEG_INF = -2.0 ** 30  # large-but-finite; keeps softmax NaN-free on empty rows


# ------------------------------------------------------------ declarations
def attn_decl(d_model: int, n_heads: int, n_kv: int, head_dim: int):
    return {
        "wq": ParamDecl((d_model, n_heads, head_dim), ("embed", "heads", "head_dim")),
        "wk": ParamDecl((d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDecl((d_model, n_kv, head_dim), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDecl((n_heads, head_dim, d_model), ("heads", "head_dim", "embed")),
    }


def xattn_decl(d_model: int, n_heads: int, n_kv: int, head_dim: int):
    d = attn_decl(d_model, n_heads, n_kv, head_dim)
    d["gate"] = ParamDecl((1,), (None,), init="zeros")  # llama-3.2 tanh gate
    return d


def repeat_kv(k: Array, groups: int) -> Array:
    """(B, S, KV, D) -> (B, S, KV*groups, D)."""
    if groups == 1:
        return k
    b, s, kv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, groups, d)).reshape(
        b, s, kv * groups, d)


# ------------------------------------------------------ full/masked attention
def sdpa(q: Array, k: Array, v: Array, mask: Optional[Array], scale: float) -> Array:
    """q: (B, T, H, D), k/v: (B, S, H, D), mask broadcastable to (B, H, T, S)."""
    s = jnp.einsum("bthd,bshd->bhts", q, k, preferred_element_type=F32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p.astype(v.dtype), v)


def causal_window_mask(t: int, s: int, window: int, offset: int = 0) -> Array:
    """(T, S) mask: query i (absolute i+offset) sees keys j with
    j <= i+offset and (window <= 0 or i+offset - j < window)."""
    qi = jnp.arange(t)[:, None] + offset
    kj = jnp.arange(s)[None, :]
    m = kj <= qi
    if window > 0:
        m &= (qi - kj) < window
    return m


def segment_mask(segment_ids: Array, positions: Array,
                 window: int = 0) -> Array:
    """(B, 1, T, T) packed-layout visibility mask.

    Query i sees key j iff they belong to the same segment and j <= i in the
    packed row (segments are stored in original token order, so row-index
    causality equals position causality within a segment).  With a sliding
    window the span is limited by ORIGINAL positions — ``positions`` restart
    per segment, so window distance must not be measured on packed indices.
    Cross-segment attention is what this mask exists to forbid: packed
    neighbors share a row only as a storage artifact.
    """
    t = segment_ids.shape[-1]
    qi = jnp.arange(t)[:, None]
    kj = jnp.arange(t)[None, :]
    m = (kj <= qi)[None]
    m = m & (segment_ids[:, :, None] == segment_ids[:, None, :])
    if window > 0:
        m = m & ((positions[:, :, None] - positions[:, None, :]) < window)
    return m[:, None]


def self_attention(
    p,
    x: Array,
    positions: Array,
    *,
    window: int,
    rope_theta: float,
    lengths: Optional[Array] = None,
    segment_ids: Optional[Array] = None,
    prefix: Optional[dict] = None,
) -> Array:
    """Full-sequence self-attention (train / prefill).

    window <= 0 -> full causal.  ``lengths`` (B,) masks keys past each
    sequence's valid length (padding from the repack bucket ladder).
    ``segment_ids`` (B, T) switches to the packed layout: attention is
    confined to same-segment tokens (see ``segment_mask``) and ``lengths``
    is ignored — packed rows carry no per-row valid prefix.

    ``prefix`` is the partial-prefix resume path (radix prefix cache,
    DESIGN.md §10): {"k"/"v": (B, Sp, KV, D) already-roped pool K/V,
    "pos": (B, Sp) absolute positions, -1 = empty}.  ``x`` then holds only
    the uncached suffix and ``positions`` must carry the suffix's absolute
    positions (prefix_len + arange).  Prefix keys are visible to a query
    iff their position is valid and strictly precedes the query's; the
    reduction order [prefix, suffix] matches a full prefill's, so resumed
    logits agree with recomputation up to dtype rounding of stored K/V.
    Restricted to full-causal attention: a sliding window or packed
    segments would need window/segment bookkeeping across the splice.
    """
    b, t, _ = x.shape
    h = p["wq"].shape[1]
    kv = p["wk"].shape[1]
    dh = p["wq"].shape[2]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    scale = 1.0 / jnp.sqrt(dh).astype(F32)

    if prefix is not None:
        if window > 0 or segment_ids is not None:
            raise ValueError(
                "prefix resume requires full-causal attention "
                "(no sliding window, no packed segments)")
        kp = prefix["k"].astype(k.dtype)
        vp = prefix["v"].astype(v.dtype)
        pp = prefix["pos"]
        sp = kp.shape[1]
        k_all = jnp.concatenate([kp, k], axis=1)
        v_all = jnp.concatenate([vp, v], axis=1)
        m_self = causal_window_mask(t, t, 0)[None, None]
        if lengths is not None:
            m_self = m_self & (jnp.arange(t)[None, None, None, :]
                               < lengths[:, None, None, None])
        m_pre = ((pp[:, None, :] >= 0)
                 & (pp[:, None, :] < positions[:, :, None]))[:, None]
        mask = jnp.concatenate(
            [jnp.broadcast_to(m_pre, (b, 1, t, sp)),
             jnp.broadcast_to(m_self, (b, 1, t, t))], axis=-1)
        o = sdpa(q, repeat_kv(k_all, h // kv), repeat_kv(v_all, h // kv),
                 mask, scale)
        out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
        return out, (k, v)

    use_banded = (window > 0 and t % window == 0 and t // window >= 2
                  and segment_ids is None)
    if segment_ids is not None:
        mask = segment_mask(segment_ids, positions, window)
        o = sdpa(q, repeat_kv(k, h // kv), repeat_kv(v, h // kv), mask, scale)
    elif use_banded:
        o = _banded_local_attention(q, repeat_kv(k, h // kv),
                                    repeat_kv(v, h // kv), window, scale, lengths)
    else:
        mask = causal_window_mask(t, t, window)[None, None]
        if lengths is not None:
            mask = mask & (jnp.arange(t)[None, None, None, :]
                           < lengths[:, None, None, None])
        o = sdpa(q, repeat_kv(k, h // kv), repeat_kv(v, h // kv), mask, scale)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
    return out, (k, v)


def _banded_local_attention(q, k, v, w: int, scale, lengths) -> Array:
    """Exact sliding-window attention via two-block banding: token t attends
    to keys in (t-w, t]; with block size w the current + previous key blocks
    cover exactly that span.  FLOPs O(T * 2w) instead of O(T^2)."""
    b, t, h, d = q.shape
    nb = t // w
    qb = q.reshape(b, nb, w, h, d)
    kb = k.reshape(b, nb, w, h, d)
    vb = v.reshape(b, nb, w, h, d)
    kprev = jnp.pad(kb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    vprev = jnp.pad(vb[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    k2 = jnp.concatenate([kprev, kb], axis=2)  # (B, nb, 2w, H, D)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    s = jnp.einsum("bnqhd,bnshd->bnhqs", qb, k2, preferred_element_type=F32) * scale
    # relative mask: query index w+i (in the 2w frame), key index j:
    # attend iff j <= w+i and (w+i) - j < w  -> i < j <= w+i
    qi = jnp.arange(w)[:, None] + w
    kj = jnp.arange(2 * w)[None, :]
    m = (kj <= qi) & ((qi - kj) < w)
    # first block has no previous block: mask the left half
    first = (jnp.arange(nb) == 0)[:, None, None] & (kj < w)[None]
    m = m[None] & ~first
    if lengths is not None:
        abs_k = (jnp.arange(nb)[:, None] - 1) * w + kj   # (nb, 2w) abs key pos
        len_ok = abs_k[None] < lengths[:, None, None]    # (B, nb, 2w)
        m = m[None] & len_ok[:, :, None, :]              # (B, nb, w, 2w)
        m = m[:, :, None]                                # (B, nb, 1, q, s)
    else:
        m = m[None, :, None]
    s = jnp.where(m, s, NEG_INF)
    pa = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnhqs,bnshd->bnqhd", pa.astype(v.dtype), v2)
    return o.reshape(b, t, h, d)


def _norm_pos(pos, b: int):
    """Normalize a position argument to (B, 1) int32."""
    p = jnp.asarray(pos)
    if p.ndim == 0:
        p = jnp.broadcast_to(p[None], (b,))
    return p.reshape(b, 1).astype(jnp.int32)


# -------------------------------------------------------------- decode step
def cache_update(cache: dict, k: Array, v: Array, pos: Array, *, window: int):
    """Write one token's K/V into its ring slot and report key visibility.

    The single cache-write primitive behind both the legacy scan decode and
    the continuous-batching slot arena (rl/engine.py): because every write
    lands at ``pos % S`` and visibility is recomputed from the ``pos`` plane
    each step, a slot whose row was retired needs no cleanup beyond having
    its rows rewritten (or invalidated to ``pos = -1``) before reuse.

    cache: {"k": (B, S, KV, D), "v": ..., "pos": (B, S) int32 absolute
    positions, -1 = empty}.  k/v: (B, 1, KV, D) roped projections of the new
    token.  pos: (B, 1) absolute position of the new token.  Returns
    (new_cache, valid (B, S) bool — keys visible to the new query).
    """
    b, s_len = cache["pos"].shape
    slot = (pos[:, 0] % s_len).astype(jnp.int32)  # ring for local, linear else
    bi = jnp.arange(b)
    new_k = cache["k"].at[bi, slot].set(k[:, 0].astype(cache["k"].dtype))
    new_v = cache["v"].at[bi, slot].set(v[:, 0].astype(cache["v"].dtype))
    new_pos = cache["pos"].at[bi, slot].set(pos[:, 0].astype(jnp.int32))
    valid = (new_pos >= 0) & (new_pos <= pos[:, :1])
    if window > 0:
        valid &= (pos[:, :1] - new_pos) < window
    return {"k": new_k, "v": new_v, "pos": new_pos}, valid


def decode_attention(
    p,
    x: Array,
    cache: dict,
    pos: Array,
    *,
    window: int,
    rope_theta: float,
) -> tuple:
    """One-token decode.  x: (B, 1, D).  cache:
      {"k": (B, S, KV, D), "v": ..., "pos": (B, S) int32 absolute positions}
    For local layers S is the ring-buffer size (window); writes go to
    pos % S.  Returns (out (B, 1, D), new_cache).
    """
    b = x.shape[0]
    h = p["wq"].shape[1]
    kvh = p["wk"].shape[1]
    dh = p["wq"].shape[2]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    posb = _norm_pos(pos, b)
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)

    new_cache, valid = cache_update(cache, k, v, posb, window=window)

    scale = 1.0 / jnp.sqrt(dh).astype(F32)
    kf = repeat_kv(new_cache["k"], h // kvh)
    vf = repeat_kv(new_cache["v"], h // kvh)
    s = jnp.einsum("bthd,bshd->bhts", q, kf.astype(q.dtype),
                   preferred_element_type=F32) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pa = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", pa.astype(vf.dtype), vf)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
    return out, new_cache


def attn_cache_decl(batch: int, s_len: int, n_kv: int, head_dim: int,
                    dtype=jnp.bfloat16):
    """Abstract cache layout for one attention layer (ring if s_len=window)."""
    return {
        "k": jax.ShapeDtypeStruct((batch, s_len, n_kv, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, s_len, n_kv, head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((batch, s_len), jnp.int32),
    }


def attn_cache_axes():
    return {
        "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "pos": ("batch", "kv_seq"),
    }


def cache_from_prefill(k: Array, v: Array, s_len: int, prefill_len,
                       window: int) -> dict:
    """Build a decode cache from prefill k/v (B, T, KV, D).

    For global layers s_len >= T and entries [0, prefill_len) are valid.
    For local layers (s_len == window ring) the last `window` positions are
    written at their ring slots.
    """
    b, t, kvh, dh = k.shape
    if s_len >= t:
        pad = ((0, 0), (0, s_len - t), (0, 0), (0, 0))
        kc = jnp.pad(k, pad)
        vc = jnp.pad(v, pad)
        pos = jnp.broadcast_to(jnp.arange(s_len)[None], (b, s_len)).astype(jnp.int32)
        valid = pos < jnp.asarray(prefill_len).reshape(-1, 1)
        pos = jnp.where(valid, pos, -1)
        return {"k": kc, "v": vc, "pos": pos}
    # ring: absolute position p lives at slot p % s_len; take last s_len tokens
    plen = jnp.asarray(prefill_len).reshape(-1)
    start = jnp.maximum(plen - s_len, 0)  # (B,)
    offs = jnp.arange(s_len)[None, :]
    src = jnp.minimum(start[:, None] + offs, t - 1)          # gather index
    gk = jnp.take_along_axis(k, src[:, :, None, None], axis=1)
    gv = jnp.take_along_axis(v, src[:, :, None, None], axis=1)
    abs_pos = start[:, None] + offs
    valid = abs_pos < plen[:, None]
    slot = (abs_pos % s_len).astype(jnp.int32)
    kc = jnp.zeros((b, s_len, kvh, dh), k.dtype)
    vc = jnp.zeros((b, s_len, kvh, dh), v.dtype)
    pc = jnp.full((b, s_len), -1, jnp.int32)
    bi = jnp.arange(b)[:, None]
    kc = kc.at[bi, slot].set(jnp.where(valid[:, :, None, None], gk, 0))
    vc = vc.at[bi, slot].set(jnp.where(valid[:, :, None, None], gv, 0))
    pc = pc.at[bi, slot].set(jnp.where(valid, abs_pos, -1).astype(jnp.int32))
    return {"k": kc, "v": vc, "pos": pc}


# ------------------------------------------------- paged decode (KV pool)
def paged_attn_cache_decl(num_pages: int, page_len: int, n_kv: int,
                          head_dim: int, dtype=jnp.bfloat16):
    """Abstract paged KV pool for one attention layer.

    Unlike the dense per-slot cache, the pool has no batch axis: pages are
    a shared resource, and per-slot structure lives entirely in the block
    tables the engine passes alongside.  ``pos`` is per-entry absolute
    position with ``-1`` = empty — the same validity convention as the
    dense cache, so the gap after a partial last prompt page (decode
    tokens always open a fresh page, keeping prompt pages read-only and
    shareable) is just more empty entries.
    """
    return {
        "k": jax.ShapeDtypeStruct((num_pages, page_len, n_kv, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((num_pages, page_len, n_kv, head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((num_pages, page_len), jnp.int32),
    }


def paged_attn_cache_axes():
    return {
        "k": ("kv_pages", None, "kv_heads", "head_dim"),
        "v": ("kv_pages", None, "kv_heads", "head_dim"),
        "pos": ("kv_pages", None),
    }


def paged_cache_update(pool: dict, k: Array, v: Array, pos: Array,
                       write_page: Array, write_off: Array):
    """Write one token's K/V per slot into its private decode page.

    pool: {"k"/"v": (P, page_len, KV, D), "pos": (P, page_len)}.  k/v:
    (S, 1, KV, D) roped projections; pos: (S, 1) absolute positions;
    write_page/write_off: (S,) int32 — ``write_page == P`` (one past the
    pool) is the drop sentinel for inactive slots.  Distinct slots always
    name distinct pages (decode pages are slot-private; prompt pages are
    never written after prefill), so the scatter has no conflicts.
    """
    new_k = pool["k"].at[write_page, write_off].set(
        k[:, 0].astype(pool["k"].dtype), mode="drop")
    new_v = pool["v"].at[write_page, write_off].set(
        v[:, 0].astype(pool["v"].dtype), mode="drop")
    new_pos = pool["pos"].at[write_page, write_off].set(
        pos[:, 0].astype(jnp.int32), mode="drop")
    return {"k": new_k, "v": new_v, "pos": new_pos}


def gather_pages(pool: dict, block_tables: Array):
    """Materialize each slot's logical KV sequence through its block table.

    block_tables: (S, M) int32 page ids, ``-1`` = unallocated (gathered
    entries come back with ``pos = -1`` so they are invisible).  Returns
    (k (S, M*page_len, KV, D), v, pos (S, M*page_len)) — the jnp reference
    realization; the Pallas kernel (repro.kernels.paged_attn) reads pages
    through the same table without the dense copy.
    """
    s, m = block_tables.shape
    bt = jnp.maximum(block_tables, 0)
    kg = pool["k"][bt]                       # (S, M, page_len, KV, D)
    vg = pool["v"][bt]
    posg = jnp.where(block_tables[..., None] >= 0, pool["pos"][bt], -1)
    pl_ = posg.shape[-1]
    return (kg.reshape(s, m * pl_, *kg.shape[3:]),
            vg.reshape(s, m * pl_, *vg.shape[3:]),
            posg.reshape(s, m * pl_))


def paged_decode_attention(
    p,
    x: Array,
    pool: dict,
    pos: Array,
    block_tables: Array,
    write_page: Array,
    write_off: Array,
    *,
    rope_theta: float,
    impl: str = "ref",
) -> tuple:
    """One-token decode against the paged KV pool.  x: (S, 1, D).

    Same math as ``decode_attention`` — write the new token's K/V, then
    attend to every valid entry the block table reaches — with the page
    gather in place of the per-slot dense cache read.  ``impl="kernel"``
    routes the attention itself through the Pallas paged kernel (gather
    via block-table index maps, no dense KV copy); ``"ref"`` is the jnp
    gather path.  Returns (out (S, 1, D), new_pool).

    The ``"ref"`` branch scores the bf16 (S, L, KV, D) gather with
    grouped-query einsums — q reshaped to (S, KV, G, D), G = H // KV — not
    with ``decode_attention``'s kv-repeat: repeating the gather to every
    query head made XLA materialize it at H width (in f32 for K, after
    converting the whole pool), several times the KV bytes decode needs.
    The arithmetic is ``decode_attention``'s: the same bf16 operands, f32
    score accumulation, NEG_INF mask, one ``jax.nn.softmax``, and bf16
    probabilities into the value product, so the paged engine reproduces
    the dense arena to decode-parity tolerance.

    Two jnp references exist on purpose, not by accident: the ``"ref"``
    branch below is that parity path, while ``kernels/paged_attn/ref.py``
    mirrors the KERNEL's decomposition (f32 upcast, explicit
    max-subtract) as its test oracle.  Folding them together would couple
    dense-parity numerics to kernel-oracle numerics.
    """
    b = x.shape[0]
    h = p["wq"].shape[1]
    kvh = p["wk"].shape[1]
    dh = p["wq"].shape[2]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    posb = _norm_pos(pos, b)
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)

    # both impl paths under one scope, so a profile names the attention
    # (and, nested, the pool write) whichever path runs
    with jax.named_scope("paged_decode_attn"):
        with jax.named_scope("paged_decode_attn.write"):
            new_pool = paged_cache_update(pool, k, v, posb, write_page,
                                          write_off)
        scale = 1.0 / jnp.sqrt(dh).astype(F32)

        if impl == "kernel":
            from repro.kernels.paged_attn import paged_attention

            o = paged_attention(
                q[:, 0], new_pool["k"], new_pool["v"], new_pool["pos"],
                block_tables, posb[:, 0])[:, None]
        else:
            kg, vg, posg = gather_pages(new_pool, block_tables)
            valid = (posg >= 0) & (posg <= posb)
            # group-indexed einsums on the (S, L, KV, D) gather: no kv
            # repeat, the group a real non-contracting dim of the dot
            q4 = q[:, 0].reshape(b, kvh, h // kvh, dh)
            s = jnp.einsum("skgd,slkd->skgl", q4, kg.astype(q.dtype),
                           preferred_element_type=F32) * scale
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
            pa = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("skgl,slkd->skgd", pa.astype(vg.dtype),
                           vg).reshape(b, 1, h, dh)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
    return out, new_pool


# ---------------------------------------------------------- cross-attention
def cross_attention(p, x: Array, image_kv: tuple, *, gated: bool = True) -> Array:
    """Cross-attend text states to precomputed frontend K/V.

    image_kv: (k, v) each (B, N_img, H_kv, D) — computed once per request
    from the stub frontend embeddings; no causal mask, no rope.
    """
    h = p["wq"].shape[1]
    kvh = p["wk"].shape[1]
    dh = p["wq"].shape[2]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k, v = image_kv
    scale = 1.0 / jnp.sqrt(dh).astype(F32)
    o = sdpa(q, repeat_kv(k, h // kvh), repeat_kv(v, h // kvh), None, scale)
    out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
    if gated:
        out = out * jnp.tanh(p["gate"].astype(F32)).astype(out.dtype)
    return out


def image_kv_from_embeds(p, image_embeds: Array) -> tuple:
    """Project stub frontend embeddings to cross-attention K/V once."""
    k = jnp.einsum("bnd,dhk->bnhk", image_embeds, p["wk"])
    v = jnp.einsum("bnd,dhk->bnhk", image_embeds, p["wv"])
    return k, v


# --------------------------------------------- paged teacher forcing (§11)
# Query-block quantum of the paged scoring path: PagedLayout aligns every
# segment start and length to this, so each kernel query block is
# single-segment.  core/layout.py's PagedLayout.qblock must equal it
# (pinned by tests/test_paged_score.py).  16 fits CPU/interpret smoke
# scale; raise both together to 128 on real TPUs.
PAGED_SCORE_BLOCK = 16


def paged_score_attention(
    p,
    x: Array,
    positions: Array,
    *,
    rope_theta: float,
    segment_ids: Array,
    pool: dict,
    block_tables: Array,
    seg_start: Array,
    impl: str = "ref",
) -> tuple:
    """Packed-suffix teacher forcing against the rollout KV pool
    (DESIGN.md §11) — zero re-prefill scoring.

    ``x`` holds a PagedLayout batch: packed rows of per-response suffixes
    (last prompt token + response hull), segment ids doubling as indices
    into ``seg_start (S,)`` / ``block_tables (S, M)``, ``positions``
    absolute.  Each suffix token attends to its segment's PROMPT KV
    (positions ``[0, seg_start)``) read from the pool pages, plus the
    packed suffix causally.  The pool is wrapped in ``stop_gradient``:
    it belongs to the rollout policy, so prompt-KV gradient paths are
    dropped by design — exact at staleness 0 (where rollout and learner
    params agree the forward is exact too); response-side gradients are
    always exact.

    ``impl="kernel"`` routes through the Pallas prefill kernel (pages via
    block-table index maps, custom vjp); ``"ref"`` is the jnp gather
    path.  As with ``paged_decode_attention``, two references exist on
    purpose: this ref computes the dense packed path's arithmetic (NEG_INF
    mask, one ``jax.nn.softmax``; grouped-query einsums in place of the
    kv-repeat) for logp parity with ``score_tokens``'s dense layouts, while
    ``kernels/paged_attn/ref.py`` mirrors the KERNEL's decomposition as
    its test oracle.  Returns (out (B, T, d_model), (k, v))."""
    b, t, _ = x.shape
    h = p["wq"].shape[1]
    kvh = p["wk"].shape[1]
    g = h // kvh
    dh = p["wq"].shape[2]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    scale = 1.0 / jnp.sqrt(dh).astype(F32)

    kp_pool = jax.lax.stop_gradient(pool["k"])
    vp_pool = jax.lax.stop_gradient(pool["v"])
    pos_pool = pool["pos"]
    s_count = seg_start.shape[0]

    if impl == "kernel":
        from repro.kernels.paged_attn import paged_prefill_attention_bthd

        o = paged_prefill_attention_bthd(
            q, k, v, segment_ids, seg_start, block_tables,
            kp_pool, vp_pool, pos_pool,
            bq=PAGED_SCORE_BLOCK, bk=PAGED_SCORE_BLOCK)
        out = jnp.einsum("bthk,hkd->btd", o, p["wo"])
        return out, (k, v)

    seg = segment_ids.astype(jnp.int32)
    segv = (seg >= 0) & (seg < s_count)
    segc = jnp.where(segv, seg, 0)

    bt = jnp.maximum(block_tables, 0)
    m = block_tables.shape[1]
    kpool = kp_pool[bt]                     # (S, M, page_len, KV, D)
    plen = kpool.shape[2]
    kpool = kpool.reshape(s_count, m * plen, kvh, dh)
    vpool = vp_pool[bt].reshape(s_count, m * plen, kvh, dh)
    ppool = jnp.where(block_tables[..., None] >= 0,
                      pos_pool[bt], -1).reshape(s_count, m * plen)

    kp = kpool[segc]                        # (B, T, L, KV, D) per-token
    vp = vpool[segc]
    posp = ppool[segc]                      # (B, T, L)

    # group-indexed einsums: no kv repeat of the (B, T, L, KV, D) gather
    q4 = q.reshape(b, t, kvh, g, dh)
    sc_pre = jnp.einsum("btkgd,btlkd->bkgtl", q4, kp.astype(q.dtype),
                        preferred_element_type=F32) * scale
    sc_sfx = jnp.einsum("btkgd,bskd->bkgts", q4, k,
                        preferred_element_type=F32) * scale

    # prompt KV only (pos < seg_start): the pool's duplicate of the last
    # prompt token is excluded — this forward recomputes it fresh.  No
    # per-query comparison needed: every suffix position >= seg_start.
    m_pre = (segv[:, :, None] & (posp >= 0)
             & (posp < seg_start[segc][:, :, None]))       # (B, T, L)
    m_sfx = segment_mask(segment_ids, positions)[:, 0]     # (B, T, T)

    sc = jnp.concatenate([sc_pre, sc_sfx], axis=-1)
    mask = jnp.concatenate([m_pre, m_sfx], axis=-1)[:, None, None]
    sc = jnp.where(mask, sc, NEG_INF)
    pa = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    o = (jnp.einsum("bkgtl,btlkd->btkgd", pa[..., :m * plen], vp)
         + jnp.einsum("bkgts,bskd->btkgd", pa[..., m * plen:], v))
    out = jnp.einsum("bthk,hkd->btd", o.reshape(b, t, h, dh), p["wo"])
    return out, (k, v)
