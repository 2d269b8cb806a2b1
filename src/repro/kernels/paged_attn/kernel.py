"""Pallas TPU kernels: paged attention over a block-table KV pool.

Two families share the pool layout (``k/v_pages (P, page_len, KV, D)``,
``pos_pages (P, page_len)`` absolute positions with ``-1`` = empty,
``block_tables`` rows of page ids with ``-1`` = unallocated; DESIGN.md
§8).  Neither ever materializes a dense per-sequence KV copy in HBM: the
block table rides in as a scalar-prefetch operand and the page id feeds
the BlockSpec index map directly, so each grid step DMAs exactly one
page.

**Decode** (``paged_decode_pallas`` / ``paged_mla_decode_pallas``) — one
query token per slot.  Grid ``(S, M)``: slot × block-table column; GQA
lays queries out as ``(S, KV, G, D)`` and one grid step DMAs one whole
page ``(page_len, KV, D)`` and scores every kv head's G queries against
it.  A ``-1`` block-table entry skips the whole page with ``pl.when``
(cost O(allocated pages), not O(M)); inside a page, key j is visible
iff ``0 <= pos_j <= q_pos`` — the dense arena's rule, so the partial
last-prompt-page gap needs no special case.  Decode is never
differentiated: no backward.

**Prefill** (``paged_prefill_fwd_pallas`` + the two ``bwd`` kernels,
DESIGN.md §11) — the learner's teacher-forcing forward.  Queries are a
PagedLayout batch ``(R, H, T, D)``: packed rows of per-response
*suffixes* (last prompt token + response hull), each tagged with a
segment id that doubles as the index into ``seg_start`` / the block
table.  Every suffix token attends to (a) its segment's prompt KV read
straight from the rollout pool pages and (b) the packed suffix KV,
causally, under ONE online softmax so the saved ``(O, LSE)`` are global.

  fwd      — grid ``(R, H, T/bq, M + T/bk)``: per query block, M
             block-table steps (pool phase) then T/bk packed-suffix
             steps.  Pool mask: same segment AND ``0 <= pos <
             seg_start[seg]`` (the pool's own copy of the last prompt
             token is excluded — the suffix recomputes it fresh).
             Suffix mask: the packed kernel's causal+segment rule with
             its block-skip tables.
  bwd dq   — grid ``(R, H, T/bq, M)``: the pool-phase dq contribution
             (the suffix contribution comes from prefix_attn's packed
             bwd, fed the fused global (O, LSE)).
  bwd dkv  — grid ``(S, H, M, T/bq)``: per (segment, page), accumulate
             dk/dv over the segment's query blocks; ops.py reduces GQA
             groups and scatter-adds through the block table into a
             pool-shaped gradient (shared prompt pages sum over GRPO
             siblings).

Chip tiling (Mosaic refuses a block whose last two dims are not
multiples of (8, 128) or the array's own): pool pages are read whole,
``(1, page_len, KV, D)``, and the kv head is picked inside the kernel;
``pos_pages`` enters as ``(P, 1, page_len)`` and per-token vectors
(segment ids, LSE, delta) as ``(..., 1, T)``.  So on a TPU ``bq``/``bk``
must be multiples of 128 (or equal T); the interpreter takes any size.

Known limits: ``bq == bk`` and both must divide the PagedLayout
alignment quantum (16 at CPU/interpret smoke scale; the chip needs 128,
so the quantum has to rise with them); every query block must be
single-segment (+ PAD tail), which PagedLayout guarantees by aligning
segment starts to the quantum; pack ids must equal segment indices in
placement order (the PagedLayout contract).  All accumulation f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret
from repro.kernels.prefix_attn import kernel as _PK

F32 = jnp.float32
NEG = -1e30


def _page_head(page_ref, h):
    """``(page_len, D)`` f32 rows of kv head ``h`` from a whole-page block
    ``(1, page_len, KV, D)``.  ``h`` may be traced: Mosaic cannot index
    the sublane dim of a bf16 ref dynamically, so the head is selected by
    a one-hot sum over that dim (exact: one term is non-zero)."""
    page = page_ref[0].astype(F32)
    hit = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1) == h
    return jnp.sum(jnp.where(hit, page, 0.0), axis=1)


def _kernel(bt_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
            m_sc, l_sc, acc_sc, *, nm, scale):
    s = pl.program_id(0)
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    qp = qpos_ref[s]

    @pl.when((bt_ref[s, mi] >= 0) & (qp >= 0))
    def _compute():
        pos = pos_ref[0]                     # (1, page_len)
        valid = (pos >= 0) & (pos <= qp)
        # one page DMA serves every kv head; heads are a static unroll
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h].astype(F32)          # (G, D)
            k = k_ref[0, :, h].astype(F32)       # (page_len, D)
            v = v_ref[0, :, h].astype(F32)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     precision=jax.lax.Precision.HIGHEST
                                     ) * scale
            sc = jnp.where(valid, sc, NEG)
            m_prev = m_sc[h]                     # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
            l_sc[h] = l_sc[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_sc[h] = acc_sc[h] * corr + jax.lax.dot(
                p, v, precision=jax.lax.Precision.HIGHEST)
            m_sc[h] = m_new

    @pl.when(mi == nm - 1)
    def _fin():
        l = l_sc[...]                        # (KV, G, 1)
        ok = l > 0
        lsafe = jnp.where(ok, l, 1.0)
        o_ref[0] = jnp.where(ok, acc_sc[...] / lsafe,
                             0.0).astype(o_ref.dtype)


def _mla_kernel(bt_ref, qpos_ref, qa_ref, qr_ref, c_ref, kr_ref, pos_ref,
                o_ref, m_sc, l_sc, acc_sc, *, nm, scale):
    s = pl.program_id(0)
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    qp = qpos_ref[s]

    @pl.when((bt_ref[s, mi] >= 0) & (qp >= 0))
    def _compute():
        qa = qa_ref[0].astype(F32)           # (H, R) absorbed queries
        qr = qr_ref[0].astype(F32)           # (H, Dr) rotary queries
        c = c_ref[0].astype(F32)             # (page_len, R) latents
        kr = kr_ref[0].astype(F32)           # (page_len, Dr)
        pos = pos_ref[0]                     # (1, page_len)
        sc = (jax.lax.dot_general(qa, c, (((1,), (1,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST)
              + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST)
              ) * scale
        valid = (pos >= 0) & (pos <= qp)
        sc = jnp.where(valid, sc, NEG)
        m_prev = m_sc[...]                   # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(sc - m_new), 0.0)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # the "value" IS the latent page: output stays in latent rank R
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot(
            p, c, precision=jax.lax.Precision.HIGHEST)
        m_sc[...] = m_new

    @pl.when(mi == nm - 1)
    def _fin():
        l = l_sc[...]
        ok = l > 0
        lsafe = jnp.where(ok, l, 1.0)
        o_ref[0] = jnp.where(ok, acc_sc[...] / lsafe,
                             0.0).astype(o_ref.dtype)


def paged_mla_decode_pallas(q_abs, q_rope, c_pages, kr_pages, pos_pages,
                            block_tables, q_pos, *, scale: float,
                            interpret: bool | None = None):
    """Paged decode attention over compressed MLA latents (absorbed form).

    q_abs: (S, H, R) absorbed queries (q_nope @ W_uk); q_rope: (S, H, Dr);
    c_pages: (P, page_len, R); kr_pages: (P, page_len, Dr); pos_pages:
    (P, page_len) int32; block_tables: (S, M) int32 (-1 = unallocated);
    q_pos: (S,) int32 (-1 = inactive slot); ``scale`` is the caller's
    1/sqrt(qk_nope + qk_rope) (NOT derivable from R).  Grid (S, M): the
    latent is MQA-shaped — one shared "kv head" — so each grid step scores
    all H heads against one latent page; the softmax output contracts
    against the SAME page (out rank R, W_uv applied by the caller).
    Returns out (S, H, R)."""
    s, h, r = q_abs.shape
    dr = q_rope.shape[-1]
    p, page_len = pos_pages.shape
    m = block_tables.shape[1]
    kern = functools.partial(_mla_kernel, nm=m, scale=scale)

    def page_idx(s_, mi, bt, qp):
        return (jnp.maximum(bt[s_, mi], 0), 0, 0)

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, m),
            in_specs=[
                pl.BlockSpec((1, h, r), lambda s_, mi, bt, qp: (s_, 0, 0)),
                pl.BlockSpec((1, h, dr), lambda s_, mi, bt, qp: (s_, 0, 0)),
                pl.BlockSpec((1, page_len, r), page_idx),
                pl.BlockSpec((1, page_len, dr), page_idx),
                pl.BlockSpec((1, 1, page_len),
                             lambda s_, mi, bt, qp:
                             (jnp.maximum(bt[s_, mi], 0), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, h, r),
                                   lambda s_, mi, bt, qp: (s_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, 1), F32),
                pltpu.VMEM((h, 1), F32),
                pltpu.VMEM((h, r), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, h, r), q_abs.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables, q_pos, q_abs, q_rope, c_pages, kr_pages,
      _PK._rows(pos_pages))
    return out


def paged_decode_pallas(q, k_pages, v_pages, pos_pages, block_tables, q_pos,
                        *, interpret: bool | None = None):
    """q: (S, KV, G, D); k_pages/v_pages: (P, page_len, KV, D); pos_pages:
    (P, page_len) int32; block_tables: (S, M) int32 (-1 = unallocated);
    q_pos: (S,) int32 (-1 = inactive slot).  Returns out (S, KV, G, D)."""
    s, kvh, g, d = q.shape
    p, page_len = pos_pages.shape
    m = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_kernel, nm=m, scale=scale)

    def page_idx(s_, mi, bt, qp):
        return (jnp.maximum(bt[s_, mi], 0), 0, 0, 0)

    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, m),
            in_specs=[
                pl.BlockSpec((1, kvh, g, d),
                             lambda s_, mi, bt, qp: (s_, 0, 0, 0)),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, 1, page_len),
                             lambda s_, mi, bt, qp:
                             (jnp.maximum(bt[s_, mi], 0), 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, kvh, g, d),
                                   lambda s_, mi, bt, qp: (s_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kvh, g, 1), F32),
                pltpu.VMEM((kvh, g, 1), F32),
                pltpu.VMEM((kvh, g, d), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, kvh, g, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_tables, q_pos, q, k_pages, v_pages, _PK._rows(pos_pages))
    return out


# ================================================ prefill (pool + suffix)
def _qblock_segments(segment_ids, bq: int, s_count: int):
    """Per-query-block segment index ``(R, T // bq)`` int32, ``-1`` for
    blocks holding no live segment.  Relies on the PagedLayout contract:
    every block is single-segment (+ PAD tail), so the first token names
    the block."""
    first = segment_ids[:, ::bq].astype(jnp.int32)
    return jnp.where((first >= 0) & (first < s_count), first, -1)


def _seg_tables(qseg, s_count: int):
    """(seg_row, seg_q0, seg_nq), each (S,) int32 — where segment s lives
    in the query grid: its packed row, first query block, block count.
    Segments absent from the grid get seg_nq == 0 (all steps skipped)."""
    onehot = qseg[:, :, None] == jnp.arange(s_count, dtype=jnp.int32)
    seg_row = jnp.argmax(onehot.any(axis=1), axis=0).astype(jnp.int32)
    seg_q0 = jnp.argmax(onehot.any(axis=0), axis=0).astype(jnp.int32)
    seg_nq = onehot.sum(axis=(0, 1)).astype(jnp.int32)
    return seg_row, seg_q0, seg_nq


def _prefill_fwd_kernel(qseg_ref, sstart_ref, bt_ref, lo_ref, hi_ref,
                        q_ref, k_ref, v_ref, kp_ref, vp_ref, pp_ref,
                        segq_ref, segk_ref, o_ref, lse_ref,
                        m_sc, l_sc, acc_sc, *, bq, bk, nm, nk, g, scale):
    r = pl.program_id(0)
    kv = pl.program_id(1) // g
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    seg = qseg_ref[r, qi]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _acc(s_mat, mask, v):
        s_mat = jnp.where(mask, s_mat, NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_mat, axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_mat - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot(
            p, v, precision=jax.lax.Precision.HIGHEST)
        m_sc[...] = m_new

    page_live = bt_ref[jnp.maximum(seg, 0), jnp.minimum(ki, nm - 1)] >= 0

    @pl.when((ki < nm) & (seg >= 0) & page_live)
    def _pool():
        q = q_ref[0, 0].astype(F32)                  # (bq, D)
        k = _page_head(kp_ref, kv)                   # (page_len, D)
        v = _page_head(vp_ref, kv)
        pos = pp_ref[0]                              # (1, page_len)
        s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST
                                    ) * scale
        # prompt KV only: the pool's own copy of the last prompt token
        # (pos == seg_start - 1 is the newest VISIBLE one; the cut is
        # pos < seg_start) is the newest the suffix may read — the
        # suffix recomputes position seg_start - 1 itself.
        vis = (pos >= 0) & (pos < sstart_ref[jnp.maximum(seg, 0)])
        mask = (segq_ref[0, 0][:, None] == seg) & vis
        _acc(s_mat, mask, v)

    kjc = jnp.maximum(ki - nm, 0)

    @pl.when((ki >= nm)
             & _PK._packed_needed(qi, kjc, bq, bk, lo_ref, hi_ref, r))
    def _suffix():
        q = q_ref[0, 0].astype(F32)
        k = k_ref[0, 0].astype(F32)
        v = v_ref[0, 0].astype(F32)
        s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST
                                    ) * scale
        mask = _PK._packed_mask(qi * bq, kjc * bk, bq, bk,
                                segq_ref[0, 0], segk_ref[0, 0])
        _acc(s_mat, mask, v)

    @pl.when(ki == nm + nk - 1)
    def _fin():
        l = l_sc[...]
        ok = l > 0
        lsafe = jnp.where(ok, l, 1.0)
        # Mosaic cannot reshape a bool vector: broadcast before comparing
        o_ref[0, 0] = jnp.where(l[:, None] > 0, acc_sc[...] / lsafe[:, None],
                                0.0).astype(o_ref.dtype)
        lse_ref[0, 0, 0] =jnp.where(ok, m_sc[...] + jnp.log(lsafe), 0.0)


def paged_prefill_fwd_pallas(q, k, v, segment_ids, seg_start, block_tables,
                             k_pages, v_pages, pos_pages, *, bq: int = 16,
                             bk: int = 16, interpret: bool | None = None):
    """Fused pool+suffix prefill forward.

    q (R, H, T, D) / k, v (R, KV, T, D): a PagedLayout batch of response
    suffixes; segment_ids (R, T); seg_start (S,) absolute position of
    each segment's first suffix token; block_tables (S, M); k/v_pages
    (P, page_len, KV, D); pos_pages (P, page_len).  Returns
    (o (R, H, T, D), lse (R, H, T) f32) — LSE is global over pool +
    suffix keys, which is what makes the split backward exact."""
    r, h, t, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    assert bq == bk, "prefill shares one block-range table: bq == bk"
    assert t % bq == 0, f"pack_len {t} must be a multiple of bq {bq}"
    s_count, nm = block_tables.shape
    assert s_count >= 1 and nm >= 1
    page_len = pos_pages.shape[1]
    nq = nk = t // bq
    scale = 1.0 / (d ** 0.5)
    lo, hi = _PK.seg_block_ranges(segment_ids, bq)
    qseg = _qblock_segments(segment_ids, bq, s_count)
    kern = functools.partial(_prefill_fwd_kernel, bq=bq, bk=bk, nm=nm,
                             nk=nk, g=g, scale=scale)

    def page_idx(r_, h_, qi, ki, qseg_, ss, bt, lo_, hi_):
        page = bt[jnp.maximum(qseg_[r_, qi], 0), jnp.minimum(ki, nm - 1)]
        return (jnp.maximum(page, 0), 0, 0, 0)

    def pos_idx(r_, h_, qi, ki, qseg_, ss, bt, lo_, hi_):
        page = bt[jnp.maximum(qseg_[r_, qi], 0), jnp.minimum(ki, nm - 1)]
        return (jnp.maximum(page, 0), 0, 0)

    def kv_idx(r_, h_, qi, ki, qseg_, ss, bt, lo_, hi_):
        return (r_, h_ // g, jnp.maximum(ki - nm, 0), 0)

    o, lse = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(r, h, nq, nm + nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda r_, h_, qi, ki, *_: (r_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bk, d), kv_idx),
                pl.BlockSpec((1, 1, bk, d), kv_idx),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, 1, page_len), pos_idx),
                pl.BlockSpec((1, 1, bq),
                             lambda r_, h_, qi, ki, *_: (r_, 0, qi)),
                pl.BlockSpec((1, 1, bk),
                             lambda r_, h_, qi, ki, *_:
                             (r_, 0, jnp.maximum(ki - nm, 0))),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda r_, h_, qi, ki, *_: (r_, h_, qi, 0)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda r_, h_, qi, ki, *_: (r_, h_, 0, qi)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq,), F32),
                pltpu.VMEM((bq,), F32),
                pltpu.VMEM((bq, d), F32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((r, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((r, h, 1, t), F32),
        ],
        interpret=resolve_interpret(interpret),
    )(qseg, seg_start, block_tables, lo, hi,
      q, k, v, k_pages, v_pages, _PK._rows(pos_pages), _PK._rows(segment_ids),
      _PK._rows(segment_ids))
    return o, lse[:, :, 0]


# ------------------------------------------------- prefill bwd: dq (pool)
def _prefill_dq_pool_kernel(qseg_ref, sstart_ref, bt_ref, q_ref, kp_ref,
                            vp_ref, pp_ref, do_ref, lse_ref, delta_ref,
                            segq_ref, dq_ref, acc_sc, *, nm, g, scale):
    r = pl.program_id(0)
    kv = pl.program_id(1) // g
    qi = pl.program_id(2)
    mi = pl.program_id(3)
    seg = qseg_ref[r, qi]

    @pl.when(mi == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when((seg >= 0) & (bt_ref[jnp.maximum(seg, 0), mi] >= 0))
    def _compute():
        q = q_ref[0, 0].astype(F32)                  # (bq, D)
        k = _page_head(kp_ref, kv)                   # (page_len, D)
        v = _page_head(vp_ref, kv)
        pos = pp_ref[0]                              # (1, page_len)
        do = do_ref[0, 0].astype(F32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST
                                    ) * scale
        vis = (pos >= 0) & (pos < sstart_ref[jnp.maximum(seg, 0)])
        mask = (segq_ref[0, 0][:, None] == seg) & vis
        p = jnp.where(mask, jnp.exp(s_mat - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        ds = p * (dp - delta[:, None]) * scale
        acc_sc[...] += jax.lax.dot(ds, k,
                                   precision=jax.lax.Precision.HIGHEST)

    @pl.when(mi == nm - 1)
    def _fin():
        dq_ref[0, 0] = acc_sc[...]


def paged_prefill_bwd_dq_pallas(q, o, lse, do, segment_ids, seg_start,
                                block_tables, k_pages, v_pages, pos_pages,
                                *, bq: int = 16,
                                interpret: bool | None = None):
    """Pool-phase dq contribution (f32, same shape as q).  The suffix
    contribution comes from prefix_attn's packed bwd run on the fused
    global (o, lse); with a global LSE and delta the two partitions'
    per-key ds are each exact, so the sum is the exact dq."""
    r, h, t, d = q.shape
    kvh = k_pages.shape[2]
    g = h // kvh
    s_count, nm = block_tables.shape
    page_len = pos_pages.shape[1]
    nq = t // bq
    scale = 1.0 / (d ** 0.5)
    qseg = _qblock_segments(segment_ids, bq, s_count)
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)  # (R, H, T)
    kern = functools.partial(_prefill_dq_pool_kernel, nm=nm, g=g,
                             scale=scale)

    def page_idx(r_, h_, qi, mi, qseg_, ss, bt):
        return (jnp.maximum(bt[jnp.maximum(qseg_[r_, qi], 0), mi], 0),
                0, 0, 0)

    def pos_idx(r_, h_, qi, mi, qseg_, ss, bt):
        return (jnp.maximum(bt[jnp.maximum(qseg_[r_, qi], 0), mi], 0), 0, 0)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(r, h, nq, nm),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda r_, h_, qi, mi, *_: (r_, h_, qi, 0)),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, 1, page_len), pos_idx),
                pl.BlockSpec((1, 1, bq, d),
                             lambda r_, h_, qi, mi, *_: (r_, h_, qi, 0)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda r_, h_, qi, mi, *_: (r_, h_, 0, qi)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda r_, h_, qi, mi, *_: (r_, h_, 0, qi)),
                pl.BlockSpec((1, 1, bq),
                             lambda r_, h_, qi, mi, *_: (r_, 0, qi)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bq, d),
                lambda r_, h_, qi, mi, *_: (r_, h_, qi, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((r, h, t, d), F32),
        interpret=resolve_interpret(interpret),
    )(qseg, seg_start, block_tables, q, k_pages, v_pages, _PK._rows(pos_pages),
      do, _PK._rows(lse), _PK._rows(delta), _PK._rows(segment_ids))


# ------------------------------------------------ prefill bwd: dkv (pool)
def _prefill_dkv_pool_kernel(srow_ref, sq0_ref, snq_ref, sstart_ref, bt_ref,
                             q_ref, kp_ref, vp_ref, pp_ref, do_ref, lse_ref,
                             delta_ref, segq_ref, dk_ref, dv_ref,
                             dk_sc, dv_sc, *, nq, g, scale):
    s = pl.program_id(0)
    kv = pl.program_id(1) // g
    mi = pl.program_id(2)
    qj = pl.program_id(3)

    @pl.when(qj == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when((qj < snq_ref[s]) & (bt_ref[s, mi] >= 0))
    def _compute():
        q = q_ref[0, 0].astype(F32)                  # (bq, D)
        k = _page_head(kp_ref, kv)                   # (page_len, D)
        v = _page_head(vp_ref, kv)
        pos = pp_ref[0]                              # (1, page_len)
        do = do_ref[0, 0].astype(F32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        s_mat = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=jax.lax.Precision.HIGHEST
                                    ) * scale
        vis = (pos >= 0) & (pos < sstart_ref[s])
        mask = (segq_ref[0, 0][:, None] == s) & vis
        p = jnp.where(mask, jnp.exp(s_mat - lse[:, None]), 0.0)  # (bq, pl)
        dv_sc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                          precision=jax.lax.Precision.HIGHEST)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        ds = p * (dp - delta[:, None]) * scale
        dk_sc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          precision=jax.lax.Precision.HIGHEST)

    @pl.when(qj == nq - 1)
    def _fin():
        dk_ref[0, 0, 0] = dk_sc[...]
        dv_ref[0, 0, 0] = dv_sc[...]


def paged_prefill_bwd_dkv_pallas(q, o, lse, do, segment_ids, seg_start,
                                 block_tables, k_pages, v_pages, pos_pages,
                                 *, bq: int = 16,
                                 interpret: bool | None = None):
    """Per-(segment, page) pool dk/dv blocks, each PER QUERY HEAD:
    returns (dk, dv), both (S, M, H, page_len, D) f32.  ops.py reduces
    the GQA groups and scatter-adds through the block table into the
    pool-shaped gradient (shared prompt pages sum over GRPO siblings).

    The query grid is walked per segment via scalar tables (packed row,
    first block, block count) derived from segment_ids; the grid's q
    axis is the STATIC upper bound T // bq and steps past a segment's
    block count are skipped."""
    r, h, t, d = q.shape
    kvh = k_pages.shape[2]
    g = h // kvh
    s_count, nm = block_tables.shape
    page_len = pos_pages.shape[1]
    nq = t // bq
    scale = 1.0 / (d ** 0.5)
    qseg = _qblock_segments(segment_ids, bq, s_count)
    srow, sq0, snq = _seg_tables(qseg, s_count)
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)  # (R, H, T)
    kern = functools.partial(_prefill_dkv_pool_kernel, nq=nq, g=g,
                             scale=scale)

    def qblk(s_, qj, sq0_, snq_):
        return sq0_[s_] + jnp.minimum(qj, jnp.maximum(snq_[s_] - 1, 0))

    def q_idx(s_, h_, mi, qj, srow_, sq0_, snq_, ss, bt):
        return (srow_[s_], h_, qblk(s_, qj, sq0_, snq_), 0)

    def qv_idx(s_, h_, mi, qj, srow_, sq0_, snq_, ss, bt):
        return (srow_[s_], h_, 0, qblk(s_, qj, sq0_, snq_))

    def seg_idx(s_, h_, mi, qj, srow_, sq0_, snq_, ss, bt):
        return (srow_[s_], 0, qblk(s_, qj, sq0_, snq_))

    def page_idx(s_, h_, mi, qj, srow_, sq0_, snq_, ss, bt):
        return (jnp.maximum(bt[s_, mi], 0), 0, 0, 0)

    def pos_idx(s_, h_, mi, qj, srow_, sq0_, snq_, ss, bt):
        return (jnp.maximum(bt[s_, mi], 0), 0, 0)

    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s_count, h, nm, nq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), q_idx),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, page_len, kvh, d), page_idx),
                pl.BlockSpec((1, 1, page_len), pos_idx),
                pl.BlockSpec((1, 1, bq, d), q_idx),
                pl.BlockSpec((1, 1, 1, bq), qv_idx),
                pl.BlockSpec((1, 1, 1, bq), qv_idx),
                pl.BlockSpec((1, 1, bq), seg_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, page_len, d),
                             lambda s_, h_, mi, qj, *_: (s_, mi, h_, 0, 0)),
                pl.BlockSpec((1, 1, 1, page_len, d),
                             lambda s_, h_, mi, qj, *_: (s_, mi, h_, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((page_len, d), F32),
                            pltpu.VMEM((page_len, d), F32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s_count, nm, h, page_len, d), F32),
            jax.ShapeDtypeStruct((s_count, nm, h, page_len, d), F32),
        ],
        interpret=resolve_interpret(interpret),
    )(srow, sq0, snq, seg_start, block_tables, q, k_pages, v_pages,
      _PK._rows(pos_pages), do, _PK._rows(lse), _PK._rows(delta),
      _PK._rows(segment_ids))
