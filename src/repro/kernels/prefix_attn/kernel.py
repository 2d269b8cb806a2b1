"""Pallas TPU kernels: prefix-aware and packed (segment-id) flash attention.

This is the TPU realization of NAT's forward saving (DESIGN.md §3/§7), in
two variants:

* **prefix** — each sequence carries a cut length L_b; query/key blocks
  past the cut frontier are SKIPPED with ``pl.when`` — compute drops from
  O(T^2) to O(L_b^2) per sequence while shapes stay static (the repack
  bucket ladder handles the batch-level savings; this kernel handles the
  per-sequence remainder).
* **packed** — rows hold several sequences back to back with per-token
  segment ids (core/layout.py).  Attention must never cross packed
  neighbors, and the block-skip exploits the same structure: per-row
  segment ids are monotone, so a KV block whose [min, max] segment range
  cannot intersect the query block's is skipped wholesale — block-sparse
  over segment boundaries, elementwise id-equality masking inside blocks.

Layout: q (B, H, T, D), k/v (B, KV, T, D); GQA is handled in the BlockSpec
index map (query head h reads kv head h // (H // KV) — no kv repeat in HBM).

Three kernels per variant (flash-standard decomposition):
  fwd     — grid (B, H, Tq/bq, Tk/bk), online softmax, saves (O, LSE)
  bwd dq  — same grid, accumulates dq over k blocks
  bwd dkv — grid (B, H, Tk/bk, Tq/bq) (k outer), accumulates dk/dv over
            q blocks
cut_lens / per-block segment ranges ride in as scalar-prefetch operands.
All accumulation f32.
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


def _rows(x):
    """``(..., N)`` -> ``(..., 1, N)``.  A block of per-token values (LSE,
    delta, segment ids, page positions) then has a second-minor dim equal
    to the array's, as Mosaic requires; the lane dim stays the block size,
    so on a TPU that must be a multiple of 128 or the whole axis."""
    return x[..., None, :]


def _block_mask(q0, k0, bq, bk, cut, window):
    """(bq, bk) validity mask for global query offset q0, key offset k0."""
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = (kj <= qi) & (kj < cut) & (qi < cut)
    if window > 0:
        m &= (qi - kj) < window
    return m


def _needed(qi, ki, bq, bk, cut, window):
    """Whether key block ki contributes to query block qi (block-level skip)."""
    q0, k0 = qi * bq, ki * bk
    need = (k0 <= q0 + bq - 1) & (k0 < cut) & (q0 < cut)
    if window > 0:
        need &= (k0 + bk - 1) > (q0 - window)
    return need


# -------------------------------------------------------------------- fwd
def _fwd_kernel(cut_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, bq, bk, nk, window, scale):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    cut = cut_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(_needed(qi, ki, bq, bk, cut, window))
    def _compute():
        q = q_ref[0, 0].astype(F32)                     # (bq, D)
        k = k_ref[0, 0].astype(F32)                     # (bk, D)
        v = v_ref[0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST) * scale
        mask = _block_mask(qi * bq, ki * bk, bq, bk, cut, window)
        s = jnp.where(mask, s, NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot(
            p, v, precision=jax.lax.Precision.HIGHEST)
        m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_sc[...]
        ok = l > 0
        lsafe = jnp.where(ok, l, 1.0)
        o_ref[0, 0] = jnp.where(ok[:, None], acc_sc[...] / lsafe[:, None],
                                0.0).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(ok, m_sc[...] + jnp.log(lsafe), 0.0)


def fwd_pallas(q, k, v, cut_lens, *, window: int = 0, bq: int = 128,
               bk: int = 128, interpret: bool | None = None):
    b, h, t, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    nq, nk = t // bq, t // bk
    scale = 1.0 / (d ** 0.5)
    kern = functools.partial(_fwd_kernel, bq=bq, bk=bk, nk=nk, window=window,
                             scale=scale)
    grid = (b, h, nq, nk)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, qi, ki, cut: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, cut: (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, cut: (b_, h_ // g, ki, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, qi, ki, cut: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bq), lambda b_, h_, qi, ki, cut: (b_, h_, qi)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq,), F32),
                pltpu.VMEM((bq,), F32),
                pltpu.VMEM((bq, d), F32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t), F32),
        ],
        interpret=resolve_interpret(interpret),
    )(cut_lens, q, k, v)
    return out


# ----------------------------------------------------------------- bwd: dq
def _bwd_dq_kernel(cut_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_sc, *, bq, bk, nk, window, scale):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    cut = cut_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(_needed(qi, ki, bq, bk, cut, window))
    def _compute():
        q = q_ref[0, 0].astype(F32)
        k = k_ref[0, 0].astype(F32)
        v = v_ref[0, 0].astype(F32)
        do = do_ref[0, 0].astype(F32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST) * scale
        mask = _block_mask(qi * bq, ki * bk, bq, bk, cut, window)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        ds = p * (dp - delta[:, None]) * scale
        acc_sc[...] += jax.lax.dot(ds, k, precision=jax.lax.Precision.HIGHEST)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0, 0] = acc_sc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------- bwd: dkv
def _bwd_dkv_kernel(cut_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, bq, bk, nq, window, scale):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    cut = cut_ref[b]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(_needed(qi, ki, bq, bk, cut, window))
    def _compute():
        q = q_ref[0, 0].astype(F32)
        k = k_ref[0, 0].astype(F32)
        v = v_ref[0, 0].astype(F32)
        do = do_ref[0, 0].astype(F32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST) * scale
        mask = _block_mask(qi * bq, ki * bk, bq, bk, cut, window)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)          # (bq, bk)
        dv_sc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                          precision=jax.lax.Precision.HIGHEST)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        ds = p * (dp - delta[:, None]) * scale                       # (bq, bk)
        dk_sc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          precision=jax.lax.Precision.HIGHEST)

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def bwd_pallas(q, k, v, o, lse, do, cut_lens, *, window: int = 0,
               bq: int = 128, bk: int = 128, interpret: bool | None = None):
    """Returns (dq (B,H,T,D), dk (B,H,T,D), dv (B,H,T,D)) — dk/dv are
    PER-QUERY-HEAD here; ops.py reduces them over GQA groups."""
    b, h, t, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    nq, nk = t // bq, t // bk
    scale = 1.0 / (d ** 0.5)
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)  # (B,H,T)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, bq=bq, bk=bk, nk=nk, window=window,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki, c: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, c: (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, c: (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki, c: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bq), lambda b_, h_, qi, ki, c: (b_, h_, qi)),
                pl.BlockSpec((1, 1, bq), lambda b_, h_, qi, ki, c: (b_, h_, qi)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda b_, h_, qi, ki, c: (b_, h_, qi, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(cut_lens, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, nq=nq, window=window,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nk, nq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda b_, h_, ki, qi, c: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, ki, qi, c: (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, ki, qi, c: (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bq, d), lambda b_, h_, ki, qi, c: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bq), lambda b_, h_, ki, qi, c: (b_, h_, qi)),
                pl.BlockSpec((1, 1, bq), lambda b_, h_, ki, qi, c: (b_, h_, qi)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, d), lambda b_, h_, ki, qi, c: (b_, h_, ki, 0)),
                pl.BlockSpec((1, 1, bk, d), lambda b_, h_, ki, qi, c: (b_, h_, ki, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), F32), pltpu.VMEM((bk, d), F32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(cut_lens, q, k, v, do, lse, delta)
    return dq, dk, dv


# ====================================================== packed (segment-id)
def _packed_mask(q0, k0, bq, bk, segq, segk):
    """(bq, bk) validity: causal in the packed row AND same segment."""
    qi = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return (kj <= qi) & (segq[:, None] == segk[None, :])


def _packed_needed(qi, ki, bq, bk, lo_ref, hi_ref, b):
    """Block-level skip: causal overlap + segment-range intersection.

    ``lo/hi`` hold each block's min/max segment id (monotone per row, so
    min/max = first/last).  Disjoint ranges cannot contain an equal pair;
    overlapping ranges fall through to the elementwise mask.
    """
    causal = ki * bk <= qi * bq + bq - 1
    inter = (lo_ref[b, ki] <= hi_ref[b, qi]) & (lo_ref[b, qi] <= hi_ref[b, ki])
    return causal & inter


# -------------------------------------------------------------- packed fwd
def _packed_fwd_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, segq_ref,
                       segk_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                       *, bq, bk, nk, scale):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(_packed_needed(qi, ki, bq, bk, lo_ref, hi_ref, b))
    def _compute():
        q = q_ref[0, 0].astype(F32)                     # (bq, D)
        k = k_ref[0, 0].astype(F32)                     # (bk, D)
        v = v_ref[0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST) * scale
        mask = _packed_mask(qi * bq, ki * bk, bq, bk, segq_ref[0], segk_ref[0])
        s = jnp.where(mask, s, NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot(
            p, v, precision=jax.lax.Precision.HIGHEST)
        m_sc[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        l = l_sc[...]
        ok = l > 0
        lsafe = jnp.where(ok, l, 1.0)
        o_ref[0, 0] = jnp.where(ok[:, None], acc_sc[...] / lsafe[:, None],
                                0.0).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(ok, m_sc[...] + jnp.log(lsafe), 0.0)


def seg_block_ranges(segment_ids, blk: int):
    """Per-block (min, max) segment-id summaries, each (B, T // blk) int32
    — the scalar-prefetch operands driving the packed block skip."""
    b, t = segment_ids.shape
    s = segment_ids.reshape(b, t // blk, blk)
    return (jnp.min(s, axis=2).astype(jnp.int32),
            jnp.max(s, axis=2).astype(jnp.int32))


def packed_fwd_pallas(q, k, v, segment_ids, *, bq: int = 128, bk: int = 128,
                      interpret: bool | None = None):
    b, h, t, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    assert bq == bk, "packed variant shares one block-range table: bq == bk"
    nq, nk = t // bq, t // bk
    scale = 1.0 / (d ** 0.5)
    lo, hi = seg_block_ranges(segment_ids, bq)
    kern = functools.partial(_packed_fwd_kernel, bq=bq, bk=bk, nk=nk,
                             scale=scale)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, lo_, hi_:
                             (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, lo_, hi_:
                             (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, bq),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, qi)),
                pl.BlockSpec((1, bk),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, ki)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bq),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, qi)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq,), F32),
                pltpu.VMEM((bq,), F32),
                pltpu.VMEM((bq, d), F32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t), F32),
        ],
        interpret=resolve_interpret(interpret),
    )(lo, hi, q, k, v, segment_ids, segment_ids)
    return out


# ----------------------------------------------------------- packed bwd: dq
def _packed_bwd_dq_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, delta_ref, segq_ref, segk_ref, dq_ref,
                          acc_sc, *, bq, bk, nk, scale):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(_packed_needed(qi, ki, bq, bk, lo_ref, hi_ref, b))
    def _compute():
        q = q_ref[0, 0].astype(F32)
        k = k_ref[0, 0].astype(F32)
        v = v_ref[0, 0].astype(F32)
        do = do_ref[0, 0].astype(F32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST) * scale
        mask = _packed_mask(qi * bq, ki * bk, bq, bk, segq_ref[0, 0],
                            segk_ref[0, 0])
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        ds = p * (dp - delta[:, None]) * scale
        acc_sc[...] += jax.lax.dot(ds, k, precision=jax.lax.Precision.HIGHEST)

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[0, 0] = acc_sc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------- packed bwd: dkv
def _packed_bwd_dkv_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref,
                           lse_ref, delta_ref, segq_ref, segk_ref, dk_ref,
                           dv_ref, dk_sc, dv_sc, *, bq, bk, nq, scale):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(_packed_needed(qi, ki, bq, bk, lo_ref, hi_ref, b))
    def _compute():
        q = q_ref[0, 0].astype(F32)
        k = k_ref[0, 0].astype(F32)
        v = v_ref[0, 0].astype(F32)
        do = do_ref[0, 0].astype(F32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST) * scale
        mask = _packed_mask(qi * bq, ki * bk, bq, bk, segq_ref[0, 0],
                            segk_ref[0, 0])
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)          # (bq, bk)
        dv_sc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                          precision=jax.lax.Precision.HIGHEST)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST)
        ds = p * (dp - delta[:, None]) * scale                       # (bq, bk)
        dk_sc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                          precision=jax.lax.Precision.HIGHEST)

    @pl.when(qi == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def packed_bwd_pallas(q, k, v, o, lse, do, segment_ids, *, bq: int = 128,
                      bk: int = 128, interpret: bool | None = None):
    """Returns (dq (B,H,T,D), dk (B,H,T,D), dv (B,H,T,D)) — dk/dv are
    PER-QUERY-HEAD here; ops.py reduces them over GQA groups."""
    b, h, t, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    assert bq == bk, "packed variant shares one block-range table: bq == bk"
    nq, nk = t // bq, t // bk
    scale = 1.0 / (d ** 0.5)
    lo, hi = seg_block_ranges(segment_ids, bq)
    delta = jnp.sum(do.astype(F32) * o.astype(F32), axis=-1)  # (B,H,T)

    dq = pl.pallas_call(
        functools.partial(_packed_bwd_dq_kernel, bq=bq, bk=bk, nk=nk,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, lo_, hi_:
                             (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, qi, ki, lo_, hi_:
                             (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, 0, qi)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, 0, qi)),
                pl.BlockSpec((1, 1, bq),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, 0, qi)),
                pl.BlockSpec((1, 1, bk),
                             lambda b_, h_, qi, ki, lo_, hi_: (b_, 0, ki)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, bq, d),
                lambda b_, h_, qi, ki, lo_, hi_: (b_, h_, qi, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lo, hi, q, k, v, do, _rows(lse), _rows(delta), _rows(segment_ids),
      _rows(segment_ids))

    dk, dv = pl.pallas_call(
        functools.partial(_packed_bwd_dkv_kernel, bq=bq, bk=bk, nq=nq,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, nk, nq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, ki, qi, lo_, hi_:
                             (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, ki, qi, lo_, hi_:
                             (b_, h_ // g, ki, 0)),
                pl.BlockSpec((1, 1, bq, d),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, h_, qi, 0)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, h_, 0, qi)),
                pl.BlockSpec((1, 1, 1, bq),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, h_, 0, qi)),
                pl.BlockSpec((1, 1, bq),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, 0, qi)),
                pl.BlockSpec((1, 1, bk),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, 0, ki)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, h_, ki, 0)),
                pl.BlockSpec((1, 1, bk, d),
                             lambda b_, h_, ki, qi, lo_, hi_: (b_, h_, ki, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), F32), pltpu.VMEM((bk, d), F32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
        ],
        interpret=resolve_interpret(interpret),
    )(lo, hi, q, k, v, do, _rows(lse), _rows(delta), _rows(segment_ids),
      _rows(segment_ids))
    return dq, dk, dv
