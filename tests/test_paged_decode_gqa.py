"""The jnp paged decode attention (``paged_decode_attention``, ``impl="ref"``)
scores and combines the gathered pages with grouped-query einsums.  Pinned
here: it computes what the kv-repeat formulation computes, for MHA, GQA and
MQA, and its lowering never builds a KV tensor at query-head width."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (
    F32,
    NEG_INF,
    _norm_pos,
    apply_rope,
    gather_pages,
    paged_cache_update,
    paged_decode_attention,
    repeat_kv,
)

S, H, D, D_MODEL, PAGE_LEN, M, P = 3, 8, 16, 32, 4, 5, 8
ROPE = 10_000.0


def _kv_repeat_oracle(p, x, pool, pos, block_tables, write_page, write_off):
    """The kv-repeat formulation paged decode used before: K and V gathered,
    repeated to every query head, then the dense decode einsums."""
    b = x.shape[0]
    h, kvh, dh = p["wq"].shape[1], p["wk"].shape[1], p["wq"].shape[2]
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"])
    posb = _norm_pos(pos, b)
    q = apply_rope(q, posb, ROPE)
    k = apply_rope(k, posb, ROPE)
    new_pool = paged_cache_update(pool, k, v, posb, write_page, write_off)
    kg, vg, posg = gather_pages(new_pool, block_tables)
    valid = (posg >= 0) & (posg <= posb)
    kf = repeat_kv(kg, h // kvh)
    vf = repeat_kv(vg, h // kvh)
    scale = 1.0 / jnp.sqrt(dh).astype(F32)
    s = jnp.einsum("bthd,bshd->bhts", q, kf.astype(q.dtype),
                   preferred_element_type=F32) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pa = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", pa.astype(vf.dtype), vf)
    return jnp.einsum("bthk,hkd->btd", o, p["wo"]), new_pool


def _case(kvh: int):
    """Three slots over an 8-page pool of 4-token pages.  Slots 0 and 1
    share a 6-token prompt (pages 0 and 1; page 1 holds 2 tokens and 2
    empty entries) and decode into private pages; slot 2 is inactive: its
    write goes to the drop sentinel (page P) and its block table is all
    ``-1``.  Every entry of the pool holds K/V, so a masking slip shows."""
    rng = np.random.default_rng(kvh)
    f = lambda *s: jnp.asarray(rng.normal(size=s), F32)  # noqa: E731
    p = {"wq": f(D_MODEL, H, D) / 4, "wk": f(D_MODEL, kvh, D) / 4,
         "wv": f(D_MODEL, kvh, D) / 4, "wo": f(H, D, D_MODEL) / 4}
    pos_pool = np.full((P, PAGE_LEN), -1, np.int32)
    pos_pool[0] = [0, 1, 2, 3]
    pos_pool[1, :2] = [4, 5]               # partial last prompt page
    pos_pool[2, :3] = [6, 7, 8]            # slot 0's decode page
    pos_pool[3] = [6, 7, 8, 9]             # slot 1's first decode page
    pool = {"k": f(P, PAGE_LEN, kvh, D) * 3, "v": f(P, PAGE_LEN, kvh, D),
            "pos": jnp.asarray(pos_pool)}
    block_tables = jnp.asarray([[0, 1, 2, -1, -1],
                                [0, 1, 3, 4, -1],
                                [-1, -1, -1, -1, -1]], jnp.int32)
    pos = jnp.asarray([9, 10, 0], jnp.int32)
    write_page = jnp.asarray([2, 4, P], jnp.int32)
    write_off = jnp.asarray([3, 0, 0], jnp.int32)
    x = f(S, 1, D_MODEL)
    return p, x, pool, pos, block_tables, write_page, write_off


@pytest.mark.parametrize("kvh", [8, 2, 1], ids=["mha", "gqa", "mqa"])
def test_grouped_decode_matches_kv_repeat(kvh):
    p, x, pool, pos, bt, wp, wo = _case(kvh)
    out, new_pool = jax.jit(lambda *a: paged_decode_attention(
        *a, rope_theta=ROPE))(p, x, pool, pos, bt, wp, wo)
    want, want_pool = jax.jit(_kv_repeat_oracle)(p, x, pool, pos, bt, wp, wo)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    for name in ("k", "v", "pos"):
        np.testing.assert_array_equal(np.asarray(new_pool[name]),
                                      np.asarray(want_pool[name]))
    # the two live writes landed; the inactive slot's was dropped
    changed = np.argwhere(np.any(np.asarray(new_pool["k"])
                                 != np.asarray(pool["k"]), axis=(2, 3)))
    assert sorted(map(tuple, changed)) == [(2, 3), (4, 0)]
    assert np.asarray(new_pool["pos"])[2, 3] == 9
    assert np.asarray(new_pool["pos"])[4, 0] == 10


def _lowered_text(fn, kvh: int) -> str:
    sd = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    p = {"wq": sd((D_MODEL, H, D), bf), "wk": sd((D_MODEL, kvh, D), bf),
         "wv": sd((D_MODEL, kvh, D), bf), "wo": sd((H, D, D_MODEL), bf)}
    pool = {"k": sd((P, PAGE_LEN, kvh, D), bf),
            "v": sd((P, PAGE_LEN, kvh, D), bf),
            "pos": sd((P, PAGE_LEN), jnp.int32)}
    i32 = lambda *shape: sd(shape, jnp.int32)  # noqa: E731
    return jax.jit(fn).lower(p, sd((S, 1, D_MODEL), bf), pool, i32(S),
                             i32(S, M), i32(S), i32(S)).as_text()


def _head_wide(text: str, kvh: int) -> list:
    """Values of shape (S, L, H, D), or the 5-D (S, L, KV, G, D) repeat."""
    length, g = M * PAGE_LEN, H // kvh
    pat = re.compile(rf"tensor<{S}x{length}x({H}x|{kvh}x{g}x){D}x")
    return pat.findall(text)


@pytest.mark.parametrize("kvh", [2, 1], ids=["gqa", "mqa"])
def test_paged_decode_lowering_has_no_kv_repeat(kvh):
    text = _lowered_text(
        lambda *a: paged_decode_attention(*a, rope_theta=ROPE), kvh)
    assert _head_wide(text, kvh) == []
    # the guard sees the repeat where it is: the oracle builds it
    assert _head_wide(_lowered_text(_kv_repeat_oracle, kvh), kvh)
