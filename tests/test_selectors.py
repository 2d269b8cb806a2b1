"""Selector invariants (unit + hypothesis property tests).

Invariants from the paper (§3.1, §4):
  * masks live only on response tokens,
  * inclusion probabilities are in (0, 1] wherever the mask can be 1,
  * E[m] = p (checked by Monte Carlo for URS and analytically for RPC),
  * RPC masks are contiguous prefixes with the minimum-cutoff survival
    function p_t = 1 (t<=C), (T-t+1)/(T-C+1) (t>C),
  * Det-Trunc keeps exactly floor(frac*T) tokens with p == 1 (the biased
    baseline).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.selectors import (
    DetTruncSelector, EntropySelector, FullSelector, RPCSelector,
    URSSelector, make_selector, rpc_survival,
)


def make_mask(lengths, prompt_lens, t):
    b = len(lengths)
    rm = np.zeros((b, t), np.float32)
    for i, (p, l) in enumerate(zip(prompt_lens, lengths)):
        rm[i, p:p + l] = 1.0
    return jnp.asarray(rm)


@pytest.mark.parametrize("name,kwargs", [
    ("full", {}), ("urs", {"p": 0.5}), ("rpc", {"min_cut": 4}),
    ("det_trunc", {}),
])
def test_mask_only_on_response(name, kwargs, key):
    rm = make_mask([10, 20, 1], [3, 0, 5], 32)
    sel = make_selector(name, **kwargs)(key, rm)
    assert np.all(np.asarray(sel.mask) <= np.asarray(rm))
    assert np.all(np.asarray(sel.inclusion) > 0)
    assert np.all(np.asarray(sel.inclusion) <= 1)
    w = np.asarray(sel.ht_weights)
    assert np.all(w[np.asarray(rm) == 0] == 0)


def test_full_selector_identity(key):
    rm = make_mask([10, 5], [2, 4], 24)
    sel = FullSelector()(key, rm)
    np.testing.assert_array_equal(np.asarray(sel.mask), np.asarray(rm))
    np.testing.assert_array_equal(np.asarray(sel.ht_weights), np.asarray(rm))


def test_urs_expectation(key):
    rm = make_mask([40], [4], 64)
    sel = URSSelector(p=0.3)
    draw = jax.jit(lambda k: sel(k, rm).mask)
    total = np.zeros((1, 64))
    n = 400
    for i in range(n):
        total += np.asarray(draw(jax.random.fold_in(key, i)))
    emp = total / n
    resp = np.asarray(rm) > 0
    assert abs(emp[resp].mean() - 0.3) < 0.03


def test_rpc_survival_formula():
    pos = jnp.arange(20)[None, :]
    length = jnp.array([20])
    p = np.asarray(rpc_survival(pos, length, min_cut=5))[0]
    np.testing.assert_allclose(p[:5], 1.0)
    for t in range(6, 21):  # 1-based t
        expect = (20 - t + 1) / (20 - 5 + 1)
        np.testing.assert_allclose(p[t - 1], expect, rtol=1e-6)


def test_rpc_prefix_structure_and_expectation(key):
    rm = make_mask([30, 12], [2, 6], 48)
    sel = RPCSelector(min_cut=4)
    draw = jax.jit(lambda k: sel(k, rm))
    kept = []
    for i in range(500):
        s = draw(jax.random.fold_in(key, i))
        m = np.asarray(s.mask)
        # contiguity: within response, mask is a prefix
        for b in range(2):
            resp = np.where(np.asarray(rm)[b] > 0)[0]
            vals = m[b, resp]
            assert np.all(np.diff(vals) <= 0), "mask must be a prefix"
        kept.append(np.asarray(s.keep_len))
    kept = np.stack(kept)  # (500, 2)
    # E[L] = (C + T)/2
    np.testing.assert_allclose(kept[:, 0].mean(), (4 + 30) / 2, atol=1.0)
    np.testing.assert_allclose(kept[:, 1].mean(), (4 + 12) / 2, atol=0.6)


def test_rpc_ht_mean_one(key):
    """E[m/p] = 1 per position — the HT identity that drives Prop. 1."""
    rm = make_mask([24], [0], 24)
    sel = RPCSelector(min_cut=2)
    draw = jax.jit(lambda k: sel(k, rm).ht_weights)
    n = 3000
    ws = jax.vmap(draw)(jax.random.split(key, n))
    np.testing.assert_allclose(np.asarray(ws).mean(0)[0], 1.0, atol=0.15)


def test_det_trunc_is_deterministic_biased(key):
    rm = make_mask([20], [3], 32)
    sel = DetTruncSelector(frac=0.5)
    s1 = sel(key, rm)
    s2 = sel(jax.random.fold_in(key, 1), rm)
    np.testing.assert_array_equal(np.asarray(s1.mask), np.asarray(s2.mask))
    assert np.asarray(s1.mask).sum() == 10
    # p == 1 on kept prefix -> weights don't compensate: that's the bias
    np.testing.assert_array_equal(np.asarray(s1.ht_weights), np.asarray(s1.mask))


def test_entropy_selector_respects_floor(key):
    rm = make_mask([30], [2], 40)
    ent = jnp.abs(jax.random.normal(key, (1, 40)))
    sel = EntropySelector(p_floor=0.25, budget=0.5)
    s = sel(key, rm, ent)
    p = np.asarray(s.inclusion)
    resp = np.asarray(rm) > 0
    assert np.all(p[resp] >= 0.25 - 1e-6)
    assert np.all(p[resp] <= 1.0 + 1e-6)


@settings(max_examples=30, deadline=None)
@given(
    t=st.integers(8, 64),
    prompt=st.integers(0, 8),
    min_cut=st.integers(1, 16),
    seed=st.integers(0, 2**31 - 1),
)
def test_rpc_properties_hypothesis(t, prompt, min_cut, seed):
    length = t - prompt
    rm = make_mask([length], [prompt], t)
    sel = RPCSelector(min_cut=min_cut)
    s = sel(jax.random.PRNGKey(seed), rm)
    m = np.asarray(s.mask)[0]
    p = np.asarray(s.inclusion)[0]
    keep = int(np.asarray(s.keep_len)[0])
    # keep length within [min(C, T), T]
    assert min(min_cut, length) <= keep <= length
    # mask matches keep_len
    assert int(m.sum()) == keep
    # survival monotone non-increasing on the response
    resp = slice(prompt, prompt + length)
    assert np.all(np.diff(p[resp]) <= 1e-7)
    # HT weights bounded by the minimum-cutoff guarantee
    c = min(min_cut, length)
    w = np.asarray(s.ht_weights)[0][resp]
    bound = (length - c + 1) / 1.0
    assert np.all(w <= bound + 1e-4)


@settings(max_examples=15, deadline=None)
@given(
    p=st.floats(0.05, 1.0),
    t=st.integers(4, 64),
    seed=st.integers(0, 2**31 - 1),
)
def test_urs_properties_hypothesis(p, t, seed):
    rm = make_mask([t], [0], t)
    s = URSSelector(p=p)(jax.random.PRNGKey(seed), rm)
    incl = np.asarray(s.inclusion)[0]
    np.testing.assert_allclose(incl, p, rtol=1e-6)
    w = np.asarray(s.ht_weights)[0]
    # every weight is 0 or 1/p (float32 tolerance)
    assert np.all((np.abs(w) < 1e-6) | (np.abs(w - 1.0 / p) < 1e-4))
