"""Proposition 1 and the paper's variance analysis, tested numerically.

* HT-masked loss is an unbiased estimator of the full-token loss (value AND
  gradient) for URS, RPC, and entropy-based designs.
* URS inflates the per-token second moment by exactly 1/p (§3.1).
* RPC covariance Cov(m_s, m_t) = p_t (1 - p_s) for s <= t (§4).
* Deterministic truncation is systematically biased (§4, Table 1).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.grpo import full_token_loss_reference, nat_grpo_loss
from repro.core.selectors import (
    DetTruncSelector, RPCSelector, URSSelector, rpc_survival,
)

B, T = 6, 40


@pytest.fixture(scope="module")
def batch():
    key = jax.random.PRNGKey(42)
    k1, k2, k3 = jax.random.split(key, 3)
    logp = -jnp.abs(jax.random.normal(k1, (B, T))) * 0.4
    old_logp = logp + 0.15 * jax.random.normal(k2, (B, T))
    adv = jax.random.normal(k3, (B,))
    rm = np.zeros((B, T), np.float32)
    lengths = [40, 32, 24, 16, 40, 8]
    for i, l in enumerate(lengths):
        rm[i, :l] = 1.0
    return logp, old_logp, adv, jnp.asarray(rm)


def mc_loss(selector, batch, n, key, grad=False):
    logp, old_logp, adv, rm = batch
    lengths = rm.sum(-1)

    def loss(lp, w):
        out, _ = nat_grpo_loss(lp, old_logp, adv, w, lengths)
        return out

    @jax.jit
    def one(k):
        w = selector(k, rm).ht_weights
        return jax.grad(loss)(logp, w) if grad else loss(logp, w)

    total = one(jax.random.fold_in(key, 0))
    for i in range(1, n):
        total = jax.tree.map(lambda a, b: a + b, total,
                             one(jax.random.fold_in(key, i)))
    return jax.tree.map(lambda a: a / n, total)


@pytest.mark.parametrize("selector,tol", [
    (URSSelector(p=0.5), 0.02),
    (URSSelector(p=0.25), 0.04),
    (RPCSelector(min_cut=4), 0.03),
    (RPCSelector(min_cut=1), 0.05),
])
def test_prop1_value_unbiased(selector, tol, batch, key):
    logp, old_logp, adv, rm = batch
    full = full_token_loss_reference(logp, old_logp, adv, rm)
    mc = mc_loss(selector, batch, 800, key)
    assert abs(float(mc - full)) < tol, (float(mc), float(full))


def test_prop1_gradient_unbiased(batch, key):
    logp, old_logp, adv, rm = batch
    g_full = jax.grad(
        lambda lp: full_token_loss_reference(lp, old_logp, adv, rm))(logp)
    for sel in (URSSelector(p=0.5), RPCSelector(min_cut=4)):
        g_mc = mc_loss(sel, batch, 1200, key, grad=True)
        rel = float(jnp.linalg.norm(g_mc - g_full) / jnp.linalg.norm(g_full))
        assert rel < 0.12, (type(sel).__name__, rel)


def test_det_trunc_biased(batch, key):
    """The negative control: deterministic truncation must NOT match."""
    logp, old_logp, adv, rm = batch
    g_full = jax.grad(
        lambda lp: full_token_loss_reference(lp, old_logp, adv, rm))(logp)
    g_det = mc_loss(DetTruncSelector(frac=0.5), batch, 4, key, grad=True)
    rel = float(jnp.linalg.norm(g_det - g_full) / jnp.linalg.norm(g_full))
    assert rel > 0.3, "deterministic truncation should be visibly biased"


def test_urs_second_moment_inflation(key):
    """E||w g||^2 = ||g||^2 / p exactly (paper §3.1)."""
    for p in (0.2, 0.5, 0.8):
        g = 1.7  # any fixed per-token score
        n = 20000
        m = jax.random.bernoulli(key, p, (n,)).astype(jnp.float32)
        w = m / p
        emp = float(jnp.mean((w * g) ** 2))
        np.testing.assert_allclose(emp, g * g / p, rtol=0.05)


def test_rpc_mask_covariance(key):
    """Cov(m_s, m_t) = p_t (1 - p_s), s <= t (§4)."""
    t_len, c = 24, 3
    rm = jnp.ones((1, t_len), jnp.float32)
    sel = RPCSelector(min_cut=c)
    draw = jax.jit(lambda k: sel(k, rm).mask[0])
    m = np.asarray(jax.vmap(draw)(jax.random.split(key, 6000)))
    pos = jnp.arange(t_len)[None, :]
    p = np.asarray(rpc_survival(pos, jnp.array([t_len]), c))[0]
    for s, t in [(4, 10), (5, 20), (10, 23), (3, 4)]:
        emp = np.cov(m[:, s], m[:, t])[0, 1]
        expect = p[t] * (1 - p[s])
        np.testing.assert_allclose(emp, expect, atol=0.02)


def test_rpc_variance_exceeds_independent(key):
    """App. B.4: positively-correlated RPC masks give variance >= the
    matched independent design (same marginal p_t) for positive losses."""
    t_len, c = 16, 2
    rm = jnp.ones((1, t_len), jnp.float32)
    pos = jnp.arange(t_len)[None, :]
    p = rpc_survival(pos, jnp.array([t_len]), c)
    losses = jnp.abs(jax.random.normal(key, (t_len,))) + 0.5

    def ht_est(w):
        return jnp.sum(w * losses) / t_len

    sel = RPCSelector(min_cut=c)

    @jax.jit
    def both(k):
        s = sel(k, rm)
        m = jax.random.uniform(k, (t_len,)) < p[0]
        return ht_est(s.ht_weights[0]), ht_est(m / p[0])

    rpc_vals, ind_vals = jax.vmap(both)(jax.random.split(key, 4000))
    assert np.var(np.asarray(rpc_vals)) > np.var(np.asarray(ind_vals)) * 0.9


def test_grpo_special_case_full_tokens(batch):
    """w == response_mask reproduces vanilla GRPO exactly."""
    logp, old_logp, adv, rm = batch
    loss, metrics = nat_grpo_loss(logp, old_logp, adv, rm, rm.sum(-1))
    ref = full_token_loss_reference(logp, old_logp, adv, rm)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-6)
    np.testing.assert_allclose(float(metrics["selected_ratio"]), 1.0)


# ---------------------------------------- arbitrary-design property test


@jax.jit
def _mc_value_and_grad(logp, old_logp, adv, rm, p, keys):
    """MC mean of (loss, grad) for independent Bernoulli(p_t) masks with
    HT weights w_t = m_t / p_t (Eq. 6), vmapped over draw keys."""
    lengths = rm.sum(-1)

    def loss(lp, w):
        out, _ = nat_grpo_loss(lp, old_logp, adv, w, lengths)
        return out

    def one(k):
        m = (jax.random.uniform(k, rm.shape) < p).astype(jnp.float32) * rm
        w = m / p
        return loss(logp, w), jax.grad(loss)(logp, w)

    vals, grads = jax.vmap(one)(keys)
    return vals, grads.mean(0)


@settings(max_examples=15)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1),
       st.floats(min_value=0.15, max_value=0.9))
def test_ht_unbiased_for_random_inclusion_probabilities(seed, p_min):
    """Eq. 6 pins w_t = m_t / p_t as unbiased for ANY inclusion-probability
    field p_t in (0, 1] — not just the shipped URS/RPC designs.  Draw a
    random per-token field, estimate by MC, and check the mean matches the
    full-token loss AND gradient within standard-error tolerance."""
    key = jax.random.PRNGKey(seed)
    kp, kb, k1, k2, k3 = jax.random.split(key, 5)
    logp = -jnp.abs(jax.random.normal(k1, (B, T))) * 0.4
    old_logp = logp + 0.15 * jax.random.normal(k2, (B, T))
    adv = jax.random.normal(k3, (B,))
    rm = np.zeros((B, T), np.float32)
    for i, l in enumerate([40, 32, 24, 16, 40, 8]):
        rm[i, :l] = 1.0
    rm = jnp.asarray(rm)
    # arbitrary inclusion probabilities in [p_min, 1]; 1 off-response so
    # the reweighting never divides by a vanishing p outside the support
    u = jax.random.uniform(kp, (B, T))
    p = jnp.where(rm > 0, p_min + (1.0 - p_min) * u, 1.0)

    full = full_token_loss_reference(logp, old_logp, adv, rm)
    g_full = jax.grad(
        lambda lp: full_token_loss_reference(lp, old_logp, adv, rm))(logp)

    n = 512
    vals, g_mc = _mc_value_and_grad(logp, old_logp, adv, rm, p,
                                    jax.random.split(kb, n))
    se = float(jnp.std(vals)) / np.sqrt(n)
    assert abs(float(jnp.mean(vals)) - float(full)) < 6 * se + 2e-3, \
        (float(jnp.mean(vals)), float(full), se)
    rel = float(jnp.linalg.norm(g_mc - g_full) / jnp.linalg.norm(g_full))
    assert rel < 0.25, (rel, p_min)
