"""Optimizer substrate: AdamW semantics, int8 moment compression, clipping,
schedule."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.optim import (
    AdamWConfig, adamw_update, clip_by_global_norm, dequantize, global_norm,
    init_opt_state, quantize, schedule,
)


def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                      weight_decay=0.0, clip_norm=100.0, end_lr_frac=1.0)
    params = {"x": jnp.array([5.0, -3.0])}
    state = init_opt_state(params, cfg)
    target = jnp.array([1.0, 2.0])
    for _ in range(200):
        g = {"x": 2 * (params["x"] - target)}
        params, state, _ = adamw_update(params, g, state, cfg)
    np.testing.assert_allclose(np.asarray(params["x"]), np.asarray(target),
                               atol=1e-2)


def test_int8_matches_fp32_closely():
    k = jax.random.PRNGKey(0)
    p0 = {"w": jax.random.normal(k, (64, 128)) * 0.1}
    tgt = jax.random.normal(jax.random.fold_in(k, 1), (64, 128)) * 0.1
    out = {}
    for mode in ("fp32", "int8"):
        cfg = AdamWConfig(lr=0.05, warmup_steps=0, total_steps=50,
                          clip_norm=100.0, moment_dtype=mode, end_lr_frac=1.0)
        p = dict(p0)
        s = init_opt_state(p, cfg)
        for _ in range(50):
            g = {"w": 2 * (p["w"] - tgt)}
            p, s, _ = adamw_update(p, g, s, cfg)
        out[mode] = np.asarray(p["w"])
    # int8-compressed moments track the fp32 trajectory and both converge
    err = np.abs(out["int8"] - out["fp32"]).max()
    assert err < 0.06, err
    np.testing.assert_allclose(out["int8"], np.asarray(tgt), atol=0.06)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 3))
def test_quantize_roundtrip(seed, nd):
    rng = np.random.default_rng(seed)
    shape = tuple(rng.integers(1, 9, size=nd))
    x = jnp.asarray(rng.normal(size=shape) * (10.0 ** (seed % 5 - 2)),
                    jnp.float32)
    q = quantize(x)
    back = dequantize(q)
    assert back.shape == x.shape
    scale = float(jnp.max(jnp.abs(x))) or 1.0
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               atol=scale / 100.0)


def test_quantize_block_structure():
    x = jnp.ones((4, 300))  # 300 pads to 3 blocks of 128
    q = quantize(x)
    assert q.q.shape == (4, 3, 128)
    assert q.scale.shape == (4, 3, 1)
    np.testing.assert_allclose(np.asarray(dequantize(q)), 1.0)


def test_clip_by_global_norm():
    g = {"a": jnp.array([3.0, 4.0])}  # norm 5
    clipped, gn = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(gn), 5.0, rtol=1e-6)
    np.testing.assert_allclose(
        float(global_norm(clipped)), 1.0, rtol=1e-5)
    # below threshold: untouched
    c2, _ = clip_by_global_norm(g, 10.0)
    np.testing.assert_allclose(np.asarray(c2["a"]), np.asarray(g["a"]))


def test_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      end_lr_frac=0.1)
    s = [float(schedule(cfg, jnp.asarray(i))) for i in range(101)]
    assert s[0] == 0.0
    np.testing.assert_allclose(s[10], 1.0, rtol=1e-5)
    assert all(a >= b - 1e-9 for a, b in zip(s[10:], s[11:]))  # decays
    np.testing.assert_allclose(s[100], 0.1, rtol=1e-4)
