"""Compile-only checks of the main-path Pallas kernels for a described TPU
v5e chip, at published widths: the chip's compiler (Mosaic) refuses block
shapes and in-kernel indexing that the CPU interpreter accepts, so these
run with ``interpret=False`` and assert the kernel survived lowering as a
``tpu_custom_call``.  Nothing executes; no chip is needed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and test workers import every
test file.  Keep all such compiles in this one file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attn import (
    paged_decode_pallas,
    paged_mla_decode_pallas,
    paged_prefill_attention,
)

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # can never be read back here: keep the cache off while this file runs
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("page_len", [16, 128])
def test_paged_decode_compiles_qwen3_8b(one_chip, page_len):
    """Qwen3-8B decode: 32 query heads over 8 kv heads of 128."""
    s, kvh, g, d, m = 64, 8, 4, 128, 8
    p = s * m
    fn = jax.jit(lambda *a: paged_decode_pallas(*a, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (s, kvh, g, d), BF16),
        _spec(one_chip, (p, page_len, kvh, d), BF16),
        _spec(one_chip, (p, page_len, kvh, d), BF16),
        _spec(one_chip, (p, page_len), I32),
        _spec(one_chip, (s, m), I32),
        _spec(one_chip, (s,), I32)).compile()
    _assert_kernel(compiled)


def test_paged_mla_decode_compiles_deepseek_v2(one_chip):
    """DeepSeek-V2 absorbed decode: 128 heads, latent rank 512, rope 64."""
    s, h, r, dr, page_len, m = 32, 128, 512, 64, 16, 8
    p = s * m
    scale = 1.0 / float(np.sqrt(128 + dr))
    fn = jax.jit(lambda *a: paged_mla_decode_pallas(
        *a, scale=scale, interpret=False))
    compiled = fn.lower(
        _spec(one_chip, (s, h, r), BF16),
        _spec(one_chip, (s, h, dr), BF16),
        _spec(one_chip, (p, page_len, r), BF16),
        _spec(one_chip, (p, page_len, dr), BF16),
        _spec(one_chip, (p, page_len), I32),
        _spec(one_chip, (s, m), I32),
        _spec(one_chip, (s,), I32)).compile()
    _assert_kernel(compiled)


def _prefill_specs(one_chip):
    # Qwen3-8B widths; 2 packed rows of 512 suffix tokens over 4 segments,
    # each with up to 16 prompt pages of 16 tokens in the pool
    r, h, kvh, t, d = 2, 32, 8, 512, 128
    s_count, m, page_len = 4, 16, 16
    p = s_count * m
    return (
        _spec(one_chip, (r, h, t, d), BF16),
        _spec(one_chip, (r, kvh, t, d), BF16),
        _spec(one_chip, (r, kvh, t, d), BF16),
        _spec(one_chip, (r, t), I32),
        _spec(one_chip, (s_count,), I32),
        _spec(one_chip, (s_count, m), I32),
        _spec(one_chip, (p, page_len, kvh, d), BF16),
        _spec(one_chip, (p, page_len, kvh, d), BF16),
        _spec(one_chip, (p, page_len), I32))


def test_paged_prefill_fwd_compiles_qwen3_8b(one_chip):
    def fwd(q, k, v, seg, sstart, bt, kp, vp, pos):
        return paged_prefill_attention(q, k, v, seg, sstart, bt, kp, vp,
                                       pos, 128, 128, False)

    compiled = jax.jit(fwd).lower(*_prefill_specs(one_chip)).compile()
    _assert_kernel(compiled)


def test_paged_prefill_bwd_compiles_qwen3_8b(one_chip):
    """The custom vjp: pool dq and dkv kernels plus the packed backward
    (dq and dkv) it calls for the suffix keys."""
    def loss(q, k, v, seg, sstart, bt, kp, vp, pos):
        o = paged_prefill_attention(q, k, v, seg, sstart, bt, kp, vp,
                                    pos, 128, 128, False)
        return jnp.sum(o.astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 6, 7)))
    compiled = grad.lower(*_prefill_specs(one_chip)).compile()
    text = compiled.as_text()
    # forward + pool dq + pool dkv + packed dq + packed dkv
    assert text.count("tpu_custom_call") >= 5


def test_paged_decode_ref_compiles_without_head_broadcast_qwen3_8b(one_chip):
    """The jnp paged decode at the benchmark's Qwen3-8B decode shapes (80
    slots, 34 pages of 16): the chip's compiler gets grouped-query dots on
    the bf16 gather, so no K/V value at query-head width and no float32
    copy of the pool (or of its gather) appear in the compiled program."""
    from repro.models.attention import paged_decode_attention

    s, m, page_len, h, kvh, d, dm = 80, 34, 16, 32, 8, 128, 4096
    p = s * m
    params = {"wq": _spec(one_chip, (dm, h, d), BF16),
              "wk": _spec(one_chip, (dm, kvh, d), BF16),
              "wv": _spec(one_chip, (dm, kvh, d), BF16),
              "wo": _spec(one_chip, (h, d, dm), BF16)}
    pool = {"k": _spec(one_chip, (p, page_len, kvh, d), BF16),
            "v": _spec(one_chip, (p, page_len, kvh, d), BF16),
            "pos": _spec(one_chip, (p, page_len), I32)}
    fn = jax.jit(lambda *a: paged_decode_attention(*a, rope_theta=1e6))
    text = fn.lower(params, _spec(one_chip, (s, 1, dm), BF16), pool,
                    _spec(one_chip, (s,), I32), _spec(one_chip, (s, m), I32),
                    _spec(one_chip, (s,), I32),
                    _spec(one_chip, (s,), I32)).compile().as_text()
    length = m * page_len
    for shape in (f"[{s},{length},{h},{d}]", f"[{s},{m},{page_len},{h},{d}]",
                  f"[{s},{length},{kvh},{h // kvh},{d}]",
                  f"f32[{p},{page_len},{kvh},{d}]",
                  f"f32[{s},{length},{kvh},{d}]",
                  f"f32[{s},{m},{page_len},{kvh},{d}]"):
        assert shape not in text, shape
