"""Random weights from the seed, made by the benchmark, in the program's
parameter layout and in the reference's.

The benchmark makes the weights (the program receives them through
``NATGRPOTrainer(params=...)``), so the reference can make the same ones
again from the seed and never reads an array the program produced.  Both
models here are dense GQA / SwiGLU / RoPE decoders; the reference's layout
is a flat dict of named arrays, per-layer ones stacked on a leading layer
axis:

    embed (V, d)   head (d, V)   final_norm (d,)
    layers.ln1 (L, d)   layers.wq (L, d, H, Dh)   layers.wk/.wv (L, d, KV, Dh)
    layers.wo (L, H, Dh, d)   layers.ln2 (L, d)
    layers.w_gate/.w_up (L, d, F)   layers.w_down (L, F, d)

Matrices are N(0, 1/fan_in) over their contracted axes, the embedding
N(0, 1), and the RMSNorm offsets 0 (the norm weight is 1 + offset).
Everything is stored in bfloat16, the type the program trains and serves.
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

STORE = jnp.bfloat16

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
                "w_down")

# program tree path of each per-layer leaf (group0/l0/<module>/<name>)
_PROGRAM_PATH = {
    "ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
    "wq": ("mixer", "wq"), "wk": ("mixer", "wk"), "wv": ("mixer", "wv"),
    "wo": ("mixer", "wo"),
    "w_gate": ("mlp", "w_gate"), "w_up": ("mlp", "w_up"),
    "w_down": ("mlp", "w_down"),
}


def dims(cfg: dict) -> dict:
    """Sizes the weights and the reference need, from a config file."""
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"], theta=cfg["rope_theta"],
                eps=cfg["rms_norm_eps"])


def layout(cfg: dict) -> dict:
    """name -> (shape, std); std 0 means zeros."""
    z = dims(cfg)
    d, h, kv, dh, f, v, n = (z["d"], z["h"], z["kv"], z["dh"], z["f"],
                             z["v"], z["layers"])
    return {
        "embed": ((v, d), 1.0),
        "head": ((d, v), 1 / math.sqrt(d)),
        "final_norm": ((d,), 0.0),
        "layers.ln1": ((n, d), 0.0),
        "layers.wq": ((n, d, h, dh), 1 / math.sqrt(d)),
        "layers.wk": ((n, d, kv, dh), 1 / math.sqrt(d)),
        "layers.wv": ((n, d, kv, dh), 1 / math.sqrt(d)),
        "layers.wo": ((n, h, dh, d), 1 / math.sqrt(h * dh)),
        "layers.ln2": ((n, d), 0.0),
        "layers.w_gate": ((n, d, f), 1 / math.sqrt(d)),
        "layers.w_up": ((n, d, f), 1 / math.sqrt(d)),
        "layers.w_down": ((n, f, d), 1 / math.sqrt(f)),
    }


def seed32(seed: int) -> int:
    """A 31-bit key for JAX from a seed of any size (``--seed`` may exceed
    32 signed bits)."""
    return zlib.crc32(str(int(seed)).encode()) & 0x7FFFFFFF


@partial(jax.jit, static_argnames=("spec",))
def _make(key, spec):
    out = {}
    for i, (name, shape, std) in enumerate(spec):
        if std == 0.0:
            out[name] = jnp.zeros(shape, STORE)
        else:
            k = jax.random.fold_in(key, i)
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(STORE)
    return out


def make(cfg: dict, seed: int) -> dict:
    """The flat weights of ``seed``, made on the device in one call."""
    spec = tuple((name, shape, std)
                 for name, (shape, std) in sorted(layout(cfg).items()))
    return _make(jax.random.PRNGKey(seed32(seed)), spec)


def to_program(flat: dict) -> dict:
    """Flat weights -> the program's parameter tree (one dense layer group
    scanned over its layers)."""
    layer: dict = {}
    for name in LAYER_LEAVES:
        mod, leaf = _PROGRAM_PATH[name]
        layer.setdefault(mod, {})[leaf] = flat["layers." + name]
    return {"embed": {"table": flat["embed"]},
            "final_norm": {"scale": flat["final_norm"]},
            "head": {"w": flat["head"]},
            "group0": {"l0": layer}}


def from_program(tree: dict) -> dict:
    """The program's parameter tree (or a tree shaped like it, such as the
    optimizer's moments) -> flat names."""
    l0 = tree["group0"]["l0"]
    flat = {"embed": tree["embed"]["table"],
            "final_norm": tree["final_norm"]["scale"],
            "head": tree["head"]["w"]}
    for name in LAYER_LEAVES:
        mod, leaf = _PROGRAM_PATH[name]
        flat["layers." + name] = l0[mod][leaf]
    return flat


def _norm(x, stacked: bool):
    """L2 norm, one per layer for a leaf stacked over layers."""
    x = x.astype(jnp.float32)
    axes = tuple(range(1, x.ndim)) if stacked else None
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))


_norms = jax.jit(lambda flat: {k: _norm(v, k.startswith("layers."))
                               for k, v in flat.items()})
_change = jax.jit(lambda a, b, stacked: _norm(
    a.astype(jnp.float32) - b.astype(jnp.float32), stacked),
    static_argnames=("stacked",))


def _named(vals: dict) -> dict:
    """Per-leaf floats; each layer's slice of a stacked leaf is a leaf of
    its own (``l2.wq``)."""
    out = {}
    for name, val in vals.items():
        if name.startswith("layers."):
            for i, x in enumerate(np.atleast_1d(val)):
                out[f"l{i}.{name[len('layers.'):]}"] = float(x)
        else:
            out[name] = float(val)
    return out


def leaf_norms(flat: dict) -> dict:
    """L2 norm of every leaf (per layer for stacked leaves)."""
    return _named(jax.device_get(_norms(flat)))


def change_norms(new: dict, old_host: dict) -> dict:
    """Per-leaf norms of ``new - old``: ``new`` on the device, ``old`` a
    host copy, moved over one leaf at a time so the device holds at most
    one extra leaf."""
    return _named({k: jax.device_get(_change(
        new[k], jnp.asarray(old_host[k]), k.startswith("layers.")))
        for k in new})


@partial(jax.jit, static_argnames=("stacked",))
def _dots(a, b, stacked: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    axes = tuple(range(1, a.ndim)) if stacked else None
    return (jnp.sum(a * b, axis=axes), jnp.sum(a * a, axis=axes),
            jnp.sum(b * b, axis=axes))


def cosines(dev: dict, host: dict) -> dict:
    """Per-leaf cosine of ``dev`` (device arrays) with ``host`` (host
    arrays of the same names), moving one host leaf over at a time; a
    leaf that is zero on either side reads 0."""
    vals = {}
    for k in dev:
        ab, aa, bb = jax.device_get(_dots(dev[k], jnp.asarray(host[k]),
                                          k.startswith("layers.")))
        vals[k] = np.asarray(ab) / np.maximum(np.sqrt(np.asarray(aa)
                                                      * np.asarray(bb)),
                                              1e-30)
    return _named(vals)
