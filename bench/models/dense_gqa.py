"""Family of dense decoders: GQA attention with RoPE, SwiGLU MLP, RMSNorm,
untied head.  A configuration file names this module under ``reference``.

What a family module provides (bench/harness.py loads it by path):

* ``STACKED``: prefix of each leaf group stacked over a layer axis -> the
  tag of one layer's leaf (``layers.wq``, layer 2 -> ``l2.wq``).
* ``dims(cfg)``: the sizes the weights and the reference need, with
  ``v`` (vocabulary rows held here), ``n`` (matmul parameters a token
  passes through: attention projections, the MLP and the head over the
  vocabulary; the embedding lookup is no matmul) and ``attn`` (forward
  FLOPs per attended key per token, over all layers: 4 * H * Dh * L for
  the scores and the values).
* ``layout(cfg)``: leaf name -> (shape, std); std 0 means zeros.
* ``logits(params, tokens, z, mm)``: the reference's (B, T - 1, V) logits
  of each next token, with every matmul through ``mm(eq, a, b)``.
* ``program_config(cfg)``, ``to_program(flat)``, ``from_program(tree)``:
  the program's model configuration and parameter tree.  These three alone
  touch the program's types, and do no arithmetic.

The reference half is written from the published equations and imports
nothing of the program: x = E[tok]; per layer x += Attn(RMSNorm(x)),
x += MLP(RMSNorm(x)); logits = RMSNorm(x) W_head.  RMSNorm is x / rms(x) *
(1 + offset); RoPE rotates the two halves of each head (freq
theta^(-2i/Dh)); query head j reads key/value head j // (H / KV); SwiGLU
is (silu(x Wg) * x Wu) Wd.

The flat layout, per-layer leaves stacked on a leading layer axis:

    embed (V, d)   head (d, V)   final_norm (d,)
    layers.ln1 (L, d)   layers.wq (L, d, H, Dh)   layers.wk/.wv (L, d, KV, Dh)
    layers.wo (L, H, Dh, d)   layers.ln2 (L, d)
    layers.w_gate/.w_up (L, d, F)   layers.w_down (L, F, d)

Matrices are N(0, 1/fan_in) over their contracted axes, the embedding
N(0, 1), and the RMSNorm offsets 0 (the norm weight is 1 + offset).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
STACKED = {"layers.": "l"}

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
    """Sizes the weights, the reference and the FLOP count need."""
    z = dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
             kv=cfg["num_key_value_heads"], dh=cfg["head_dim"],
             f=cfg["intermediate_size"], v=cfg["vocab_size"],
             layers=cfg["num_hidden_layers"], theta=cfg["rope_theta"],
             eps=cfg["rms_norm_eps"])
    d, h, kv, dh, f = z["d"], z["h"], z["kv"], z["dh"], z["f"]
    per_layer = 2 * d * h * dh + 2 * d * kv * dh + 3 * d * f
    z["n"] = z["layers"] * per_layer + d * z["v"]
    z["attn"] = 4 * h * dh * z["layers"]
    return z


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


# --------------------------------------------------------------- program
def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry's settings with every size taken from the file."""
    from repro.configs import get_config
    from repro.models.config import dense_blocks

    base = get_config(cfg["registry_id"])
    mc = dataclasses.replace(
        base, d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        blocks=dense_blocks(cfg["num_hidden_layers"]),
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"])
    if (mc.mlp_kind != "swiglu" or mc.tie_embeddings
            != cfg["tie_word_embeddings"] or mc.moe or mc.mla
            or cfg["hidden_act"] != "silu"):
        raise ValueError(f"{cfg['name']}: the reference covers dense SwiGLU "
                         f"decoders with untied heads only")
    return mc


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


# ------------------------------------------------------------- reference
def _rmsnorm(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + offset)


def _rope(x, pos, theta):
    """x (B, T, H, D), pos (T,)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq            # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, z, mm):
    b, t, _ = x.shape
    pos = jnp.arange(t)
    h = _rmsnorm(x, p["ln1"], z["eps"])
    q = _rope(mm("btd,dhk->bthk", h, p["wq"]), pos, z["theta"])
    k = _rope(mm("btd,dhk->bthk", h, p["wk"]), pos, z["theta"])
    v = mm("btd,dhk->bthk", h, p["wv"])
    g = z["h"] // z["kv"]
    q = q.reshape(b, t, z["kv"], g, z["dh"])
    s = mm("btngk,bsnk->bngts", q, k) / math.sqrt(z["dh"])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("bngts,bsnk->btngk", a, v).reshape(b, t, z["h"], z["dh"])
    x = x + mm("bthk,hkd->btd", o, p["wo"])
    h = _rmsnorm(x, p["ln2"], z["eps"])
    u = jax.nn.silu(mm("btd,df->btf", h, p["w_gate"])) * mm(
        "btd,df->btf", h, p["w_up"])
    return x + mm("btf,fd->btd", u, p["w_down"])


def logits(params, tokens, z, mm):
    """(B, T - 1, V) logits of each next token; ``params`` flat float32
    weights, ``mm(eq, a, b)`` every matmul."""
    x = params["embed"][tokens]
    for i in range(z["layers"]):
        p = {k[len("layers."):]: v[i] for k, v in params.items()
             if k.startswith("layers.")}
        x = jax.checkpoint(partial(_layer, z=z, mm=mm))(x, p)
    h = _rmsnorm(x, params["final_norm"], z["eps"])
    return mm("btd,dv->btv", h[:, :-1], params["head"])
