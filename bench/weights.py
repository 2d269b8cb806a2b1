"""Random weights from the seed, made by the benchmark in the reference's
flat layout (the family module turns them into the program's tree), and
their per-leaf norms.

The benchmark makes the weights (the program receives them through
``NATGRPOTrainer(params=...)``), so the reference can make the same ones
again from the seed and never reads an array the program produced.  The
reference's layout is a flat dict of named arrays, given by the
configuration's family module (``layout``: name -> (shape, std)); leaves
of a group that the family lists in ``STACKED`` carry a leading layer
axis, and each layer's slice is a leaf of its own in the norms below.
Everything is stored in bfloat16, the type the program trains and serves.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

STORE = jnp.bfloat16


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


def make(layout: dict, seed: int) -> dict:
    """The flat weights of ``seed`` in ``layout`` (a family's name ->
    (shape, std); std 0 means zeros), made on the device in one call."""
    spec = tuple((name, shape, std)
                 for name, (shape, std) in sorted(layout.items()))
    return _make(jax.random.PRNGKey(seed32(seed)), spec)


def _group(name: str, groups) -> str | None:
    """The stacked group prefix that ``name`` falls in, or None."""
    return next((p for p in groups if name.startswith(p)), None)


def _norm(x, stacked: bool):
    """L2 norm, one per layer for a leaf stacked over layers."""
    x = x.astype(jnp.float32)
    axes = tuple(range(1, x.ndim)) if stacked else None
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))


_norms = jax.jit(lambda flat, groups: {
    k: _norm(v, _group(k, groups) is not None) for k, v in flat.items()},
    static_argnames=("groups",))
_change = jax.jit(lambda a, b, stacked: _norm(
    a.astype(jnp.float32) - b.astype(jnp.float32), stacked),
    static_argnames=("stacked",))


def _named(vals: dict, groups: dict) -> dict:
    """Per-leaf floats; each layer's slice of a stacked leaf is a leaf of
    its own, named by its group's tag and the layer (``<tag><i>.<leaf>``)."""
    out = {}
    for name, val in vals.items():
        p = _group(name, groups)
        if p is None:
            out[name] = float(val)
        else:
            for i, x in enumerate(np.atleast_1d(val)):
                out[f"{groups[p]}{i}.{name[len(p):]}"] = float(x)
    return out


def leaf_norms(flat: dict, groups: dict) -> dict:
    """L2 norm of every leaf (per layer for stacked leaves); ``groups``
    the family's ``STACKED``."""
    return _named(jax.device_get(_norms(flat, tuple(groups))), groups)


def change_norms(new: dict, old_host: dict, groups: dict) -> dict:
    """Per-leaf norms of ``new - old``: ``new`` on the device, ``old`` a
    host copy, moved over one leaf at a time so the device holds at most
    one extra leaf."""
    return _named({k: jax.device_get(_change(
        new[k], jnp.asarray(old_host[k]), _group(k, groups) is not None))
        for k in new}, groups)


@partial(jax.jit, static_argnames=("stacked",))
def _dots(a, b, stacked: bool):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    axes = tuple(range(1, a.ndim)) if stacked else None
    return (jnp.sum(a * b, axis=axes), jnp.sum(a * a, axis=axes),
            jnp.sum(b * b, axis=axes))


def cosines(dev: dict, host: dict, groups: dict) -> dict:
    """Per-leaf cosine of ``dev`` (device arrays) with ``host`` (host
    arrays of the same names), moving one host leaf over at a time; a
    leaf that is zero on either side reads 0."""
    vals = {}
    for k in dev:
        ab, aa, bb = jax.device_get(_dots(dev[k], jnp.asarray(host[k]),
                                          _group(k, groups) is not None))
        vals[k] = np.asarray(ab) / np.maximum(np.sqrt(np.asarray(aa)
                                                      * np.asarray(bb)),
                                              1e-30)
    return _named(vals, groups)
