"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program is handed
them, and the plain reference reads the same arrays.  The tree has the
layout the program's ``abstract_params`` declares; each leaf is drawn by
the rule its family's reference module gives for the leaf's name
(``INIT``), in the type the model is served in.

Rules:
    ("embedding", std)          normal draws, rows at and past the
                                vocabulary zero, so padding ids score 0
    ("fan_in", n_in, n_out)     normal, std 1/sqrt(product of the n_in
                                dims before the last n_out)
    ("ones",) / ("zeros",)
    ("uniform", lo, hi)
    ("log_uniform", lo, hi)     log of a uniform draw (Mamba2's A_log)
    ("inv_softplus_log_uniform", lo, hi)
                                softplus^-1 of a log-uniform draw
                                (Mamba2's dt_bias)
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A JAX key from any whole number, however large."""
    import jax

    word = int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])
    return jax.random.key(word & 0x7FFFFFFF)


def _leaf(rule: Tuple, shape: Tuple[int, ...], key, dtype, vocab: int):
    import jax
    import jax.numpy as jnp

    kind = rule[0]
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "embedding":
        w = jax.random.normal(key, shape, jnp.float32) * rule[1]
        return jnp.where(jnp.arange(shape[0])[:, None] < vocab, w, 0.0).astype(dtype)
    if kind == "fan_in":
        n_in, n_out = rule[1], rule[2]
        fan_in = math.prod(shape[len(shape) - n_in - n_out:len(shape) - n_out])
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, rule[1], rule[2]).astype(dtype)
    if kind == "log_uniform":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, rule[1], rule[2])).astype(dtype)
    if kind == "inv_softplus_log_uniform":
        u = jax.random.uniform(key, shape, jnp.float32, math.log(rule[1]), math.log(rule[2]))
        dt = jnp.exp(u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    raise ValueError(f"unknown init rule {rule!r}")


def make(abstract: Any, init: Dict[str, Tuple], seed: int, dtype, vocab: int) -> Any:
    """The whole weight tree, from ``seed``, in one jitted call."""
    import jax

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [str(getattr(path[-1], "key", path[-1])) for path, _ in flat]
    missing = sorted(set(names) - set(init))
    if missing:
        raise KeyError(f"no init rule for weights {missing}")
    shapes = [tuple(leaf.shape) for _, leaf in flat]

    def build(key):
        keys = jax.random.split(key, len(shapes))
        leaves = [_leaf(init[n], s, k, dtype, vocab) for n, s, k in zip(names, shapes, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))
