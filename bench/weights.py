"""Weights the benchmark makes from the seed, on the device, in one call.

The program receives them as its parameter tree (the tree's shapes come
from ``jax.eval_shape`` of the program's initializer, no values), and the
plain reference reads the same arrays. Norm weights and biases are drawn
too, so a reference that left one out would disagree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORMS = {"ln1", "ln2", "ln", "final_norm", "q_norm", "k_norm", "latent_norm"}


def jax_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits and more."""
    s = int(seed)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, (s >> 32) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    for k in reversed(path):
        if hasattr(k, "key"):
            return str(k.key)
    return ""


def make_params(model_cfg, seed: int):
    """The parameter tree of ``model_cfg``, drawn from ``seed`` on the
    default device in one jitted call, in the type the program serves."""
    from repro.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(model_cfg, k), jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for (path, sd), k in zip(flat, keys):
            name = _leaf_name(path)
            n = jax.random.normal(k, sd.shape, jnp.float32)
            if name in NORMS:
                x = 1.0 + 0.1 * n
            elif name == "b":
                x = 0.02 * n
            elif name == "embed":
                x = 0.02 * n
            else:
                x = n / math.sqrt(sd.shape[-2])
            leaves.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jax_key(seed))


def count_params(params) -> int:
    return int(sum(x.size for x in jax.tree_util.tree_leaves(params)))
