"""Random weights from ``--seed``, in the program's parameter layout.

The benchmark makes the weights, not the program: `make_params` builds
the whole tree on the device in one jitted call, in the served dtype,
and the plain reference (`references/`) rebuilds any single leaf from
the same seed with the same functions, so the two sides share no array.

Every leaf is drawn from its own key, ``fold_in(base, leaf_id)``.  A
layer's leaves, with their shapes, scales and ids, are its
architecture's (`archs`: ``layer_leaves``), and so is how layers group
into stacked trees (``groups``).  The two vocabulary-sized matrices are
drawn in row blocks of `VOCAB_BLOCK`, each from its own key: the
reference can then make the embedding rows and the LM head one block at
a time, in memory it can afford.

Scales follow the program's own initialisation: N(0, 1) embeddings,
fan-in scaled projections, zero RMSNorm offsets (the norm multiplies by
1 + scale).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import archs

VOCAB_BLOCK = 32768


def base_key(seed: int):
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)).astype(dtype)


def layer(cfg: dict, key, l: int, dtype) -> dict:
    """Layer `l`'s weights, as its architecture lays them out
    (``layer_leaves``): leaf `i` from ``fold_in(fold_in(key, 1000 + l), i)``."""
    lk = jax.random.fold_in(key, 1000 + l)
    out: dict = {}
    for path, shape, std, leaf_id in archs.of(cfg).layer_leaves(cfg, l):
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = (jnp.zeros(shape, dtype) if std == 0 else
                          _normal(jax.random.fold_in(lk, leaf_id), shape, std, dtype))
    return out


def embed_block(cfg: dict, key, b: int, dtype):
    """Rows [b*VOCAB_BLOCK, ...) of the (V, D) embedding."""
    V, D = cfg["vocab_size"], cfg["d_model"]
    rows = min(VOCAB_BLOCK, V - b * VOCAB_BLOCK)
    return _normal(jax.random.fold_in(jax.random.fold_in(key, 1), b), (rows, D), 1.0, dtype)


def head_block(cfg: dict, key, b: int, dtype):
    """Columns [b*VOCAB_BLOCK, ...) of the (D, V) LM head."""
    V, D = cfg["vocab_size"], cfg["d_model"]
    cols = min(VOCAB_BLOCK, V - b * VOCAB_BLOCK)
    return _normal(jax.random.fold_in(jax.random.fold_in(key, 2), b), (D, cols), D ** -0.5, dtype)


def n_vocab_blocks(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // VOCAB_BLOCK)


def make_params(cfg: dict, seed: int, dtype=None):
    """The whole parameter tree on the default device, in one jitted call.
    The layers of each of the architecture's groups are stacked on a
    leading axis under the group's key, as the program stores them."""
    dtype = jnp.dtype(dtype or cfg["dtype"])

    def build(key):
        nb = n_vocab_blocks(cfg)
        tree = {
            "embed": jnp.concatenate([embed_block(cfg, key, b, dtype) for b in range(nb)]),
            "final_norm": jnp.zeros((cfg["d_model"],), dtype),
            "lm_head": jnp.concatenate([head_block(cfg, key, b, dtype) for b in range(nb)], axis=1),
        }
        for name, ls in archs.of(cfg).groups(cfg):
            layers = [layer(cfg, key, l, dtype) for l in ls]
            tree[name] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
        return tree

    return jax.jit(build)(base_key(seed))
