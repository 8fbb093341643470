"""Plain float32 reference of the test architecture ``wide_first_mlp``,
its weights rebuilt leaf by leaf from the seed by `weights`."""
import jax
import jax.numpy as jnp
import numpy as np

import weights as W


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def logits(cfg, seed, seqs, n_last):
    key, dt, f32 = W.base_key(seed), jnp.dtype(cfg["dtype"]), jnp.float32
    blocks = range(W.n_vocab_blocks(cfg))
    emb = jnp.concatenate([W.embed_block(cfg, key, b, dt) for b in blocks]).astype(f32)
    head = jnp.concatenate([W.head_block(cfg, key, b, dt) for b in blocks], axis=1).astype(f32)
    layers = [jax.tree_util.tree_map(lambda a: a.astype(f32), W.layer(cfg, key, l, dt))
              for l in range(cfg["n_layers"])]
    out = []
    with jax.default_matmul_precision("highest"):
        for s in seqs:
            x = emb[jnp.asarray(s)]
            for lp in layers:
                h = _norm(x, cfg["norm_eps"]) * (1.0 + lp["norm"])
                x = x + jnp.square(jax.nn.relu(h @ lp["mlp"]["w_up"])) @ lp["mlp"]["w_down"]
            out.append(np.asarray(_norm(x[-n_last:], cfg["norm_eps"]) @ head))
    return out
