"""JAX-facing wrapper for the fused paged-decode attention kernel.

`paged_decode_mha` takes the serving layout — (N, Hq, Dh) single-token
queries and the pool arenas — folds the GQA group axis into the query
tile (padded to `q_block` so tiny group factors still fill the MXU's
sublane dimension), and dispatches one kernel launch for one layer.
The layer index is static: the decode step's Python layer loop issues
one call per layer, and each call's BlockSpec index maps touch only
that layer's (page, Dh) planes of the referenced pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_decode_attention


@functools.partial(
    jax.jit, static_argnames=("layer", "rope_theta", "q_block", "interpret")
)
def paged_decode_mha(
    q: jax.Array,
    arena_k: jax.Array,
    arena_v: jax.Array,
    page_ids: jax.Array,
    slot_pos: jax.Array,
    *,
    layer: int,
    rope_theta: float = 10_000.0,
    q_block: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """q: (N, Hq, Dh) post-RoPE decode queries; arena_k/arena_v:
    (P, L, Hkv, page, Dh) paged pool; page_ids: (N, Pmax); slot_pos:
    (N, Pmax, page) logical position per slot or -1 (see
    `kv_pool.page_views`).  -> (N, Hq, Dh).
    """
    n, hq, d = q.shape
    hkv = arena_k.shape[2]
    g = hq // hkv
    if g * hkv != hq:
        raise ValueError(f"n_heads {hq} not divisible by n_kv_heads {hkv}")
    g_pad = -(-g // q_block) * q_block
    qg = q.reshape(n, hkv, g, d)
    if g_pad != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    out = paged_decode_attention(
        qg,
        arena_k,
        arena_v,
        page_ids,
        slot_pos,
        layer=layer,
        rope_theta=rope_theta,
        interpret=interpret,
    )
    return out[:, :, :g].reshape(n, hq, d)
