"""Pure-jnp oracles for the fused paged-decode attention kernel.

Two layers of reference share ONE attention body:

* `masked_decode_attention_ref` — the GQA masked-softmax decode
  attention the serving gather path (`batch_engine._decode_attn`) calls
  directly.  Keeping the masking constant (`NEG_INF`) and the dtype
  discipline (fp32 scores, value-dtype probabilities) in this single
  helper is what guarantees the gather oracle and the paged oracle can
  never drift apart — `tests/test_kernel_properties.py` pins their
  bitwise equality.

* `paged_decode_ref` — the materializing counterpart of the Pallas
  paged kernel: gather the referenced physical pages, rotate keys to
  their logical positions (RoPE group property — cached keys are stored
  pre-RoPE), then run the shared attention body over the flattened
  (page, slot) axis.  Attention is permutation-invariant over keys, so
  physical-page order needs no unscramble back to logical order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.block_gather.ref import rope_rotate

# The one masking constant both decode oracles (and the Pallas kernels)
# share: large enough that exp underflows to exactly 0.0 in fp32, small
# enough not to overflow to -inf when scores are added to it.
NEG_INF = -1e30


def masked_decode_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, kv_valid: jax.Array
) -> jax.Array:
    """One-token-per-request GQA attention under a key-liveness mask.

    q: (N, Hq, Dh); k, v: (N, T, Hkv, Dh) with Hkv dividing Hq;
    kv_valid: (N, T) bool — dead keys (padding, slots past a request's
    length, unused page slots) are masked to `NEG_INF` *before* softmax.
    Scores accumulate in fp32; probabilities are cast to the value dtype
    for the weighted sum (the exact discipline `_decode_attn` has always
    used).  -> (N, Hq, Dh).
    """
    n, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = 1.0 / (d**0.5)
    qr = q.reshape(n, hkv, g, d)
    s = jnp.einsum("nhgd,nshd->nhgs", qr, k, preferred_element_type=jnp.float32)
    s = jnp.where(kv_valid[:, None, None, :], s * scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhgs,nshd->nhgd", p.astype(v.dtype), v)
    return o.reshape(n, hq, d)


def paged_decode_ref(
    q: jax.Array,
    arena_k: jax.Array,
    arena_v: jax.Array,
    page_ids: jax.Array,
    slot_pos: jax.Array,
    *,
    layer: int,
    rope_theta: float,
) -> jax.Array:
    """Materializing oracle for `paged_attention.kernel`.

    q: (N, Hq, Dh) post-RoPE single-token queries;
    arena_k/arena_v: (P, L, Hkv, page, Dh) paged pool (keys pre-RoPE);
    page_ids: (N, Pmax) physical page per referenced page-view column;
    slot_pos: (N, Pmax, page) logical position served by each slot of
    the referenced page, or -1 for slots holding no live token of the
    row.  -> (N, Hq, Dh).
    """
    n, pmax = page_ids.shape
    hkv, page, d = arena_k.shape[2:]
    flat = page_ids.reshape(-1)
    # (N*Pmax, Hkv, page, Dh) -> (N, Pmax*page, Hkv, Dh)
    kg = jnp.take(arena_k[:, layer], flat, axis=0).transpose(0, 2, 1, 3)
    vg = jnp.take(arena_v[:, layer], flat, axis=0).transpose(0, 2, 1, 3)
    kg = kg.reshape(n, pmax * page, hkv, d)
    vg = vg.reshape(n, pmax * page, hkv, d)
    pos = slot_pos.reshape(n, pmax * page)
    kg = rope_rotate(kg, pos[:, :, None], rope_theta)
    return masked_decode_attention_ref(q, kg, vg, pos >= 0)
