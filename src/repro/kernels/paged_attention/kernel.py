"""Pallas TPU fused paged-decode attention kernel.

Decode's steady state is one query token per request attending over that
request's whole paged KV — ROADMAP open item 1.  The jnp gather path
materializes every request's K/V as an (N, S, L, Hkv, Dh) tensor first;
this kernel never does: the per-request **page view** (`kv_pool.
page_views`) is scalar-prefetched, so the BlockSpec index map reads each
referenced physical page of the arena directly — the indirection happens
in the DMA descriptor, not as a gather in HBM.

Tiling: grid (N, Hkv, Pmax) with the trailing page axis sequential on
TPU, so the (m, l, acc) running-softmax state lives in VMEM scratch
across a row's pages — the flash recurrence, one KV tile per physical
page.  The arena is laid out (P, L, Hkv, page, Dh), so one grid step
reads a (page, Dh) tile of one layer's one kv head: the block's two
trailing dims are the array's own, which Mosaic's tiling accepts for
any page size and head dim.  GQA folds the `n_heads // n_kv_heads`
group axis into the query block: queries arrive as (N, Hkv, G_pad, Dh),
so each KV head's pages stream through VMEM exactly once per request
while all of its grouped query heads ride in the same q tile.

Per-slot `slot_pos` carries each arena slot's *logical* position
(-1 = slot holds no live token of this row): it is simultaneously the
key-liveness mask (ragged lengths, pad slots, interleaved store/private
slots at arbitrary alignment) and the RoPE realignment angle — keys are
stored pre-RoPE, so the kernel fuses the one rotation decode needs
(group property) right before the dot product.  It enters twice, as a
(page, 1) column that drives the rotation and a (1, page) row that
masks the scores, so neither needs an in-kernel transpose.  Causality
never needs checking: the newest token is, by construction, the
largest live position in its row, so key-liveness IS the causal mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.paged_attention.ref import NEG_INF


def _paged_decode_kernel(
    pids_ref,
    plive_ref,
    freq_ref,
    pcol_ref,
    prow_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_scr,
    l_scr,
    acc_scr,
    *,
    sm_scale: float,
    head_dim: int,
):
    i = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pad pages (view columns with no live slot of this row) skip their
    # rotate+matmul entirely.  Skipped blocks leave (m, l, acc)
    # untouched, which the flash recurrence is already exact under — a
    # masked-out block contributes corr=1, p=0.
    @pl.when(plive_ref[i * nj + j] > 0)
    def _attend():
        q = q_ref[0, 0]  # (g_pad, d)
        k = k_ref[0, 0, 0].astype(jnp.float32)  # (page, d) pre-RoPE
        v = v_ref[0, 0, 0]
        # RoPE as k*cos + rotate_half(k)*sin: the half-swap is a lane
        # rotation by d/2, and `freq_ref` holds the per-lane frequency
        # (the d/2 frequencies, repeated for both halves)
        ang = pcol_ref[0, 0].astype(jnp.float32) * freq_ref[...]  # (page, d)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, head_dim), 1)
        sign = jnp.where(lane < head_dim // 2, -1.0, 1.0).astype(jnp.float32)
        k_swap = pltpu.roll(k, head_dim // 2, 1)
        k = k * jnp.cos(ang) + k_swap * jnp.sin(ang) * sign
        s = jax.lax.dot_general(
            q,
            k.astype(q.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        live = prow_ref[0, 0] >= 0  # (1, page)
        s = jnp.where(live, s * sm_scale, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == nj - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    arena_k: jax.Array,
    arena_v: jax.Array,
    page_ids: jax.Array,
    slot_pos: jax.Array,
    *,
    layer: int,
    rope_theta: float = 10_000.0,
    interpret: bool = False,
) -> jax.Array:
    """q: (N, Hkv, G_pad, Dh) post-RoPE queries, group axis pre-padded by
    the ops wrapper; arena_k/arena_v: (P, L, Hkv, page, Dh) paged pool
    (keys pre-RoPE); page_ids: (N, Pmax) int32 physical page per view
    column; slot_pos: (N, Pmax, page) int32 logical position per slot or
    -1.  `layer` is static — one pallas_call per layer reads only that
    layer's (page, Dh) planes of each referenced page.
    -> (N, Hkv, G_pad, Dh).
    """
    n, hkv, g_pad, d = q.shape
    page = arena_k.shape[3]
    pmax = page_ids.shape[1]
    half = d // 2
    # the same frequencies as `block_gather.ref.rope_rotate`, per lane
    freqs = 1.0 / (rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    freq_row = jnp.concatenate([freqs, freqs])[None]  # (1, d)
    slot_pos = slot_pos.astype(jnp.int32)
    page_live = (slot_pos >= 0).any(axis=-1).astype(jnp.int32).reshape(-1)

    kernel = functools.partial(
        _paged_decode_kernel,
        sm_scale=1.0 / d**0.5,
        head_dim=d,
    )
    arena_spec = pl.BlockSpec(
        (1, 1, 1, page, d),
        lambda i, h, j, pids, live: (pids[i, j], layer, h, 0, 0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, hkv, pmax),
        in_specs=[
            pl.BlockSpec((1, d), lambda i, h, j, pids, live: (0, 0)),
            pl.BlockSpec((1, 1, page, 1), lambda i, h, j, pids, live: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, page), lambda i, h, j, pids, live: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, g_pad, d), lambda i, h, j, pids, live: (i, h, 0, 0)),
            arena_spec,
            arena_spec,
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g_pad, d), lambda i, h, j, pids, live: (i, h, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g_pad,), jnp.float32),
            pltpu.VMEM((g_pad,), jnp.float32),
            pltpu.VMEM((g_pad, d), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, hkv, g_pad, d), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )
    return fn(
        page_ids.astype(jnp.int32),
        page_live,
        freq_row,
        slot_pos[..., None],
        slot_pos[:, :, None, :],
        q,
        arena_k,
        arena_v,
    )
