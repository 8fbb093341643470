"""Pallas TPU flash-attention (prefill) kernel.

Tiling: grid (batch·kv_heads·groups, nq, nk) — the trailing kv axis is
sequential on TPU, so the (m, l, acc) running-softmax state lives in VMEM
scratch across kv steps.  Block shapes are MXU-aligned (q_block × d and
kv_block × d tiles, d a multiple of 128 for full MXU utilization; smaller
d still lowers, padded by Mosaic).

Two mask sources compose:

* ``causal`` — the static iota-based triangle (contiguous positions);
* ``kv_valid`` — an optional per-row key-liveness bitmap, the serving
  engine's ragged-batch mask (padded prompt tails, paged-decode slots
  past a request's length).  It rides in as a normal int32 kernel input
  laid out (NB, 1, Skv) and tiled (1, 1, kv_block) — the singleton
  sublane axis keeps the block's trailing dims tile-legal for Mosaic
  (kv_block a multiple of 128 on TPU) — with NB mask rows shared across
  each row's heads by BlockSpec index arithmetic, never materialized per
  head, so the wrapper stays jit-traceable end-to-end.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(*refs, causal: bool, sm_scale: float, q_block: int,
                  kv_block: int, kv_len: int, has_valid: bool):
    if has_valid:
        q_ref, k_ref, v_ref, valid_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                    # (q_block, d)
    k = k_ref[0]                                    # (kv_block, d)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    q_pos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                    (q_block, kv_block), 0)
    k_pos = ki * kv_block + jax.lax.broadcasted_iota(jnp.int32,
                                                     (q_block, kv_block), 1)
    mask = k_pos < kv_len
    if has_valid:
        mask &= valid_ref[0] > 0                    # (1, kv_block) row
    if causal:
        mask &= q_pos >= k_pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    kv_valid: jax.Array = None,
                    causal: bool = True, q_block: int = 128,
                    kv_block: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, D); k, v: (BH, Skv, D) — heads pre-flattened (GQA groups
    expanded by the ops wrapper).  `kv_valid`: optional (NB, Skv) bool/int
    key-liveness mask with NB dividing BH — mask row b·NB/BH serves
    flattened row b, so a per-request mask is shared by that request's
    heads without per-head copies.  On TPU, q_block must be a multiple of
    8 and kv_block of 128 (Mosaic's tile).  Returns (BH, Sq, D)."""
    bh, sq, d = q.shape
    skv = k.shape[1]
    sq_p = ((sq + q_block - 1) // q_block) * q_block
    skv_p = ((skv + kv_block - 1) // kv_block) * kv_block
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0)))
    nq = sq_p // q_block
    nk = skv_p // kv_block

    in_specs = [
        pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, kv_block, d), lambda b, qi, ki: (b, ki, 0)),
        pl.BlockSpec((1, kv_block, d), lambda b, qi, ki: (b, ki, 0)),
    ]
    args = [q, k, v]
    if kv_valid is not None:
        nb = kv_valid.shape[0]
        if bh % nb:
            raise ValueError(f"kv_valid batch {nb} must divide BH={bh}")
        kvv = jnp.pad(kv_valid.astype(jnp.int32),
                      ((0, 0), (0, skv_p - skv)))[:, None, :]
        in_specs.append(pl.BlockSpec((1, 1, kv_block),
                                     lambda b, qi, ki: (b * nb // bh, 0, ki)))
        args.append(kvv)

    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=1.0 / d ** 0.5,
        q_block=q_block, kv_block=kv_block, kv_len=skv,
        has_valid=kv_valid is not None)
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, q_block, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(*args)
    return out[:, :sq]
