"""Pallas TPU selective-attention kernel (§III-C2b on TPU).

Computes attention for the R recomputed queries against keys restricted to
(heavy hitters ∪ causal sliding window ∪ recomputed tokens): the paper's
per-token mask becomes a *block-sparse* pattern — a (nq, nk) block liveness
map marks which (query-block, key-block) tiles can contribute; dead tiles
are skipped entirely (`@pl.when`), live tiles apply the fine-grained bitmap
in VREGs.  This is the TPU-native form of the CUDA selective mask: static
128×128 MXU tiles + predicated skip, instead of per-row divergence.

The liveness map is *data* (a kernel input), not trace-time control flow:
callers precompute it host-side with `block_liveness` from concrete
positions/mask and pass it in, which makes the whole wrapper jit-traceable
— the serving engine bakes the map per shape bucket and runs the kernel
inside its jitted selective-prefill step.  When `live` is omitted the
kernel computes it on the host (concrete inputs only, the pre-seam
behaviour).

Masks are per *mask row*: `q_positions`/`hh_mask`/`live` carry a leading
NB dim that divides the flattened BH batch·head dim, so one request's
masks are shared by its heads without materializing BH copies (NB=1 is
the fully-shared single-request case).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def block_liveness(q_positions, hh_mask, *, window: int,
                   q_block: int = 128, kv_block: int = 128) -> np.ndarray:
    """Host-side block-liveness map for `selective_attention`.

    q_positions: (R,) or (NB, R) int absolute query positions (pad = -1);
    hh_mask: (S,) or (NB, S) heavy-hitter/recomputed key bitmap.  Tile
    (qi, kj) is live iff any query in it can see any key in the tile
    (window hit, or any HH key causally visible).  -> (NB, nq, nk) int32.
    """
    qp = np.asarray(q_positions)
    hh = np.asarray(hh_mask)
    if qp.ndim == 1:
        qp = qp[None]
    if hh.ndim == 1:
        hh = hh[None]
    nb, r = qp.shape
    s_len = hh.shape[1]
    r_p = ((r + q_block - 1) // q_block) * q_block
    s_p = ((s_len + kv_block - 1) // kv_block) * kv_block
    qp = np.pad(qp.astype(np.int64), ((0, 0), (0, r_p - r)),
                constant_values=-1)
    hh = np.pad(hh.astype(np.int8), ((0, 0), (0, s_p - s_len)))
    nq, nk = r_p // q_block, s_p // kv_block
    live = np.zeros((nb, nq, nk), np.int32)
    for bi in range(nb):
        qpos_r = qp[bi].reshape(nq, q_block)
        hh_r = hh[bi].reshape(nk, kv_block)
        for qi in range(nq):
            qmax = int(qpos_r[qi].max())
            qmin_valid = qpos_r[qi][qpos_r[qi] >= 0]
            qmin = int(qmin_valid.min()) if len(qmin_valid) else -1
            if qmin < 0 and qmax < 0:
                continue
            for kj in range(nk):
                k_lo, k_hi = kj * kv_block, (kj + 1) * kv_block - 1
                if k_lo > qmax:
                    continue                         # fully acausal
                # window liveness: ∃ q∈[qmin,qmax], k∈[k_lo,k_hi] with
                # 0 ≤ q−k < window ⟺ [qmin−window+1, qmax] ∩ [k_lo, k_hi] ≠ ∅
                # (conservative superset for non-contiguous q positions)
                win_hit = k_hi > qmin - window and k_lo <= qmax
                hh_hit = bool(hh_r[kj].any())
                if win_hit or hh_hit:
                    live[bi, qi, kj] = 1
    return live


def _sel_kernel(live_ref, qpos_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, m_scr, l_scr, acc_scr,
                *, sm_scale: float, q_block: int, kv_block: int,
                window: int, kv_len: int, nb: int, bh: int):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nq = pl.num_programs(1)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the liveness map is scalar-prefetched into SMEM, flattened
    # (NB, nq, nk) -> one int per tile
    @pl.when(live_ref[((b * nb // bh) * nq + qi) * nk + ki] > 0)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        q_pos = qpos_ref[0]                                 # (q_block, 1)
        k_pos = ki * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, kv_block), 1)
        in_window = (q_pos >= k_pos) & (q_pos - k_pos < window)
        hh = mask_ref[0] > 0                    # (1, kv_block) heavy hitters
        causal = q_pos >= k_pos
        valid = (k_pos < kv_len) & causal & (in_window | hh)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def selective_attention(q: jax.Array, q_positions: jax.Array,
                        k: jax.Array, v: jax.Array, hh_mask: jax.Array, *,
                        live=None, window: int = 256, q_block: int = 128,
                        kv_block: int = 128,
                        interpret: bool = False) -> jax.Array:
    """q: (BH, R, D) recomputed queries; q_positions: (R,) or (NB, R)
    absolute positions; k, v: (BH, S, D) assembled keys; hh_mask: (S,) or
    (NB, S) int8 marking heavy-hitter/recomputed keys.  NB must divide BH
    (mask row b·NB/BH serves flattened row b).  Attend where causal AND
    (within `window` OR hh_mask).  `live`: optional precomputed
    (NB, nq, nk) block-liveness map (`block_liveness`); required for
    jit-traced calls, computed host-side when omitted.

    Layouts are chosen so every block is tile-legal for Mosaic: query
    positions ride as a (NB, R, 1) column (block (1, q_block, 1)), the
    heavy-hitter bitmap as a (NB, 1, S) row (block (1, 1, kv_block)),
    and the liveness map is scalar-prefetched.  On TPU q_block must be a
    multiple of 8 and kv_block of 128."""
    bh, r, d = q.shape
    s_len = k.shape[1]
    qp2 = q_positions if q_positions.ndim == 2 else q_positions[None]
    hh2 = hh_mask if hh_mask.ndim == 2 else hh_mask[None]
    nb = qp2.shape[0]
    if bh % nb or hh2.shape[0] != nb:
        raise ValueError(
            f"mask batch {nb}/{hh2.shape[0]} must divide BH={bh}")
    r_p = ((r + q_block - 1) // q_block) * q_block
    s_p = ((s_len + kv_block - 1) // kv_block) * kv_block
    q = jnp.pad(q, ((0, 0), (0, r_p - r), (0, 0)))
    qpos = jnp.pad(qp2.astype(jnp.int32), ((0, 0), (0, r_p - r)),
                   constant_values=-1)[:, :, None]
    k = jnp.pad(k, ((0, 0), (0, s_p - s_len), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, s_p - s_len), (0, 0)))
    hh = jnp.pad(hh2.astype(jnp.int32), ((0, 0), (0, s_p - s_len)))[:, None]
    nq, nk = r_p // q_block, s_p // kv_block

    if live is None:
        # host-side fallback: needs concrete positions/mask (the ops
        # wrapper raises a clear TypeError under tracing before this)
        live = block_liveness(np.asarray(qp2), np.asarray(hh2),
                              window=window, q_block=q_block,
                              kv_block=kv_block)
    live = jnp.asarray(live, jnp.int32)
    if live.ndim == 2:
        live = live[None]
    if live.shape != (nb, nq, nk):
        raise ValueError(
            f"liveness map {live.shape} != (NB, nq, nk) = {(nb, nq, nk)}")

    kernel = functools.partial(
        _sel_kernel, sm_scale=1.0 / d ** 0.5, q_block=q_block,
        kv_block=kv_block, window=window, kv_len=s_len, nb=nb, bh=bh)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, q_block, 1),
                         lambda b, qi, ki, lv: (b * nb // bh, qi, 0)),
            pl.BlockSpec((1, q_block, d), lambda b, qi, ki, lv: (b, qi, 0)),
            pl.BlockSpec((1, kv_block, d), lambda b, qi, ki, lv: (b, ki, 0)),
            pl.BlockSpec((1, kv_block, d), lambda b, qi, ki, lv: (b, ki, 0)),
            pl.BlockSpec((1, 1, kv_block),
                         lambda b, qi, ki, lv: (b * nb // bh, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, q_block, d),
                               lambda b, qi, ki, lv: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, r_p, d), q.dtype),
        interpret=interpret,
        name="selective_attention",
    )(live.reshape(-1), qpos, q, k, v, hh)
    return out[:, :r]
