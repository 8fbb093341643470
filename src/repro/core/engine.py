"""RcLLM local execution engine (§III-C2b, §III-C3) — the accuracy prototype.

Runs a real JAX transformer whose attention is modified for beyond-prefix
reuse:  layer 0 computes full attention for every token (cheap: 1/L of the
FLOPs) and scores tokens with Eq. 3

    S_i = (1−λ)·‖A_i‖₁ + λ·Σ_{M∈{K,V}} ‖M_i^new − M_i^cached‖₁

Heavy hitters, instruction tokens, instance-specific markers, cache misses
and the trailing local window are recomputed exactly through layers 1..L−1;
every other token's deeper-layer K/V comes from the assembled cache blocks
(pre-RoPE, rotated to the request position — exact positional realignment
by RoPE's group property).  This mirrors the paper's HuggingFace prototype,
in JAX.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig
from repro.core.assembly import FROM_ITEM, FROM_SEMANTIC, AssemblyPlan
from repro.kernels import default_interpret
from repro.kernels.flash_attention.ops import mha_flash
from repro.kernels.selective_attention.ops import (build_block_liveness,
                                                  selective_mha)
from repro.models import layers as L

# Pallas tile sizes for the serving-path kernels: MXU-shaped, and the
# smallest the TPU accepts for the key-mask blocks, whose key axis is
# the lane axis (a multiple of 128).  The kernels pad the engine's
# 64-multiple shape buckets up to these tiles.
PALLAS_Q_BLOCK = 128
PALLAS_KV_BLOCK = 128


def decode_uses_paged(cfg: LMConfig) -> bool:
    """Resolve `cfg.decode_kernel` for the serving decode step: does it
    read K/V through the fused paged-attention kernel (True) or the jnp
    arena gather (False)?  "auto" ties the choice to the attention
    backend — pallas decodes paged, jnp keeps the gather path as the
    bitwise oracle; "paged"/"gather" pin either path explicitly (the
    parity tests run the kernel under the jnp backend this way, so a
    decode-only diff can't hide behind prefill differences)."""
    if cfg.decode_kernel == "paged":
        return True
    if cfg.decode_kernel == "gather":
        return False
    if cfg.decode_kernel != "auto":
        raise ValueError(
            f"decode_kernel={cfg.decode_kernel!r}: want auto|gather|paged")
    return cfg.attn_backend == "pallas"

# Placeholder liveness map for the jnp backend: the jitted selective
# entry points take `live` positionally so the pallas/jnp traces share
# one signature; the jnp trace never reads it.
_NO_LIVE = np.zeros((1, 1, 1), np.int32)


@dataclass
class SelectiveConfig:
    r_item: float = 0.3               # recompute budget over item tokens
    r_rev: float = 0.3                # recompute budget over history tokens
    lam: float = 0.5                  # Eq. 3 λ (divergence weight)
    window: int = 32                  # trailing local window, always exact
    layer0_full: bool = True          # identify HH with full first layer


# ---------------------------------------------------------------------------
# Shared per-request building blocks.  `serving/batch_engine.py` reuses these
# (and the jitted entry points below) rather than duplicating the math, so
# the single-request and batched paths cannot drift apart.
# ---------------------------------------------------------------------------

def layer_params(params, l: int):
    return jax.tree_util.tree_map(lambda a: a[l], params["layers"])


def qkv_proj(h, lp, cfg: LMConfig, positions):
    """h: (S, D) -> rotated (q, k), pre-RoPE k_raw, and v: (S, H, Dh)."""
    q = jnp.einsum("sd,dhe->she", h, lp["wq"])
    k_raw = jnp.einsum("sd,dhe->she", h, lp["wk"])
    v = jnp.einsum("sd,dhe->she", h, lp["wv"])
    q = L.apply_rope(q[None], positions, cfg.rope_theta)[0]
    k = L.apply_rope(k_raw[None], positions, cfg.rope_theta)[0]
    return q, k, k_raw, v


def full_attn(q, k, v, cfg: LMConfig, q_pos, k_pos, return_probs=False,
              k_valid=None, contiguous=False):
    """Single-request attention: q (Sq, Hq, Dh) vs k/v (Sk, Hkv, Dh).

    `cfg.attn_backend` picks the implementation.  The pallas route
    (flash kernel) needs `contiguous=True` — the caller's assertion that
    q_pos/k_pos are the standard aranges, which the kernel's iota-based
    causal mask assumes — and cannot return probabilities (flash never
    materializes P), so Eq. 3 layer-0 scoring always takes the jnp path.
    """
    if cfg.attn_backend == "pallas" and contiguous and not return_probs:
        kv_valid = None if k_valid is None else k_valid[None]
        o = mha_flash(q[None], k[None], v[None], kv_valid=kv_valid,
                      causal=True, q_block=PALLAS_Q_BLOCK,
                      kv_block=PALLAS_KV_BLOCK,
                      interpret=default_interpret())[0]
        return o
    Hq, Hkv = q.shape[1], k.shape[1]
    G = Hq // Hkv
    scale = 1.0 / (q.shape[-1] ** 0.5)
    qr = q.reshape(q.shape[0], Hkv, G, -1)
    s = jnp.einsum("qhgd,khd->hgqk", qr, k,
                   preferred_element_type=jnp.float32) * scale
    mask = q_pos[:, None] >= k_pos[None, :]
    if k_valid is not None:
        mask = mask & k_valid[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p.astype(v.dtype), v)
    o = o.reshape(q.shape[0], Hq, -1)
    if return_probs:
        return o, p
    return o


def full_attn_batched(q, k, v, cfg: LMConfig, q_pos, k_pos,
                      return_probs=False, k_valid=None):
    """Batched jnp attention: q (B, Sq, Hq, Dh) vs k/v (B, Sk, Hkv, Dh).

    q_pos/k_pos: (Sq,)/(Sk,) shared or (B, Sq)/(B, Sk) per row;
    k_valid: optional (B, Sk) bool.  The jnp reference for the batched
    selective path (the pallas route goes through `selective_mha`).
    """
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    Hq, Hkv = q.shape[2], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / (q.shape[-1] ** 0.5)
    qr = q.reshape(B, Sq, Hkv, G, -1)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qr, k,
                   preferred_element_type=jnp.float32) * scale
    qp = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(q_pos[None], (B, Sq))
    kp = k_pos if k_pos.ndim == 2 else jnp.broadcast_to(k_pos[None], (B, Sk))
    mask = qp[:, :, None] >= kp[:, None, :]                 # (B, Sq, Sk)
    if k_valid is not None:
        mask = mask & k_valid[:, None, :]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    o = o.reshape(B, Sq, Hq, -1)
    if return_probs:
        return o, p
    return o


def mlp_block(h, lp, cfg: LMConfig):
    """Dense/MoE MLP over a flat (T, D) or (S, D) token matrix."""
    from repro.models.layers import mlp_apply, moe_apply
    if cfg.moe is not None:
        y, _ = moe_apply(h, lp["moe"], n_experts=cfg.moe.n_experts,
                         top_k=cfg.moe.top_k,
                         capacity_factor=cfg.moe.capacity_factor,
                         mlp_type=cfg.mlp_type)
        return y
    return mlp_apply(h, lp["mlp"], cfg.mlp_type)


# Backward-compatible aliases (baselines.py and older call sites).
_layer_params = layer_params
_qkv = qkv_proj
_full_attn = full_attn
_mlp = mlp_block


def _batched_forward(params, toks, valid, cfg: LMConfig):
    """Shared padded (N, S) forward pass.

    -> (x, k_all, v_all): the final residual stream (N, S, D) plus the
    pre-RoPE per-layer caches (N, S, L, Hkv, Dh).  Invalid (padding) keys
    are masked out of the in-context attention via `valid` (N, S) bool.
    """
    N, S = toks.shape
    pos = jnp.arange(S)
    x = params["embed"][toks].astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:
        x = x * (cfg.d_model ** 0.5)
    ks, vs = [], []
    for l in range(cfg.n_layers):
        lp = layer_params(params, l)
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = jnp.einsum("nsd,dhe->nshe", h, lp["wq"])
        k_raw = jnp.einsum("nsd,dhe->nshe", h, lp["wk"])
        v = jnp.einsum("nsd,dhe->nshe", h, lp["wv"])
        ks.append(k_raw)
        vs.append(v)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k_raw, pos, cfg.rope_theta)
        if cfg.attn_backend == "pallas":
            o = mha_flash(q, k, v, kv_valid=valid, causal=True,
                          q_block=PALLAS_Q_BLOCK, kv_block=PALLAS_KV_BLOCK,
                          interpret=default_interpret())
        else:
            o = L.chunked_attention(q, k, v, causal=True, q_positions=pos,
                                    kv_positions=pos, kv_valid=valid,
                                    q_chunk=min(cfg.attn_q_chunk, S),
                                    kv_chunk=min(cfg.attn_kv_chunk, S))
        x = x + jnp.einsum("nshe,hed->nsd", o, lp["wo"])
        x = x + mlp_block_batched(L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps),
                                  lp, cfg)
    k_all = jnp.stack(ks, axis=2)                          # (N, S, L, Hkv, Dh)
    v_all = jnp.stack(vs, axis=2)
    return x, k_all, v_all


@functools.partial(jax.jit, static_argnums=(2,))
def _batched_kv_jit(params, toks, cfg: LMConfig):
    """toks: (N, S) padded with PAD=0 → pre-RoPE (k, v): (N, S, L, Hkv, Dh).
    Padding keys are masked out of the in-context attention."""
    _, k_all, v_all = _batched_forward(params, toks, toks != 0, cfg)
    return k_all, v_all


@functools.partial(jax.jit, static_argnums=(3,))
def _jit_batched_prefill(params, toks, last_idx, cfg: LMConfig):
    """Padded multi-request full prefill for the batched serving engine.

    toks: (N, S) padded; last_idx: (N,) index of each request's final real
    token.  -> (logits (N, V), pre-RoPE k, v (N, S, L, Hkv, Dh)).
    """
    N, S = toks.shape
    valid = jnp.arange(S)[None, :] <= last_idx[:, None]
    x, k_all, v_all = _batched_forward(params, toks, valid, cfg)
    x_last = x[jnp.arange(N), last_idx]                    # (N, D)
    xf = L.rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return xf @ head, k_all, v_all


def mlp_block_batched(h, lp, cfg: LMConfig):
    if cfg.moe is not None:
        N, S, D = h.shape
        y, _ = L.moe_apply(h.reshape(N * S, D), lp["moe"],
                           n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
                           capacity_factor=cfg.moe.capacity_factor,
                           mlp_type=cfg.mlp_type)
        return y.reshape(N, S, D)
    return L.mlp_apply(h, lp["mlp"], cfg.mlp_type)


_mlp_batched = mlp_block_batched


def precompute_kv_batch(params, cfg: LMConfig, docs, bucket: int = 64):
    """Batched offline KV materialization with length bucketing (keeps jit
    retraces bounded).  -> list of (S_i, L, Hkv, Dh) pre-RoPE (k, v)."""
    order = np.argsort([len(d) for d in docs])
    out = [None] * len(docs)
    i = 0
    while i < len(order):
        max_len = ((len(docs[order[i]]) + bucket - 1) // bucket) * bucket
        group = [j for j in order[i:i + 64]
                 if len(docs[j]) <= max_len]
        batch = np.zeros((len(group), max_len), np.int32)
        for gi, j in enumerate(group):
            batch[gi, :len(docs[j])] = docs[j]
        k, v = _batched_kv_jit(params, jnp.asarray(batch), cfg)
        k = np.asarray(k, np.float32)
        v = np.asarray(v, np.float32)
        for gi, j in enumerate(group):
            s = len(docs[j])
            out[j] = (k[gi, :s], v[gi, :s])
        i += len(group)
    return out


def precompute_kv(params, cfg: LMConfig, tokens: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Offline KV materialization: run the model over one sequence at
    canonical positions and return PRE-RoPE per-layer K and V:
    (S, n_layers, Hkv, Dh).  Used to build both cache pools."""
    toks = jnp.asarray(tokens)
    S = toks.shape[0]
    pos = jnp.arange(S)
    x = params["embed"][toks].astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:
        x = x * (cfg.d_model ** 0.5)
    ks, vs = [], []
    for l in range(cfg.n_layers):
        lp = _layer_params(params, l)
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, k_raw, v = _qkv(h, lp, cfg, pos)
        ks.append(np.asarray(k_raw, np.float32))
        vs.append(np.asarray(v, np.float32))
        o = _full_attn(q, k, v, cfg, pos, pos, contiguous=True)
        x = x + jnp.einsum("she,hed->sd", o, lp["wo"])
        x = x + _mlp(L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp, cfg)
    k_all = np.stack(ks, axis=1)
    v_all = np.stack(vs, axis=1)
    return k_all, v_all


@functools.partial(jax.jit, static_argnums=(3,))
def _jit_full_prefill(params, toks, last, cfg: LMConfig):
    from repro.models import transformer as T
    logits, _ = T.forward(params, toks[None], cfg)
    return logits[0, last]


def full_prefill_logits(params, cfg: LMConfig, tokens: np.ndarray,
                        bucket: int = 128) -> np.ndarray:
    """Full-Recompute oracle: exact final-position logits (padded + jitted;
    padding is causally invisible to the final real token)."""
    n = len(tokens)
    n_pad = ((n + bucket - 1) // bucket) * bucket
    toks = np.pad(np.asarray(tokens, np.int32), (0, n_pad - n))
    logits = _jit_full_prefill(params, jnp.asarray(toks), n - 1, cfg)
    return np.asarray(logits, np.float32)


@dataclass
class EngineStats:
    n_tokens: int
    n_recomputed: int
    n_reused_item: int
    n_reused_semantic: int
    n_heavy_hitters: int
    layer0_full: bool
    # (n,) bool — which tokens went through layers 1..L-1 exactly; the
    # serving path uses it to scatter fresh KV over the paged pool.
    recompute_mask: Optional[np.ndarray] = None

    def recompute_fraction(self) -> float:
        return self.n_recomputed / max(self.n_tokens, 1)


def _pad_to(x: np.ndarray, n: int, fill=0):
    if len(x) >= n:
        return x[:n]
    return np.concatenate([x, np.full((n - len(x),) + x.shape[1:], fill,
                                      x.dtype)])


# Eq. 3 divergence at most this share of the cached row's L1 norm is
# rounding, not a different key.  A reused token's layer-0 K/V is
# context-free, so it matches its cache exactly on one device; a sharded
# program sums the same row in another order and lands a few ulps off.
# `select_recompute` scales divergence by its max, which would turn those
# ulps into a full-scale score and let them pick the recompute set.
DIV_RTOL = 1e-2


def _divergence(k_raw, ck0, v, cv0):
    """Per-token Eq. 3 divergence |k - k̂|₁ + |v - v̂|₁ over (T, Hkv, Dh)
    rows, zero where it is within `DIV_RTOL` of rounding."""
    div = (jnp.abs(k_raw - ck0).sum(axis=(1, 2))
           + jnp.abs(v - cv0).sum(axis=(1, 2)))
    scale = jnp.abs(ck0).sum(axis=(1, 2)) + jnp.abs(cv0).sum(axis=(1, 2))
    return jnp.where(div <= DIV_RTOL * scale, 0.0, div)


def _layer0_impl(params, toks, valid, ck0, cv0, cfg: LMConfig):
    n = toks.shape[0]
    pos = jnp.arange(n)
    x = params["embed"][toks].astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:
        x = x * (cfg.d_model ** 0.5)
    lp = layer_params(params, 0)
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, k_raw, v = qkv_proj(h, lp, cfg, pos)
    o, probs = full_attn(q, k, v, cfg, pos, pos, return_probs=True,
                         k_valid=valid)
    # A_i: attention mass received by key i from *valid* queries
    attn_mass = (probs * valid[None, None, :, None]).mean(axis=(0, 1)).sum(axis=0)
    x = x + jnp.einsum("she,hed->sd", o, lp["wo"])
    x = x + mlp_block(L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp, cfg)
    return x, attn_mass, _divergence(k_raw, ck0, v, cv0), k_raw, v


@functools.partial(jax.jit, static_argnums=(5,))
def _jit_layer0(params, toks, valid, ck0, cv0, cfg: LMConfig):
    """Layer-0 full pass (padded): -> (x_after_l0, attn_mass, divergence)."""
    x, attn_mass, div, _, _ = _layer0_impl(params, toks, valid, ck0, cv0, cfg)
    return x, attn_mass, div


@functools.partial(jax.jit, static_argnums=(5,))
def _jit_layer0_kv(params, toks, valid, ck0, cv0, cfg: LMConfig):
    """Layer-0 full pass that also returns the fresh pre-RoPE (k, v) —
    the serving path stores them in the paged KV pool for decode."""
    return _layer0_impl(params, toks, valid, ck0, cv0, cfg)




def _sel_attn(qr, k_l, v_l, cfg: LMConfig, r_pos, pos, valid, live):
    """One selective-layer attention: recomputed queries vs assembled keys.

    Backend seam: jnp runs the batched masked-softmax reference; pallas
    runs `selective_mha` with every valid key marked attendable (window
    0 + hh = the key-validity mask ⇒ causal attention over valid keys,
    exactly the reference's mask) and the precomputed block-liveness map
    `live`, which keeps the wrapper jit-traceable.
    qr: (B, R, Hq, Dh); k_l/v_l: (B, S, Hkv, Dh); r_pos: (B, R);
    valid: (B, S) bool; live: (B, nq, nk) int32 (unused under jnp).
    """
    if cfg.attn_backend == "pallas":
        return selective_mha(qr, r_pos, k_l, v_l, valid.astype(jnp.int8),
                             live=live, window=0, q_block=PALLAS_Q_BLOCK,
                             kv_block=PALLAS_KV_BLOCK,
                             interpret=default_interpret())
    return full_attn_batched(qr, k_l, v_l, cfg, r_pos, pos, k_valid=valid)


def _selective_layers_impl(params, x, r_idx, r_valid, ck, cv, valid,
                           key_rot_pos, final_slot, cfg: LMConfig,
                           live, collect_kv: bool):
    """Batched layers 1..L-1 over the recompute sets.

    x: (B, n, D); r_idx/r_valid: (B, R); ck/cv: (B, n, L, Hkv, Dh);
    valid: (B, n); key_rot_pos: (n,) shared or (B, n); final_slot: (B,).
    -> logits (B, V) [+ merged pre-RoPE (k, v): (B, n, L-1, Hkv, Dh)].
    """
    B, n, _ = x.shape
    pos = jnp.arange(n)
    rows = jnp.arange(B)
    r_pos = jnp.clip(r_idx, 0, n - 1)                          # (B, R)
    xr = jnp.take_along_axis(x, r_pos[..., None], axis=1)      # (B, R, D)
    ks, vs = [], []
    for l in range(1, cfg.n_layers):
        lp = layer_params(params, l)
        hr = L.rms_norm(xr, lp["attn_norm"], cfg.norm_eps)
        qr = jnp.einsum("brd,dhe->brhe", hr, lp["wq"])
        kr_raw = jnp.einsum("brd,dhe->brhe", hr, lp["wk"])
        vr = jnp.einsum("brd,dhe->brhe", hr, lp["wv"])
        qr = L.apply_rope(qr, r_pos, cfg.rope_theta)
        kr = L.apply_rope(kr_raw, r_pos, cfg.rope_theta)
        # assembled keys: cached pre-RoPE keys rotated per key_rot_pos
        k_l = L.apply_rope(ck[:, :, l], key_rot_pos, cfg.rope_theta)
        v_l = cv[:, :, l]
        widx = jnp.where(r_valid, r_idx, n)                    # n → dropped
        k_l = k_l.at[rows[:, None], widx].set(kr, mode="drop")
        v_l = v_l.at[rows[:, None], widx].set(vr.astype(v_l.dtype),
                                              mode="drop")
        if collect_kv:
            # merged pre-RoPE cache: cached blocks + fresh recomputed keys
            ks.append(ck[:, :, l].at[rows[:, None], widx].set(kr_raw,
                                                              mode="drop"))
            vs.append(v_l)
        o = _sel_attn(qr, k_l, v_l.astype(kr.dtype), cfg, r_pos, pos,
                      valid, live)
        xr = xr + jnp.einsum("brhe,hed->brd", o, lp["wo"])
        xr = xr + mlp_block_batched(
            L.rms_norm(xr, lp["mlp_norm"], cfg.norm_eps), lp, cfg)

    xf = L.rms_norm(xr[rows, final_slot], params["final_norm"],
                    cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = xf @ head                                         # (B, V)
    if collect_kv:
        return logits, jnp.stack(ks, axis=2), jnp.stack(vs, axis=2)
    return logits


@functools.partial(jax.jit, static_argnums=(9,))
def _jit_selective_layers(params, x, r_idx, r_valid, ck, cv, valid,
                          key_rot_pos, final_slot, cfg: LMConfig,
                          live=_NO_LIVE):
    """Layers 1..L-1 computed only for the (padded) recompute sets; final
    logits at the recompute slot `final_slot` (each prompt's last token).
    `key_rot_pos` rotates cached pre-RoPE keys (RcLLM: the request position
    = exact realignment; CacheBlend baseline: the block's original position).
    All array args carry a leading batch dim — the single-request path is
    the B=1 special case."""
    return _selective_layers_impl(params, x, r_idx, r_valid, ck, cv, valid,
                                  key_rot_pos, final_slot, cfg, live,
                                  collect_kv=False)


@functools.partial(jax.jit, static_argnums=(9,))
def _jit_selective_layers_kv(params, x, r_idx, r_valid, ck, cv, valid,
                             key_rot_pos, final_slot, cfg: LMConfig,
                             live=_NO_LIVE):
    """As `_jit_selective_layers`, but also returns the merged pre-RoPE
    (k, v) for layers 1..L-1: (B, n, L-1, Hkv, Dh) — cached blocks with
    the recomputed tokens' fresh keys scattered in."""
    return _selective_layers_impl(params, x, r_idx, r_valid, ck, cv, valid,
                                  key_rot_pos, final_slot, cfg, live,
                                  collect_kv=True)


def _liveness_for(cfg: LMConfig, r_idx_p: np.ndarray, valid: np.ndarray
                  ) -> np.ndarray:
    """Host-side block-liveness for the selective pallas route.

    r_idx_p: (B, R) padded recompute indices; valid: (B, n) key-validity.
    Under the jnp backend returns the shared placeholder (the trace never
    reads it), so both backends call the jitted entry points identically.
    """
    if cfg.attn_backend != "pallas":
        return _NO_LIVE
    n = valid.shape[1]
    r_pos = np.clip(np.asarray(r_idx_p, np.int64), 0, n - 1)
    return build_block_liveness(r_pos, valid.astype(np.int8), window=0,
                                q_block=PALLAS_Q_BLOCK,
                                kv_block=PALLAS_KV_BLOCK)


def run_selective_layers(params, cfg, x, recompute: np.ndarray,
                         ck, cv, n_valid: int, bucket: int = 64,
                         key_positions: Optional[np.ndarray] = None,
                         return_kv: bool = False):
    """Pad the recompute set + sequence, dispatch the jitted layer stack.

    Single-request wrapper over the batched (B=1) selective stack.  With
    ``return_kv`` the merged pre-RoPE caches for layers 1..L-1 come
    back too: -> (logits, k (n, L-1, Hkv, Dh), v) — the serving engine's
    source for paged-pool insertion.
    """
    n = x.shape[0]
    r_idx = np.where(recompute)[0]
    r_count = len(r_idx)
    r_pad = max(bucket, ((r_count + bucket - 1) // bucket) * bucket)
    r_valid = np.zeros(r_pad, bool)
    r_valid[:r_count] = True
    r_idx_p = _pad_to(r_idx.astype(np.int32), r_pad, fill=n_valid - 1)
    valid = np.zeros(n, bool)
    valid[:n_valid] = True
    if key_positions is None:
        key_positions = np.arange(n)
    else:
        key_positions = _pad_to(key_positions.astype(np.int64), n)
    final_slot = r_count - 1          # last recomputed token = prompt tail
    live = _liveness_for(cfg, r_idx_p[None], valid[None])
    args = (params, x[None], jnp.asarray(r_idx_p[None]),
            jnp.asarray(r_valid[None]), jnp.asarray(ck)[None],
            jnp.asarray(cv)[None], jnp.asarray(valid[None]),
            jnp.asarray(key_positions), jnp.asarray([final_slot]), cfg,
            jnp.asarray(live))
    if return_kv:
        logits, k_m, v_m = _jit_selective_layers_kv(*args)
        return (np.asarray(logits[0], np.float32),
                np.asarray(k_m[0], np.float32),
                np.asarray(v_m[0], np.float32))
    logits = _jit_selective_layers(*args)
    return np.asarray(logits[0], np.float32)


def selective_prefill_logits(
    params, cfg: LMConfig, plan: AssemblyPlan,
    cached_k: np.ndarray, cached_v: np.ndarray, have_cache: np.ndarray,
    sel: SelectiveConfig, bucket: int = 128,
) -> Tuple[np.ndarray, EngineStats]:
    """Beyond-prefix prefill with selective recomputation.

    cached_k/v: (n, n_layers, Hkv, Dh) pre-RoPE assembled blocks
    (zeros where RECOMPUTE / miss).  Sequences are padded to `bucket`
    multiples so the jitted engine retraces O(1) times.
    """
    logits, stats, _, _ = _selective_prefill(
        params, cfg, plan, cached_k, cached_v, have_cache, sel, bucket,
        return_kv=False)
    return logits, stats


def selective_prefill_with_kv(
    params, cfg: LMConfig, plan: AssemblyPlan,
    cached_k: np.ndarray, cached_v: np.ndarray, have_cache: np.ndarray,
    sel: SelectiveConfig, bucket: int = 128,
) -> Tuple[np.ndarray, EngineStats, np.ndarray, np.ndarray]:
    """Selective prefill that also materializes the request's full merged
    pre-RoPE KV cache (n, L, Hkv, Dh): layer 0 fresh, layers 1..L-1 cached
    blocks with recomputed tokens scattered in.  The batched serving engine
    writes this into the paged pool so decode can attend to the prompt.
    """
    return _selective_prefill(params, cfg, plan, cached_k, cached_v,
                              have_cache, sel, bucket, return_kv=True)


def select_recompute(plan: AssemblyPlan, have: np.ndarray,
                     attn_mass, div_raw, sel: SelectiveConfig
                     ) -> Tuple[np.ndarray, EngineStats]:
    """Eq. 3 scoring + heavy-hitter selection under per-class budgets.

    attn_mass/div_raw: layer-0 outputs (padded; only [:n] is read).
    Shared by the single-request and batched selective prefills, so the
    two paths cannot drift on *which* tokens they recompute.
    -> (recompute mask (n,), EngineStats).
    """
    n = plan.n
    attn_mass = np.asarray(attn_mass)[:n]
    a_norm = attn_mass / max(attn_mass.max(), 1e-9)
    div = np.asarray(div_raw)[:n] * have.astype(np.float32)
    div = div / max(div.max(), 1e-9)
    s_score = (1.0 - sel.lam) * a_norm + sel.lam * div              # Eq. 3

    src = plan.source
    recompute = ~have.copy()                                 # misses
    # instructions: always recomputed — unless their exact KV is already
    # cached (`have`), which only the serving block store's prefix tier
    # sets (its bytes ARE the recomputed rows, so skipping is lossless;
    # offline flows never mark seg0 tokens as cached)
    recompute |= (plan.seg_kind == 0) & ~have
    recompute[max(0, n - sel.window):] = True                # local window
    n_hh = 0
    for kind, budget in ((2, sel.r_item), (1, sel.r_rev)):
        cls = np.where((plan.seg_kind == kind) & ~recompute)[0]
        if len(cls) == 0:
            continue
        k_top = int(np.ceil(budget * len(cls)))
        top = cls[np.argsort(-s_score[cls])[:k_top]]
        recompute[top] = True
        n_hh += len(top)

    stats = EngineStats(
        n_tokens=n, n_recomputed=int(recompute.sum()),
        n_reused_item=int(((src == FROM_ITEM) & ~recompute).sum()),
        n_reused_semantic=int(((src == FROM_SEMANTIC) & ~recompute).sum()),
        n_heavy_hitters=n_hh, layer0_full=sel.layer0_full,
        recompute_mask=recompute.copy())
    return recompute, stats


def _selective_prefill(
    params, cfg: LMConfig, plan: AssemblyPlan,
    cached_k: np.ndarray, cached_v: np.ndarray, have_cache: np.ndarray,
    sel: SelectiveConfig, bucket: int = 128, return_kv: bool = False,
):
    n = plan.n
    n_pad = ((n + bucket - 1) // bucket) * bucket
    toks = _pad_to(plan.tokens.astype(np.int32), n_pad)
    ckp = _pad_to(cached_k.astype(np.float32), n_pad)
    cvp = _pad_to(cached_v.astype(np.float32), n_pad)
    have = have_cache
    valid = np.zeros(n_pad, bool)
    valid[:n] = True

    # ---- layer 0 (jitted): full attention + Eq. 3 terms ----
    layer0 = _jit_layer0_kv if return_kv else _jit_layer0
    out0 = layer0(params, jnp.asarray(toks), jnp.asarray(valid),
                  jnp.asarray(ckp[:, 0]), jnp.asarray(cvp[:, 0]), cfg)
    if return_kv:
        x, attn_mass, div_raw, k0_raw, v0 = out0
    else:
        x, attn_mass, div_raw = out0
        k0_raw = v0 = None
    recompute, stats = select_recompute(plan, have, attn_mass, div_raw, sel)

    if not return_kv:
        logits = run_selective_layers(params, cfg, x, recompute, ckp, cvp, n)
        return logits, stats, None, None

    logits, k_rest, v_rest = run_selective_layers(
        params, cfg, x, recompute, ckp, cvp, n, return_kv=True)
    k_all = np.concatenate(
        [np.asarray(k0_raw, np.float32)[:, None], k_rest], axis=1)[:n]
    v_all = np.concatenate(
        [np.asarray(v0, np.float32)[:, None], v_rest], axis=1)[:n]
    return logits, stats, k_all, v_all


def _pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def selective_layers_batch(params, cfg: LMConfig, items,
                           r_bucket: int = 64, return_kv: bool = True):
    """Bucketed batched selective-layer pass (phase 2 of the selective
    prefill): requests are grouped by (padded length, padded recompute
    budget), stacked with the batch axis padded to the next power of
    two, and ONE jitted selective step runs per bucket.

    items: sequence of (plan, x (n_pad, D), recompute (n,), ckp, cvp)
    with ckp/cvp padded to n_pad.  -> list of (logits (V,), k_rest,
    v_rest) per item in input order (k_rest/v_rest are the merged
    pre-RoPE layers 1..L-1, (n_pad, L-1, Hkv, Dh); None unless
    ``return_kv``).

    This is THE selective dispatch for every serving path — the wave
    batched prefill and the chunked unified-step finalize both land
    here, so their logits (and decoded tokens) cannot drift apart.
    """
    results = [None] * len(items)
    by_shape: Dict[tuple, list] = {}
    for i, (plan, x, recompute, ckp, cvp) in enumerate(items):
        n_pad = ckp.shape[0]
        r_count = int(recompute.sum())
        r_pad = max(r_bucket, ((r_count + r_bucket - 1) // r_bucket)
                    * r_bucket)
        by_shape.setdefault((n_pad, r_pad), []).append(i)
    for (n_pad, r_pad), idxs in sorted(by_shape.items()):
        B = _pow2(len(idxs))
        r_idx_p = np.zeros((B, r_pad), np.int32)
        r_valid = np.zeros((B, r_pad), bool)
        valid = np.zeros((B, n_pad), bool)
        final_slot = np.zeros(B, np.int32)
        for bi, i in enumerate(idxs):
            plan = items[i][0]
            r_idx = np.where(items[i][2])[0]
            r_idx_p[bi] = _pad_to(r_idx.astype(np.int32), r_pad,
                                  fill=plan.n - 1)
            r_valid[bi, :len(r_idx)] = True
            valid[bi, :plan.n] = True
            final_slot[bi] = len(r_idx) - 1
        live = _liveness_for(cfg, r_idx_p, valid)
        zrow_x = jnp.zeros_like(items[idxs[0]][1])
        zrow_ck = np.zeros_like(items[idxs[0]][3])
        xs = [items[i][1] for i in idxs] + [zrow_x] * (B - len(idxs))
        cks = [items[i][3] for i in idxs] + [zrow_ck] * (B - len(idxs))
        cvs = [items[i][4] for i in idxs] + [zrow_ck] * (B - len(idxs))
        args = (params, jnp.stack(xs),
                jnp.asarray(r_idx_p), jnp.asarray(r_valid),
                jnp.asarray(np.stack(cks)), jnp.asarray(np.stack(cvs)),
                jnp.asarray(valid), jnp.arange(n_pad),
                jnp.asarray(final_slot), cfg, jnp.asarray(live))
        if return_kv:
            logits, k_rest, v_rest = _jit_selective_layers_kv(*args)
            k_rest = np.asarray(k_rest, np.float32)
            v_rest = np.asarray(v_rest, np.float32)
        else:
            logits = _jit_selective_layers(*args)
            k_rest = v_rest = None
        logits = np.asarray(logits, np.float32)
        for bi, i in enumerate(idxs):
            kr = k_rest[bi] if return_kv else None
            vr = v_rest[bi] if return_kv else None
            results[i] = (logits[bi], kr, vr)
    return results


def selective_prefill_batch(
    params, cfg: LMConfig, items: Sequence, sel: SelectiveConfig,
    bucket: int = 128, r_bucket: int = 64, return_kv: bool = True,
):
    """Batched beyond-prefix prefill over many requests at once.

    Phase 1 runs layer 0 + Eq. 3 scoring per request — the *identical*
    padded dispatches as the single-request path, so the batched
    prefill's selection and activations are bit-for-bit the loop's.
    (Stacking layer 0 buys no compute: it materializes (B, H, G, S, S)
    probability tensors that thrash CPU caches, and its dispatch count
    is not the bottleneck.)  Phase 2 is where batching pays: ONE jitted
    selective-layer step per (padded length, padded recompute budget)
    bucket over the stacked recompute sets, with the batch axis padded
    to the next power of two — so steady-state serving retraces
    O(#distinct buckets · log batch) regardless of how the continuous
    batcher composes batches, at ≤ 2× padded-row waste.

    items: sequence of (plan, cached_k, cached_v, have) tuples.
    -> list of (logits (V,), EngineStats, k_all (n, L, Hkv, Dh), v_all)
    per request, in input order (k_all/v_all None unless ``return_kv``).
    """
    if not items:
        return []
    # ---- phase 1: per-request layer 0 + host-side Eq. 3 selection ----
    x_of, rec_of, stats_of, k0_of, v0_of, ckp_of, cvp_of = (
        {}, {}, {}, {}, {}, {}, {})
    layer0 = _jit_layer0_kv if return_kv else _jit_layer0
    for i, (plan, ck, cv, have) in enumerate(items):
        n_pad = ((plan.n + bucket - 1) // bucket) * bucket
        toks = _pad_to(plan.tokens.astype(np.int32), n_pad)
        valid = np.zeros(n_pad, bool)
        valid[:plan.n] = True
        ckp = _pad_to(ck.astype(np.float32), n_pad)
        cvp = _pad_to(cv.astype(np.float32), n_pad)
        out0 = layer0(params, jnp.asarray(toks), jnp.asarray(valid),
                      jnp.asarray(ckp[:, 0]), jnp.asarray(cvp[:, 0]), cfg)
        if return_kv:
            x, attn_mass, div_raw, k0, v0 = out0
            k0_of[i] = np.asarray(k0, np.float32)
            v0_of[i] = np.asarray(v0, np.float32)
        else:
            x, attn_mass, div_raw = out0
            k0_of[i] = v0_of[i] = None
        rec_of[i], stats_of[i] = select_recompute(
            plan, have, attn_mass, div_raw, sel)
        x_of[i] = x
        ckp_of[i], cvp_of[i] = ckp, cvp

    # ---- phase 2: selective layers per (n_pad, r_pad) bucket ----
    sel_items = [(items[i][0], x_of[i], rec_of[i], ckp_of[i], cvp_of[i])
                 for i in range(len(items))]
    sel_out = selective_layers_batch(params, cfg, sel_items,
                                     r_bucket=r_bucket, return_kv=return_kv)
    results = []
    for i, (logits, k_rest, v_rest) in enumerate(sel_out):
        n = items[i][0].n
        k_all = v_all = None
        if return_kv:
            k_all = np.concatenate(
                [k0_of[i][:, None], k_rest], axis=1)[:n]
            v_all = np.concatenate(
                [v0_of[i][:, None], v_rest], axis=1)[:n]
        results.append((logits, stats_of[i], k_all, v_all))
    return results


# ---------------------------------------------------------------------------
# Chunk-resumable layer 0 (the unified-step serving path).
#
# The monolithic selective prefill runs layer 0 over the whole prompt in
# one dispatch; under load that makes a long prompt stall every running
# request for its full n^2 scan.  The chunked pass processes the prompt
# in fixed-size query chunks against a full-length key buffer: chunk c
# computes q/k/v for its tokens, appends its rotated keys into the
# buffer, and attends causally over everything scanned so far.  Because
# every per-token quantity (projections, divergence, post-layer-0
# residual, pre-RoPE k0/v0) is row-independent and the attention softmax
# reduces over the same zero-extended key axis, each chunk's rows are
# bitwise identical to the monolithic pass's rows — verified by
# tests/test_chunked.py.  The one cross-token reduction, Eq. 3's
# attention mass (a sum over queries), is accumulated as per-query rows
# and summed once at finalize through `_jit_mass_sum`, reproducing the
# monolithic XLA reduction bitwise (a host-side numpy sum does NOT).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(8,))
def _jit_layer0_chunk(params, toks_c, offset, valid, ck0_c, cv0_c,
                      kbuf, vbuf, cfg: LMConfig):
    """One layer-0 chunk: queries [offset, offset+C) vs all scanned keys.

    toks_c: (C,) chunk token ids (0-padded past the prompt); offset:
    scalar int32 (traced, so one compile serves every chunk index);
    valid: (nbuf,) key validity (True at real prompt positions);
    ck0_c/cv0_c: (C, Hkv, Dh) cached layer-0 rows for Eq. 3 divergence;
    kbuf/vbuf: (nbuf, Hkv, Dh) accumulated rotated-key / value buffers.
    -> (x_c, m_c, div_c, k0_c, v0_c, kbuf', vbuf') where m_c (C, nbuf)
    holds per-query head-mean attention probabilities (the Eq. 3 mass
    rows) and k0_c/v0_c are the chunk's fresh pre-RoPE layer-0 KV.

    Unscanned keys (positions >= offset+C) are zeros in the buffers but
    causally invisible to every chunk query, so the standard causal +
    validity mask is exactly the monolithic mask.
    """
    C = toks_c.shape[0]
    pos_c = offset + jnp.arange(C)
    x = params["embed"][toks_c].astype(jnp.dtype(cfg.dtype))
    if cfg.tie_embeddings:
        x = x * (cfg.d_model ** 0.5)
    lp = layer_params(params, 0)
    h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, k_raw, v = qkv_proj(h, lp, cfg, pos_c)
    kbuf = jax.lax.dynamic_update_slice(kbuf, k, (offset, 0, 0))
    vbuf = jax.lax.dynamic_update_slice(vbuf, v, (offset, 0, 0))
    k_pos = jnp.arange(kbuf.shape[0])
    # layer-0 scoring needs materialized probabilities, so this always
    # takes the jnp path — same as the monolithic layer 0 (`_layer0_impl`)
    o, probs = full_attn(q, kbuf, vbuf, cfg, pos_c, k_pos,
                         return_probs=True, k_valid=valid)
    qvalid = jax.lax.dynamic_slice(valid, (offset,), (C,))
    m_c = (probs * qvalid[None, None, :, None]).mean(axis=(0, 1))
    x = x + jnp.einsum("she,hed->sd", o, lp["wo"])
    x = x + mlp_block(L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp, cfg)
    return x, m_c, _divergence(k_raw, ck0_c, v, cv0_c), k_raw, v, kbuf, vbuf


@jax.jit
def _jit_mass_sum(m):
    """Eq. 3 attention-mass finalize: sum the accumulated per-query rows
    over the query axis.  Must run through XLA — the monolithic layer 0
    reduces this sum inside its jit, and only the same XLA reduction
    reproduces it bitwise."""
    return m.sum(axis=0)


class ChunkedPrefill:
    """Resumable selective prefill state for ONE request.

    Drives the prompt scan in `chunk_tokens`-sized steps (`run_chunk`),
    finalizes Eq. 3 recompute selection once the prompt is fully
    scanned, and hands the selective-layer pass to the SAME bucketed
    dispatch as the wave path (`selective_layers_batch`) — so chunked
    and monolithic prefill decode bitwise-identical tokens.

    The serving engine (`serving.batch_engine.PrefillState`) wraps this
    with pool/store bookkeeping; this class is pure compute + state.
    """

    def __init__(self, params, cfg: LMConfig, plan: AssemblyPlan,
                 cached_k: np.ndarray, cached_v: np.ndarray,
                 have: np.ndarray, sel: SelectiveConfig,
                 chunk_tokens: int, bucket: int = 128):
        self.params = params
        self.cfg = cfg
        self.plan = plan
        self.have = have
        self.sel = sel
        self.chunk = int(chunk_tokens)
        n = plan.n
        self.n = n
        self.n_pad = ((n + bucket - 1) // bucket) * bucket
        # the key buffers are sized to n_pad — the monolithic layer-0
        # shape — so every chunk's attention reduces over the exact
        # reduction axis the monolithic pass uses (zero-extending the
        # key axis past n_pad is NOT bitwise-safe).  The scan grid
        # covers n_pad in `chunk`-wide steps with a ragged final chunk
        # (n_pad and chunk are both multiples of the 64-token engine
        # bucket, so tail widths stay on the same O(1) shape grid).
        self.toks = _pad_to(plan.tokens.astype(np.int32), self.n_pad)
        self.valid = np.zeros(self.n_pad, bool)
        self.valid[:n] = True
        self.ckp = _pad_to(cached_k.astype(np.float32), self.n_pad)
        self.cvp = _pad_to(cached_v.astype(np.float32), self.n_pad)
        Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
        # the model's dtype, like the monolithic layer 0's keys
        self.kbuf = jnp.zeros((self.n_pad, Hkv, Dh), jnp.dtype(cfg.dtype))
        self.vbuf = jnp.zeros((self.n_pad, Hkv, Dh), jnp.dtype(cfg.dtype))
        self.offset = 0
        self._xs: list = []
        self._ms: list = []
        self._divs: list = []
        self._k0s: list = []
        self._v0s: list = []
        self.recompute: Optional[np.ndarray] = None
        self.stats: Optional[EngineStats] = None

    @property
    def scan_done(self) -> bool:
        return self.offset >= self.n_pad

    def pending_tokens(self) -> int:
        """Chunk-grid tokens still to scan (padded — what a budget is
        charged for, since the dispatch width is the work)."""
        return self.n_pad - self.offset

    def next_chunk_tokens(self) -> int:
        """Dispatch width of the next chunk (ragged at the tail)."""
        return min(self.chunk, self.n_pad - self.offset)

    def finalize_charge(self) -> int:
        """Token charge of the selective finalize dispatch (the padded
        recompute budget) — known as soon as the scan completes."""
        if self.recompute is None:
            raise RuntimeError("finalize_charge before scan completed")
        r_count = int(self.recompute.sum())
        return max(64, -(-r_count // 64) * 64)

    def run_chunk(self):
        """Scan the next chunk.  -> (positions, k0_rows, v0_rows): the
        real prompt positions covered and their fresh pre-RoPE layer-0
        KV, ready for incremental pool insertion (empty on an all-pad
        tail chunk).  Completing the scan finalizes Eq. 3 selection."""
        if self.scan_done:
            raise RuntimeError("prompt fully scanned")
        off = self.offset
        C = self.next_chunk_tokens()
        x_c, m_c, div_c, k0_c, v0_c, self.kbuf, self.vbuf = \
            _jit_layer0_chunk(
                self.params, jnp.asarray(self.toks[off:off + C]),
                jnp.asarray(off, jnp.int32), jnp.asarray(self.valid),
                jnp.asarray(self.ckp[off:off + C, 0]),
                jnp.asarray(self.cvp[off:off + C, 0]),
                self.kbuf, self.vbuf, self.cfg)
        self._xs.append(x_c)
        self._ms.append(np.asarray(m_c))
        self._divs.append(np.asarray(div_c))
        k0 = np.asarray(k0_c, np.float32)
        v0 = np.asarray(v0_c, np.float32)
        self._k0s.append(k0)
        self._v0s.append(v0)
        self.offset = off + C
        lo, hi = off, min(off + C, self.n)
        if self.scan_done:
            self._select()
        if hi <= lo:
            return np.zeros(0, np.int64), k0[:0], v0[:0]
        return np.arange(lo, hi), k0[:hi - lo], v0[:hi - lo]

    def _select(self) -> None:
        attn_mass = _jit_mass_sum(jnp.asarray(np.concatenate(self._ms)))
        div = np.concatenate(self._divs)[:self.n_pad]
        self.recompute, self.stats = select_recompute(
            self.plan, self.have, np.asarray(attn_mass), div, self.sel)
        # the mass rows are O(n_pad^2) host floats per request and many
        # requests sit mid-scan concurrently — free them the moment the
        # scan-wide reduction has consumed them
        self._ms = []
        self._divs = []

    def x_full(self):
        """Post-layer-0 residual stream (n_pad, D), assembled from the
        chunk outputs — the selective pass's input."""
        return jnp.concatenate(self._xs)[:self.n_pad]

    def k0_full(self) -> np.ndarray:
        """Fresh pre-RoPE layer-0 K (n, Hkv, Dh) over the real prompt."""
        return np.concatenate(self._k0s)[:self.n]

    def v0_full(self) -> np.ndarray:
        return np.concatenate(self._v0s)[:self.n]

    def sel_item(self) -> tuple:
        """This request's `selective_layers_batch` entry."""
        if self.recompute is None:
            raise RuntimeError("selective pass before scan completed")
        return (self.plan, self.x_full(), self.recompute, self.ckp,
                self.cvp)
