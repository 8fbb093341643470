"""Plain reference of a dense decoder-only transformer, in float32.

The ``dense_decoder`` architecture (`archs/dense_decoder.py` lays out its
parameters and counts its operations): token embedding; per layer a
pre-RMSNorm grouped-query attention block with rotary position embedding
(half-split rotation, base ``rope_theta``) and a pre-RMSNorm MLP
(``swiglu``: silu(x Wg) * (x Wu) Wd; ``relu2``: relu(x Wu)^2 Wd); a final
RMSNorm and an untied LM head.  RMSNorm multiplies by (1 + scale).

Straight `jax.numpy`, every matmul at ``"highest"`` precision, no cache,
no batching across requests, no kernels.  It imports nothing of the
program: the weights are rebuilt leaf by leaf from the seed by the
benchmark's own `weights` module, in the served dtype, then widened to
float32, one layer (and one vocabulary block) at a time so that the
reference fits beside nothing else on the chip.

``quant="fp8"`` is the control: the same pass with both operands of
every projection, MLP and head matmul rounded to float8 e4m3 under a
per-tensor scale — the step below bfloat16 a later change might take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

Q_CHUNK = 512
SEQ_BUCKET = 256
FP8_MAX = 448.0


def _q(t, quant):
    if quant is None:
        return t
    if quant == "bf16":
        return t.astype(jnp.bfloat16).astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / FP8_MAX  # one scale per tensor
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant, eq):
    return jnp.einsum(eq, _q(a, quant), _q(w, quant))


def _norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _layer(x, lp, cfg_items, quant):
    """One layer over one sequence x (S, D); padding sits past the real
    tokens, so causality keeps it out of every real row."""
    cfg = dict(cfg_items)
    S = x.shape[0]
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    G = H // Hkv
    pos = jnp.arange(S)
    h = _norm(x, cfg["norm_eps"]) * (1.0 + lp["attn_norm"])
    q = _rope(_mm(h, lp["wq"], quant, "sd,dhe->she"), pos, cfg["rope_theta"])
    k = _rope(_mm(h, lp["wk"], quant, "sd,dhe->she"), pos, cfg["rope_theta"])
    v = _mm(h, lp["wv"], quant, "sd,dhe->she")
    scale = 1.0 / np.sqrt(q.shape[-1])
    outs = []
    for c0 in range(0, S, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK].reshape(-1, Hkv, G, q.shape[-1])
        s = jnp.einsum("qhgd,khd->hgqk", qc, k) * scale
        causal = (c0 + jnp.arange(qc.shape[0]))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hgqk,khd->qhgd", p, v).reshape(qc.shape[0], H, -1))
    o = jnp.concatenate(outs)
    x = x + _mm(o, lp["wo"], quant, "she,hed->sd")
    h = _norm(x, cfg["norm_eps"]) * (1.0 + lp["mlp_norm"])
    m = lp["mlp"]
    up = _mm(h, m["w_up"], quant, "sd,df->sf")
    if cfg["mlp_type"] == "swiglu":
        act = jax.nn.silu(_mm(h, m["w_gate"], quant, "sd,df->sf")) * up
    else:
        act = jnp.square(jax.nn.relu(up))
    return x + _mm(act, m["w_down"], quant, "sf,fd->sd")


def _widen(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer_batch(x, lp, cfg_items, quant):
    """One layer over every sequence of x (B, S, D), one after another."""
    return jax.lax.map(lambda xi: _layer(xi, lp, cfg_items, quant), x)


@jax.jit
def _embed_rows(x, toks, emb, lo):
    """Rows of x whose token lies in the embedding block starting at lo."""
    rel = toks - lo
    inside = (rel >= 0) & (rel < emb.shape[0])
    return jnp.where(inside[..., None], emb[jnp.clip(rel, 0, emb.shape[0] - 1)], x)


@functools.partial(jax.jit, static_argnames=("eps", "n_last"))
def _last_rows(x, lens, eps, n_last):
    """Normalised rows lens - n_last .. lens - 1 of each sequence."""
    idx = lens[:, None] - n_last + jnp.arange(n_last)[None, :]
    return _norm(x[jnp.arange(x.shape[0])[:, None], idx], eps)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, head, quant):
    return _mm(h, head, quant, "bsd,dv->bsv")


def logits(cfg: dict, seed: int, seqs, n_last: int, quant=None):
    """Logits at the last `n_last` positions of each token sequence.

    The sequences are padded to one length (past their ends, where
    causality keeps the padding out of every real row) and run together.
    -> list of (n_last, vocab) float32 numpy arrays, one per sequence."""
    key = W.base_key(seed)
    dt = jnp.dtype(cfg["dtype"])
    cfg_items = tuple(sorted((k, cfg[k]) for k in (
        "n_heads", "n_kv_heads", "norm_eps", "rope_theta", "mlp_type")))
    lens = np.asarray([len(t) for t in seqs], np.int32)
    s_pad = -(-int(lens.max()) // SEQ_BUCKET) * SEQ_BUCKET
    toks = np.zeros((len(seqs), s_pad), np.int32)
    for i, t in enumerate(seqs):
        toks[i, :len(t)] = t
    toks = jnp.asarray(toks)
    with jax.default_matmul_precision("highest"):
        x = jnp.zeros((len(seqs), s_pad, cfg["d_model"]), jnp.float32)
        for b in range(W.n_vocab_blocks(cfg)):
            emb = W.embed_block(cfg, key, b, dt).astype(jnp.float32)
            x = _embed_rows(x, toks, emb, jnp.int32(b * W.VOCAB_BLOCK))
            del emb
        for l in range(cfg["n_layers"]):
            x = _layer_batch(x, _widen(W.layer(cfg, key, l, dt)), cfg_items, quant)
        h = _last_rows(x, jnp.asarray(lens), cfg["norm_eps"], n_last)
        del x
        out = []
        for b in range(W.n_vocab_blocks(cfg)):
            head = W.head_block(cfg, key, b, dt).astype(jnp.float32)
            out.append(np.asarray(_head(h, head, quant)))
            del head
    full = np.concatenate(out, axis=2)
    return [full[i] for i in range(len(seqs))]
