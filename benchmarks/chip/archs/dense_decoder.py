"""Dense decoder-only transformer: grouped-query attention, a dense MLP.

Token embedding; per layer a pre-RMSNorm grouped-query attention block
with rotary position embedding and a pre-RMSNorm MLP (``swiglu`` or
``relu2``); a final RMSNorm and an untied LM head.  Every layer has the
same kind, so the program stacks them all in one tree (``layers``).
The plain reference is ``references/dense_decoder.py``.
"""
from __future__ import annotations


def harness_keys(c: dict) -> dict:
    return {
        "name": c["name"],
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "head_dim": c["head_dim"], "d_ff": c["intermediate_size"],
        "vocab_size": c["vocab_size"], "mlp_type": c["mlp"],
        "rope_theta": float(c["rope_theta"]), "norm_eps": c["rms_norm_eps"],
        "dtype": c["torch_dtype"], "n_pages": c["serving"]["n_pages"],
    }


def lm_config(cfg: dict):
    from repro.configs.base import LMConfig

    return LMConfig(
        name=cfg["name"], n_layers=cfg["n_layers"], d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["d_ff"], vocab_size=cfg["vocab_size"],
        mlp_type=cfg["mlp_type"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["norm_eps"], dtype=cfg["dtype"], remat=False)


# ------------------------------------------------------------ parameters
def layer_leaves(cfg: dict, l: int) -> list:
    """{attn_norm, mlp_norm, wq, wk, wv, wo, mlp{w_up, w_down, w_gate}};
    the leaf ids are the benchmark's since it began, so the weights of a
    seed never change."""
    D, H, Hkv, Dh, F = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    leaves = [
        (("attn_norm",), (D,), 0.0, None),
        (("mlp_norm",), (D,), 0.0, None),
        (("wq",), (D, H, Dh), D ** -0.5, 0),
        (("wk",), (D, Hkv, Dh), D ** -0.5, 1),
        (("wv",), (D, Hkv, Dh), D ** -0.5, 2),
        (("wo",), (H, Dh, D), (H * Dh) ** -0.5, 3),
        (("mlp", "w_up"), (D, F), D ** -0.5, 4),
        (("mlp", "w_down"), (F, D), F ** -0.5, 5),
    ]
    if cfg["mlp_type"] == "swiglu":
        leaves.append((("mlp", "w_gate"), (D, F), D ** -0.5, 6))
    return leaves


def groups(cfg: dict) -> list:
    return [("layers", range(cfg["n_layers"]))]


# ------------------------------------------------------------ operations
def linear_per_row(cfg: dict) -> int:
    """Projection and MLP operations for one row through one layer."""
    D, H, Hkv, Dh, F = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                        cfg["head_dim"], cfg["d_ff"])
    n_mlp = 3 if cfg["mlp_type"] == "swiglu" else 2
    return 2 * (2 * D * H * Dh + 2 * D * Hkv * Dh + n_mlp * D * F)


def attention(cfg: dict, keys: int) -> int:
    """QK^T and PV for query rows attending to `keys` keys in total."""
    return 4 * cfg["n_heads"] * cfg["head_dim"] * keys


def head(cfg: dict) -> int:
    return 2 * cfg["d_model"] * cfg["vocab_size"]


def prefill(cfg: dict, n: int, n_recomputed: int, recomputed_ctx: int) -> int:
    """One prompt of `n` tokens whose deep layers recompute `n_recomputed`
    rows attending to `recomputed_ctx` keys in all (row p sees p + 1)."""
    lin = linear_per_row(cfg)
    layer0 = n * lin + attention(cfg, n * (n + 1) // 2)
    deep = (cfg["n_layers"] - 1) * (n_recomputed * lin + attention(cfg, recomputed_ctx))
    return layer0 + deep + head(cfg)


def decode(cfg: dict, position: int) -> int:
    """One decoded token written at `position` (it sees position + 1 keys)."""
    return cfg["n_layers"] * (linear_per_row(cfg) + attention(cfg, position + 1)) + head(cfg)
