"""A test architecture with two kinds of layer: per layer a pre-RMSNorm
residual relu^2 MLP, the first one wider than the rest and stacked
apart (``first``); no attention.  It shows that an architecture enters
the harness as files of its own, and is never served."""


def harness_keys(c):
    return {"name": c["name"], "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "d_first": c["first_intermediate_size"], "d_ff": c["intermediate_size"],
            "vocab_size": c["vocab_size"], "norm_eps": c["rms_norm_eps"],
            "dtype": c["torch_dtype"], "n_pages": c["serving"]["n_pages"]}


def width(cfg, l):
    return cfg["d_first"] if l == 0 else cfg["d_ff"]


def layer_leaves(cfg, l):
    D, F = cfg["d_model"], width(cfg, l)
    return [(("norm",), (D,), 0.0, None),
            (("mlp", "w_up"), (D, F), D ** -0.5, 0),
            (("mlp", "w_down"), (F, D), F ** -0.5, 1)]


def groups(cfg):
    return [("first", [0]), ("layers", range(1, cfg["n_layers"]))]


def _row(cfg, l):
    return 2 * 2 * cfg["d_model"] * width(cfg, l)


def prefill(cfg, n, n_recomputed, recomputed_ctx):
    deep = sum(_row(cfg, l) for l in range(1, cfg["n_layers"]))
    return n * _row(cfg, 0) + n_recomputed * deep + 2 * cfg["d_model"] * cfg["vocab_size"]


def decode(cfg, position):
    return (sum(_row(cfg, l) for l in range(cfg["n_layers"]))
            + 2 * cfg["d_model"] * cfg["vocab_size"])
