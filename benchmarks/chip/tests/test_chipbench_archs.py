"""Architectures enter the harness as files of their own, on the CPU.

The dense decoder's readings are pinned to what the harness gave before
its architecture moved into `archs/dense_decoder.py`: the weights of
``tiny`` bit for bit at two seeds, the tree of shapes and dtypes of
``qwen3-8b-d8``, and both configurations' operation counts.  A second
architecture, ``wide_first_mlp`` (`tests/data/`: a module, a
configuration and a reference, named by no harness file), goes through
configuration loading, weights in two stacked groups, the reference's
leaves, the ``correct`` check and the window's operation count.
"""
import glob
import hashlib
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
DATA = os.path.join(HERE, "data")
sys.path.insert(0, CHIP)

import archs  # noqa: E402
import check  # noqa: E402
import flops as F  # noqa: E402
import run as R  # noqa: E402
import serve as S  # noqa: E402
import traffic as TR  # noqa: E402
import weights as W  # noqa: E402


def _digests(tree) -> dict:
    return {"/".join(str(k.key) for k in path):
            hashlib.sha256(np.asarray(leaf).view(np.uint16).tobytes()).hexdigest()[:16]
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ------------------------------------------------- the dense decoder, unchanged
# sha256 (first 16 hex digits) of each bf16 leaf's bytes, made by the
# harness before the architecture module existed
_ZERO_NORM = "5341e6b2646979a7"
TINY_DIGESTS = {
    1: {"embed": "236ea56cd0de166f", "final_norm": "38723a2e5e8a17aa",
        "layers/attn_norm": _ZERO_NORM, "layers/mlp_norm": _ZERO_NORM,
        "layers/mlp/w_down": "8e538270c8b31195", "layers/mlp/w_gate": "4c11322451876c15",
        "layers/mlp/w_up": "82fe7e8a109c7673", "layers/wk": "c7514589c306b3cf",
        "layers/wo": "914ef4f8ae0b0f21", "layers/wq": "d47a1a00b4285e3b",
        "layers/wv": "e4916ff6d7b0df32", "lm_head": "14bd4a3b62582c6c"},
    2 ** 33 + 5: {"embed": "e7f0f82850fd7066", "final_norm": "38723a2e5e8a17aa",
                  "layers/attn_norm": _ZERO_NORM, "layers/mlp_norm": _ZERO_NORM,
                  "layers/mlp/w_down": "a75388bb7f88ce01", "layers/mlp/w_gate": "b2b4be381db1f192",
                  "layers/mlp/w_up": "54deafb44c521b10", "layers/wk": "f5d59806cce66f47",
                  "layers/wo": "671558186491bd10", "layers/wq": "e1d5e9e7febb85fa",
                  "layers/wv": "1e4ba60da8af7884", "lm_head": "55c4645fb077cf92"},
}

QWEN3_SHAPES = {
    "embed": [151936, 4096], "final_norm": [4096], "lm_head": [4096, 151936],
    "layers/attn_norm": [8, 4096], "layers/mlp_norm": [8, 4096],
    "layers/mlp/w_down": [8, 12288, 4096], "layers/mlp/w_gate": [8, 4096, 12288],
    "layers/mlp/w_up": [8, 4096, 12288], "layers/wk": [8, 4096, 8, 128],
    "layers/wo": [8, 32, 128, 4096], "layers/wq": [8, 4096, 32, 128],
    "layers/wv": [8, 4096, 8, 128],
}


def _tiny():
    return R.load_config("tiny", root=DATA)


@pytest.mark.parametrize("seed", sorted(TINY_DIGESTS))
def test_tiny_weights_are_unchanged_bit_for_bit(seed):
    assert _digests(W.make_params(_tiny(), seed)) == TINY_DIGESTS[seed]


def test_qwen3_parameter_tree_is_unchanged():
    tree = jax.eval_shape(lambda: W.make_params(R.load_config("qwen3-8b-d8"), 3))
    got = {"/".join(str(k.key) for k in path): x
           for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert {k: list(x.shape) for k, x in got.items()} == QWEN3_SHAPES
    assert {x.dtype for x in got.values()} == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("name, root, counts", [
    ("qwen3-8b-d8", CHIP, ((2213, 2011, 2011 * 1100, 6581002878976), (2220, 4622778368))),
    ("tiny", DATA, ((300, 250, 250 * 160, 63987200), (305, 1942528))),
], ids=["qwen3-8b-d8", "tiny"])
def test_operation_counts_are_unchanged(name, root, counts):
    cfg = R.load_config(name, root=root)
    (n, rec, ctx, want_prefill), (pos, want_decode) = counts
    assert F.prefill(cfg, n, rec, ctx) == want_prefill
    assert F.decode(cfg, pos) == want_decode


def test_dense_configurations_keep_their_keys():
    cfg = R.load_config("qwen3-8b-d8")
    assert {k: cfg[k] for k in ("name", "reference", "n_layers", "d_model", "n_heads",
                                "n_kv_heads", "head_dim", "d_ff", "vocab_size", "mlp_type",
                                "rope_theta", "norm_eps", "dtype", "n_pages")} == {
        "name": "qwen3-8b-d8", "reference": "dense_decoder", "n_layers": 8, "d_model": 4096,
        "n_heads": 32, "n_kv_heads": 8, "head_dim": 128, "d_ff": 12288, "vocab_size": 151936,
        "mlp_type": "swiglu", "rope_theta": 1e6, "norm_eps": 1e-6, "dtype": "bfloat16",
        "n_pages": 1024}
    assert archs.of(cfg) is archs.of({})   # no `reference`: the dense decoder
    assert archs.of(cfg).lm_config(cfg).n_kv_heads == 8


# ------------------------------------------------- a second architecture
FIXTURE = "wide_first_mlp"


def _two_kinds():
    return R.load_config("two-kinds", root=DATA)


def test_config_is_flat_hashable_and_keeps_unknown_keys():
    cfg = _two_kinds()
    assert cfg["reference"] == FIXTURE and cfg["d_first"] == 96 and cfg["d_ff"] == 48
    # keys the architecture does not read are kept, nested ones flattened
    assert cfg["architectures"] == ("WideFirstMLP",)
    assert cfg["rope_scaling.type"] == "none" and cfg["serving.page_size"] == 16
    hash(tuple(sorted(cfg.items())))
    assert archs.of(cfg).__file__ == os.path.join(DATA, "archs", f"{FIXTURE}.py")
    assert archs.reference(cfg).__file__ == os.path.join(DATA, "references", f"{FIXTURE}.py")


def test_two_kinds_of_layer_stack_apart_and_the_reference_rebuilds_each_leaf():
    cfg, seed = _two_kinds(), 2 ** 35 + 3
    tree = W.make_params(cfg, seed)
    assert set(tree) == {"embed", "final_norm", "lm_head", "first", "layers"}
    assert tree["first"]["mlp"]["w_up"].shape == (1, 32, 96)
    assert tree["layers"]["mlp"]["w_up"].shape == (2, 32, 48)
    assert tree["embed"].shape == (40000, 32)     # two vocabulary blocks
    key = W.base_key(seed)
    for group, layers in archs.of(cfg).groups(cfg):
        for i, l in enumerate(layers):
            want = W.layer(cfg, key, l, jnp.bfloat16)
            got = jax.tree_util.tree_map(lambda a: a[i], tree[group])
            assert jax.tree_util.tree_all(jax.tree_util.tree_map(
                lambda a, b: bool((a == b).all()), got, want))
    assert float(jnp.abs(tree["layers"]["mlp"]["w_up"][0].astype(jnp.float32)).sum()) > 0


def _forward(cfg, tree, seq):
    """The fixture's model over `make_params`' stacked groups, in float32."""
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(tree["embed"])[jnp.asarray(seq)]
    for group, layers in archs.of(cfg).groups(cfg):
        for i, _ in enumerate(layers):
            lp = jax.tree_util.tree_map(lambda a: f32(a[i]), tree[group])
            h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["norm_eps"])
            h = h * (1.0 + lp["norm"])
            x = x + jnp.square(jax.nn.relu(h @ lp["mlp"]["w_up"])) @ lp["mlp"]["w_down"]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg["norm_eps"])
    return np.asarray(x @ f32(tree["lm_head"]))


def _greedy(cfg, tree, prompt, n):
    seq = list(prompt)
    with jax.default_matmul_precision("highest"):
        for _ in range(n):
            seq.append(int(np.argmax(_forward(cfg, tree, seq)[-1])))
    return seq[len(prompt):]


def test_check_finds_the_reference_by_name_and_decides_correct():
    """Tokens served greedily from `make_params`' tree read a gap of
    rounding size against the fixture's reference; tokens from another
    seed's weights read a gap the size of the logits' spread."""
    cfg, seed = _two_kinds(), 11
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n) for n in (5, 9)]
    sound = [_greedy(cfg, W.make_params(cfg, seed), p, 4) for p in prompts]
    other = [_greedy(cfg, W.make_params(cfg, seed + 1), p, 4) for p in prompts]
    assert check.readings(cfg, seed, prompts, sound)["max_gap"] < 1e-3
    assert check.readings(cfg, seed, prompts, other)["max_gap"] > 0.1


def test_window_operations_are_the_architectures():
    cfg = _two_kinds()
    D, V = 32, 40000
    first, deep = 4 * D * 96, 2 * 4 * D * 48
    st = {"n_tokens": 6, "n_recomputed": 2, "recomputed_ctx": 9}
    r = S.Req(0, 0, 10.0, 6, submit=10.0)
    r.token_t, r.tokens, r.done_t, r.reason = [10.5, 10.7], [1, 2], 10.7, "length"
    served = SimpleNamespace(reqs=[r], ticks=[], stats={0: st}, t0=10.0, t1=20.0,
                             drained_at=20.0)
    view = R.RunView(cfg, served, 10.0, 0.0, {"bf16_flops_per_s": 1e9}, None)
    assert view.arch is archs.of(cfg)
    want = (6 * first + 2 * deep + 2 * D * V) + (first + deep + 2 * D * V)
    assert view.flops_in_window() == want
    assert R.load_reader("mfu")(view) == pytest.approx(100.0 * want / (10.0 * 1e9))


def test_no_harness_file_names_the_fixture():
    harness = [p for p in glob.glob(os.path.join(CHIP, "**", "*"), recursive=True)
               if os.path.isfile(p) and not p.startswith(HERE + os.sep)
               and "__pycache__" not in p]
    assert len(harness) > 20
    for path in harness:
        with open(path, "rb") as f:
            text = f.read()
        assert FIXTURE.encode() not in text and b"two-kinds" not in text, path


def test_arena_bytes_sum_every_plane():
    one_plane = SimpleNamespace(arena_c=np.zeros((4, 3, 576), np.float32), arena_note="x",
                                page_size=16)
    assert R.arena_bytes(one_plane) == 4 * 3 * 576 * 4
    two = SimpleNamespace(arena_k=np.zeros((2, 8), np.float32), arena_v=np.zeros((2, 8)))
    assert R.arena_bytes(two) == 2 * 8 * 4 + 2 * 8 * 8


# ------------------------------------------------- token ids inside the vocabulary
@pytest.mark.parametrize("name", ["rec-amazon", "rec-cold"])
def test_token_id_bound_covers_every_id_drawn(name):
    mix = TR.load_mix(name)
    mix["catalog"].update(n_items=80, n_clusters=8)
    mix["users"].update(n=30)
    mix["corpus_users"] = 5
    tr = TR.make_traffic(mix, 2 ** 40 + 1, 20.0)
    drawn = np.concatenate([r.tokens for r in tr.window + tr.warmup + tr.offline_requests]
                           + list(tr.corpus))
    assert 0 <= drawn.min() and drawn.max() < TR.token_id_bound(mix)
    assert drawn.max() >= TR.token_id_bound(mix) - 64   # and is close to it


def test_refuses_traffic_beyond_the_vocabulary(tmp_path, monkeypatch, capsys):
    """A configuration whose vocabulary the mix's ids overrun is refused
    before the look for a chip, naming both; one that holds them is not.
    A configuration without ``reference`` is the dense decoder."""
    c = json.load(open(os.path.join(DATA, "configs", "tiny.json")))
    del c["reference"]
    (tmp_path / "configs").mkdir()
    for name, vocab in (("small", 12000), ("fits", 12288)):
        json.dump({**c, "name": name, "vocab_size": vocab},
                  open(tmp_path / "configs" / f"{name}.json", "w"))
    mix = TR.load_mix("rec-cold")
    small = R.load_config("small", root=str(tmp_path))
    assert archs.of(small) is archs.of(_tiny())
    R.refuse_unknown_tokens(R.load_config("fits", root=str(tmp_path)), mix, "rec-cold")
    with pytest.raises(R.BadCell, match=r"rec-cold.*12287.*vocab_size 12000.*'small'"):
        R.refuse_unknown_tokens(small, mix, "rec-cold")

    monkeypatch.setattr(R, "load_config", lambda name: small)
    rc = R.main(["--workload", "qwen3-8b.rec-cold", "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "vocab_size 12000" in err and "12287" in err and "TPU" not in err
