"""Per-family sharding rules: params, optimizer state, inputs, KV caches.

Rules are expressed as PartitionSpec trees matching the param structures in
repro.models / repro.recsys / repro.gnn.  See DESIGN.md §5 for the rationale
per tensor.  These are the *baseline* layouts; §Perf hillclimbs mutate them.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import LMConfig, RecsysConfig
from repro.launch.mesh import axis_size, data_axes


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def tree_shardings(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: _ns(mesh, s), spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def lm_param_specs(cfg: LMConfig, mesh, *, mode: str = "train") -> Dict[str, Any]:
    """PartitionSpec tree matching transformer.init_params structure.

    `mode='serve'` additionally shards attention/embedding weights over the
    data axis (ZeRO-3-style gather-on-use) so 1T-param MoE checkpoints fit
    for inference without a DP replica per data shard.
    """
    m = "model"
    msz = mesh.shape[m]
    dax = data_axes(mesh)
    kv_heads_div = cfg.n_kv_heads % msz == 0

    # serve mode: shard the d_model (input) dim of projections over data
    din = dax if (mode == "serve" and cfg.is_moe) else None

    layer = {
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
        "wq": P(None, din, m, None),
        "wk": P(None, din, m, None) if kv_heads_div else P(None, din, None, m),
        "wv": P(None, din, m, None) if kv_heads_div else P(None, din, None, m),
        "wo": P(None, m, None, din),
    }
    if cfg.moe is not None:
        # experts over model (EP) + expert-ff over data: both axes carry the
        # (potentially TB-scale) expert weights even during training.
        moe = {
            "router": P(None, None, None),
            "w_up": P(None, m, None, dax),
            "w_down": P(None, m, dax, None),
        }
        if cfg.mlp_type in ("swiglu", "geglu"):
            moe["w_gate"] = P(None, m, None, dax)
        layer["moe"] = moe
    else:
        mlp = {"w_up": P(None, None, m), "w_down": P(None, m, None)}
        if cfg.mlp_type in ("swiglu", "geglu"):
            mlp["w_gate"] = P(None, None, m)
        layer["mlp"] = mlp

    specs: Dict[str, Any] = {
        "embed": P(m, None),
        "layers": layer,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, m)
    return specs


def serving_arena_spec() -> P:
    """Paged KV arena (n_pages, L, Hkv, page_size, Dh): kv heads over the
    model axis — the same head split as wk/wv, so the decode gather and
    the per-layer arena scatters stay local to each device's plane.
    Pages/slots replicate (slot tables are host-side numpy and
    device-agnostic: one logical page id addresses every device's slice
    of that page)."""
    return P(None, None, "model", None, None)


def check_serving_divisibility(cfg: LMConfig, mesh) -> None:
    """Serving tensor parallelism splits whole heads: both head counts
    must divide by the model-axis size (no padded-shard fallback — a
    config error here names the two knobs instead of degrading)."""
    msz = mesh.shape["model"]
    if cfg.n_heads % msz or cfg.n_kv_heads % msz:
        raise ValueError(
            f"mesh model axis of {msz} devices (mesh.tp={msz}) must divide "
            f"n_heads={cfg.n_heads} and n_kv_heads={cfg.n_kv_heads}: pick a "
            f"tp dividing both, or a model with more kv heads"
        )


def shard_lm_params(params, cfg: LMConfig, mesh):
    """Place a host-resident LM param tree onto the mesh by
    `lm_param_specs` (dense serving layout).  The jitted engine steps
    need no changes — GSPMD propagates these shardings and inserts the
    tensor-parallel collectives."""
    check_serving_divisibility(cfg, mesh)
    return jax.device_put(params, tree_shardings(mesh, lm_param_specs(cfg, mesh)))


def zero_shard(spec_tree, shape_tree, mesh):
    """ZeRO-style sharding for optimizer moments: take each tensor's spec and
    shard the first still-replicated, divisible dim over the data axis."""
    dax = data_axes(mesh)
    dsz = axis_size(mesh, dax)

    def one(spec: P, sds) -> P:
        dims = list(spec) + [None] * (len(sds.shape) - len(spec))
        used = set()
        for d in dims:
            for a in (d if isinstance(d, tuple) else (d,)):
                used.add(a)
        if any(a in used for a in dax):  # already data-sharded somewhere
            return P(*dims)
        for i, (ax, size) in enumerate(zip(dims, sds.shape)):
            if ax is None and size % dsz == 0 and size >= dsz:
                dims[i] = dax
                return P(*dims)
        return P(*dims)

    return jax.tree_util.tree_map(
        one, spec_tree, shape_tree, is_leaf=lambda x: isinstance(x, P)
    )


def lm_opt_state_specs(opt_abstract, param_specs, params_abstract, mesh):
    """Match optimizer-state pytrees (moments shaped like params, or
    adafactor's reduced-rank factors) to sharding specs."""
    from repro.training.optimizer import OptState

    def spec_for(path_leaf, sds):
        # factored adafactor stats: match prefix dims of the param spec
        return None

    # moments shaped exactly like params reuse (zero-sharded) param specs
    zspecs = zero_shard(param_specs, params_abstract, mesh)

    def map_inner(inner):
        if isinstance(inner, dict) and set(inner) <= {"m", "v"}:
            return {k: zspecs for k in inner}
        # adafactor: per-leaf dict {"vr","vc"} or {"v"} — derive from param spec
        flat_p, tdef = jax.tree_util.tree_flatten(params_abstract)
        flat_spec = tdef.flatten_up_to(param_specs)
        flat_state = tdef.flatten_up_to(inner)

        def one(spec: P, sds, st):
            dims = list(spec) + [None] * (len(sds.shape) - len(spec))
            out = {}
            for key in st:
                if key == "v":
                    out["v"] = P(*dims)
                elif key == "vr":  # param dims minus last
                    out["vr"] = P(*dims[:-1])
                elif key == "vc":  # param dims minus second-to-last
                    out["vc"] = P(*(dims[:-2] + dims[-1:]))
            return out

        flat_out = [one(s, p, st) for s, p, st in zip(flat_spec, flat_p, flat_state)]
        return tdef.unflatten(flat_out)

    return OptState(step=P(), inner=map_inner(opt_abstract.inner))


def lm_input_specs(cfg: LMConfig, mesh, step: str, dims: Dict[str, int]):
    dax = data_axes(mesh)
    dsz = axis_size(mesh, dax)
    b = dims["batch"]
    if step == "train":
        return {"tokens": P(dax, None), "labels": P(dax, None)}
    if step == "prefill":
        return {"tokens": P(dax, None)}
    if step == "decode":
        return {
            "tokens": P(dax, None) if b % dsz == 0 else P(None, None),
            "cache": lm_cache_spec(cfg, mesh, b, dims["seq"]),
            "positions": P(dax) if b % dsz == 0 else P(None),
        }
    raise ValueError(step)


def lm_cache_spec(cfg: LMConfig, mesh, batch: int, seq: int):
    """KV cache (L, B, S, Hkv, Dh) sharding.  batch→data when divisible;
    kv-heads→model when divisible, else sequence→(remaining axes) —
    flash-decoding split-K, combined by XLA via all-reduce."""
    m = "model"
    msz = mesh.shape[m]
    dax = data_axes(mesh)
    dsz = axis_size(mesh, dax)
    if batch % dsz == 0:
        if cfg.n_kv_heads % msz == 0:
            spec = P(None, dax, None, m, None)
        else:
            spec = P(None, dax, m, None, None)  # shard sequence on model
    else:
        # tiny batch (long_500k): shard the sequence across everything
        all_ax = tuple(dax) + (m,)
        spec = P(None, None, all_ax, None, None)
    return {"k": spec, "v": spec}


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

def recsys_param_specs(cfg: RecsysConfig, mesh) -> Dict[str, Any]:
    dax = data_axes(mesh)
    rows = tuple(dax) + ("model",)

    def spec_of(path, leaf):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        if "table" in name and leaf.ndim == 2 and leaf.shape[0] >= 4096:
            return P(rows, None)
        return P(*([None] * leaf.ndim))

    from repro.recsys import models as RM

    abstract = RM.abstract_params(cfg)
    return jax.tree_util.tree_map_with_path(spec_of, abstract)


def recsys_input_specs(cfg: RecsysConfig, mesh, step: str, dims: Dict[str, int]):
    dax = data_axes(mesh)
    dsz = axis_size(mesh, dax)
    b = dims["batch"]
    bspec = dax if b % dsz == 0 else None

    def leaf_spec(leaf_shape):
        return P(bspec, *([None] * (len(leaf_shape) - 1)))

    from repro.configs.registry import input_specs as reg_specs

    specs = reg_specs(cfg.name, _shape_name_of(cfg, step, dims))
    out = {}
    for k, v in specs.items():
        if k == "candidate_ids":
            # 1M candidates not divisible by 256/512 — replicate the (4 MB)
            # id vector; the gather + batched dot still run sharded via the
            # row-sharded table
            out[k] = P(None)
        elif k == "neg_samples":
            out[k] = P(None)
        else:
            out[k] = leaf_spec(v.shape)
    return out


def _shape_name_of(cfg, step, dims):
    from repro.configs.registry import SHAPES

    for name, s in SHAPES["recsys"].items():
        if s.step == step and s.dims.get("batch") == dims.get("batch"):
            return name
    raise KeyError((step, dims))


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

def gnn_param_specs(params_abstract, mesh):
    return jax.tree_util.tree_map(lambda l: P(*([None] * l.ndim)), params_abstract)


def gnn_input_specs(mesh, shape_name: str, spec_shapes: Dict[str, Any]):
    dax = data_axes(mesh)
    edge_ax = tuple(dax) + ("model",)
    esz = axis_size(mesh, edge_ax)
    out = {}
    for k, v in spec_shapes.items():
        if k.startswith("edge_"):
            if len(v.shape) == 1:
                # shard flat edge arrays only when divisible (pjit argument
                # constraint); the step pads + re-shards internally otherwise
                out[k] = P(edge_ax) if v.shape[0] % esz == 0 else P(None)
            else:  # molecule regime: (B, E)
                out[k] = P(dax, None)
        elif k in ("atom_types", "positions", "targets") and shape_name == "molecule":
            out[k] = P(*([dax] + [None] * (len(v.shape) - 1)))
        elif (
            k == "node_feat"
            and v.shape[0] * v.shape[1] > 2**27
            and v.shape[0] % axis_size(mesh, dax) == 0
        ):
            out[k] = P(dax, None)  # huge node features, if divisible
        else:
            out[k] = P(*([None] * len(v.shape)))
    return out
