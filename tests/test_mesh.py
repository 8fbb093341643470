"""Tensor-parallel serving on a real mesh: config surface + parity.

The sharded cases need forced host devices, set in the environment
BEFORE jax initializes (the CI ``mesh`` job exports it; locally run
``XLA_FLAGS=--xla_force_host_platform_device_count=8 pytest
tests/test_mesh.py``).  It is deliberately NOT set from conftest: the
flag changes XLA:CPU's reduction partitioning, which would break the
bitwise chunked-vs-monolithic invariants the rest of the suite pins.
The two invariants the mesh carries (and these tests pin):

* tp=1 on an explicit (1, 1) mesh decodes tokens **bitwise identical**
  to the unsharded engine (same devices, same executable semantics);
* tp>1 decodes **the same tokens** (logits allclose — GSPMD's
  all-reduces reorder float sums, so bitwise equality is not expected).
"""
import dataclasses

import numpy as np
import pytest

import jax

from repro.serving import api as API
from repro.serving.api import MeshConfig, ServeConfig

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 host devices: run with XLA_FLAGS="
    "--xla_force_host_platform_device_count=8 (set before jax imports)")


# ------------------------------------------------------------ MeshConfig
def test_mesh_config_defaults_disabled():
    m = MeshConfig()
    assert not m.enabled
    assert m.build() is None
    assert ServeConfig().mesh == m


def test_mesh_config_shape_derives_tp_dp():
    m = MeshConfig(mesh_shape=(2, 4))
    assert (m.tp, m.dp) == (4, 2)
    assert m.enabled
    m = MeshConfig(tp=2)
    assert m.resolved_shape == (1, 2)
    m = MeshConfig(mesh_shape=(2, 2, 2), axis_names=("pod", "data", "model"))
    assert (m.tp, m.dp) == (2, 4)


@pytest.mark.parametrize("kw", [
    dict(tp=0),
    dict(tp=3, mesh_shape=(1, 2)),
    dict(dp=3, mesh_shape=(2, 4)),
    dict(axis_names=("data", "expert")),            # no model axis
    dict(axis_names=("model",)),                    # custom names, no shape
    dict(mesh_shape=(2, 2, 2)),                     # rank != axis_names
    dict(mesh_shape=(0, 2)),
])
def test_mesh_config_rejects(kw):
    with pytest.raises(ValueError, match="invalid MeshConfig"):
        MeshConfig(**kw)


@pytest.mark.parametrize("kw, names", [
    (dict(engine="sim", mesh=MeshConfig(tp=2)), ("mesh.tp", "engine")),
    (dict(attn_backend="pallas", mesh=MeshConfig(tp=2)),
     ("attn_backend", "mesh.tp")),
    (dict(decode_kernel="paged", mesh=MeshConfig(tp=2)),
     ("decode_kernel", "mesh.tp")),
])
def test_serve_config_cross_validates_mesh(kw, names):
    with pytest.raises(ValueError) as ei:
        ServeConfig(**kw)
    for name in names:      # the error names both conflicting knobs
        assert name.split(".")[0] in str(ei.value)


def test_apply_to_resolves_auto_to_gather_under_tp():
    from repro.configs.base import LMConfig
    from repro.core import engine as ENG

    lm = LMConfig(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab_size=4096)
    cfg = ServeConfig(mesh=MeshConfig(tp=2)).apply_to(lm)
    assert cfg.decode_kernel == "gather"
    assert not ENG.decode_uses_paged(cfg)
    # without a mesh, auto keeps its backend-driven resolution
    assert ServeConfig().apply_to(lm).decode_kernel == "auto"


# ------------------------------------------------- grammar + round trip
def test_parse_dotted_mesh_keys():
    c = ServeConfig.parse("mesh.tp=2,sched=chunked")
    assert c.mesh.tp == 2 and c.sched == "chunked"
    c = ServeConfig.parse("mesh.mesh_shape=2x4")
    assert (c.mesh.tp, c.mesh.dp) == (4, 2)
    c = ServeConfig.parse(
        "mesh.mesh_shape=2x2x2,mesh.axis_names=pod+data+model")
    assert c.mesh.axis_names == ("pod", "data", "model")
    with pytest.raises(ValueError, match="mesh.bogus"):
        ServeConfig.parse("mesh.bogus=1")
    with pytest.raises(ValueError, match="sub-config"):
        ServeConfig.parse("mesh=2")
    with pytest.raises(ValueError, match="int tuple"):
        ServeConfig.parse("mesh.mesh_shape=two")


@pytest.mark.parametrize("cfg", [
    ServeConfig(),
    ServeConfig(engine="sim", k=40, mode="prefix"),
    ServeConfig(sched="chunked", kv_reuse=True, step_tokens=256,
                chunk_tokens=64, r_item=0.5),
    ServeConfig(mesh=MeshConfig(tp=2)),
    ServeConfig(mesh=MeshConfig(mesh_shape=(2, 4))),
    ServeConfig(mesh=MeshConfig(mesh_shape=(2, 1, 2),
                                axis_names=("pod", "data", "model")),
                sched="chunked", kv_reuse=True),
])
def test_config_render_round_trip(cfg):
    """The --config grammar is total: parse(render(cfg)) == cfg for
    every field, including the nested mesh.* keys."""
    assert ServeConfig.parse(cfg.render()) == cfg


def test_from_args_warns_with_exact_config_keys():
    import argparse

    ns = argparse.Namespace(engine="jax", pages=64, kv_reuse="on")
    with pytest.warns(DeprecationWarning) as rec:
        cfg = ServeConfig.from_args(ns)
    assert cfg.n_pages == 64 and cfg.kv_reuse and cfg.engine == "jax"
    msg = str(rec[0].message)
    # the exact --config replacement, not just a generic pointer
    assert "engine=jax" in msg and "n_pages=64" in msg and "kv_reuse=on" in msg


def test_cluster_legacy_kwargs_warn_with_config_keys(tiny):
    from repro.serving.cluster import ClusterEngine

    system, _ = tiny
    with pytest.warns(DeprecationWarning, match=r"--config k=2"):
        ClusterEngine(system, k=2)


# --------------------------------------------------- production mesh fix
@needs_devices
def test_make_production_mesh_auto_factors():
    from repro.launch.mesh import factor_devices, make_production_mesh

    assert factor_devices(8) == (4, 2)
    assert factor_devices(256) == (16, 16)
    assert factor_devices(7) == (7, 1)
    mesh = make_production_mesh()
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    mesh = make_production_mesh(multi_pod=True)
    assert dict(mesh.shape) == {"pod": 2, "data": 2, "model": 2}


def test_make_production_mesh_explicit_shape_error():
    from repro.launch.mesh import make_production_mesh

    with pytest.raises(RuntimeError, match=r"needs 256 devices"):
        make_production_mesh(shape=(16, 16))


# ------------------------------------------------------- parity fixtures
@pytest.fixture(scope="module")
def tiny():
    """One tiny system whose head counts divide every tested tp, plus a
    short trace — shared by the whole parity matrix."""
    from repro.core.rcllm import make_tiny_system
    from repro.data import synth as SY

    system, pool_rv, prof, _ = make_tiny_system(
        n_items=40, n_requests_hist=25, k_instances=2,
        n_layers=2, d_model=32, n_heads=4, n_kv_heads=4)
    trace = SY.make_trace(system.catalog, pool_rv, prof, 6, qps=50.0,
                          n_users=3, n_candidates=6, reviews_per_user=1,
                          seed=3)
    return system, trace


def _serve(system, trace, config):
    """Run the trace through the real batching stack; -> (tokens, engine)."""
    from repro.serving.workload import rcllm_reuse_info, rcllm_workload

    reqs, plans = rcllm_workload(system, trace,
                                 decode_steps=config.decode_steps)
    reuse = rcllm_reuse_info(system, trace, plans) if config.kv_reuse else None
    engine = API.build_engine(system.params, system.cfg, config)
    backend = API.build_backend(engine, config, plans=plans, reuse=reuse)
    API.build_batcher(backend, config).run(reqs)
    return {rid: [int(t) for t in toks]
            for rid, toks in backend.generated.items()}, engine


_REFS = {}


def _reference(system, trace, base):
    key = (base.sched, base.kv_reuse)
    if key not in _REFS:
        _REFS[key] = _serve(system, trace, base)[0]
    return _REFS[key]


@needs_devices
@pytest.mark.parametrize("kv_reuse", [False, True], ids=["priv", "reuse"])
@pytest.mark.parametrize("sched", ["wave", "chunked"])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_sharded_decode_token_parity(tiny, tp, sched, kv_reuse):
    """tp x {wave,chunked} x {reuse on,off}: decoded tokens equal the
    unsharded reference.  tp=1 runs on an explicit (1, 1) mesh — the
    enabled-but-single-device path must stay bitwise."""
    system, trace = tiny
    base = ServeConfig(engine="jax", sched=sched, kv_reuse=kv_reuse,
                       decode_steps=2)
    ref = _reference(system, trace, base)
    mesh = MeshConfig(mesh_shape=(1, 1)) if tp == 1 else MeshConfig(tp=tp)
    got, engine = _serve(system, trace, base.replace(mesh=mesh))
    assert got == ref
    # the arena really is sharded over the model axis
    msz = dict(engine.mesh.shape)["model"]
    shards = engine.pool.arena_k.addressable_shards
    assert len({s.device for s in shards}) == msz * dict(engine.mesh.shape)["data"]
    hkv = system.cfg.n_kv_heads
    for s in shards:
        assert s.data.shape[0] == engine.pool.n_pages   # pages replicated
        assert s.data.shape[2] == hkv // msz            # kv heads split


@needs_devices
def test_tp1_prefill_logits_bitwise(tiny):
    """Sharded-at-(1,1) params produce byte-identical prefill logits —
    the stronger form of the tp=1 invariant, straight off the jit."""
    from repro.core import engine as ENG
    from repro.sharding.specs import shard_lm_params

    system, _ = tiny
    mesh = MeshConfig(mesh_shape=(1, 1)).build()
    sharded = shard_lm_params(system.params, system.cfg, mesh)
    toks = np.arange(1, 33, dtype=np.int32)[None, :]
    last = np.asarray([31], np.int32)
    ref, rk, rv = ENG._jit_batched_prefill(system.params, toks, last,
                                           system.cfg)
    got, gk, gv = ENG._jit_batched_prefill(sharded, toks, last, system.cfg)
    assert np.array_equal(np.asarray(ref), np.asarray(got))
    assert np.array_equal(np.asarray(rk), np.asarray(gk))


@needs_devices
def test_tp2_prefill_logits_allclose(tiny):
    from repro.core import engine as ENG
    from repro.sharding.specs import shard_lm_params

    system, _ = tiny
    mesh = MeshConfig(tp=2).build()
    sharded = shard_lm_params(system.params, system.cfg, mesh)
    toks = np.arange(1, 33, dtype=np.int32)[None, :]
    last = np.asarray([31], np.int32)
    ref, _, _ = ENG._jit_batched_prefill(system.params, toks, last,
                                         system.cfg)
    got, _, _ = ENG._jit_batched_prefill(sharded, toks, last, system.cfg)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               atol=1e-5, rtol=1e-5)


# -------------------------------------------- arena partition invariant
@needs_devices
def test_arena_planes_never_alias_across_requests(tiny):
    """check_partition-style invariant under tp=2 + kv_reuse: page
    ownership stays a partition, and because every device plane indexes
    pages identically (pages replicated, only kv-heads split), disjoint
    page ownership on the host means disjoint planes on every device."""
    from repro.serving import block_store as BS
    from repro.serving.workload import rcllm_reuse_info, rcllm_workload

    system, trace = tiny
    config = ServeConfig(engine="jax", sched="chunked", kv_reuse=True,
                         decode_steps=2, mesh=MeshConfig(tp=2))
    reqs, plans = rcllm_workload(system, trace, decode_steps=2)
    reuse = rcllm_reuse_info(system, trace, plans)
    engine = API.build_engine(system.params, system.cfg, config)
    backend = API.build_backend(engine, config, plans=plans, reuse=reuse)
    batcher = API.build_batcher(backend, config)

    # mid-run + end-of-run: the partition holds at every boundary the
    # batcher exposes (here: after the full run, with live store pages)
    batcher.run(reqs)
    BS.check_partition(engine.pool, engine.store)
    # per-device planes: one page id addresses the same page on every
    # device, so a page owned by request A can never alias request B's
    # rows on any plane
    for arr in (engine.pool.arena_k, engine.pool.arena_v):
        for s in arr.addressable_shards:
            assert s.data.shape[0] == engine.pool.n_pages
    # slot tables are host-side numpy (device-agnostic by construction)
    for table in engine.pool.slot_tables.values():
        assert isinstance(table, np.ndarray)


# ----------------------------------------------------- divisibility guard
@needs_devices
def test_tp_must_divide_kv_heads(tiny):
    system, _ = tiny            # n_kv_heads=4: tp=8 does not divide... use 3
    config = ServeConfig(engine="jax", mesh=MeshConfig(tp=3))
    with pytest.raises(ValueError, match=r"n_kv_heads"):
        API.build_engine(system.params, system.cfg, config)


# -------------------------------------------------- measured transfers
@needs_devices
def test_shard_client_measured_transfers(tiny):
    """With home devices, a cross-shard pull is a real device_put D2D
    copy: measured_s lands in the TransferRecord and the pending
    accumulator, and the block bytes are unchanged."""
    from repro.core import item_cache as IC

    system, _ = tiny
    store = system.item_store
    devs = jax.devices()[:2]
    # find an item resident on shard 1 but not on shard 0
    remote = next(it for it in store.shards[1].blocks
                  if it not in store.shards[0].blocks)
    ledger = IC.ShardClient(store, 0)
    assert not ledger.measures
    blk_l = ledger.pull(remote)
    assert ledger.transfers[0].measured_s == 0.0

    client = IC.ShardClient(store, 0, devices=devs)
    assert client.measures
    blk = client.pull(remote)
    rec = client.transfers[0]
    assert rec.measured_s > 0.0
    assert client.measured_seconds() == rec.measured_s
    assert client.take_measured_s() == rec.measured_s
    assert client.take_measured_s() == 0.0          # drained
    np.testing.assert_array_equal(blk.k, blk_l.k)   # same bytes moved


@needs_devices
def test_cluster_bills_measured_transfer_time(tiny):
    """Under config.mesh the cluster bills the measured D2D seconds
    (sum of per-pull measurements == sum of per-worker billing) and
    decodes the same tokens as the ledgered path."""
    from repro.serving.cluster import ClusterEngine

    system, trace = tiny
    base = ServeConfig(engine="jax", k=2, decode_steps=2)
    rep0 = ClusterEngine(system, base).run(trace, decode_steps=2)
    ce = ClusterEngine(system,
                       base.replace(mesh=MeshConfig(mesh_shape=(1, 1))))
    assert ce.worker_devices is not None
    rep1 = ce.run(trace, decode_steps=2)
    tok = lambda rep: {r: [int(t) for t in ts]            # noqa: E731
                       for r, ts in rep.generated.items()}
    assert tok(rep0) == tok(rep1)
    measured = sum(b.shard.measured_seconds()
                   for b in ce.backends if b.shard)
    billed = sum(b.transfer_seconds for b in ce.backends)
    assert measured == pytest.approx(billed, abs=1e-9)
    n_pulls = sum(len(b.shard.transfers) for b in ce.backends if b.shard)
    if n_pulls:
        assert measured > 0.0


# --------------------------------------------------------- engine guard
@needs_devices
def test_batch_engine_rejects_paged_decode_on_tp_mesh(tiny):
    from repro.serving.batch_engine import BatchEngine

    system, _ = tiny
    mesh = MeshConfig(tp=2).build()
    cfg = dataclasses.replace(system.cfg, decode_kernel="paged")
    with pytest.raises(ValueError, match="paged"):
        BatchEngine(system.params, cfg, mesh=mesh)
