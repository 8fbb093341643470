"""Tiered quantized KV store: int8 payload round-trips, host-RAM spill
tier (spill <-> promote preserves digests/refcounts, LRU order survives
the hop), affinity prefetch budgeting, the `StoreConfig` surface, and
fp32-mode bitwise decoded-token parity with spill enabled."""
import numpy as np
import pytest

from repro.serving import api as API
from repro.serving import workload as WL
from repro.serving.batch_engine import BatchEngine
from repro.serving.batching import ContinuousBatcher, JaxEngineBackend
from repro.serving.block_store import (BlockPayload, SharedBlockStore,
                                       check_partition, dequantize_rows,
                                       quantize_rows)
from repro.serving.kv_pool import PagedKVPool, pool_for

from _hypothesis_compat import given, settings, st


def _tiny_pool(n_pages=16, page_size=4):
    return PagedKVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                       page_size=page_size, n_pages=n_pages)


def _blk(rng, n, L=2, H=2, D=4):
    return (rng.normal(size=(n, L, H, D)).astype(np.float32),
            rng.normal(size=(n, L, H, D)).astype(np.float32))


@pytest.fixture(scope="module")
def tiny_system():
    from repro.core.rcllm import make_tiny_system
    return make_tiny_system(n_items=60, n_requests_hist=30, k_instances=2,
                            n_layers=2, d_model=32)


# ------------------------------------------------------- quantization
def test_quantize_rows_shapes_and_bounds():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 2, 3, 8)).astype(np.float32) * 10
    q, s = quantize_rows(x)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert q.shape == x.shape and s.shape == (5, 2, 3, 1)
    # per-(row, kv-head) scaling: the absmax element of every row maps
    # exactly to +-127
    assert np.abs(q).max(axis=-1).min() == 127
    err = np.abs(dequantize_rows(q, s) - x)
    assert err.max() <= (np.abs(x).max() / 127.0) * 0.5 + 1e-6


def test_quantize_rows_zero_rows_exact():
    x = np.zeros((3, 1, 2, 4), np.float32)
    q, s = quantize_rows(x)
    np.testing.assert_array_equal(dequantize_rows(q, s), x)
    np.testing.assert_array_equal(s, np.ones_like(s))


def test_quantize_rows_idempotent():
    """q(dq(q(x))) == q(x): a block can hop store->payload->store any
    number of times without drift (migration relies on this)."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=(6, 2, 2, 8)).astype(np.float32)
    q1, s1 = quantize_rows(x)
    q2, s2 = quantize_rows(dequantize_rows(q1, s1))
    np.testing.assert_array_equal(q1, q2)
    np.testing.assert_array_equal(s1, s2)


def test_int8_store_arena_holds_dequantized_bytes():
    """Under kv_store_dtype=int8 the arena receives dq(q(x)) — the same
    bytes host_k reports — and the prefix tier stays bit-exact fp32."""
    pool = _tiny_pool()
    store = SharedBlockStore(pool, kv_store_dtype="int8")
    rng = np.random.default_rng(2)
    k, v = _blk(rng, 6)
    blk = store.insert(("item", "a"), "item", k, v)
    assert blk.scale_k is not None and blk.data_k.dtype == np.int8
    q, s = quantize_rows(k)
    np.testing.assert_array_equal(blk.host_k, dequantize_rows(q, s))
    # arena (P, L, Hkv, page, Dh) -> slot rows (P*page, L, Hkv, Dh)
    rows = np.asarray(pool.arena_k).transpose(0, 3, 1, 2, 4)
    gk = rows.reshape(-1, 2, 2, 4)[blk.slots]
    np.testing.assert_array_equal(gk, blk.host_k)
    assert store.dequant_s > 0.0
    # prefix tier: never quantized
    pk, pv = _blk(rng, 4)
    pblk = store.insert(("prefix", "p"), "prefix", pk, pv)
    assert pblk.scale_k is None
    np.testing.assert_array_equal(pblk.host_k, pk)
    check_partition(pool, store)


def test_fp32_store_is_bit_exact():
    pool = _tiny_pool()
    store = SharedBlockStore(pool)          # default fp32
    rng = np.random.default_rng(3)
    k, v = _blk(rng, 5)
    blk = store.insert(("item", "x"), "item", k, v)
    assert blk.scale_k is None
    np.testing.assert_array_equal(blk.host_k, k)
    assert store.dequant_s == 0.0


# --------------------------------------------------------- spill tier
def test_evict_spills_and_promotes_on_reinsert():
    pool = _tiny_pool(n_pages=16, page_size=4)
    store = SharedBlockStore(pool, spill_mb=4)
    rng = np.random.default_rng(4)
    k, v = _blk(rng, 8)
    store.insert(("item", "a"), "item", k, v)
    assert store._evict_lru()
    assert not store.has(("item", "a"))
    assert store.in_spill(("item", "a")) and store.resident(("item", "a"))
    assert store.counters["spills"] == 1
    check_partition(pool, store)
    # re-insert under the same key: served from the spill tier, counted
    # as a spill hit, bytes identical
    blk = store.insert(("item", "a"), "item", k, v)
    assert blk is not None and store.has(("item", "a"))
    assert not store.in_spill(("item", "a"))
    assert store.counters["spill_hits"] == 1
    store.flush_writes()
    np.testing.assert_array_equal(blk.host_k, k)
    check_partition(pool, store)


def test_spill_capacity_trims_oldest():
    """LRU order survives the spill hop: the device-tier last_used stamp
    rides along, so capacity trimming drops the coldest block first."""
    pool = _tiny_pool(n_pages=32, page_size=4)
    rng = np.random.default_rng(5)
    k, v = _blk(rng, 4)
    one_block = 2 * k.nbytes               # k + v, fp32
    cap_mb = max(1, int(np.ceil(2.5 * one_block / 2**20)))
    # capacity for ~2 blocks when one_block is a whole MB multiple;
    # easier: use a store whose cap we compute in bytes directly
    store = SharedBlockStore(pool, spill_mb=cap_mb)
    store.spill_cap = int(2.5 * one_block)  # precise 2.5-block budget
    keys = [("item", f"b{i}") for i in range(3)]
    for i, key in enumerate(keys):
        ki, vi = _blk(rng, 4)
        store.insert(key, "item", ki, vi)
    # touch b1 then b2 so b0 is coldest, then evict everything
    store.get(keys[1])
    store.get(keys[2])
    while store._evict_lru():
        pass
    # three spills against a 2.5-block budget: b0 (coldest) was trimmed
    assert store.counters["spills"] == 3
    assert store.counters["spill_drops"] == 1
    assert not store.in_spill(keys[0])
    assert store.in_spill(keys[1]) and store.in_spill(keys[2])
    assert store.spill_nbytes == 2 * one_block
    check_partition(pool, store)


def test_import_payload_spill_hit_is_digest_hit():
    """A migration payload whose key sits in the spill tier re-stages
    from host RAM and reports digest_hit=True (zero transport bytes)."""
    pool = _tiny_pool(n_pages=16, page_size=4)
    store = SharedBlockStore(pool, spill_mb=4)
    rng = np.random.default_rng(6)
    k, v = _blk(rng, 6)
    store.insert(("item", "m"), "item", k, v)
    store._evict_lru()
    assert store.in_spill(("item", "m"))
    payload = BlockPayload(key=("item", "m"), kind="item",
                           slots=np.arange(6), host_k=k, host_v=v)
    blk, hit = store.import_payload(payload)
    assert hit and blk is not None and blk.refcount == 1
    assert store.counters["spill_hits"] == 1
    store.flush_writes()
    np.testing.assert_array_equal(blk.host_k, k)
    check_partition(pool, store)


@settings(max_examples=25, deadline=None)
@given(
    n_tokens=st.lists(st.integers(min_value=1, max_value=10),
                      min_size=1, max_size=6),
    dtype=st.sampled_from(["fp32", "int8"]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_spill_promote_roundtrip_property(n_tokens, dtype, seed):
    """Property: evict-to-spill then promote preserves every block's
    content digest (the stored bytes hash to the same key-determining
    payload) and refcounts, and the partition invariant holds at every
    hop."""
    pytest.importorskip("hypothesis")
    pool = _tiny_pool(n_pages=64, page_size=4)
    store = SharedBlockStore(pool, kv_store_dtype=dtype, spill_mb=64)
    rng = np.random.default_rng(seed)
    before = {}
    for i, n in enumerate(n_tokens):
        k, v = _blk(rng, n)
        key = ("item", f"p{i}")
        blk = store.insert(key, "item", k, v)
        assert blk is not None
        store.flush_writes()
        before[key] = (blk.host_k.copy(), blk.host_v.copy())
    check_partition(pool, store)
    while store._evict_lru():           # demote everything
        pass
    assert not store.blocks and len(store.spill) == len(before)
    assert store.spill_nbytes == sum(
        s.nbytes for s in store.spill.values())
    check_partition(pool, store)
    for key, (hk, hv) in before.items():  # promote everything back
        blk = store._promote(key)
        assert blk is not None and blk.refcount == 0
        np.testing.assert_array_equal(blk.host_k, hk)
        np.testing.assert_array_equal(blk.host_v, hv)
    store.flush_writes()
    assert not store.spill and store.spill_nbytes == 0
    check_partition(pool, store)


# ----------------------------------------------------------- prefetch
def test_prefetch_budget_is_respected():
    pool = _tiny_pool(n_pages=16, page_size=4)
    store = SharedBlockStore(pool, spill_mb=4, prefetch_pages_per_tick=2)
    rng = np.random.default_rng(7)
    keys = [("item", f"f{i}") for i in range(3)]
    for key in keys:
        k, v = _blk(rng, 8)              # 2 pages each
        store.insert(key, "item", k, v)
    while store._evict_lru():
        pass
    store.hint(keys)
    # budget 2 pages/tick, blocks are 2 pages: one promotion per tick
    assert store.prefetch() == 1
    assert store.prefetch() == 1
    assert store.prefetch() == 1
    assert store.prefetch() == 0         # hints drained
    assert store.counters["prefetch_promotions"] == 3
    assert all(store.has(k) for k in keys)
    store.flush_writes()
    check_partition(pool, store)


def test_prefetch_never_steals_referenced_pages():
    """With every resident block referenced, a hinted promotion is
    refused (in-use pages are never stolen) and the hint is dropped —
    the insert path promotes it on demand instead."""
    pool = _tiny_pool(n_pages=8, page_size=4)     # 7 usable
    store = SharedBlockStore(pool, max_pages=4, spill_mb=4,
                             prefetch_pages_per_tick=8)
    rng = np.random.default_rng(8)
    k, v = _blk(rng, 8)
    store.insert(("item", "cold"), "item", k, v)
    store._evict_lru()
    for i in range(2):                   # refill the device tier
        ki, vi = _blk(rng, 8)
        blk = store.insert(("item", f"hot{i}"), "item", ki, vi)
        blk.refcount = 1                 # referenced: not evictable
    assert store.pages_held() == store.max_pages
    store.hint([("item", "cold")])
    assert store.prefetch() == 0
    assert store.in_spill(("item", "cold"))       # still spilled
    assert len(store._hints) == 0                 # refused hint dropped
    assert store.counters["evictions"] == 1       # residents untouched
    store.flush_writes()
    check_partition(pool, store)


def test_prefetch_demand_swaps_cold_blocks():
    """At steady-state budget occupancy, a hinted promotion evicts the
    LRU refcount-0 victim — which demotes to the spill tier rather than
    dropping, so the swap reorders the device tier without losing bytes."""
    pool = _tiny_pool(n_pages=8, page_size=4)     # 7 usable
    store = SharedBlockStore(pool, max_pages=4, spill_mb=4,
                             prefetch_pages_per_tick=8)
    rng = np.random.default_rng(11)
    k, v = _blk(rng, 8)
    store.insert(("item", "wanted"), "item", k, v)
    store._evict_lru()
    for i in range(2):                   # fill the budget with cold blocks
        ki, vi = _blk(rng, 8)
        store.insert(("item", f"cold{i}"), "item", ki, vi)
    assert store.pages_held() == store.max_pages
    store.hint([("item", "wanted")])
    assert store.prefetch() == 1
    assert store.has(("item", "wanted"))
    assert not store.in_spill(("item", "wanted"))
    assert store.in_spill(("item", "cold0"))      # victim spilled, not lost
    assert store.counters["prefetch_promotions"] == 1
    assert store.counters["spill_drops"] == 0
    store.flush_writes()
    check_partition(pool, store)


def test_prefetch_drops_oversized_hint():
    pool = _tiny_pool(n_pages=32, page_size=4)
    store = SharedBlockStore(pool, spill_mb=4, prefetch_pages_per_tick=1)
    rng = np.random.default_rng(9)
    k, v = _blk(rng, 8)                  # 2 pages > 1-page tick budget
    store.insert(("item", "big"), "item", k, v)
    store._evict_lru()
    store.hint([("item", "big")])
    assert store.prefetch() == 0
    assert len(store._hints) == 0        # dropped, not queued forever
    assert store.in_spill(("item", "big"))


# ----------------------------------------------------- config surface
def test_store_config_validation():
    with pytest.raises(ValueError, match="kv_store_dtype"):
        API.StoreConfig(kv_store_dtype="int4")
    with pytest.raises(ValueError, match="spill_mb"):
        API.StoreConfig(spill_mb=-1)
    with pytest.raises(ValueError, match="prefetch_pages_per_tick"):
        API.StoreConfig(spill_mb=16, prefetch_pages_per_tick=-2)
    with pytest.raises(ValueError, match="needs spill_mb"):
        API.StoreConfig(prefetch_pages_per_tick=4)
    assert not API.StoreConfig().enabled
    assert API.StoreConfig(kv_store_dtype="int8").enabled
    assert API.StoreConfig(spill_mb=16).enabled


def test_store_config_requires_reuse():
    with pytest.raises(ValueError, match="kv_reuse"):
        API.ServeConfig(store=API.StoreConfig(spill_mb=16))
    with pytest.raises(ValueError, match="engine='jax'"):
        API.ServeConfig(engine="sim", mode="prefix",
                        store=API.StoreConfig(kv_store_dtype="int8"))
    cfg = API.ServeConfig(kv_reuse=True, store=API.StoreConfig(
        kv_store_dtype="int8", spill_mb=16, prefetch_pages_per_tick=4))
    assert cfg.store.enabled


def test_store_config_grammar_roundtrip():
    cfg = API.ServeConfig.parse(
        "kv_reuse=on,store.kv_store_dtype=int8,store.spill_mb=64,"
        "store.prefetch_pages_per_tick=8")
    assert cfg.store == API.StoreConfig(
        kv_store_dtype="int8", spill_mb=64, prefetch_pages_per_tick=8)
    assert API.ServeConfig.parse(cfg.render()) == cfg
    with pytest.raises(ValueError, match="sub-config"):
        API.ServeConfig.parse("store=int8")
    with pytest.raises(ValueError, match="StoreConfig field"):
        API.ServeConfig.parse("store.dtype=int8")


def test_build_engine_threads_store_config(tiny_system):
    system, *_ = tiny_system
    cfg = API.ServeConfig(kv_reuse=True, n_pages=64, store=API.StoreConfig(
        kv_store_dtype="int8", spill_mb=16, prefetch_pages_per_tick=4))
    eng = API.build_engine(system.params, system.cfg, cfg)
    assert eng.store.kv_store_dtype == "int8"
    assert eng.store.spill_cap == 16 * 2**20
    assert eng.store.prefetch_pages_per_tick == 4


# ---------------------------------------------- fp32 spill parity
def _run_reuse(system, pend, plans, reuse, sched, store_kw, n_pages=96):
    pool = pool_for(system.cfg, n_pages=n_pages)
    store = SharedBlockStore(pool, **store_kw)
    engine = BatchEngine(system.params, system.cfg, pool=pool, store=store)
    backend = JaxEngineBackend(engine, mode="rcllm", plans=plans,
                               reuse=reuse)
    ContinuousBatcher(backend=backend, max_batch_tokens=4096,
                      sched=sched).run(list(pend))
    assert engine.pool.stats().pages_in_use == 0
    check_partition(engine.pool, engine.store)
    return backend, engine


@pytest.mark.parametrize("sched", ["wave", "chunked"])
def test_fp32_spill_decoded_parity(tiny_system, sched):
    """kv_store_dtype=fp32 with the spill tier enabled decodes bitwise
    identical tokens to the plain store — demotion/promotion changes
    where bytes wait, never what they are.  The small pool forces real
    eviction traffic through the spill tier."""
    system, pool_rv, prof, _ = tiny_system
    trace = WL.zipf_repeat_trace(system.catalog, pool_rv, prof, 8,
                                 qps=12.0, n_users=3, zipf_a=1.4, seed=3)
    pend, plans = WL.rcllm_workload(system, trace, decode_steps=3)
    reuse = WL.rcllm_reuse_info(system, trace, plans)
    b_plain, e_plain = _run_reuse(system, pend, plans, reuse, sched, {})
    b_spill, e_spill = _run_reuse(
        system, pend, plans, reuse, sched,
        {"spill_mb": 64, "prefetch_pages_per_tick": 4})
    for rid in b_plain.generated:
        assert b_plain.generated[rid] == b_spill.generated[rid]
    st_plain = e_plain.store.stats()
    st_spill = e_spill.store.stats()
    if st_plain["evictions"] > 0:
        assert st_spill["spills"] > 0
