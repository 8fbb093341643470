"""Batched serving path: paged KV pool, batched prefill/decode parity
with the single-request engine, the continuous batcher over the real
JAX backend, and adversarial slot-table layouts through the fused
paged-decode kernel (gather path as oracle)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import LMConfig
from repro.core import assembly as ASM
from repro.core import engine as ENG
from repro.models import transformer as T
from repro.serving.batch_engine import BatchEngine, BatchRequest
from repro.serving.batching import (ContinuousBatcher, JaxEngineBackend,
                                    PendingRequest)
from repro.serving.kv_pool import PagedKVPool, PoolExhausted, pool_for


@pytest.fixture(scope="module")
def tiny():
    cfg = LMConfig(name="serve-test", n_layers=2, d_model=64, n_heads=4,
                   n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
                   mlp_type="swiglu", dtype="float32", attn_q_chunk=32,
                   attn_kv_chunk=32, remat=False)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(scope="module")
def tiny_system():
    from repro.core.rcllm import make_tiny_system
    return make_tiny_system(n_items=60, n_requests_hist=30, k_instances=2,
                            n_layers=2, d_model=32)


# ---------------------------------------------------------------- kv pool
def test_pool_alloc_free_and_exhaustion():
    pool = PagedKVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                       page_size=4, n_pages=9)          # 8 usable (page 0
    pool.alloc(0, 13)                                   # reserved: scratch)
    assert len(pool.page_tables[0]) == 4
    pool.alloc(1, 16)
    with pytest.raises(PoolExhausted):
        pool.alloc(2, 5)
    assert not pool.can_admit(5)
    pool.free(0)
    assert pool.can_admit(5)
    pool.alloc(2, 5)                                    # reuses freed pages
    assert pool.stats().pages_in_use == 6
    assert pool.peak_pages == 8


def test_pool_write_gather_roundtrip(rng):
    pool = PagedKVPool(n_layers=3, n_kv_heads=2, head_dim=4,
                       page_size=4, n_pages=32)
    n = 11
    k = rng.normal(size=(n, 3, 2, 4)).astype(np.float32)
    v = rng.normal(size=(n, 3, 2, 4)).astype(np.float32)
    pool.alloc(7, n)
    pool.write_prompt(7, k, v)
    gk, gv = pool.gather(7)
    np.testing.assert_allclose(gk, k)
    np.testing.assert_allclose(gv, v)
    # decode append crosses a page boundary transparently
    pages0 = len(pool.page_tables[7])
    for _ in range(6):
        pool.append_slots([7])
    assert pool.seq_len(7) == n + 6
    assert len(pool.page_tables[7]) > pages0


@pytest.mark.parametrize("src_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("planes", [0, "deep", "all"])
def test_arena_rows_match_slot_indexing(rng, planes, src_dtype):
    """The pool's scatter and gather (`kv_pool.arena_set`/`arena_get`)
    write and read exactly what plain ``arena.at[pages, layers, :,
    slots]`` indexing does, bit for bit — float32 rows (the prefill's
    planes) and bfloat16 rows cast into the float32 arena (the decode
    step's fresh K/V), pad rows landing twice on the scratch slot (0, 0)
    included."""
    import jax.numpy as jnp

    from repro.serving import kv_pool as KV

    P, L, H, page, D, t = 6, 3, 2, 4, 8, 9
    sel = {0: 0, "deep": slice(1, None), "all": slice(None)}[planes]
    arena_k = jnp.asarray(rng.normal(size=(P, L, H, page, D)), jnp.float32)
    arena_v = -arena_k
    slot_ids = rng.choice(np.arange(page, P * page), t - 2, replace=False)
    pages = np.concatenate([slot_ids // page, [0, 0]]).astype(np.int32)
    slots = np.concatenate([slot_ids % page, [0, 0]]).astype(np.int32)
    rows = np.concatenate([rng.permutation(t - 2), [0, 0]]).astype(np.int32)
    n_planes = np.arange(L)[sel].size
    src = (t, H, D) if planes == 0 else (t, n_planes, H, D)
    k = jnp.asarray(rng.normal(size=src), src_dtype)
    v = k * 3

    got_k, got_v = KV._pool_scatter(arena_k, arena_v, pages, slots, rows, k, v,
                                    planes)
    want_k = arena_k.at[pages, sel, :, slots].set(k[rows].astype(jnp.float32))
    want_v = arena_v.at[pages, sel, :, slots].set(v[rows].astype(jnp.float32))
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)
    read_k, read_v = KV._pool_gather(got_k, got_v, pages, slots)
    np.testing.assert_array_equal(read_k, got_k[pages, :, :, slots])
    np.testing.assert_array_equal(read_v, got_v[pages, :, :, slots])


def test_plan_spans_partition(tiny_system):
    from repro.data import synth as SY
    system, pool_rv, prof, _ = tiny_system
    req = SY.make_trace(system.catalog, pool_rv, prof, 1, qps=1.0,
                        n_users=3, n_candidates=8, reviews_per_user=1,
                        seed=5)[0]
    plan = system.plan_for(req)
    spans = ASM.plan_spans(plan)
    assert spans[0].start == 0 and spans[-1].end == plan.n
    for a, b in zip(spans, spans[1:]):
        assert a.end == b.start                     # exact partition
    for s in spans:
        assert (plan.source[s.start:s.end] == s.source).all()
        if s.source == ASM.FROM_ITEM:               # contiguous block run
            off = plan.block_offset[s.start:s.end]
            assert (np.diff(off) == 1).all()


# ------------------------------------------------------- prefill parity
def test_batched_prefill_matches_single_request(tiny, rng):
    params, cfg = tiny
    lens = [37, 52, 41, 64]
    reqs = [BatchRequest(rid=i,
                         tokens=rng.integers(1, 512, n).astype(np.int32))
            for i, n in enumerate(lens)]
    eng = BatchEngine(params, cfg, pool=pool_for(cfg, page_size=8,
                                                 n_pages=128), bucket=32)
    logits = eng.prefill(reqs, mode="full")
    for i, r in enumerate(reqs):
        ref = ENG.full_prefill_logits(params, cfg, r.tokens)
        np.testing.assert_allclose(logits[i], ref, atol=2e-3, rtol=1e-3)


def test_paged_decode_matches_full_forward(tiny, rng):
    """Greedy decode through page tables == full forward over the
    extended sequence (exact K/V in the pool -> fp32 tolerance)."""
    params, cfg = tiny
    lens = [23, 40]
    reqs = [BatchRequest(rid=i,
                         tokens=rng.integers(1, 512, n).astype(np.int32))
            for i, n in enumerate(lens)]
    eng = BatchEngine(params, cfg, pool=pool_for(cfg, page_size=8,
                                                 n_pages=64), bucket=32)
    logits = eng.prefill(reqs, mode="full")
    toks = {r.rid: list(r.tokens) for r in reqs}
    last = {r.rid: int(np.argmax(logits[i])) for i, r in enumerate(reqs)}
    for _ in range(3):
        rids = [r.rid for r in reqs]
        out = eng.decode(rids, [last[r] for r in rids])
        for i, rid in enumerate(rids):
            toks[rid].append(last[rid])
            ref = ENG.full_prefill_logits(
                params, cfg, np.asarray(toks[rid], np.int32))
            np.testing.assert_allclose(out[i], ref, atol=2e-3, rtol=1e-3)
            last[rid] = int(np.argmax(out[i]))


@pytest.mark.slow
def test_selective_batch_prefill_matches_engine(tiny_system):
    """The rcllm-mode batched prefill is the same selective path as the
    single-request engine — logits must agree exactly, and the pool must
    hold a full merged KV cache for decode."""
    from repro.data import synth as SY
    from repro.serving.workload import rcllm_batch_requests
    system, pool_rv, prof, _ = tiny_system
    trace = SY.make_trace(system.catalog, pool_rv, prof, 2, qps=1.0,
                          n_users=3, n_candidates=8, reviews_per_user=1,
                          seed=11)
    brs = rcllm_batch_requests(system, trace)
    eng = BatchEngine(system.params, system.cfg,
                      pool=pool_for(system.cfg, n_pages=256), bucket=64)
    logits = eng.prefill(brs, mode="rcllm")
    for i, br in enumerate(brs):
        ref, stats = ENG.selective_prefill_logits(
            system.params, system.cfg, br.plan, br.cached_k, br.cached_v,
            br.have, eng.sel, bucket=64)
        np.testing.assert_allclose(logits[i], ref, atol=2e-3, rtol=1e-3)
        assert eng.pool.seq_len(br.rid) == br.plan.n
        # recomputed tokens hold fresh KV, reused tokens the cached block
        k_pool, _ = eng.pool.gather(br.rid)
        st = eng.last_stats[br.rid]
        reused = ~st.recompute_mask & br.have
        if reused.any():
            np.testing.assert_allclose(k_pool[reused][:, 1:],
                                       br.cached_k[reused][:, 1:],
                                       atol=1e-6)
    out = eng.decode([0, 1], [int(np.argmax(l)) for l in logits])
    assert np.isfinite(out).all()


# ------------------------------------- paged decode kernel, adversarial
def _adversarial_pool(cfg, seed):
    """A pool whose slot tables are maximally hostile to the paged
    kernel's page views: rid 0's table interleaves store-shared and
    private slots at a non-page-aligned boundary (store run starts at
    logical position 3, enters the shared pages at slot offset 2, and
    crosses their page boundary mid-run), and both rids' lengths are not
    multiples of the page size.  Seeded so the gather/paged twin engines
    see bit-identical arenas."""
    r = np.random.default_rng(seed)
    pool = pool_for(cfg, page_size=4, n_pages=64)
    hd = cfg.resolved_head_dim

    def kv(t):
        return (r.normal(size=(t, cfg.n_layers, cfg.n_kv_heads, hd))
                .astype(np.float32))

    shared = pool.alloc_pages(3)                  # store-owned pages
    sslots = pool.page_slots(shared)
    pool.write_slots(sslots, kv(len(sslots)), kv(len(sslots)))
    # rid 0: S=13 (% 4 != 0); positions 3..9 served by shared slots 2..8
    pool.alloc_mapped(0, 13, np.arange(3, 10), sslots[2:9])
    priv = np.asarray([0, 1, 2, 10, 11, 12])
    pool.write_at(0, priv, kv(len(priv)), kv(len(priv)))
    # rid 1: S=6 (% 4 != 0), fully private
    pool.alloc(1, 6)
    pool.write_prompt(1, kv(6), kv(6))
    return pool


def test_paged_kernel_adversarial_slot_tables(tiny):
    """Greedy decode through the fused paged kernel must emit the same
    tokens as the jnp gather path over interleaved store/private slot
    tables at arbitrary alignment — including decode appends that grow
    the tables across page boundaries mid-sequence."""
    params, cfg = tiny
    runs = {}
    for kern in ("gather", "paged"):
        eng = BatchEngine(params,
                          dataclasses.replace(cfg, decode_kernel=kern),
                          pool=_adversarial_pool(cfg, seed=3), bucket=32)
        last = [3, 7]
        toks, logits = [], []
        for _ in range(6):                # rid 0: 13->19, rid 1: 6->12
            out = eng.decode([0, 1], last)
            last = [int(np.argmax(row)) for row in out]
            toks.append(tuple(last))
            logits.append(np.asarray(out))
        runs[kern] = (toks, logits)
    assert runs["gather"][0] == runs["paged"][0]   # bitwise token parity
    for lg, lp in zip(runs["gather"][1], runs["paged"][1]):
        np.testing.assert_allclose(lg, lp, atol=1e-5, rtol=1e-5)


def test_requeued_victim_decodes_through_paged_kernel(tiny_system):
    """A preempted-then-requeued victim (chunk in flight, abort rolls
    pages and chunk state back, fresh begin_prefill) must decode the
    same tokens through the paged kernel as through the gather path."""
    from repro.data import synth as SY
    system, pool_rv, prof, _ = tiny_system
    rq = SY.make_trace(system.catalog, pool_rv, prof, 1, qps=1.0,
                       n_users=3, n_candidates=8, reviews_per_user=2,
                       seed=23)[0]
    plan = system.plan_for(rq)
    ck, cv, have = system.cached_kv(plan)
    req = BatchRequest(rid=0, tokens=plan.tokens, plan=plan, cached_k=ck,
                       cached_v=cv, have=have, n_reserve=4)

    def run(decode_kernel):
        cfg = dataclasses.replace(system.cfg, decode_kernel=decode_kernel)
        eng = BatchEngine(system.params, cfg,
                          pool=pool_for(cfg, n_pages=256), chunk_tokens=64)
        eng.begin_prefill(req)
        eng.step(64, [], [], [0])              # one chunk in flight
        assert 0 in eng.prefill_states
        eng.abort_prefill(0)                   # preempted
        assert eng.pool.stats().pages_in_use == 0
        eng.begin_prefill(req)                 # requeued from its plan
        rep = eng.step(10_000, [], [], [0])
        last = int(np.argmax(rep.finalized[0]))
        toks = [last]
        for _ in range(4):
            out = eng.decode([0], [last])
            last = int(np.argmax(out[0]))
            toks.append(last)
        return toks

    assert run("gather") == run("paged")


# ------------------------------------------------ batcher over real engine
def test_continuous_batcher_jax_backend(tiny, rng):
    """Tight pool: 11 usable pages vs ~27 pages of total demand, so the
    loop must interleave admission waves under KV-pool backpressure, and
    decode-page reservation must keep in-flight requests from starving
    the free list mid-decode."""
    params, cfg = tiny
    eng = BatchEngine(params, cfg, pool=pool_for(cfg, page_size=8,
                                                 n_pages=12), bucket=32)
    backend = JaxEngineBackend(eng, mode="full")
    reqs = [PendingRequest(
        arrival_s=0.01 * i, rid=i, n_tokens=n, decode_steps=3,
        tokens=rng.integers(1, 512, n).astype(np.int32))
        for i, n in enumerate([30, 45, 25, 50, 33])]
    done = ContinuousBatcher(backend=backend, max_batch_tokens=128).run(reqs)
    assert len(done) == 5
    for c in done:
        assert c.first_token_s >= c.arrival_s
        assert c.done_s >= c.first_token_s
        assert len(backend.generated[c.rid]) == 3     # prefill + 2 decodes
    # every request released its pages back to the pool
    assert eng.pool.stats().pages_in_use == 0


def test_sim_and_jax_share_batching_loop():
    """The same loop semantics hold for both backends: one request,
    decode_steps tokens, completion ordering."""
    reqs = [PendingRequest(arrival_s=0.0, rid=0, n_tokens=100,
                           decode_steps=2)]
    done = ContinuousBatcher(lambda tok: 1e-3, lambda n: 1e-4).run(reqs)
    assert len(done) == 1
    assert done[0].done_s == pytest.approx(1e-3 + 1e-4)
