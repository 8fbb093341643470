"""Unified token-budget serving: chunk-resumable selective prefill.

Pins the tentpole invariants of the chunked scheduler:

* chunked and monolithic prefill are bitwise identical — layer-0 chunk
  rows, Eq. 3 selection, logits, merged KV, and decoded tokens through
  the full serving loop, across {kv-reuse on/off} x {jnp, pallas};
* a mid-prefill preemption rolls `PrefillState` back cleanly (pages,
  store refs, chunk state) and the victim re-prefills to the same
  tokens;
* per-tick token accounting never exceeds the step budget except for a
  single indivisible oversized item;
* the pool's incremental mapped-table machinery (spare slots, private
  remap) preserves the ownership partition.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import engine as ENG
from repro.serving import workload as WL
from repro.serving.batch_engine import BatchEngine
from repro.serving.batching import (ContinuousBatcher, JaxEngineBackend,
                                    PendingRequest)
from repro.serving.block_store import SharedBlockStore, check_partition
from repro.serving.kv_pool import PagedKVPool, pool_for


@pytest.fixture(scope="module")
def tiny_system():
    from repro.core.rcllm import make_tiny_system
    return make_tiny_system(n_items=60, n_requests_hist=30, k_instances=2,
                            n_layers=2, d_model=32)


@pytest.fixture(scope="module")
def heavy_workload(tiny_system):
    """Heavy-tail trace (some long prompts) + plans + reuse metadata."""
    system, pool_rv, prof, _ = tiny_system
    trace = WL.heavy_tail_trace(system.catalog, pool_rv, prof, 6, qps=8.0,
                                n_users=3, long_prompt_frac=0.4,
                                long_prompt_reviews=6, seed=5)
    pend, plans = WL.rcllm_workload(system, trace, decode_steps=3)
    reuse = WL.rcllm_reuse_info(system, trace, plans)
    return trace, pend, plans, reuse


def _items(system, trace):
    out = []
    for rq in trace:
        inst = system.best_instance(rq)
        plan = system.plan_for(rq, inst)
        ck, cv, have = system.cached_kv(plan, inst)
        out.append((plan, ck, cv, have))
    return out


# --------------------------------------------- core bitwise parity
@pytest.mark.parametrize("chunk", [64, 128, 96])
def test_chunked_prefill_matches_monolithic(tiny_system, chunk):
    """ChunkedPrefill (any chunk size, ragged tails included) reproduces
    the monolithic selective prefill bitwise: Eq. 3 selection, final
    logits and the merged pre-RoPE KV."""
    system, pool_rv, prof, _ = tiny_system
    from repro.data import synth as SY
    trace = SY.make_trace(system.catalog, pool_rv, prof, 3, qps=4.0,
                          n_users=3, n_candidates=8, reviews_per_user=1,
                          seed=9)
    sel = ENG.SelectiveConfig()
    for item in _items(system, trace):
        logits_m, stats_m, k_m, v_m = ENG.selective_prefill_with_kv(
            system.params, system.cfg, *item, sel, bucket=64)
        cp = ENG.ChunkedPrefill(system.params, system.cfg, *item, sel,
                                chunk_tokens=chunk, bucket=64)
        n_chunks = 0
        while not cp.scan_done:
            cp.run_chunk()
            n_chunks += 1
        assert n_chunks == -(-cp.n_pad // chunk)
        (logits_c, k_rest, v_rest), = ENG.selective_layers_batch(
            system.params, system.cfg, [cp.sel_item()])
        assert np.array_equal(stats_m.recompute_mask, cp.stats.recompute_mask)
        assert np.array_equal(logits_m, logits_c)
        n = cp.n
        k_c = np.concatenate([np.asarray(cp.k0)[:n, None],
                              np.asarray(k_rest)[:n]], axis=1)
        v_c = np.concatenate([np.asarray(cp.v0)[:n, None],
                              np.asarray(v_rest)[:n]], axis=1)
        assert np.array_equal(k_m, k_c)
        assert np.array_equal(v_m, v_c)


# ------------------------------------- finalize lands in the arena
def _host_merge(plan, rec, cached, fresh, mapped):
    """Reference rows the host used to scatter: cached span values first,
    then the recomputed tokens' fresh rows over them (all L planes),
    at the positions not mapped at a store slot -> {position: row}."""
    from repro.core.assembly import RECOMPUTE, plan_spans

    write = rec.copy()
    for sp in plan_spans(plan):
        if sp.source != RECOMPUTE:
            write[sp.start:sp.end] = True
    rows = np.array(cached, np.float32)
    rows[rec] = fresh[rec]
    return {p: rows[p] for p in np.where(write & ~mapped)[0]}


@pytest.mark.parametrize("eager_kv_writes", [False, True])
@pytest.mark.parametrize("kv_reuse", [False, True])
def test_finalize_lands_the_host_merge_in_the_arena(tiny_system, heavy_workload,
                                                    kv_reuse, eager_kv_writes):
    """After a chunked finalize, the arena row at every position of the
    request equals, bitwise, what the host merge of the same
    `ChunkedPrefill` outputs gives: layer 0 fresh, layers 1..L-1 the
    cached span values with the recomputed rows over them, where the
    position is private; the store block's bytes where it stays mapped.
    With reuse, a repeat of the first prompt hits the prefix tier, and
    Eq. 3 recomputes positions mapped at admission (remapped private).
    Layer 0 lands chunk by chunk (``eager_kv_writes``, the TPU default)
    or with the finalize scatter (the CPU default)."""
    from repro.serving.batch_engine import BatchRequest

    system, *_ = tiny_system
    _, _, plans, reuse = heavy_workload
    cfg = system.cfg
    pool = pool_for(cfg, n_pages=1024)
    store = SharedBlockStore(pool) if kv_reuse else None
    eng = BatchEngine(system.params, cfg, pool=pool, store=store,
                      chunk_tokens=64, eager_kv_writes=eager_kv_writes)
    first = sorted(plans)[0]
    runs = [(rid, rid) for rid in sorted(plans)] + [(100, first)]
    hits = remaps = 0
    for rid, src in runs:
        plan, ck, cv, have = plans[src]
        eng.begin_prefill(BatchRequest(
            rid=rid, tokens=plan.tokens, plan=plan, cached_k=ck, cached_v=cv,
            have=have, n_reserve=2, reuse=reuse[src] if kv_reuse else None))
        st = eng.prefill_states[rid]
        mapped_at_admit = st.mapped_mask.copy()
        while not st.cp.scan_done:
            eng.step(64, [], [], [rid])  # one chunk a tick, no finalize
        cp = st.cp
        (logits, k_rest, v_rest), = ENG.selective_layers_batch(
            system.params, cfg, [cp.sel_item()])
        rec = cp.stats.recompute_mask
        rep = eng.step(10_000, [], [], [rid])
        assert np.array_equal(rep.finalized[rid], logits)

        n = plan.n
        k0, v0 = np.asarray(cp.k0)[:n], np.asarray(cp.v0)[:n]
        k_all = np.concatenate([k0[:, None], np.asarray(k_rest)[:n]], axis=1)
        v_all = np.concatenate([v0[:, None], np.asarray(v_rest)[:n]], axis=1)
        mapped = st.mapped_mask
        hits += int(have[0] != mapped_at_admit[0])  # the instruction mapped
        remaps += int((mapped_at_admit & rec).sum())
        ref_k = _host_merge(plan, rec, ck, k_all, mapped)
        ref_v = _host_merge(plan, rec, cv, v_all, mapped)
        got_k, got_v = pool.gather(rid)
        table = pool.slot_tables[rid]
        blocks = {} if store is None else {
            int(sl): (blk.host_k[i], blk.host_v[i])
            for blk in store.blocks.values() for i, sl in enumerate(blk.slots)}
        assert pool.seq_len(rid) == n
        for p in range(n):
            if mapped[p]:
                bk, bv = blocks[int(table[p])]
                assert np.array_equal(got_k[p], bk) and np.array_equal(got_v[p], bv)
                continue
            assert np.array_equal(got_k[p, 0], k0[p]), p
            assert np.array_equal(got_v[p, 0], v0[p]), p
            assert np.array_equal(got_k[p, 1:], ref_k[p][1:]), p
            assert np.array_equal(got_v[p, 1:], ref_v[p][1:]), p
    if kv_reuse:
        assert hits >= 1 and remaps >= 1
    for rid, _ in runs:
        eng.release(rid)
    check_partition(pool, store)


# ------------------------------------------ serving-loop token parity
def _run_sched(system, pend, plans, reuse, sched, attn_backend,
               chunk_tokens=64, step_tokens=256, n_pages=512,
               eager_kv_writes=None, decode_kernel="auto"):
    cfg = dataclasses.replace(system.cfg, attn_backend=attn_backend,
                              decode_kernel=decode_kernel)
    pool = pool_for(cfg, n_pages=n_pages)
    eng = BatchEngine(system.params, cfg, pool=pool,
                      store=SharedBlockStore(pool) if reuse else None,
                      chunk_tokens=chunk_tokens,
                      eager_kv_writes=eager_kv_writes)
    backend = JaxEngineBackend(eng, mode="rcllm", plans=plans, reuse=reuse)
    batcher = ContinuousBatcher(backend=backend, sched=sched,
                                chunk_tokens=chunk_tokens,
                                step_tokens=step_tokens)
    done = batcher.run([PendingRequest(r.arrival_s, r.rid, r.n_tokens,
                                       r.decode_steps, r.tokens)
                        for r in pend])
    check_partition(eng.pool, eng.store)
    assert eng.pool.stats().pages_in_use == 0          # all released
    assert not eng.prefill_states                      # no stragglers
    return backend.generated, done, batcher.workers[0]


@pytest.mark.parametrize("kv_reuse", [False, True])
@pytest.mark.parametrize("attn_backend,decode_kernel",
                         [("jnp", "auto"),      # gather decode (oracle)
                          ("pallas", "auto"),   # paged decode via backend
                          ("jnp", "paged")])    # paged decode isolated
def test_chunked_decoded_token_parity(tiny_system, heavy_workload,
                                      kv_reuse, attn_backend,
                                      decode_kernel):
    """Decoded tokens are bitwise identical between --sched wave and
    --sched chunked, with and without the shared block store, under
    both attention backends and both decode kernels — on the heavy-tail
    trace, so long-prompt chunking (many chunks, mid-stream finalizes)
    is actually exercised.  The ("jnp", "paged") rows pin the fused
    paged-decode kernel against the same-backend prefill, isolating
    decode-kernel effects from prefill-backend effects.
    """
    system, *_ = tiny_system
    _, pend, plans, reuse = heavy_workload
    reuse = reuse if kv_reuse else None
    gen_w, done_w, _ = _run_sched(system, pend, plans, reuse, "wave",
                                  attn_backend,
                                  decode_kernel=decode_kernel)
    gen_c, done_c, w = _run_sched(system, pend, plans, reuse, "chunked",
                                  attn_backend,
                                  decode_kernel=decode_kernel)
    assert gen_w == gen_c
    assert len(done_c) == len(pend)
    assert len(w.ticks) > 0
    for c in done_c:
        assert c.arrival_s <= c.admitted_s <= c.first_token_s <= c.done_s


def test_eager_kv_writes_mode_identical(tiny_system, heavy_workload):
    """Per-tick eager layer-0 pool writes (the TPU/donation incremental
    mode) and the CPU-default lazy fused-at-finalize mode decode the
    same tokens — nothing reads a request's rows before its decode."""
    system, *_ = tiny_system
    _, pend, plans, reuse = heavy_workload
    gen_lazy, _, _ = _run_sched(system, pend, plans, reuse, "chunked",
                                "jnp", eager_kv_writes=False)
    gen_eager, _, _ = _run_sched(system, pend, plans, reuse, "chunked",
                                 "jnp", eager_kv_writes=True)
    assert gen_lazy == gen_eager


def test_chunked_needs_chunk_capable_backend():
    """The simulator backend is wave-only; asking it for the chunked
    discipline is a configuration error, not a silent fallback."""
    with pytest.raises(ValueError, match="chunk-capable"):
        ContinuousBatcher(lambda n: 1e-3, lambda n: 1e-4,
                          sched="chunked").run(
            [PendingRequest(0.0, 0, 8, 1)])


# ------------------------------------------------ budget accounting
def test_tick_budget_property(tiny_system, heavy_workload):
    """Per-tick token accounting never exceeds the step budget: decode
    is mandatory (one token per running request), and chunk/finalize
    work packs into the remainder — except a tick may carry ONE
    indivisible oversized item (a selective finalize whose padded
    recompute budget exceeds any fixed step size must not starve)."""
    system, *_ = tiny_system
    _, pend, plans, _ = heavy_workload
    for chunk_tokens, step_tokens in ((64, 192), (128, 512), (128, 96)):
        _, _, w = _run_sched(system, pend, plans, None, "chunked",
                             "jnp", chunk_tokens=chunk_tokens,
                             step_tokens=step_tokens)
        assert w.ticks
        for t in w.ticks:
            prefill_charge = t.chunk_tokens + t.finalize_tokens
            if not t.oversized:
                assert prefill_charge <= max(0, step_tokens - t.decode_tokens)
            else:
                # oversized = a single item that alone beats the budget
                assert prefill_charge > max(0, step_tokens - t.decode_tokens)
                assert (t.chunk_tokens == 0) or (t.finalize_tokens == 0)


def test_engine_step_random_budgets(tiny_system):
    """Property-style: driving BatchEngine.step directly with random
    budgets per tick always respects the charge bound and finishes
    every request with the wave path's exact tokens."""
    system, pool_rv, prof, _ = tiny_system
    from repro.data import synth as SY
    trace = SY.make_trace(system.catalog, pool_rv, prof, 4, qps=50.0,
                          n_users=3, n_candidates=8, reviews_per_user=1,
                          seed=11)
    reqs = WL.rcllm_batch_requests(system, trace, n_reserve=2)
    ref_pool = pool_for(system.cfg, n_pages=512)
    ref_eng = BatchEngine(system.params, system.cfg, pool=ref_pool)
    ref_logits = ref_eng.prefill(list(reqs), mode="rcllm")
    ref = {r.rid: np.argmax(lg) for r, lg in zip(reqs, ref_logits)}

    rng = np.random.default_rng(0)
    eng = BatchEngine(system.params, system.cfg,
                      pool=pool_for(system.cfg, n_pages=512),
                      chunk_tokens=64)
    for r in reqs:
        eng.begin_prefill(r)
    queue = [r.rid for r in reqs]
    got = {}
    for _ in range(400):
        if not queue:
            break
        budget = int(rng.integers(16, 400))
        rep = eng.step(budget, [], [], queue)
        assert rep.charge_decode == 0
        if not rep.oversized:
            assert rep.charged <= budget
        got.update({rid: np.argmax(lg) for rid, lg in rep.finalized.items()})
        queue = [rid for rid in queue if rid not in rep.finalized]
    assert not queue
    assert got == ref


# ------------------------------------------- mid-prefill preemption
def test_abort_prefill_rolls_back_cleanly(tiny_system, heavy_workload):
    """Aborting between chunks releases every page and store ref, and a
    fresh begin_prefill re-runs the request to the same logits."""
    system, *_ = tiny_system
    _, _, plans, reuse = heavy_workload
    pool = pool_for(system.cfg, n_pages=512)
    eng = BatchEngine(system.params, system.cfg, pool=pool,
                      store=SharedBlockStore(pool), chunk_tokens=64)
    rid = sorted(plans)[0]
    plan, ck, cv, have = plans[rid]
    from repro.serving.batch_engine import BatchRequest
    req = BatchRequest(rid=rid, tokens=plan.tokens, plan=plan, cached_k=ck,
                       cached_v=cv, have=have, n_reserve=2, reuse=reuse[rid])
    eng.begin_prefill(req)
    eng.step(64, [], [], [rid])                  # one chunk in flight
    assert rid in eng.prefill_states
    eng.abort_prefill(rid)
    assert rid not in eng.prefill_states
    assert pool.stats().pages_in_use == 0
    for key in eng.store.blocks:
        assert eng.store.blocks[key].refcount == 0
    check_partition(pool, eng.store)
    # the victim re-prefills from its kept plan, to the same first token
    eng.begin_prefill(req)
    rep = eng.step(10_000, [], [], [rid])
    eng2 = BatchEngine(system.params, system.cfg,
                       pool=pool_for(system.cfg, n_pages=512))
    ref = eng2.prefill([dataclasses.replace(req, reuse=None)], mode="rcllm")
    assert np.array_equal(rep.finalized[rid], ref[0])


def test_midprefill_preemption_in_loop(tiny_system):
    """Decode-time PoolExhausted with a request mid-prefill: the
    batcher preempts the (younger) prefilling request, its chunk state
    rolls back, and both requests still finish with full outputs.

    The scenario runs under both decode kernels — the preemption/retry
    dance (append rollback, victim re-prefill) must decode the exact
    same tokens through the fused paged kernel as through the jnp
    gather path, with page_size=1 as the degenerate worst case for the
    page views (every slot its own page)."""
    system, pool_rv, prof, _ = tiny_system
    trace = WL.heavy_tail_trace(system.catalog, pool_rv, prof, 6, qps=8.0,
                                n_users=3, long_prompt_frac=0.5,
                                long_prompt_reviews=10, seed=13)
    _, all_plans = WL.rcllm_workload(system, trace, decode_steps=3)
    by_len = sorted(all_plans, key=lambda r: all_plans[r][0].n)
    short, long_ = by_len[0], by_len[-1]
    n_a = all_plans[short][0].n
    n_b = all_plans[long_][0].n
    assert n_b - n_a >= 128, "need a real length gap for the scenario"
    # rid 0: short, decoding (3 steps) with broken zero reservation;
    # rid 1: long, TTFT-only (reserves nothing).  Both arrive at t=0:
    # admission hands them every page, rid 0 finalizes while rid 1 is
    # still scanning, and rid 0's first un-reserved decode append hits
    # an empty free list — forcing a preemption whose victim is the
    # younger rid 1, mid-prefill.
    plans = {0: all_plans[short], 1: all_plans[long_]}

    class NoReserveBackend(JaxEngineBackend):
        def _batch_requests(self, batch):
            out = super()._batch_requests(batch)
            for br in out:
                br.n_reserve = 0              # simulate broken accounting
            return out

    def run(decode_kernel):
        cfg = dataclasses.replace(system.cfg, decode_kernel=decode_kernel)
        pend = [
            PendingRequest(0.0, 0, n_a, 3, plans[0][0].tokens),
            PendingRequest(0.0, 1, n_b, 1, plans[1][0].tokens),
        ]
        pool = PagedKVPool(cfg.n_layers, cfg.n_kv_heads,
                           cfg.resolved_head_dim, page_size=1,
                           n_pages=n_a + n_b + 1)
        eng = BatchEngine(system.params, cfg, pool=pool, chunk_tokens=64)
        backend = NoReserveBackend(eng, mode="rcllm", plans=plans)
        batcher = ContinuousBatcher(backend=backend, sched="chunked",
                                    chunk_tokens=64, step_tokens=128)
        done = batcher.run(pend)
        assert len(done) == 2                     # nobody was lost
        assert batcher.workers[0].preempted >= 1
        assert len(backend.generated[0]) == 3
        assert len(backend.generated[1]) == 1
        assert pool.stats().pages_in_use == 0
        assert not eng.prefill_states
        check_partition(pool)
        return backend.generated

    gen = {k: run(k) for k in ("gather", "paged")}
    assert gen["gather"] == gen["paged"]          # bitwise token parity


# --------------------------------------------------- pool machinery
def test_pool_remap_private_and_spare():
    """alloc_mapped(extra_pages=) banks spare private slots; remap
    repoints mapped positions at them (growing only when spares run
    out) and free() returns everything."""
    pool = PagedKVPool(n_layers=2, n_kv_heads=2, head_dim=4,
                       page_size=4, n_pages=32)
    shared = pool.alloc_pages(2)
    shared_slots = pool.page_slots(shared)
    mapped_pos = np.asarray([0, 1, 2, 3, 8, 9])
    pool.alloc_mapped(5, 20, mapped_pos, shared_slots[:6], extra_pages=2)
    table = pool.slot_tables[5]
    assert np.array_equal(table[mapped_pos], shared_slots[:6])
    spare0 = len(pool._spare[5])
    assert spare0 >= 2 * 4                        # the extra pages' slots
    free0 = pool.free_pages
    pool.remap_private(5, np.asarray([1, 8]))
    assert pool.free_pages == free0               # spares absorbed it
    assert len(pool._spare[5]) == spare0 - 2
    table = pool.slot_tables[5]
    own = set(pool.page_slots(pool.page_tables[5]))
    assert int(table[1]) in own and int(table[8]) in own
    assert np.array_equal(table[[0, 2, 3, 9]],
                          shared_slots[[0, 2, 3, 5]])
    # exhaust the spares: remap grows by fresh pages
    pool.remap_private(5, np.asarray([0, 2, 3, 9]))
    n_more = spare0 - 2 - 4
    assert len(pool._spare[5]) == max(n_more, 0)
    pages_before = len(pool.page_tables[5])
    big = np.arange(4, 8)                         # force page growth
    pool.slot_tables[5][big] = shared_slots[2:6]  # pretend mapped
    pool.remap_private(5, big)
    assert len(pool.page_tables[5]) >= pages_before
    pool.free(5)
    assert 5 not in pool._spare
    pool.release_pages(shared)
    check_partition(pool)
