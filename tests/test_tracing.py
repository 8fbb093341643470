"""Spans and counters inside the serving tick (`serving.tracing`).

A tiny system served through `AsyncSessionServer` (chunked, rcllm) on
the CPU:

* spans change no token: a run with the profiler recording decodes
  what a run without it decodes, and the trace holds ``rcllm.*`` host
  events carrying the request's ``rid``;
* each `TickRecord`'s phase seconds fit inside the tick's wall seconds;
* a tick that only finalizes pulls the logits plus only the rows of a
  block-store insert it owes, and a whole chunked prefill moves less
  than one float32 K/V plane of its prompt either way: the K/V stays on
  the device;
* the first tick of a shape records a compile, a repeat records none;
* queue waits run on the server's wall clock: never negative, and
  longer by the time the server was stalled before the tick.
"""
import asyncio
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.serving import api as API
from repro.serving import tracing
from repro.serving import workload as WL
from repro.serving.kv_pool import row_bucket
from repro.serving.server import AsyncSessionServer


@pytest.fixture(scope="module")
def tiny_trace():
    from repro.core.rcllm import make_tiny_system

    system, pool_rv, prof, _ = make_tiny_system(
        n_items=60, n_requests_hist=30, k_instances=1, n_layers=2, d_model=32)
    trace = WL.heavy_tail_trace(system.catalog, pool_rv, prof, 2, qps=8.0,
                                n_users=2, seed=7)
    return system, trace


@pytest.fixture(scope="module")
def tiny(tiny_trace):
    system, trace = tiny_trace
    pend, plans = WL.rcllm_workload(system, trace, decode_steps=3)
    return system, pend[0], plans[pend[0].rid]


def _config(p) -> API.ServeConfig:
    """A budget of exactly the prompt's padded scan: the scan fills one
    tick, so the selective finalize runs alone in the next one."""
    n_pad = -(-p.n_tokens // 64) * 64
    return API.ServeConfig(engine="jax", sched="chunked", n_pages=256,
                           chunk_tokens=64, step_tokens=n_pad)


def _serve(system, p, plan, rids, stall_s=0.0, trace_dir=None):
    """Serve `rids` (all `p`'s prompt) one after another through a fresh
    server; before each submission's first tick the event loop is held
    for `stall_s`.  -> (server, tokens by rid)."""
    scfg = _config(p)
    engine = API.build_engine(system.params, system.cfg, scfg)
    backend = API.build_backend(engine, scfg)
    server = AsyncSessionServer(backend, scfg)

    async def drive():
        out = {}
        async with server:
            for rid in rids:
                sess = server.submit(API.SubmitRequest(
                    rid=rid, tokens=p.tokens, max_tokens=p.decode_steps,
                    context=plan))
                time.sleep(stall_s)  # holds the loop: no tick can start
                out[rid] = (await sess.result()).tokens
        return out

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        tokens = asyncio.run(drive())
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return server, tokens


@pytest.fixture(scope="module")
def served(tiny):
    """Two identical requests served one after another, from cold jit
    caches: the first compiles every shape, the second none."""
    system, p, plan = tiny
    jax.clear_caches()
    return _serve(system, p, plan, [1, 2])


def _ticks_of(server, rid):
    """The ticks from `rid`'s first chunk up to the next request's."""
    ticks = server.worker.ticks
    starts = {r: i for i, t in enumerate(ticks) for r, _ in t.waits}
    later = [i for i in starts.values() if i > starts[rid]]
    return ticks[starts[rid]:min(later, default=len(ticks))]


# ------------------------------------------------------- the tracing module
def test_span_self_time_excludes_nested_spans():
    with tracing.Tick() as t:
        with tracing.span("outer"):
            time.sleep(0.01)
            with tracing.span("outer.inner"):
                time.sleep(0.1)
    ps = dict(t.phase_s)
    assert ps["rcllm.outer.inner"] >= 0.1
    assert 0.01 <= ps["rcllm.outer"] < 0.1
    assert 0 <= ps["rcllm.sched.tick"] < 0.1
    assert sum(ps.values()) <= t.seconds + 1e-6


def test_counters_and_compiles_land_only_in_an_open_tick():
    tracing.count("h2d_bytes", 10)  # no tick open: dropped
    with tracing.Tick() as t:
        tracing.count("h2d_bytes", 7)
        tracing.count("h2d_bytes", 5)
        # a fresh function: traced (with jitted helpers traced inside
        # it), lowered and compiled once, inside this tick
        jax.jit(lambda x: jax.numpy.where(x > 0, x, 0.0).sum() + 0.125)(
            np.ones(13, np.float32)).block_until_ready()
    assert t.counts["h2d_bytes"] == 12
    comp = t.compile_s()
    assert comp and all(s >= 0 for s in comp.values())
    spans = [(s, e) for s, e, _ in t.compiles]
    # no recorded compile event lies inside another
    assert all(not (s0 <= s1 and e1 <= e0)
               for i, (s0, e0) in enumerate(spans)
               for j, (s1, e1) in enumerate(spans) if i != j)
    assert sum(comp.values()) <= t.seconds


# ------------------------------------------------------ through the server
def test_spans_change_no_token_and_reach_the_profiler(tiny, served, tmp_path):
    system, p, plan = tiny
    _, plain = served
    _, traced = _serve(system, p, plan, [1, 2], trace_dir=str(tmp_path))
    assert traced == plain
    assert all(len(t) == p.decode_steps for t in plain.values())
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(tracing.PREFIX)]
    names = {ev.name for ev in events}
    for name in ("server.admit", "server.step", "server.publish", "sched.tick",
                 "engine.admit", "engine.chunk", "engine.chunk.run",
                 "engine.select", "engine.finalize", "engine.finalize.d2h",
                 "engine.decode", "engine.decode.run", "pool.write"):
        assert tracing.PREFIX + name in names, name
    rids = {str(v) for ev in events if ev.name == "rcllm.engine.admit"
            for k, v in ev.stats if k == "rid"}
    assert rids == {"1", "2"}


def test_phase_seconds_fit_inside_the_tick(served):
    server, _ = served
    ticks = server.worker.ticks
    assert ticks
    for t in ticks:
        ps = dict(t.phase_s)
        assert ps["rcllm.sched.tick"] >= 0
        assert sum(ps.values()) <= t.seconds + 1e-3
    assert sum(t.n_finalized for t in ticks) == 2


def test_finalize_tick_counts_the_bytes_it_pulls(tiny_trace, tiny, served):
    system, p, plan = tiny
    server, _ = served
    cfg = system.cfg
    row = cfg.n_layers * cfg.n_kv_heads * cfg.resolved_head_dim * 4
    plane = p.n_tokens * row  # one float32 K (or V) plane of the prompt
    logits = cfg.vocab_size * jax.numpy.dtype(cfg.dtype).itemsize
    alone = [t for t in server.worker.ticks
             if t.finalize_tokens and not t.chunk_tokens and not t.decode_tokens]
    assert len(alone) == 2
    for t in alone:
        # no store here: the K/V stays on the device, the logits come back
        assert t.n_finalized == 1
        assert t.d2h_bytes == logits
        assert t.h2d_bytes < plane
    # the client's cached rows go up once, padded to the row bucket;
    # beyond them a whole prefill moves less than one plane either way
    cached = 2 * row_bucket(int(plan[3].sum())) * row
    for rid in (1, 2):
        prefill = [t for t in _ticks_of(server, rid)
                   if t.chunk_tokens or t.finalize_tokens]
        assert sum(t.h2d_bytes for t in prefill) - cached < plane
        assert sum(t.d2h_bytes for t in prefill) < plane

    # with the block store, the first finalize owes the tier inserts that
    # missed and pulls exactly their rows (padded to the row bucket)
    # beside its logits; a repeat hits every block and pulls the logits
    # alone.  `cold` is the benchmark's client: nothing cached, so only
    # the prefix tier (the instruction) is shared
    from repro.serving.block_store import RequestReuse, user_reuse_positions

    trace = tiny_trace[1]
    _, plans = WL.rcllm_workload(system, trace)
    reuse = WL.rcllm_reuse_info(system, trace, plans)[p.rid]
    npfx = min(reuse.prefix_len, plan[0].n)
    u_pos = user_reuse_positions(plan[0], plan[3], reuse.prefix_end)
    assert npfx > 0 and len(u_pos) > 0
    prefix_rows = 2 * row_bucket(npfx) * row  # every layer
    user_rows = 2 * row_bucket(len(u_pos)) * row // cfg.n_layers  # layer 0
    cold = RequestReuse(prefix_key=reuse.prefix_key, prefix_len=reuse.prefix_len)
    zeros = np.zeros_like(plan[1])
    for have, ck, cv, ru, owed in (
            (plan[3], plan[1], plan[2], reuse, prefix_rows + user_rows),
            (np.zeros_like(plan[3]), zeros, zeros, cold, prefix_rows)):
        pulled, up, down = _store_prefills(system, plan[0], ck, cv, have, ru)
        assert pulled == [logits + owed, logits]
        # the repeat's prefix rows are gathered from the arena on the
        # device: nothing but its own cached rows goes up
        n_have = int(have.sum())
        assert up - (2 * row_bucket(n_have) * row if n_have else 0) < plane
        assert down < plane


def _store_prefills(system, plan, ck, cv, have, reuse):
    """Two chunked prefills of one prompt through an engine with a block
    store, a 64-token tick budget.  -> (bytes each finalize tick pulled,
    bytes the repeat moved up and down, admission included)."""
    from repro.serving.batch_engine import BatchEngine, BatchRequest
    from repro.serving.block_store import SharedBlockStore
    from repro.serving.kv_pool import pool_for

    pool = pool_for(system.cfg, n_pages=256)
    eng = BatchEngine(system.params, system.cfg, pool=pool,
                      store=SharedBlockStore(pool), chunk_tokens=64)
    pulled = []
    for rid in (1, 2):
        with tracing.Tick() as t:
            eng.begin_prefill(BatchRequest(
                rid=rid, tokens=plan.tokens, plan=plan, cached_k=ck,
                cached_v=cv, have=have, reuse=reuse))
        up, down = t.counts["h2d_bytes"], t.counts["d2h_bytes"]
        while True:
            with tracing.Tick() as t:
                rep = eng.step(64, [], [], [rid])
            up, down = up + t.counts["h2d_bytes"], down + t.counts["d2h_bytes"]
            if rep.finalized:
                pulled.append(t.counts["d2h_bytes"])
                break
    return pulled, up, down


def test_compile_on_the_first_tick_of_a_shape_only(served):
    server, _ = served
    first, second = _ticks_of(server, 1), _ticks_of(server, 2)
    assert first[0].compile_s, "the first chunk tick compiled nothing"
    assert any(t.compile_s for t in first if t.n_finalized)
    assert not [t.compile_s for t in second if t.compile_s]


def test_queue_wait_on_the_wall_clock(tiny, served):
    system, p, plan = tiny
    server, _ = served
    waits = [w for t in server.worker.ticks for _, w in t.waits]
    assert len(waits) == 2 and min(waits) >= 0
    assert all(t.wall_s is not None for t in server.worker.ticks)
    stall = 1.0
    stalled, _ = _serve(system, p, plan, [3], stall_s=stall)
    (w,) = [w for t in stalled.worker.ticks for _, w in t.waits]
    assert w >= stall > max(waits)
