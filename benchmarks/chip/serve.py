"""Drive the program's one-chip wall-clock serving path with a traffic stream.

The system under test, as a user would stand it up:
`api.ServeConfig(engine="jax", k=1, mode="rcllm", sched="chunked",
kv_reuse=True, attn_backend="pallas", n_pages=<config>)` ->
`api.build_engine` -> `api.build_backend` (`JaxEngineBackend`) ->
`server.AsyncSessionServer`, fed open loop on the wall clock.

Each request is stamped with its *due* time (``submit(req,
arrival_s=due)``) and every latency is read on the server's own clock
from that due time, so a submission that falls behind shows as
queueing and as generator lag, never as a fast server.

A request's assembly context (`plan_for`, `cached_kv`, reuse keys) is
the client's side of the program's API.  The client builds every
context of a stream before the stream starts, the way a separate client
process would have them ready: inside the window the benchmark's only
work is sleeping until a request is due and submitting it, so it takes
no CPU from the server it measures.

The warm-up serves, one at a time, one request for each distinct
program shape of the window's prompts (`warmup_plan`), each from another
user (its history altered, so no user block it leaves in the store is
one the window asks for): every shape a window request meets alone is
compiled, or loaded from the cache, before the window opens.

The harness only observes: it submits, reads the published stream, and
reads the program's counters (`engine.last_stats` at a request's first
token and the scheduler's tick records).  It frees
nothing of the program's state and replaces none of its methods, apart
from the host spans that a traced run wraps around the calls into each
layer.
"""
from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

DRAIN_S = 60.0  # how long past the window's close answers are awaited
WARMUP_WAIT_S = 300.0  # how long the warm-up may take before the run fails
SHAPE_BUCKET = 64  # the program's prompt-length and slot-table buckets, in tokens
WARMUP_RID = 1_000_000  # warm-up requests' rids start here
WINDOW_SPAN = "bench.window"  # host span over the measured window (traced runs)


def serve_config(cfg: dict):
    from repro.serving import api as API

    return API.ServeConfig(engine="jax", k=1, mode="rcllm", sched="chunked",
                           kv_reuse=True, attn_backend="pallas",
                           n_pages=cfg["n_pages"])


@dataclass
class Req:
    """One served request, as the client saw it (server clock, seconds)."""

    idx: int
    rid: int
    due: float
    n_prompt: int
    submit: Optional[float] = None
    token_t: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done_t: Optional[float] = None
    reason: Optional[str] = None

    @property
    def finished(self) -> bool:
        return self.reason in ("length", "stop")


@dataclass
class Served:
    """What one window served: requests, window bounds, the scheduler's
    ticks in the window and each request's Eq. 3 counts."""

    reqs: List[Req]
    t0: float
    t1: float
    ticks: list
    stats: Dict[int, dict]
    compiles: Dict[str, int]
    drained_at: float


class System:
    """The built program: offline caches, engine, backend and server.

    Where the traffic has offline caches (``traffic.offline``), the
    program builds its semantic history pool and item KV store from the
    traffic's corpus and catalog (`RcLLMSystem.build`); where it has
    none, the system holds neither, so every prompt token is recomputed
    and only the session store's shared instruction prefix is reused."""

    def __init__(self, cfg: dict, params, traffic, structure_seed: int):
        import archs
        from repro.core.rcllm import RcLLMSystem
        from repro.serving import api as API

        self.lm = archs.of(cfg).lm_config(cfg)
        if traffic.offline:
            self.system = RcLLMSystem.build(
                params, self.lm, traffic.catalog, traffic.corpus,
                traffic.offline_requests, k_instances=1,
                n_instruction=len(traffic.instruction),
                item_coverage=traffic.item_coverage, seed=structure_seed)
            # the traffic owns the prompt: its instruction is the one served
            self.system.instruction = traffic.instruction
        else:
            self.system = RcLLMSystem(
                cfg=self.lm, params=params, catalog=traffic.catalog,
                instruction=traffic.instruction, token_embed=None, semantic=None,
                item_store=None, placement=None)
        self.config = serve_config(cfg)
        self.engine = API.build_engine(self.system.params, self.lm, self.config)
        self.backend = API.build_backend(self.engine, self.config)
        self.server = None
        self.stats: Dict[int, dict] = {}

    def record_stats(self, rid: int) -> None:
        """Keep a request's Eq. 3 counts (`EngineStats`), read when its
        first token is published: the engine holds them from the prefill's
        finalize until the request finishes."""
        s = self.engine.last_stats.get(rid)
        if s is None:
            return
        rec = np.asarray(s.recompute_mask, bool)
        self.stats[rid] = {
            "n_tokens": int(s.n_tokens),
            "n_recomputed": int(s.n_recomputed),
            # sum over recomputed rows of the keys each attends to
            "recomputed_ctx": int((np.where(rec)[0] + 1).sum()),
        }

    def new_server(self):
        """A fresh session server over the same engine, pool and store."""
        from repro.serving.server import AsyncSessionServer

        self.server = AsyncSessionServer(self.backend, self.config)
        return self.server

    def request(self, rq, rid: int, max_tokens: int):
        """The client's side: assembly context and reuse keys."""
        from repro.serving import api as API
        from repro.serving.workload import rcllm_reuse_info

        plan = self.system.plan_for(rq, 0)
        ck, cv, have = self.system.cached_kv(plan, 0)
        reuse = rcllm_reuse_info(self.system, [rq], {0: (plan, ck, cv, have)})[0]
        return API.SubmitRequest(rid=rid, tokens=plan.tokens, max_tokens=max_tokens,
                                 context=(plan, ck, cv, have), reuse=reuse)

    def annotate(self) -> None:
        """Host spans around the calls into each layer, for the traced run:
        the reduction labels each device idle gap by the span it falls in."""
        import jax

        from repro.core import engine as ENG

        def wrap(obj, attr, name):
            fn = getattr(obj, attr)

            def spanned(*a, **kw):
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **kw)

            setattr(obj, attr, spanned)

        wrap(self.engine, "decode", "bench.decode")
        wrap(self.engine, "finalize_prefill", "bench.finalize")
        wrap(self.engine, "begin_prefill", "bench.admit")
        wrap(self.engine.pool, "write_at_batch", "bench.pool_write")
        wrap(ENG.ChunkedPrefill, "run_chunk", "bench.layer0_chunk")
        wrap(self.backend, "step", "bench.tick")
        wrap(self.server, "_publish", "bench.publish")


class CompileCounter:
    """Counts JAX compilations, persistent-cache loads and traces while on."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/core/compile/jaxpr_trace_duration": "traces"}

    def __init__(self):
        import jax.monitoring as M

        self.on = False
        self.counts = {name: 0 for name in self.EVENTS.values()}

        def count(event, *a, **kw):
            name = self.EVENTS.get(event)
            if self.on and name is not None:
                self.counts[name] += 1

        M.register_event_duration_secs_listener(count)
        M.register_event_listener(count)


async def _consume(sess, rec: Req, system: System) -> None:
    async for ev in sess:
        if ev.token is not None and not ev.finished:
            if not rec.token_t:
                system.record_stats(rec.rid)
            rec.token_t.append(ev.t_s)
            rec.tokens.append(int(ev.token))
        if ev.finished:
            rec.done_t = ev.t_s
            rec.reason = ev.reason


async def _wait(tasks, timeout: float) -> bool:
    """Wait for `tasks`, at most `timeout` seconds.  -> whether all
    finished.  (A scheduler that died leaves its sessions open until the
    timeout; leaving the server's context then raises its error.)"""
    if not tasks:
        return True
    everything = asyncio.gather(*tasks)
    done, _ = await asyncio.wait({everything}, timeout=timeout)
    if everything not in done:
        everything.cancel()
        await asyncio.gather(everything, return_exceptions=True)
        return False
    everything.result()
    return True


def prepare(system: System, requests, rid0: int, max_tokens: int) -> list:
    """Every request's submission, context included, before the stream."""
    return [system.request(rq, rid0 + i, max_tokens) for i, rq in enumerate(requests)]


def shape_key(n_prompt: int, max_tokens: int) -> tuple:
    """The program shapes one request meets alone: its prompt's bucket
    and the slot-table buckets of its prompt and of its last decode."""
    b = SHAPE_BUCKET
    return (-(-n_prompt // b), -(-(n_prompt + max_tokens) // b))


def warmup_plan(requests, max_tokens: int) -> list:
    """The warm-up's requests: the first of each distinct `shape_key`."""
    seen, out = set(), []
    for rq in requests:
        key = shape_key(len(rq.tokens), max_tokens)
        if key not in seen:
            seen.add(key)
            out.append(rq)
    return out


async def one_by_one(system: System, submits) -> None:
    """Serve `submits` closed loop, each submitted once the one before
    has finished."""
    server = system.server
    for sr in submits:
        rec = Req(0, sr.rid, server.now(), len(sr.tokens))
        done = await _wait([asyncio.create_task(_consume(server.submit(sr), rec, system))],
                           WARMUP_WAIT_S)
        if not done or not rec.finished:
            raise RuntimeError(f"warm-up request {sr.rid} did not finish")


async def open_loop(system: System, requests, submits, start) -> tuple:
    """Submit `submits` open loop, each at ``t0 + arrival_s`` of its
    request; `start(t0)` is called with the window start first.
    -> (Req records, consumer tasks)."""
    server = system.server
    t0 = server.now() + 0.05
    start(t0)
    reqs = [Req(i, sr.rid, t0 + rq.arrival_s, len(sr.tokens))
            for i, (rq, sr) in enumerate(zip(requests, submits))]
    tasks = []
    for i, rec in enumerate(reqs):
        delay = rec.due - server.now()
        if delay > 0:
            await asyncio.sleep(delay)
        rec.submit = server.now()
        tasks.append(asyncio.create_task(
            _consume(server.submit(submits[i], arrival_s=rec.due), rec, system)))
        submits[i] = None  # the server holds it now
    return reqs, tasks


async def _serve(system: System, traffic, seconds: float, counter: CompileCounter,
                 trace_dir: Optional[str], on_window_start, annotate: bool) -> Served:
    server = system.new_server()
    if annotate:
        system.annotate()
    max_tokens = traffic.max_tokens
    marks: Dict[str, object] = {}
    async with server:
        # ---- warm-up: one request per program shape, from other users ----
        await one_by_one(system, prepare(system, warmup_plan(traffic.warmup, max_tokens),
                                         WARMUP_RID, max_tokens))

        # ---- the window ----
        submits = prepare(system, traffic.window, 0, max_tokens)
        ticks = server.worker.ticks

        async def window_marks(t0):
            t1 = t0 + seconds
            await asyncio.sleep(max(t0 - server.now(), 0.0))
            marks["tick0"] = len(ticks)
            counter.on = True
            span = None
            if trace_dir is not None:
                import jax

                # the traced window on the profiler's clock, for the reduction
                span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
                span.__enter__()
            await asyncio.sleep(max(t1 - server.now(), 0.0))
            counter.on = False
            marks["tick1"] = len(ticks)
            if span is not None:
                span.__exit__(None, None, None)

        def start(t0):
            marks["t0"] = t0
            marks["task"] = asyncio.create_task(window_marks(t0))
            # the window start on this process's perf_counter clock
            on_window_start(time.perf_counter() + (t0 - server.now()))

        if trace_dir is not None:
            import jax

            jax.profiler.start_trace(trace_dir)  # before the window: it blocks
        reqs, tasks = await open_loop(system, traffic.window, submits, start)
        await marks["task"]
        if trace_dir is not None:
            jax.profiler.stop_trace()  # after the window: it blocks too
        await _wait(tasks, DRAIN_S)
        drained_at = server.now()
    t0 = marks["t0"]
    return Served(
        reqs=reqs, t0=t0, t1=t0 + seconds,
        ticks=list(ticks[marks["tick0"]:marks["tick1"]]),
        stats={r.idx: system.stats[r.rid] for r in reqs if r.rid in system.stats},
        compiles=dict(counter.counts), drained_at=drained_at)


def serve(system: System, traffic, seconds: float, counter: CompileCounter,
          trace_dir: Optional[str] = None, on_window_start=lambda t0: None,
          annotate: bool = False) -> Served:
    """Warm up, then serve the window open loop; with `trace_dir`, the
    profiler records the whole window under a `WINDOW_SPAN` host span.
    `on_window_start` gets the window's start on `time.perf_counter`'s
    clock.  -> `Served`."""
    return asyncio.run(_serve(system, traffic, seconds, counter, trace_dir,
                              on_window_start, annotate))
