"""Continuous batching for the serving path — single instance or cluster.

Requests arrive asynchronously; the batcher schedules them under one of
two disciplines:

* ``sched="wave"`` — the classic prefill-prioritized loop (vLLM's
  default shape): each step runs either one whole-prefill batch under a
  token budget or one decode iteration.  A long prompt therefore stalls
  every running request's decode and every arrival's TTFT for its full
  prefill — the long-sequence head-of-line problem.

* ``sched="chunked"`` — the unified budgeted step: every tick packs one
  decode token for each running request PLUS fixed-size prefill chunks
  (and selective finalizes) for admitted requests, under a global
  ``step_tokens`` budget.  Prefill becomes chunk-resumable
  (`serving.batch_engine.PrefillState`), admission charges chunks
  rather than whole prompts, and backpressure / preemption are
  reasoned per tick.  Decode never waits out a prefill wave, and a
  short prompt admitted behind a long one finishes in proportion to
  its own length.

The *same loop* drives both execution targets through the
`EngineBackend` seam:

* `SimBackend` — the analytic cost model as a virtual clock (tests,
  scheduling/benchmark sweeps; the seed behaviour; wave-only);
* `JaxEngineBackend` — the real batched JAX engine + paged KV pool
  (`serving.batch_engine`), timed on the wall clock.  The engine's
  `cfg.attn_backend` (threaded from `launch/serve.py --attn-backend`)
  picks jnp vs Pallas attention inside its jitted steps; the batcher is
  agnostic and surfaces the choice via `JaxEngineBackend.attn_backend`
  for reporting.

A backend returns the seconds each step took; the loop only ever adds
those to a clock, so scheduling policy is identical in both worlds.

The loop state lives in `WorkerState` — one serving instance's clock,
FIFO admission queue, prefilling set and decode set — so the same step
logic scales from one backend (`ContinuousBatcher`) to K concurrent
backends behind a dispatch policy (`ClusterBatcher`): per-worker
clocks, per-worker KV-pool backpressure, one shared arrival stream.
`serving.cluster` plugs the Eq. 2 affinity router into the dispatch
hook.  Under the chunked discipline every worker keeps a per-tick
`TickRecord` log: token charges by kind, the tick's wall seconds and,
from the spans and counters of `serving.tracing`, where those seconds
went (host phases, bytes crossing to and from the device, compiles)
and how long each request started in it had waited.
"""

from __future__ import annotations

import bisect
import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Protocol, Sequence, Tuple

import numpy as np

from repro.serving import tracing
from repro.serving.api import GREEDY, SamplingParams, match_stop, sample_token
from repro.serving.kv_pool import PoolExhausted


@dataclass(order=True)
class PendingRequest:
    arrival_s: float
    rid: int = field(compare=False)
    n_tokens: int = field(compare=False)
    decode_steps: int = field(compare=False, default=4)
    # real-engine payload (None for the simulator)
    tokens: Optional[np.ndarray] = field(compare=False, default=None)


@dataclass
class DecodeEntry:
    """One running request in a worker's decode set (rid-keyed —
    `PendingRequest` equality compares only ``arrival_s``, so identity
    or equality lookups alias equal-arrival requests)."""

    req: PendingRequest
    ttft_s: float
    steps_left: int


@dataclass
class Completion:
    rid: int
    arrival_s: float
    first_token_s: float  # TTFT
    done_s: float
    worker: int = 0  # serving instance that ran the request
    # when prefill work for this request first started (wave: its
    # prefill batch launched; chunked: it was admitted into the
    # prefilling set) — splits latency into queue-wait vs compute
    admitted_s: float = 0.0
    # why generation ended: "length" (token budget) or "stop" (a stop
    # sequence matched; see api.SubmitRequest)
    reason: str = "length"

    @property
    def queue_wait_s(self) -> float:
        return self.admitted_s - self.arrival_s

    @property
    def prefill_s(self) -> float:
        return self.first_token_s - self.admitted_s

    @property
    def decode_s(self) -> float:
        return self.done_s - self.first_token_s


@dataclass(frozen=True)
class TickRecord:
    """One scheduling tick's accounting (chunked sched)."""

    t: float  # clock when the tick completed
    seconds: float  # wall seconds of the whole tick, admission included
    decode_tokens: int
    chunk_tokens: int
    finalize_tokens: int
    oversized: bool  # a single indivisible item exceeded the budget
    # the tick's start on the server's clock (None off the server)
    wall_s: Optional[float] = None
    # self seconds by `tracing.span` name; they sum to at most `seconds`
    phase_s: Tuple[Tuple[str, float], ...] = ()
    h2d_bytes: int = 0  # host -> device bytes the tick's spans counted
    d2h_bytes: int = 0  # device -> host
    # (function, seconds) JAX spent tracing, lowering and compiling
    compile_s: Tuple[Tuple[str, float], ...] = ()
    # (rid, wall_s - arrival_s) for each request whose first chunk ran
    waits: Tuple[Tuple[int, float], ...] = ()
    n_finalized: int = 0  # requests whose prefill finished in the tick


class EngineBackend(Protocol):
    """What the batching loop needs from an execution target.

    The chunked discipline additionally needs `begin_prefill` /
    `step` / `preempt_prefill` (see `JaxEngineBackend`); a backend
    without them is wave-only and `WorkerState` rejects it up front.
    """

    def prefill(self, batch: Sequence[PendingRequest]) -> float:
        """Run one prefill batch; -> seconds it took."""

    def decode(self, batch: Sequence[PendingRequest]) -> float:
        """Run one decode iteration for `batch`; -> seconds it took."""

    def can_admit(
        self, req: PendingRequest, batch: Sequence[PendingRequest] = ()
    ) -> bool:
        """Room for this request *on top of* the forming `batch`?  False
        defers admission (backpressure) until running requests finish
        and free capacity."""

    def finish(self, req: PendingRequest) -> None:
        """Request left the decode set — release its resources."""

    def preempt(self, req: PendingRequest) -> None:
        """Request was evicted mid-decode and will re-prefill: release
        its execution resources but KEEP whatever the backend needs to
        run it again (plans, staged KV)."""


class SimBackend:
    """Virtual clock: analytic prefill/decode time functions."""

    def __init__(
        self,
        prefill_time_fn: Callable[[int], float],
        decode_time_fn: Callable[[int], float],
    ):
        self.prefill_time_fn = prefill_time_fn
        self.decode_time_fn = decode_time_fn

    def prefill(self, batch: Sequence[PendingRequest]) -> float:
        return self.prefill_time_fn(sum(r.n_tokens for r in batch))

    def decode(self, batch: Sequence[PendingRequest]) -> float:
        return self.decode_time_fn(len(batch))

    def can_admit(
        self, req: PendingRequest, batch: Sequence[PendingRequest] = ()
    ) -> bool:
        return True

    def finish(self, req: PendingRequest) -> None:
        pass

    def preempt(self, req: PendingRequest) -> None:
        pass


class JaxEngineBackend:
    """Real hardware: the batched JAX engine behind the same seam.

    `mode="full"` prefills every prompt exactly; `mode="rcllm"` runs the
    beyond-prefix selective path (requests then need `.plan`/cached KV —
    supply them via `plans`).  Generated tokens are kept per request for
    inspection.

    Token selection is greedy argmax unless a session registered
    per-request `api.SamplingParams` via `set_session` — then the token
    is drawn with a per-request PRNG seeded from the params, and the
    generated stream is checked against the session's stop sequences
    after every append (`early_stop` tells the scheduling loop to retire
    the request before its token budget runs out).
    """

    def __init__(
        self,
        engine,
        mode: str = "full",
        plans: Optional[Dict] = None,
        reuse: Optional[Dict] = None,
    ):
        self.engine = engine
        self.mode = mode
        self.plans = plans if plans is not None else {}
        # rid -> block_store.RequestReuse, for a store-backed engine
        self.reuse = reuse if reuse is not None else {}
        # rid -> (store.version, bound, n_ins): admission bounds are
        # immutable until the store's resident set changes
        self._admit_cache: Dict[int, tuple] = {}
        self.last_token: Dict[int, int] = {}
        self.generated: Dict[int, List[int]] = {}
        # session state (api.py front end): per-request sampling params,
        # stop sequences, lazily-built PRNGs, and the reason a request's
        # generation ended early ("stop"); absent rids sample greedily
        self.sampling: Dict[int, SamplingParams] = {}
        self.stop_seqs: Dict[int, tuple] = {}
        self._rngs: Dict[int, np.random.Generator] = {}
        self.finish_reason: Dict[int, str] = {}

    def set_session(
        self,
        rid: int,
        sampling: SamplingParams = GREEDY,
        stop: Sequence[Sequence[int]] = (),
    ) -> None:
        """Register session semantics for a request before it is served."""
        if not sampling.greedy:
            self.sampling[rid] = sampling
        if stop:
            self.stop_seqs[rid] = tuple(tuple(s) for s in stop)

    def _pick(self, rid: int, lg) -> int:
        params = self.sampling.get(rid, GREEDY)
        if params.greedy:
            return int(np.argmax(lg))
        rng = self._rngs.get(rid)
        if rng is None:
            # per-request stream: (seed, rid) so two sessions with the
            # same params still draw independently, yet one (seed, rid,
            # prompt) triple replays exactly — including after a
            # preemption re-prefills the request from scratch
            rng = np.random.default_rng((params.seed, rid))
            self._rngs[rid] = rng
        return sample_token(np.asarray(lg), params, rng)

    def _append(self, rid: int, tok: int, first: bool = False) -> None:
        self.last_token[rid] = tok
        if first:
            self.generated[rid] = [tok]
        else:
            self.generated[rid].append(tok)
        stops = self.stop_seqs.get(rid)
        if stops and match_stop(self.generated[rid], stops):
            self.finish_reason[rid] = "stop"

    def early_stop(self, rid: int) -> bool:
        """Did this request hit a stop sequence (retire it now)?"""
        return rid in self.finish_reason

    @property
    def attn_backend(self) -> str:
        """Attention implementation the wrapped engine runs (jnp/pallas)."""
        return getattr(self.engine.cfg, "attn_backend", "jnp")

    def _batch_requests(self, batch: Sequence[PendingRequest]):
        from repro.serving.batch_engine import BatchRequest

        out = []
        for r in batch:
            if r.tokens is None:
                raise ValueError(f"request {r.rid}: real engine needs tokens")
            # decode appends decode_steps-1 KV slots: the first output
            # token comes from prefill and the last sampled token is
            # never written back
            br = BatchRequest(
                rid=r.rid,
                tokens=r.tokens,
                n_reserve=max(r.decode_steps - 1, 0),
            )
            if self.mode == "rcllm":
                plan, ck, cv, have = self.plans[r.rid]
                br.plan, br.cached_k, br.cached_v, br.have = plan, ck, cv, have
                br.reuse = self.reuse.get(r.rid)
            out.append(br)
        return out

    def prefill(self, batch: Sequence[PendingRequest]) -> float:
        t0 = time.perf_counter()
        logits = self.engine.prefill(self._batch_requests(batch), self.mode)
        for r, lg in zip(batch, logits):
            self._append(r.rid, self._pick(r.rid, lg), first=True)
        return time.perf_counter() - t0

    def can_admit(
        self, req: PendingRequest, batch: Sequence[PendingRequest] = ()
    ) -> bool:
        # pages for the prompt + the decode tokens it will append, on top
        # of what the rest of the forming batch will claim
        pool = self.engine.pool
        store = getattr(self.engine, "store", None)
        if store is None or self.mode != "rcllm":
            need = sum(
                pool.pages_for(r.n_tokens + max(r.decode_steps - 1, 0))
                for r in (*batch, req)
            )
            return need <= pool.free_pages
        # cross-request reuse: count only private pages against the
        # free list plus what LRU eviction could reclaim (excluding the
        # blocks these very requests count on mapping).  Store inserts
        # are NOT charged: they are optional and the engine's keep_free
        # gate already refuses any insert that would eat the batch's
        # remaining mandatory demand
        from repro.serving import block_store as BS

        need = 0
        hit_keys = set()
        for r in (*batch, req):
            reuse = self.reuse.get(r.rid)
            entry = self._admit_cache.get(r.rid)
            if entry is not None and entry[0] == store.version:
                _, bound, n_ins = entry
            else:
                plan, _, _, have = self.plans[r.rid]
                bound, n_ins = BS.admission_pages(
                    pool,
                    store,
                    plan,
                    have,
                    self.engine.sel,
                    reuse,
                    max(r.decode_steps - 1, 0),
                    bucket=self.engine.bucket,
                )
                self._admit_cache[r.rid] = (store.version, bound, n_ins)
            need += bound
            if reuse is not None:
                for ref in reuse.blocks:
                    if store.has(ref.key):
                        hit_keys.add(ref.key)
                if reuse.user_key is not None and store.has(reuse.user_key):
                    hit_keys.add(reuse.user_key)
        free = pool.free_pages + store.reclaimable_pages(exclude=hit_keys)
        return need <= free

    def decode(self, batch: Sequence[PendingRequest]) -> float:
        t0 = time.perf_counter()
        rids = [r.rid for r in batch]
        logits = self.engine.decode(rids, [self.last_token[r] for r in rids])
        for rid, lg in zip(rids, logits):
            self._append(rid, self._pick(rid, lg))
        return time.perf_counter() - t0

    def _release(self, rid: int) -> None:
        self.engine.release(rid)
        self.last_token.pop(rid, None)
        self._admit_cache.pop(rid, None)

    def finish(self, req: PendingRequest) -> None:
        self._release(req.rid)
        self.sampling.pop(req.rid, None)
        self.stop_seqs.pop(req.rid, None)
        self._rngs.pop(req.rid, None)
        # finish_reason is kept: the session server reads it after the
        # completion is retired to label the terminal StreamEvent

    def preempt(self, req: PendingRequest) -> None:
        """Release pages/refs for a mid-decode eviction, keeping the
        request re-runnable (subclasses that drop plans in `finish`
        must NOT drop them here — the victim re-prefills).  Sampling
        params and stop sequences are kept too; the PRNG is reset so the
        re-run replays the identical token stream from its seed."""
        JaxEngineBackend._release(self, req.rid)
        self._rngs.pop(req.rid, None)
        self.finish_reason.pop(req.rid, None)

    # ----------------------------- migration ------------------------------
    def export_request_kv(self, rid: int):
        """Snapshot one request as an engine `RequestKV` record with the
        backend's sampling watermarks attached (generated stream, rng
        state, stop criteria, plan/reuse payloads) — everything a
        different backend needs to continue the request mid-stream.
        Read-only; call `evacuate` only after the import succeeded."""
        rec = self.engine.export_request_kv(rid)
        rec.session = {
            "last_token": self.last_token.get(rid),
            "generated": self.generated.get(rid),
            "sampling": self.sampling.get(rid),
            "stop": self.stop_seqs.get(rid),
            "rng": self._rngs.get(rid),
            "finish_reason": self.finish_reason.get(rid),
            "plan": self.plans.get(rid),
            "reuse": self.reuse.get(rid),
        }
        return rec

    def import_request_kv(self, rec) -> Dict[str, int]:
        """Install a migrated request: engine-side pages/store refs plus
        the session watermarks.  -> the engine's migration counters.
        Transactional through the engine (`PoolExhausted` rolls back)."""
        counters = self.engine.import_request_kv(rec)
        rid = rec.rid
        s = rec.session or {}
        if s.get("last_token") is not None:
            self.last_token[rid] = s["last_token"]
        if s.get("generated") is not None:
            self.generated[rid] = list(s["generated"])
        for key, store in (
            ("sampling", self.sampling),
            ("stop", self.stop_seqs),
            ("rng", self._rngs),
            ("finish_reason", self.finish_reason),
            ("plan", self.plans),
            ("reuse", self.reuse),
        ):
            if s.get(key) is not None:
                store[rid] = s[key]
        return counters

    def evacuate(self, rid: int) -> None:
        """Source-side cleanup after a successful migration: drop every
        trace of the request here (pages, store refs, chunk state,
        session maps) — the destination backend owns it now."""
        self.engine.abort_prefill(rid)
        for store in (
            self.last_token,
            self.generated,
            self._admit_cache,
            self.sampling,
            self.stop_seqs,
            self._rngs,
            self.finish_reason,
            self.plans,
            self.reuse,
        ):
            store.pop(rid, None)

    # ------------------------- chunked discipline -------------------------
    def begin_prefill(self, req: PendingRequest) -> None:
        """Admit one request into chunk-resumable prefill (claims its
        pool pages and resolves the block store — see
        `BatchEngine.begin_prefill`)."""
        if self.mode != "rcllm":
            raise ValueError(
                "sched='chunked' drives the beyond-prefix selective "
                "prefill; mode='full' has no chunk-resumable path"
            )
        self.engine.begin_prefill(self._batch_requests([req])[0])

    def step(
        self,
        budget: int,
        decode_batch: Sequence[PendingRequest],
        prefill_queue: Sequence[PendingRequest],
    ):
        """One unified engine tick; -> (StepReport, seconds).  Samples
        greedy tokens for whatever the tick produced (decode logits for
        the running set, first tokens for finalized prefills)."""
        t0 = time.perf_counter()
        rids = [r.rid for r in decode_batch]
        rep = self.engine.step(
            budget,
            rids,
            [self.last_token[r] for r in rids],
            [r.rid for r in prefill_queue],
        )
        if rep.decode_logits is not None:
            for rid, lg in zip(rids, rep.decode_logits):
                self._append(rid, self._pick(rid, lg))
        for rid, lg in rep.finalized.items():
            self._append(rid, self._pick(rid, lg), first=True)
        return rep, time.perf_counter() - t0

    def preempt_prefill(self, req: PendingRequest) -> None:
        """Roll back a mid-prefill preemption: the engine drops the
        chunk state and frees pages + store refs; plans are KEPT so the
        victim can re-prefill after readmission."""
        self.engine.abort_prefill(req.rid)
        self.last_token.pop(req.rid, None)
        self._admit_cache.pop(req.rid, None)
        self._rngs.pop(req.rid, None)
        self.finish_reason.pop(req.rid, None)


class WorkerState:
    """One serving instance inside a (possibly multi-worker) batching loop.

    Owns its backend, FIFO admission queue, prefilling set (chunked
    sched), decode set and clock.  The loop only ever adds
    backend-reported step seconds to `clock`, so K workers model K
    instances running in parallel regardless of how their steps actually
    execute (virtual clock, or serialized on one host's wall clock).
    Backpressure is per worker and — under the chunked discipline — per
    tick: a full KV pool stalls this worker's admission queue at the
    tick boundary and nobody else's.
    """

    def __init__(
        self,
        backend: EngineBackend,
        wid: int = 0,
        max_batch_tokens: int = 8192,
        max_decode_batch: int = 64,
        sched: str = "wave",
        chunk_tokens: int = 128,
        step_tokens: Optional[int] = None,
        role: str = "unified",
        now: Optional[Callable[[], float]] = None,
    ):
        if sched not in ("wave", "chunked"):
            raise ValueError(f"unknown sched {sched!r}")
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown worker role {role!r}")
        if sched == "chunked" and not hasattr(backend, "begin_prefill"):
            raise ValueError(
                "sched='chunked' needs a chunk-capable backend "
                "(JaxEngineBackend); the simulator is wave-only"
            )
        self.backend = backend
        self.wid = wid
        # role-typed tick phases (prefill/decode disaggregation): a
        # 'prefill' worker admits and prefills, then hands each finished
        # request to `migrate` instead of entering its own decode set; a
        # 'decode' worker never admits — it receives migrated requests
        # through `receive_migration`.  'unified' (the default) runs
        # both phases exactly as before.
        self.role = role
        # migration hook, set by the cluster: (worker, entry, admitted_s)
        # -> True when the request was handed off to a decode worker
        self.migrate: Optional[Callable] = None
        # migrated requests awaiting their transfer-delayed start:
        # (available_t, DecodeEntry, admitted_s)
        self.inbound: List[tuple] = []
        self.migrated_out = 0
        # rids a decode-role worker preempted itself and may re-admit
        self._preempt_ok: set = set()
        self.max_batch_tokens = max_batch_tokens
        self.max_decode_batch = max_decode_batch
        self.sched = sched
        self.chunk_tokens = chunk_tokens
        # the per-tick token budget: room for one chunk per default
        # decode batch plus slack, so decode alone can't starve prefill
        self.step_tokens = (
            step_tokens
            if step_tokens is not None
            else max(4 * chunk_tokens, 512)
        )
        # the server's wall clock, when a server drives this worker:
        # `TickRecord.wall_s` and `.waits` are read on it
        self.now = now
        self.clock = 0.0
        self.busy_seconds = 0.0  # step time only, no idle gaps
        self.preempted = 0  # decode-time pool-pressure victims
        self._preempt_counts: Dict[int, int] = {}
        self.waiting: List[PendingRequest] = []
        self.prefilling: List[PendingRequest] = []  # chunked sched only
        # decode set, rid-keyed (insertion-ordered, so batch slicing is
        # FIFO); equality/identity lookups on PendingRequest alias
        # equal-arrival requests — rids are the only safe key
        self.decoding: Dict[int, DecodeEntry] = {}
        self.done: List[Completion] = []
        self.ticks: List[TickRecord] = []
        self.tbt: List[float] = []  # time-between-tokens samples
        self._admit_t: Dict[int, float] = {}
        self._last_tok_t: Dict[int, float] = {}
        # measured service rates (EWMA over observed steps) — these feed
        # the router's live queue-depth estimate, so load balancing uses
        # what this worker actually costs, not an a-priori model
        self._prefill_s_per_tok = 0.0
        self._decode_s_per_step = 0.0

    def has_work(self) -> bool:
        return bool(
            self.waiting or self.prefilling or self.decoding or self.inbound
        )

    def ready_time(self) -> float:
        """Earliest instant this worker can take its next step."""
        if self.decoding or self.prefilling:
            return self.clock
        due = []
        if self.waiting:
            due.append(self.waiting[0].arrival_s)
        if self.inbound:
            due.append(min(x[0] for x in self.inbound))
        if not due:
            return self.clock
        return max(self.clock, min(due))

    def receive_migration(
        self,
        entry: DecodeEntry,
        available_t: float,
        admitted_s: float,
        prefilling: bool = False,
    ) -> None:
        """Accept a migrated request: it joins the decode set at
        `available_t` (the source's handoff time plus the billed
        transfer seconds), carrying its already-sampled first token.
        A chunk-partial handoff (`prefilling=True` — the live
        `PrefillState` rode the KV record) joins the prefilling set
        instead and resumes chunking on this worker's engine."""
        self.inbound.append((available_t, entry, admitted_s, prefilling))

    def _accept_inbound(self) -> None:
        """Move transfer-complete migrations into the decode (or, for
        chunk-partial handoffs, prefilling) set."""
        due = [x for x in self.inbound if x[0] <= self.clock]
        if not due:
            return
        self.inbound = [x for x in self.inbound if x[0] > self.clock]
        for t, entry, admitted_s, prefilling in due:
            rid = entry.req.rid
            self._admit_t[rid] = admitted_s
            if prefilling:
                self.prefilling.append(entry.req)
                continue
            self._last_tok_t[rid] = t
            self.decoding[rid] = entry

    def _check_role_waiting(self) -> None:
        """Decode-role workers never take dispatched admissions; the one
        exception is a migrated request this worker itself preempted
        under pool pressure (it re-prefills locally from the plan the
        import installed)."""
        if self.role != "decode":
            return
        bad = [r for r in self.waiting if r.rid not in self._preempt_ok]
        if bad:
            raise RuntimeError(
                f"decode-role worker {self.wid} was dispatched request "
                f"{bad[0].rid}: admissions must route to prefill workers"
            )

    def backlog_seconds(self, t: float) -> float:
        """Estimated seconds of outstanding work as seen at time `t`:
        busy time already on the clock plus queued work at this worker's
        measured service rates (0 until the first step is observed)."""
        est = max(self.clock - t, 0.0)
        est += sum(r.n_tokens for r in self.waiting) * self._prefill_s_per_tok
        est += sum(r.n_tokens for r in self.prefilling) * self._prefill_s_per_tok
        if self.decoding:
            est += (
                max(e.steps_left for e in self.decoding.values())
                * self._decode_s_per_step
            )
        return est

    @staticmethod
    def _ewma(old: float, new: float) -> float:
        return new if old == 0.0 else 0.5 * old + 0.5 * new

    def _stopped(self, rid: int) -> bool:
        es = getattr(self.backend, "early_stop", None)
        return es is not None and es(rid)

    def step(self) -> None:
        if self.sched == "chunked":
            self._step_chunked()
        else:
            self._step_wave()

    # ------------------------------ wave sched ------------------------------
    def _step_wave(self) -> None:
        """One scheduling step: a prefill batch if one can form under the
        token budget and pool capacity, else one decode iteration
        (prefill-prioritized, identical to the seed single-instance loop).
        """
        self.clock = self.ready_time()
        self._accept_inbound()
        batch: List[PendingRequest] = []
        tok = 0
        self._check_role_waiting()
        for r in self.waiting:
            if r.arrival_s > self.clock:
                break
            if tok + r.n_tokens > self.max_batch_tokens and batch:
                break
            if not self.backend.can_admit(r, batch):
                # strict FCFS under backpressure: never admit a
                # younger request past one waiting on capacity
                # (head-of-line wait beats unbounded starvation)
                break
            batch.append(r)
            tok += r.n_tokens
        if not batch and not self.decoding:
            if not self.waiting:
                return  # only future inbound migrations
            raise RuntimeError(
                f"request {self.waiting[0].rid} ({self.waiting[0].n_tokens} "
                "tokens) can never be admitted: KV pool too small "
                "even with no other request running"
            )
        if batch:
            admitted = self.clock
            # remove by rid: PendingRequest equality compares only
            # arrival_s (the sort key), so equal-arrival requests would
            # alias under list.remove
            picked = {r.rid for r in batch}
            self.waiting = [r for r in self.waiting if r.rid not in picked]
            dt = self.backend.prefill(batch)
            self.clock += dt
            self.busy_seconds += dt
            self._prefill_s_per_tok = self._ewma(
                self._prefill_s_per_tok, dt / max(tok, 1)
            )
            for r in batch:
                stopped = self._stopped(r.rid)
                if r.decode_steps <= 1 or stopped:  # TTFT token was the output
                    self.done.append(
                        Completion(
                            r.rid,
                            r.arrival_s,
                            self.clock,
                            self.clock,
                            self.wid,
                            admitted_s=admitted,
                            reason="stop" if stopped else "length",
                        )
                    )
                    self.backend.finish(r)
                else:
                    entry = DecodeEntry(
                        r, self.clock - r.arrival_s, r.decode_steps - 1
                    )
                    if (
                        self.role == "prefill"
                        and self.migrate is not None
                        and self.migrate(self, entry, admitted)
                    ):
                        self.migrated_out += 1
                        continue  # a decode worker owns it now
                    self._admit_t[r.rid] = admitted
                    self._last_tok_t[r.rid] = self.clock
                    self.decoding[r.rid] = entry
        else:
            while True:
                db = list(self.decoding.values())[: self.max_decode_batch]
                try:
                    dt = self.backend.decode([e.req for e in db])
                    break
                except PoolExhausted:
                    # decode could not claim a KV slot for every running
                    # request: preempt the youngest (free its pages,
                    # requeue it for a fresh prefill) instead of letting
                    # the error kill the worker and leak every running
                    # request's pages — then retry so the survivors step
                    # past the growth boundary *before* the next prefill
                    # can re-admit the victim into the same conflict
                    self._preempt_youngest()
                    if not self.decoding:
                        return
            self.clock += dt
            self.busy_seconds += dt
            self._decode_s_per_step = self._ewma(self._decode_s_per_step, dt)
            for e in db:
                e.steps_left -= 1
                if self._stopped(e.req.rid):
                    e.steps_left = 0
                self._sample_tbt(e.req.rid)
            self._retire_decoded()

    # ---------------------------- chunked sched ----------------------------
    def _step_chunked(self) -> None:
        """One unified tick: admit what fits, then run one budgeted
        engine step packing decode tokens for every running request
        plus prefill chunks/finalizes for the admitted set.  The tick's
        spans, counters and compiles land on its `TickRecord`."""
        wall_s = self.now() if self.now is not None else None
        with tracing.Tick() as acc:
            ran = self._tick_chunked()
        if ran is None:
            return
        rep, arrival = ran
        waits = () if wall_s is None else tuple(
            (rid, wall_s - arrival[rid]) for rid in rep.started)
        self.ticks.append(
            TickRecord(
                t=self.clock,
                seconds=acc.seconds,
                decode_tokens=rep.charge_decode,
                chunk_tokens=rep.charge_chunks,
                finalize_tokens=rep.charge_finalize,
                oversized=rep.oversized,
                wall_s=wall_s,
                phase_s=tuple(acc.phase_s.items()),
                h2d_bytes=acc.counts["h2d_bytes"],
                d2h_bytes=acc.counts["d2h_bytes"],
                compile_s=tuple(acc.compile_s().items()),
                waits=waits,
                n_finalized=len(rep.finalized),
            )
        )

    def _tick_chunked(self):
        """The tick's work.  -> None when nothing ran, else (StepReport,
        arrival time by rid of the requests prefilling in it)."""
        self.clock = self.ready_time()
        self._accept_inbound()
        self._admit_chunked()
        if not self.decoding and not self.prefilling:
            return  # only future inbound migrations
        while True:
            db = list(self.decoding.values())[: self.max_decode_batch]
            try:
                rep, dt = self.backend.step(
                    self.step_tokens,
                    [e.req for e in db],
                    self.prefilling,
                )
                break
            except PoolExhausted:
                # same retry contract as the wave loop, per tick: evict
                # the youngest request (mid-prefill victims roll their
                # chunk state back; mid-decode victims free their pages)
                # and retry before any prefill work runs
                self._preempt_youngest()
                if not self.decoding and not self.prefilling:
                    return
        self.clock += dt
        self.busy_seconds += dt
        # apportion the tick's seconds across work kinds by token charge
        # so the router's backlog estimate prices queued/mid-scan prompt
        # tokens and decode steps separately (a single EWMA over whole
        # ticks would report zero prefill cost and blind Eq. 2 dispatch
        # to prompt backlog)
        charge = max(rep.charged, 1)
        pf_tokens = rep.charge_chunks + rep.charge_finalize
        if pf_tokens:
            self._prefill_s_per_tok = self._ewma(self._prefill_s_per_tok, dt / charge)
        if rep.charge_decode:
            self._decode_s_per_step = self._ewma(
                self._decode_s_per_step, dt * rep.charge_decode / charge
            )
        arrival = {r.rid: r.arrival_s for r in self.prefilling}
        if rep.decode_logits is not None:
            for e in db:
                e.steps_left -= 1
                if self._stopped(e.req.rid):
                    e.steps_left = 0
                self._sample_tbt(e.req.rid)
            self._retire_decoded()
        finalized = [r for r in self.prefilling if r.rid in rep.finalized]
        self.prefilling = [r for r in self.prefilling if r.rid not in rep.finalized]
        for req in finalized:
            admitted = self._admit_t.get(req.rid, req.arrival_s)
            stopped = self._stopped(req.rid)
            if req.decode_steps <= 1 or stopped:
                self._admit_t.pop(req.rid, None)
                self.done.append(
                    Completion(
                        req.rid,
                        req.arrival_s,
                        self.clock,
                        self.clock,
                        self.wid,
                        admitted_s=admitted,
                        reason="stop" if stopped else "length",
                    )
                )
                self.backend.finish(req)
            else:
                entry = DecodeEntry(
                    req, self.clock - req.arrival_s, req.decode_steps - 1
                )
                if (
                    self.role == "prefill"
                    and self.migrate is not None
                    and self.migrate(self, entry, admitted)
                ):
                    self.migrated_out += 1
                    self._admit_t.pop(req.rid, None)
                    continue  # a decode worker owns it now
                self._last_tok_t[req.rid] = self.clock
                self.decoding[req.rid] = entry
        return rep, arrival

    def _admit_chunked(self) -> None:
        """Move due arrivals into the prefilling set, FIFO, while pool
        capacity allows — admission charges chunks, so an admitted
        request competes for the step budget from this tick on."""
        self._check_role_waiting()
        while self.waiting:
            r = self.waiting[0]
            if r.arrival_s > self.clock:
                break
            if not self.backend.can_admit(r):
                break
            try:
                self.backend.begin_prefill(r)
            except PoolExhausted:
                break
            self.waiting.pop(0)
            self.prefilling.append(r)
            self._admit_t[r.rid] = self.clock
        if not self.decoding and not self.prefilling and self.waiting:
            raise RuntimeError(
                f"request {self.waiting[0].rid} ({self.waiting[0].n_tokens} "
                "tokens) can never be admitted: KV pool too small "
                "even with no other request running"
            )

    # ------------------------------- shared -------------------------------
    def _sample_tbt(self, rid: int) -> None:
        last = self._last_tok_t.get(rid)
        if last is not None:
            self.tbt.append(self.clock - last)
        self._last_tok_t[rid] = self.clock

    def _retire_decoded(self) -> None:
        spent = [rid for rid, e in self.decoding.items() if e.steps_left <= 0]
        for rid in spent:
            e = self.decoding.pop(rid)
            req = e.req
            self.done.append(
                Completion(
                    req.rid,
                    req.arrival_s,
                    req.arrival_s + e.ttft_s,
                    self.clock,
                    self.wid,
                    admitted_s=self._admit_t.pop(rid, req.arrival_s),
                    reason="stop" if self._stopped(rid) else "length",
                )
            )
            self._last_tok_t.pop(rid, None)
            self.backend.finish(req)

    def _preempt_youngest(self) -> None:
        """Evict the youngest running request under pool pressure:
        release its resources and put it back in the arrival queue (it
        will re-prefill — deterministic sampling regenerates the same
        tokens, so only its latency suffers).  Under the chunked
        discipline the victim set includes mid-prefill requests; their
        chunk state rolls back cleanly (`preempt_prefill`) and the plan
        is kept."""
        cands = [e.req for e in self.decoding.values()] + list(self.prefilling)
        req = max(cands, key=lambda r: (r.arrival_s, r.rid))
        self._preempt_counts[req.rid] = self._preempt_counts.get(req.rid, 0) + 1
        if self._preempt_counts[req.rid] > 8:
            raise RuntimeError(
                f"request {req.rid} preempted {self._preempt_counts[req.rid]}"
                " times: the pool cannot hold its decode tokens even "
                "alone — backend decode-page reservation is broken"
            )
        if any(r.rid == req.rid for r in self.prefilling):
            if (
                self.role == "prefill"
                and self.migrate is not None
                and self.migrate(
                    self,
                    DecodeEntry(req, 0.0, req.decode_steps),
                    self._admit_t.get(req.rid, req.arrival_s),
                )
            ):
                # chunk-partial handoff instead of preemption: the live
                # PrefillState rode the KV record to a decode worker,
                # which resumes chunking there; the migrate hook's
                # evacuate already freed this worker's pages, so pool
                # pressure is relieved without losing the scan progress
                self.prefilling = [r for r in self.prefilling if r.rid != req.rid]
                self._admit_t.pop(req.rid, None)
                self.migrated_out += 1
                return
            self.prefilling = [r for r in self.prefilling if r.rid != req.rid]
            self._admit_t.pop(req.rid, None)
            self.backend.preempt_prefill(req)
        else:
            self.decoding.pop(req.rid)
            self._last_tok_t.pop(req.rid, None)
            self._admit_t.pop(req.rid, None)
            self.backend.preempt(req)
        self.preempted += 1
        if self.role == "decode":
            # a migrated request evicted here re-prefills locally — its
            # plan/session already live on this backend, and its source
            # prefill worker evacuated it at handoff
            self._preempt_ok.add(req.rid)
        bisect.insort(self.waiting, req)

    def cancel(self, rid: int) -> Optional[str]:
        """Cancel a request wherever it currently lives, rolling pool
        state back through the same seams preemption uses: a waiting
        request is simply dequeued; a mid-prefill request drops its
        chunk state, pages and store refs (`preempt_prefill`); a
        mid-decode request releases through `finish`.  -> the stage it
        was cancelled in, or None if unknown here (already completed, or
        dispatched to a different worker).  Call only at a tick boundary
        (never mid-`step`)."""
        for i, r in enumerate(self.waiting):
            if r.rid == rid:
                del self.waiting[i]
                return "waiting"
        for i, r in enumerate(self.prefilling):
            if r.rid == rid:
                del self.prefilling[i]
                self._admit_t.pop(rid, None)
                self.backend.preempt_prefill(r)
                self.backend.finish(r)  # release is idempotent; drops
                return "prefilling"  # plans + session state for good
        e = self.decoding.pop(rid, None)
        if e is not None:
            self._admit_t.pop(rid, None)
            self._last_tok_t.pop(rid, None)
            self.backend.finish(e.req)
            return "decoding"
        return None


# dispatch hook: (request, arrival time, workers) -> worker index
DispatchFn = Callable[[PendingRequest, float, List[WorkerState]], int]


def least_backlog_dispatch(
    req: PendingRequest, t: float, workers: List[WorkerState]
) -> int:
    """Default dispatch: the worker with the least estimated backlog."""
    return min(range(len(workers)), key=lambda i: (workers[i].backlog_seconds(t), i))


class ClusterBatcher:
    """Continuous batching across K workers sharing one arrival stream.

    Each worker is an independent `WorkerState` over its own backend
    (own KV pool, own clock, own backpressure); `dispatch` assigns every
    arrival to a worker *at its arrival time*, seeing live worker state —
    the Eq. 2 affinity router plugs in here (`serving.cluster`).  Events
    are processed in global time order: an arrival is dispatched only
    once every busy worker's next step lies at or after it, so queue
    depths observed by the router are exactly what a real global
    scheduler would see.
    """

    def __init__(
        self,
        backends: Sequence[EngineBackend],
        dispatch: Optional[DispatchFn] = None,
        max_batch_tokens: int = 8192,
        max_decode_batch: int = 64,
        sched: str = "wave",
        chunk_tokens: int = 128,
        step_tokens: Optional[int] = None,
    ):
        self.workers = [
            WorkerState(
                b,
                wid=i,
                max_batch_tokens=max_batch_tokens,
                max_decode_batch=max_decode_batch,
                sched=sched,
                chunk_tokens=chunk_tokens,
                step_tokens=step_tokens,
            )
            for i, b in enumerate(backends)
        ]
        self.dispatch = dispatch or least_backlog_dispatch

    def run(self, requests: Sequence[PendingRequest]) -> List[Completion]:
        # every per-request map in the loop (decode set, admit times,
        # backend plans/sessions) is rid-keyed, so duplicate rids would
        # silently cross streams — reject them up front
        seen: set = set()
        for r in requests:
            if r.rid in seen:
                raise ValueError(f"duplicate request rid {r.rid}")
            seen.add(r.rid)
        pending = sorted(requests)
        i = 0
        while i < len(pending) or any(w.has_work() for w in self.workers):
            busy = [w for w in self.workers if w.has_work()]
            t_work = min((w.ready_time() for w in busy), default=math.inf)
            t_arr = pending[i].arrival_s if i < len(pending) else math.inf
            if t_arr <= t_work:
                req = pending[i]
                i += 1
                wid = int(self.dispatch(req, t_arr, self.workers))
                self.workers[wid].waiting.append(req)
            else:
                min(busy, key=lambda w: (w.ready_time(), w.wid)).step()
        done = [c for w in self.workers for c in w.done]
        done.sort(key=lambda c: c.done_s)  # stable: in-step order kept
        return done


class ContinuousBatcher:
    """Single-instance continuous batching over an `EngineBackend`.

    Backward-compatible construction: passing `prefill_time_fn` /
    `decode_time_fn` (the seed API) wraps them in a `SimBackend`.
    Internally this is a one-worker `ClusterBatcher`.
    """

    def __init__(
        self,
        prefill_time_fn: Optional[Callable[[int], float]] = None,
        decode_time_fn: Optional[Callable[[int], float]] = None,
        max_batch_tokens: int = 8192,
        max_decode_batch: int = 64,
        backend: Optional[EngineBackend] = None,
        sched: str = "wave",
        chunk_tokens: int = 128,
        step_tokens: Optional[int] = None,
    ):
        if backend is None:
            if prefill_time_fn is None or decode_time_fn is None:
                raise ValueError("need a backend or both time functions")
            backend = SimBackend(prefill_time_fn, decode_time_fn)
        self.backend = backend
        self.max_batch_tokens = max_batch_tokens
        self.max_decode_batch = max_decode_batch
        self.sched = sched
        self.chunk_tokens = chunk_tokens
        self.step_tokens = step_tokens
        self.workers: List[WorkerState] = []

    def run(self, requests: List[PendingRequest]) -> List[Completion]:
        cb = ClusterBatcher(
            [self.backend],
            dispatch=lambda req, t, ws: 0,
            max_batch_tokens=self.max_batch_tokens,
            max_decode_batch=self.max_decode_batch,
            sched=self.sched,
            chunk_tokens=self.chunk_tokens,
            step_tokens=self.step_tokens,
        )
        self.workers = cb.workers
        return cb.run(requests)
