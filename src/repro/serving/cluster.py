"""Multi-instance real serving: K JAX engines over sharded item caches.

This is the distributed half of the paper running on real engines rather
than the analytic simulator: `ClusterEngine` instantiates K
`serving.batch_engine.BatchEngine` workers — each with its own
`PagedKVPool`, its own continuous-batching queue and its own
Algorithm-1 item-cache shard (hot items replicated everywhere, long-tail
items resident only on their shard) — behind the Eq. 2 affinity
scheduler, which dispatches every arrival using *live* per-worker
backlog and the real placement map.

Residency is enforced, not simulated: a request routed to a worker whose
shard lacks one of its item blocks triggers an explicit transfer step —
the bytes are pulled from the holder shard through
`core.item_cache.ShardClient` (ledgered per block) and the worker's
clock is charged the modeled network time (`core.cost_model.fetch_time_s`
with the paper's 100 Gbps interconnect) — or, with `config.mesh`
enabled, the *measured* wall time of a real `jax.device_put`
device-to-device copy between the workers' home devices.  Routing
therefore changes *where* a request runs and what it costs, never
*what* it decodes: the
staged bytes are identical on every worker, which the parity tests pin
down.

Wall-clock semantics: the K engines execute serially on this host, but
each worker's clock accumulates only its own backend-reported step
seconds — the cluster models K instances running in parallel on
dedicated hardware (per-worker TTFT is each instance's own wall work).
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core import assembly as ASM
from repro.core import cost_model as CM
from repro.core import engine as ENG
from repro.core import item_cache as IC
from repro.core import scheduler as SCH
from repro.data import synth as SY
from repro.serving import api as API
from repro.serving import workload as WL
from repro.serving.batch_engine import BatchEngine, RequestKV, migration_bytes
from repro.serving.batching import (
    ClusterBatcher,
    Completion,
    DecodeEntry,
    JaxEngineBackend,
    PendingRequest,
    WorkerState,
)
from repro.serving.kv_pool import PoolExhausted


class ClusterWorkerBackend(JaxEngineBackend):
    """`JaxEngineBackend` plus the explicit item-block transfer step.

    A request whose plan references blocks not resident on this worker's
    shard pays a modeled network transfer the first time it prefills;
    the bytes really were pulled from the peer shard (`ShardClient`
    ledger), so the step is measurable in both seconds and bytes.
    """

    def __init__(
        self,
        engine: BatchEngine,
        shard: Optional[IC.ShardClient] = None,
        mode: str = "rcllm",
        hw: CM.Hardware = CM.V5E_1,
    ):
        super().__init__(engine, mode=mode, plans={})
        self.shard = shard
        self.hw = hw
        self.pending_transfer_s: Dict[int, float] = {}  # rid -> seconds owed
        self.transfer_seconds = 0.0
        # cross-shard pulls skipped because the worker's shared block
        # store already held the (previously transferred) item bytes
        self.transfers_avoided = 0
        # KV-migration ledger (disaggregated serving): requests this
        # worker received mid-flight, the pages/bytes that moved, the
        # seconds billed, and store payloads skipped on a digest hit
        self.migrations_in = 0
        self.migrated_pages = 0
        self.migration_bytes = 0
        self.migration_seconds = 0.0
        self.migration_digest_hits = 0

    def prefill(self, batch: Sequence[PendingRequest]) -> float:
        dt = super().prefill(batch)
        moved = sum(self.pending_transfer_s.pop(r.rid, 0.0) for r in batch)
        self.transfer_seconds += moved
        return dt + moved

    def step(self, budget, decode_batch, prefill_queue):
        """Per-tick accounting for the chunked discipline: a request's
        owed transfer time is billed in the tick its first prefill
        chunk runs (the staged bytes must be resident before layer 0
        reads the cached KV), not as a whole-wave surcharge."""
        rep, dt = super().step(budget, decode_batch, prefill_queue)
        moved = sum(self.pending_transfer_s.pop(rid, 0.0) for rid in rep.started)
        self.transfer_seconds += moved
        return rep, dt + moved

    def finish(self, req: PendingRequest) -> None:
        # unlike the single-engine backend (caller owns and may reuse the
        # plans dict across passes), the cluster binds each plan exactly
        # once at dispatch — release its assembled KV with the request,
        # or a long run retains every request's (n, L, Hkv, Dh) arrays
        super().finish(req)
        self.plans.pop(req.rid, None)
        self.reuse.pop(req.rid, None)
        self.pending_transfer_s.pop(req.rid, None)

    def evacuate(self, rid: int) -> None:
        super().evacuate(rid)
        self.pending_transfer_s.pop(rid, None)


@dataclass
class WorkerReport:
    worker: int
    n_requests: int
    mean_hit_rate: Optional[float]   # None when no request ran here
    transfer_blocks: int
    transfer_tokens: int
    transfer_bytes: int
    transfer_seconds: float
    pool_peak_pages: int
    busy_seconds: float
    preempted: int = 0
    # shared-block-store tier stats when kv_reuse is on (None otherwise):
    # user/item tier hit rates + pages held + transfers avoided
    kv_reuse: Optional[dict] = None
    # disaggregated serving: KV migrations this worker received
    # (decode role) / handed off (prefill role), and what they cost
    migrations: int = 0
    migrated_out: int = 0
    migrated_pages: int = 0
    migration_bytes: int = 0
    migration_s: float = 0.0
    migration_digest_hits: int = 0
    # tiered store: device/spill occupancy and tier-traffic counters
    # (zero everywhere unless kv_reuse is on)
    device_blocks: int = 0
    spill_blocks: int = 0
    spill_hits: int = 0
    prefetch_promotions: int = 0
    dequant_s: float = 0.0


@dataclass
class ClusterReport:
    """What one cluster run produced, per request and per worker."""

    completions: List[Completion]
    assigned: Dict[int, int]  # rid -> worker
    hit_rate: Dict[int, float]  # rid -> item-cache hit rate on its worker
    generated: Dict[int, List[int]]  # rid -> decoded tokens
    workers: List[WorkerReport]
    policy: str

    def ttft(self) -> np.ndarray:
        done = sorted(self.completions, key=lambda c: c.rid)
        return np.asarray([c.first_token_s - c.arrival_s for c in done])

    def mean_hit_rate(self) -> float:
        return float(np.mean(list(self.hit_rate.values())))

    def summary(self) -> dict:
        ttft = self.ttft()
        return {
            "policy": self.policy,
            "requests": len(self.completions),
            "mean_hit_rate": round(self.mean_hit_rate(), 4),
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p90_s": float(np.percentile(ttft, 90)),
            "ttft_mean_s": float(ttft.mean()),
            "transfer_blocks": sum(w.transfer_blocks for w in self.workers),
            "transfer_mbytes": round(
                sum(w.transfer_bytes for w in self.workers) / 1e6, 3
            ),
            "transfer_seconds": round(
                sum(w.transfer_seconds for w in self.workers), 6
            ),
        }


class ClusterEngine:
    """K real engine workers behind the Eq. 2 affinity dispatcher.

    `system` is an `RcLLMSystem` whose placement was built with
    `k_instances == config.k`; each worker w serves placement shard w.
    `config.mode` selects the prefill path ("rcllm" beyond-prefix
    selective, or "full" recompute — the latter never touches the item
    cache, so transfers and hit rates degenerate to the placement map
    only).

    Construction takes one `api.ServeConfig` — every engine / scheduler
    / backend / kernel / reuse knob lives there, validated up front.
    The historical per-knob keywords (``ClusterEngine(system, k=2,
    kv_reuse=True, ...)``) still work through a deprecation shim that
    folds them into a `ServeConfig`, with one `DeprecationWarning`.
    """

    #: legacy per-knob keywords the shim folds into a ServeConfig
    LEGACY_KW = frozenset(API.ServeConfig.LEGACY_FLAGS.values()) | {"max_decode_batch"}

    def __init__(
        self,
        system,
        config: Optional[API.ServeConfig] = None,
        *,
        sel: Optional[ENG.SelectiveConfig] = None,
        hw: CM.Hardware = CM.V5E_1,
        seed: int = 0,
        alpha: float = 0.7,
        beta: float = 0.3,
        devices: Optional[Sequence] = None,
        **legacy,
    ):
        if legacy:
            unknown = sorted(set(legacy) - self.LEGACY_KW)
            if unknown:
                raise TypeError(f"unknown ClusterEngine kwargs: {unknown}")
            keys = ",".join(
                f"{k}={API.render_value(v)}"
                for k, v in sorted(legacy.items())
                if v is not None
            )
            warnings.warn(
                "per-knob ClusterEngine keywords are deprecated; pass one "
                f"api.ServeConfig (--config {keys})",
                DeprecationWarning,
                stacklevel=2,
            )
            legacy = {k: v for k, v in legacy.items() if v is not None}
            if isinstance(legacy.get("kv_reuse"), str):
                legacy["kv_reuse"] = legacy["kv_reuse"] == "on"
            config = (config or API.ServeConfig()).replace(**legacy)
        if config is None:
            raise TypeError("ClusterEngine needs an api.ServeConfig (or legacy kwargs)")
        if config.engine != "jax":
            raise ValueError(
                f"ClusterEngine runs real engines; config.engine="
                f"{config.engine!r} (the simulator cluster is "
                "launch/serve.py run_sim)"
            )
        k, mode = config.k, config.mode
        if system.placement.k != k:
            raise ValueError(
                f"placement has {system.placement.k} shards, cluster wants "
                f"{k} workers: rebuild the system with k_instances={k}"
            )
        if mode == "rcllm" and system.item_store is None:
            raise ValueError(
                "mode='rcllm' needs the system's item store (the sharded "
                "item-KV pool); build the system with one, or use "
                "mode='full'"
            )
        self.system = system
        self.config = config
        self.k = k
        self.mode = mode
        self.hw = hw
        # the attention-backend seam: workers run the system's model under
        # the config's attention implementation (jnp reference vs the
        # Pallas kernels) — the offline caches were built once with the
        # system's config and are backend-invariant (pre-RoPE bytes)
        self.cfg = config.apply_to(system.cfg)
        self.kv_reuse = config.kv_reuse
        self._item_keys: Dict[int, tuple] = {}
        # every worker gets a home device, round-robin over `devices`
        # (default: this process's local devices).  Without a mesh the
        # worker's params, KV arena and block store live there — K
        # workers on K chips are K one-chip replicas behind the router.
        # Under a real mesh every engine spans the mesh instead, and the
        # home device only anchors transfers: cross-shard item pulls
        # become real jax.device_put device-to-device copies whose
        # *measured* wall time is billed instead of the modeled network
        # time
        import jax

        devs = list(devices) if devices is not None else jax.local_devices()
        homes = [devs[w % len(devs)] for w in range(k)]
        self.worker_devices = homes if config.mesh.enabled else None
        self.backends: List[ClusterWorkerBackend] = []
        for w in range(k):
            engine = API.build_engine(
                system.params,
                system.cfg,
                config,
                sel=sel,
                device=None if config.mesh.enabled else homes[w],
            )
            shard = None
            if system.item_store is not None:
                shard = IC.ShardClient(
                    system.item_store, w, devices=self.worker_devices
                )
            backend = ClusterWorkerBackend(engine, shard, mode=mode, hw=hw)
            self.backends.append(backend)
        self.scheduler = SCH.ClusterScheduler(
            system.placement,
            policy=config.policy,
            alpha=alpha,
            beta=beta,
            seed=seed,
        )
        self.batcher = ClusterBatcher(
            self.backends,
            dispatch=self._dispatch,
            max_batch_tokens=config.max_batch_tokens,
            max_decode_batch=config.max_decode_batch,
            sched=config.sched,
            chunk_tokens=config.chunk_tokens,
            step_tokens=config.step_tokens,
        )
        # disaggregated serving: type every worker, route admissions to
        # the prefill side, and register the migration hook that hands
        # finished prefills to a decode worker over the block-store
        # transport (unified config leaves every worker untyped)
        self.disagg = config.disagg
        self._prefill_ids = list(range(k))
        self._decode_ids: List[int] = []
        if self.disagg.enabled:
            self._prefill_ids = [
                w for w in range(k) if self.disagg.role_of(w) == "prefill"
            ]
            self._decode_ids = [
                w for w in range(k) if self.disagg.role_of(w) == "decode"
            ]
            for w, worker in enumerate(self.batcher.workers):
                worker.role = self.disagg.role_of(w)
                if worker.role == "prefill":
                    worker.migrate = self._migrate
        self._trace_by_rid: Dict[int, object] = {}
        self.assigned: Dict[int, int] = {}
        self.hit_rate: Dict[int, float] = {}

    # ------------------------------ dispatch ------------------------------
    def _dispatch(
        self, req: PendingRequest, t: float, workers: List[WorkerState]
    ) -> int:
        rq = self._trace_by_rid[req.rid]
        if self.disagg.enabled:
            wid = self._dispatch_prefill(rq, t, workers)
        else:
            depths = [w.backlog_seconds(t) for w in workers]
            wid = self.scheduler.dispatch(rq.candidate_items, depths)
        self._bind(req, rq, wid)
        return wid

    def _dispatch_prefill(
        self, rq, t: float, workers: List[WorkerState]
    ) -> int:
        """Admission routing under disaggregation: the configured policy
        runs over the prefill workers only (decode workers never admit —
        they receive requests through migration)."""
        inds = self._prefill_ids
        sch = self.scheduler
        if sch.policy == "round_robin":
            wid = inds[sch.state.rr_next % len(inds)]
            sch.state.rr_next += 1
            return wid
        if sch.policy == "random":
            return int(sch.rng.choice(inds))
        depths = np.asarray(
            [workers[w].backlog_seconds(t) for w in inds], float
        )
        if sch.policy == "least_loaded":
            return inds[int(np.argmin(depths))]
        hits = SCH.hit_vector(
            np.asarray(rq.candidate_items), self.system.placement
        )[inds]
        hi = depths.max()
        load = depths / hi if hi > 0 else np.zeros_like(depths)
        if sch.policy == "hit_only":
            score = hits - 1e-9 * load
        elif sch.policy == "load_only":
            score = -load
        else:
            score = sch.alpha * hits + sch.beta * (1.0 - load)  # Eq. 2
        return inds[int(np.argmax(score))]

    # ------------------------------ migration ------------------------------
    def _migrate(
        self, src: WorkerState, entry: DecodeEntry, admitted_s: float
    ) -> bool:
        """Hand one finished prefill from `src` to a decode worker.

        Destination choice extends the Eq. 2 affinity score with a
        migration-byte term: `mig_gamma * (1 - bytes/max_bytes)` where
        each candidate's bytes are what it would *actually* move
        (`batch_engine.migration_bytes` — a worker whose shared block
        store already holds a payload's content key pays nothing for
        it).  Candidates are tried best-first; `PoolExhausted` on import
        rolls back and falls through to the next.  Returns False when no
        decode worker can take the request, in which case it simply
        decodes on the prefill worker (unified fallback).
        """
        rid = entry.req.rid
        src_backend = self.backends[src.wid]
        rec = src_backend.export_request_kv(rid)
        rq = self._trace_by_rid[rid]
        inds = self._decode_ids
        t = src.clock
        depths = np.asarray(
            [self.batcher.workers[w].backlog_seconds(t) for w in inds], float
        )
        hi = depths.max()
        load = depths / hi if hi > 0 else np.zeros_like(depths)
        hits = SCH.hit_vector(
            np.asarray(rq.candidate_items), self.system.placement
        )[inds]
        nbytes = np.asarray(
            [
                float(migration_bytes(rec, self.backends[w].engine.store))
                for w in inds
            ]
        )
        bmax = nbytes.max()
        bnorm = nbytes / bmax if bmax > 0 else np.zeros_like(nbytes)
        sch = self.scheduler
        score = (
            sch.alpha * hits
            + sch.beta * (1.0 - load)
            + self.disagg.mig_gamma * (1.0 - bnorm)
        )
        order = sorted(range(len(inds)), key=lambda i: (-score[i], inds[i]))
        for i in order:
            wid = inds[i]
            dst_backend = self.backends[wid]
            # snapshot what would travel BEFORE the import inserts the
            # missed payloads into the destination store
            store_d = dst_backend.engine.store
            moved = [rec.export.page_k, rec.export.page_v]
            for key, payload in rec.payloads.items():
                # resident() covers the spill tier too: a spilled key
                # re-stages from host RAM, so the transport moves nothing
                if store_d is None or not store_d.resident(key):
                    moved += [payload.host_k, payload.host_v]
            try:
                counters = dst_backend.import_request_kv(rec)
            except PoolExhausted:
                continue
            mig_s = self._migration_seconds(moved, src.wid, wid, counters)
            dst_backend.migrations_in += 1
            dst_backend.migrated_pages += counters["pages"]
            dst_backend.migration_bytes += counters["bytes"]
            dst_backend.migration_seconds += mig_s
            dst_backend.migration_digest_hits += counters["digest_hits"]
            self.batcher.workers[wid].receive_migration(
                entry,
                src.clock + mig_s,
                admitted_s,
                prefilling=rec.prefill is not None,
            )
            src_backend.evacuate(rid)
            return True
        return False

    def _migration_seconds(
        self, arrs: List[np.ndarray], src_wid: int, dst_wid: int,
        counters: Dict,
    ) -> float:
        """Bill one migration's transfer time: under a real mesh, the
        measured wall time of `jax.device_put` moving the travelling
        arrays (`arrs`, snapshotted pre-import) between the two workers'
        home devices (the `ShardClient` pull idiom); otherwise the
        modeled network time for the moved bytes on the paper's
        interconnect.  Digest-hit payloads never travel, so they cost
        nothing either way."""
        if counters["bytes"] == 0:
            return 0.0
        if self.worker_devices is not None:
            import jax

            src_dev = self.worker_devices[src_wid]
            dst_dev = self.worker_devices[dst_wid]
            staged = [jax.device_put(a, src_dev) for a in arrs if a.size]
            jax.block_until_ready(staged)
            t0 = time.perf_counter()
            moved = [jax.device_put(a, dst_dev) for a in staged]
            jax.block_until_ready(moved)
            return time.perf_counter() - t0
        cfg = self.system.cfg
        row_bytes = (
            2 * cfg.n_layers * cfg.n_kv_heads * cfg.resolved_head_dim * 4
        )
        moved_tokens = int(np.ceil(counters["bytes"] / row_bytes))
        return CM.fetch_time_s(cfg, self.hw, 0, moved_tokens)

    def _item_key(self, item: int) -> tuple:
        """Memoized content key of one catalog item's block (same token
        derivation as the offline `build_item_store`: SEP + item text)."""
        it = int(item)
        key = self._item_keys.get(it)
        if key is None:
            doc = np.concatenate(
                [[SY.ITEM_SEP], self.system.catalog.item_tokens[it]]
            ).astype(np.int64)
            key = WL.item_block_key(doc)
            self._item_keys[it] = key
        return key

    def _bind(self, req: PendingRequest, rq, wid: int) -> None:
        """Build the request's plan *for the chosen worker*, stage its
        item blocks against that worker's shard (recording transfers),
        and hand plan + assembled KV to the worker's backend.

        With `kv_reuse` on, staging consults the worker's shared block
        store first: an item whose bytes the store already holds is
        staged from the store's host copy — for a non-resident item that
        means the cross-shard pull (and its modeled network time) is
        skipped entirely, the ledgered transfer having been paid exactly
        once when the block first entered the store.
        """
        system = self.system
        backend = self.backends[wid]
        plan = system.plan_for(rq, wid)
        req.tokens = plan.tokens
        req.n_tokens = plan.n
        self.assigned[req.rid] = wid
        n_item = plan.n_local + plan.n_remote + plan.n_miss
        self.hit_rate[req.rid] = plan.n_local / max(n_item, 1)
        if self.mode != "rcllm":
            return
        items = np.unique(plan.block_item[plan.source == ASM.FROM_ITEM])
        store = backend.engine.store
        staged: Dict[int, IC.ItemBlock] = {}
        to_stage = []
        hint_keys = []
        for it in items:
            it = int(it)
            key = self._item_key(it) if store else None
            if store is not None and store.spill_cap > 0:
                # declare this request's item keys to the store now (the
                # Eq. 2 router just fixed the destination worker): a key
                # already in the spill tier queues for prefetch promotion,
                # a still-resident one registers interest so churn before
                # this request's admission auto-queues the hint
                hint_keys.append(key)
            blk_s = store.peek(key) if store else None
            if blk_s is None and store is not None:
                # spill tier: the bytes are still on this worker's host
                # RAM — stage from there (no cross-shard pull)
                blk_s = store.spill_peek(key)
            if blk_s is not None:
                staged[it] = IC.ItemBlock(
                    item_id=it,
                    tokens=blk_s.tokens,
                    k=blk_s.host_k,
                    v=blk_s.host_v,
                )
                if not backend.shard.resident(it):
                    backend.transfers_avoided += 1
            else:
                to_stage.append(it)
        if hint_keys:
            store.hint(hint_keys)
        pulled, moved_tokens = backend.shard.stage(to_stage)
        staged.update(pulled)
        ck, cv, have = ASM.gather_cached_kv(
            plan,
            IC.StagedBlocks(staged),
            system.semantic,
            wid,
            system.cfg.n_layers,
            system.cfg.n_kv_heads,
            system.cfg.resolved_head_dim,
        )
        backend.plans[req.rid] = (plan, ck, cv, have)
        if store is not None:
            backend.reuse[req.rid] = WL.build_request_reuse(
                plan,
                have,
                staged,
                WL.user_prefix_key(system.instruction, rq),
                len(system.instruction) + len(rq.history_tokens),
                item_keys=self._item_keys,
                instr_len=len(system.instruction),
            )
        if moved_tokens:
            if backend.shard.measures:
                # real device-to-device copies: bill what the wall clock
                # actually measured for this dispatch's pulls
                backend.pending_transfer_s[req.rid] = (
                    backend.shard.take_measured_s()
                )
            else:
                backend.pending_transfer_s[req.rid] = CM.fetch_time_s(
                    system.cfg, self.hw, 0, moved_tokens
                )

    # -------------------------------- run ---------------------------------
    def run(self, trace: Sequence, decode_steps: int = 4) -> ClusterReport:
        """Serve a synthetic request trace end to end. -> ClusterReport."""
        pend = []
        for rid, rq in enumerate(trace):
            self._trace_by_rid[rid] = rq
            req = PendingRequest(
                arrival_s=float(rq.arrival_s),
                rid=rid,
                n_tokens=0,  # set at dispatch, once the plan exists
                decode_steps=decode_steps,
            )
            pend.append(req)
        completions = self.batcher.run(pend)
        generated = {}
        workers = []
        for w, backend in enumerate(self.backends):
            generated.update(backend.generated)
            rids = [r for r, i in self.assigned.items() if i == w]
            shard = backend.shard
            hit = None
            if rids:
                hit = float(np.mean([self.hit_rate[r] for r in rids]))
            store = backend.engine.store
            reuse_stats = None
            if store is not None:
                reuse_stats = dict(store.stats())
                reuse_stats["transfers_avoided"] = backend.transfers_avoided
                for tier in ("user", "item", "prefix"):
                    h = reuse_stats[f"hits_{tier}"]
                    m = reuse_stats[f"misses_{tier}"]
                    reuse_stats[f"{tier}_hit_rate"] = h / max(h + m, 1)
            report = WorkerReport(
                worker=w,
                n_requests=len(rids),
                mean_hit_rate=hit,
                transfer_blocks=len(shard.transfers) if shard else 0,
                transfer_tokens=shard.transferred_tokens() if shard else 0,
                transfer_bytes=shard.transferred_bytes() if shard else 0,
                transfer_seconds=backend.transfer_seconds,
                pool_peak_pages=backend.engine.pool.peak_pages,
                busy_seconds=self.batcher.workers[w].busy_seconds,
                preempted=self.batcher.workers[w].preempted,
                kv_reuse=reuse_stats,
                migrations=backend.migrations_in,
                migrated_out=self.batcher.workers[w].migrated_out,
                migrated_pages=backend.migrated_pages,
                migration_bytes=backend.migration_bytes,
                migration_s=backend.migration_seconds,
                migration_digest_hits=backend.migration_digest_hits,
                device_blocks=(
                    reuse_stats["device_blocks"] if reuse_stats else 0
                ),
                spill_blocks=(
                    reuse_stats["spill_blocks"] if reuse_stats else 0
                ),
                spill_hits=reuse_stats["spill_hits"] if reuse_stats else 0,
                prefetch_promotions=(
                    reuse_stats["prefetch_promotions"] if reuse_stats else 0
                ),
                dequant_s=reuse_stats["dequant_s"] if reuse_stats else 0.0,
            )
            workers.append(report)
        return ClusterReport(
            completions=completions,
            assigned=dict(self.assigned),
            hit_rate=dict(self.hit_rate),
            generated=generated,
            workers=workers,
            policy=self.scheduler.policy,
        )
