"""Batched prefill/decode over the real JAX engine (the serving tentpole).

Two jitted steps drive every request:

* **prefill** — a padded multi-request step.  ``mode="full"`` runs the
  Full-Recompute batch (`core.engine._jit_batched_prefill`); ``mode=
  "rcllm"`` runs the beyond-prefix selective path *batched*
  (`core.engine.selective_prefill_batch`): requests are bucketed by
  (padded length, padded recompute budget), their plans and cached KV
  stacked, and one jitted layer-0 + one jitted selective step run per
  bucket — the same Eq. 3 scoring and layer stack as the single-request
  engine, shared code, not a copy.  Either way the prompt's pre-RoPE KV
  lands in the paged pool: cached spans are inserted block-granularly
  from the assembly plan, then only the recomputed tokens' fresh KV is
  scattered on top.

* **decode** — a single-token batched step that reads K/V *through the
  page tables*: one arena gather per step, keys realigned to their
  request positions by RoPE's group property, GQA attention over the
  variable-length batch, and the new token's KV written back into the
  arena inside the jit.

`cfg.attn_backend` selects the attention implementation inside both
steps: ``jnp`` (masked-einsum reference) or ``pallas`` — the selective
kernels for prefill and the **fused paged-decode attention kernel**
(`repro.kernels.paged_attention`) for decode, interpret mode off-TPU
and real Mosaic lowering on TPU.  Under the paged kernel no gather is
materialized at all: the per-request page view (`kv_pool.page_views`)
is scalar-prefetched and the kernel's BlockSpec index maps read the
referenced arena pages directly, with per-slot logical positions
doubling as the liveness mask and the fused RoPE realignment angles.
The jnp gather path stays on as the bitwise oracle (causality is
implied either way: the new token is the newest position in its row);
`cfg.decode_kernel` can pin either decode path independently of the
backend (`core.engine.decode_uses_paged`).

Shapes are bucketed (sequence bucket for prefill, page/batch buckets for
decode) so steady-state serving retraces O(1) times.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig
from repro.core import engine as ENG
from repro.core.assembly import RECOMPUTE, AssemblyPlan, plan_spans
from repro.kernels import default_interpret
from repro.kernels.paged_attention.ops import paged_decode_mha
from repro.kernels.paged_attention.ref import masked_decode_attention_ref
from repro.models import layers as L
from repro.serving import block_store as BS
from repro.serving.kv_pool import (
    KVExport,
    PagedKVPool,
    PoolExhausted,
    page_views,
    pool_for,
)

# Decode runs one query per request: a small q tile keeps the padded
# query block cheap while kv tiles stay MXU-sized.
DECODE_Q_BLOCK = 8


@dataclass
class BatchRequest:
    """One prompt for the batched engine.  `plan` + cached KV arrays are
    required for the selective (rcllm) path and ignored for full prefill.
    `n_reserve` pre-reserves page capacity for that many decode tokens so
    decode never has to grab pages from the free list mid-flight.
    `reuse` (optional) names the request's shareable blocks for a
    store-backed engine; without it the request stays fully private."""

    rid: int
    tokens: np.ndarray
    plan: Optional[AssemblyPlan] = None
    cached_k: Optional[np.ndarray] = None
    cached_v: Optional[np.ndarray] = None
    have: Optional[np.ndarray] = None
    n_reserve: int = 0
    reuse: Optional[BS.RequestReuse] = None


@dataclass
class PrefillState:
    """One request's chunk-resumable prefill, engine-side.

    Wraps the pure-compute `engine.ChunkedPrefill` with the pool and
    block-store bookkeeping the serving path needs: which logical
    positions were mapped at store slots when the request was admitted
    (`mapped_mask` — un-shared again at finalize for positions Eq. 3
    selects to recompute), and which store inserts are still owed once
    the request's fresh bytes exist (prefix/user tiers need computed
    KV, so their misses insert at finalize, unlike item blocks whose
    offline bytes insert at admission)."""

    req: BatchRequest
    cp: ENG.ChunkedPrefill
    mapped_mask: np.ndarray
    pending_prefix: Optional[tuple] = None
    pending_user: Optional[tuple] = None  # (key, u_pos)
    started: bool = False
    # buffered layer-0 rows awaiting the finalize scatter (lazy mode):
    # (positions, k0, v0) per completed chunk
    l0_buf: List[tuple] = field(default_factory=list)


@dataclass
class RequestKV:
    """One request's engine-side state as a handoff record — the unit a
    KV migration moves between workers.

    Everything `BatchEngine` used to keep implicitly per-request is
    factored out here: the pool snapshot (`export` — private page bytes
    + slot table), the store blocks the request references (`payloads`,
    riding their content keys so a destination holding a digest pays
    zero transfer), the engine stats, and — for a chunk-partial handoff
    — the live `PrefillState` (owed prefix/user inserts, mapped-mask,
    buffered layer-0 rows, chunk scan position), so `finalize_prefill`
    can run on a *different* engine than `begin_prefill`.  The serving
    layer adds the sampling watermarks (`session`: generated tokens,
    rng state, stop criteria) before routing.

    The pool/store payloads are self-contained host bytes; a partial
    handoff's `prefill.cp` additionally references the model params,
    which migration assumes are replicated across workers (they are —
    every cluster worker serves the same model).
    """

    rid: int
    export: "KVExport"
    held: List[tuple] = field(default_factory=list)  # store keys, w/ dups
    payloads: Dict[tuple, BS.BlockPayload] = field(default_factory=dict)
    stats: Optional[ENG.EngineStats] = None
    prefill: Optional["PrefillState"] = None
    session: Optional[dict] = None  # backend sampling watermarks

    @property
    def nbytes(self) -> int:
        """Worst-case payload: private pages + every store block."""
        return self.export.nbytes + sum(
            p.nbytes for p in self.payloads.values()
        )


def migration_bytes(rec: RequestKV, store: Optional[BS.SharedBlockStore]) -> int:
    """Bytes a worker holding `store` would actually move to import
    `rec`: the private pages always travel; a store payload travels only
    when its content key misses (the digest fast path)."""
    moved = rec.export.nbytes
    for key, payload in rec.payloads.items():
        if store is None or not store.resident(key):
            moved += payload.nbytes
    return moved


@dataclass
class StepReport:
    """What one unified `BatchEngine.step` tick executed and charged."""

    decode_logits: Optional[np.ndarray] = None
    finalized: Dict[int, np.ndarray] = field(default_factory=dict)
    started: List[int] = field(default_factory=list)
    chunked: List[int] = field(default_factory=list)
    charge_decode: int = 0
    charge_chunks: int = 0
    charge_finalize: int = 0
    oversized: bool = False

    @property
    def charged(self) -> int:
        return self.charge_decode + self.charge_chunks + self.charge_finalize


def _decode_attn(q, k_l, v_l, kv_valid):
    """One decode-layer attention on the gather path: q (N, Hq, Dh) vs
    rotated k_l/v_l (N, S+1, Hkv, Dh) under the per-row `kv_valid`
    (N, S+1) mask.

    Causality never needs positions here: the new token is the newest in
    its row, so the key-liveness mask IS the causal mask.  The body is
    `paged_attention.ref.masked_decode_attention_ref` — the SAME helper
    the paged kernel's oracle calls, so the two oracles (and their
    masking constant / dtype discipline) cannot drift apart.
    """
    return masked_decode_attention_ref(q, k_l, v_l, kv_valid)


def _decode_step(
    params,
    toks,
    slot_tables,
    seq_lens,
    new_pages,
    new_slots,
    page_ids,
    slot_pos,
    arena_k,
    arena_v,
    cfg: LMConfig,
):
    """One decode token per request, K/V read through slot tables.

    toks: (N,) last sampled token ids; slot_tables: (N, S) physical slot
    ids (logical order — entries may point into shared store pages);
    seq_lens: (N,) tokens resident *before* this step (= the new token's
    position); new_pages/new_slots: (N,) physical slot claimed for the
    new token's KV; page_ids/slot_pos: the page-granular view
    (`kv_pool.page_views`) the paged kernel consumes — tiny dummies on
    the gather path, where they are dead code.
    -> (logits (N, V), arena_k', arena_v').

    The paged route writes each layer's fresh K/V into the arena
    *before* attention, so the kernel reads the new token (tagged with
    logical position len) through the same page view as every cached
    token — the gather path's explicit concat disappears.

    Jitted by `_decode_step_jit` with the arenas donated where the
    backend implements donation (TPU/GPU), so the update is in-place;
    CPU doesn't, so there each step copies the arenas (fine at test
    scale).
    """
    N = toks.shape[0]
    page = arena_k.shape[3]
    S = slot_tables.shape[1]

    x = params["embed"][toks].astype(jnp.dtype(cfg.dtype))  # (N, D)
    if cfg.tie_embeddings:
        x = x * (cfg.d_model**0.5)
    pos_new = seq_lens.astype(jnp.int32)  # (N,)

    paged = ENG.decode_uses_paged(cfg)
    if not paged:
        # one arena gather per step: slot-granular, so a row may
        # interleave private pages with store-shared pages
        # -> (N, S, L, Hkv, Dh)
        kg = arena_k[slot_tables // page, :, :, slot_tables % page]
        vg = arena_v[slot_tables // page, :, :, slot_tables % page]
        slot_idx = jnp.arange(S)
        kv_pos = jnp.concatenate(
            [jnp.broadcast_to(slot_idx[None], (N, S)), pos_new[:, None]],
            axis=1,
        )
        kv_valid = jnp.concatenate(
            [slot_idx[None, :] < seq_lens[:, None], jnp.ones((N, 1), bool)],
            axis=1,
        )  # (N, S+1)

    for layer in range(cfg.n_layers):
        lp = ENG.layer_params(params, layer)
        h = L.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = jnp.einsum("nd,dhe->nhe", h, lp["wq"])
        k_new = jnp.einsum("nd,dhe->nhe", h, lp["wk"])  # pre-RoPE
        v_new = jnp.einsum("nd,dhe->nhe", h, lp["wv"])
        arena_k = arena_k.at[new_pages, layer, :, new_slots].set(
            k_new.astype(arena_k.dtype)
        )
        arena_v = arena_v.at[new_pages, layer, :, new_slots].set(
            v_new.astype(arena_v.dtype)
        )

        q = L.apply_rope(q[:, None], pos_new[:, None], cfg.rope_theta)[:, 0]
        if paged:
            o = paged_decode_mha(
                q,
                arena_k,
                arena_v,
                page_ids,
                slot_pos,
                layer=layer,
                rope_theta=cfg.rope_theta,
                q_block=DECODE_Q_BLOCK,
                interpret=default_interpret(),
            )
        else:
            k_l = jnp.concatenate([kg[:, :, layer], k_new[:, None]], axis=1)
            v_l = jnp.concatenate([vg[:, :, layer], v_new[:, None]], axis=1)
            k_l = L.apply_rope(k_l, kv_pos, cfg.rope_theta)  # realign
            o = _decode_attn(q, k_l, v_l, kv_valid)
        x = x + jnp.einsum("nhe,hed->nd", o.astype(x.dtype), lp["wo"])
        x = x + ENG.mlp_block(
            L.rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp, cfg
        )

    xf = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return xf @ head, arena_k, arena_v


@functools.lru_cache(maxsize=None)
def _decode_step_jit(donate: bool):
    """The jitted decode step, built on first use: with ``donate`` the
    arenas (args 8, 9) are donated so the per-token KV write is
    in-place.  Deciding here rather than at import keeps importing this
    module from touching any backend."""
    return jax.jit(
        _decode_step,
        static_argnums=(10,),
        donate_argnums=(8, 9) if donate else (),
    )


def _donates(arr) -> bool:
    """Does the backend holding `arr` implement buffer donation?"""
    return any(d.platform in ("tpu", "gpu") for d in arr.devices())


class BatchEngine:
    """Multi-request prefill + paged continuous decode on real hardware.

    ``batched_selective`` switches the rcllm prefill between the bucketed
    batched path (`engine.selective_prefill_batch`, the default) and the
    legacy per-request loop — kept for parity tests and the
    `bench_attn_backend` batched-vs-loop comparison.

    ``store`` (a `block_store.SharedBlockStore` over this engine's pool)
    turns on cross-request KV reuse for the rcllm path: prefill *compute*
    is unchanged, but pool insertion maps shareable positions at the
    store's pages and writes only the private remainder — decoded tokens
    are bitwise identical with or without it.
    """

    def __init__(
        self,
        params,
        cfg: LMConfig,
        pool: Optional[PagedKVPool] = None,
        sel: Optional[ENG.SelectiveConfig] = None,
        bucket: int = 64,
        decode_bucket: int = 8,
        batched_selective: bool = True,
        store: Optional[BS.SharedBlockStore] = None,
        chunk_tokens: int = 128,
        eager_kv_writes: Optional[bool] = None,
        mesh=None,
    ):
        # `mesh` is the jax.sharding.Mesh the params/arenas were placed on
        # (None = the classic unsharded engine).  The jitted steps need no
        # mesh plumbing — GSPMD propagates the input shardings — so the
        # engine only records it and rejects the single-device Pallas
        # decode route, which cannot run over sharded arenas.
        if (
            mesh is not None
            and dict(mesh.shape).get("model", 1) > 1
            and ENG.decode_uses_paged(cfg)
        ):
            raise ValueError(
                f"decode_kernel={cfg.decode_kernel!r} routes decode through "
                f"the single-device paged kernel, but the mesh model axis "
                f"has {dict(mesh.shape)['model']} devices: use "
                "decode_kernel='auto'/'gather' under tensor parallelism"
            )
        self.mesh = mesh
        self.params = params
        self.cfg = cfg
        self.pool = pool if pool is not None else pool_for(cfg, mesh=mesh)
        self.sel = sel or ENG.SelectiveConfig()
        self.bucket = bucket
        self.decode_bucket = decode_bucket
        self.batched_selective = batched_selective
        self.store = store
        self.chunk_tokens = chunk_tokens
        # chunked prefill writes each chunk's fresh layer-0 KV into the
        # pool as it completes.  With arena donation (TPU/GPU) the write
        # is in-place and eager per-tick writes are the natural
        # incremental mode; on CPU every eager scatter is a full-arena
        # copy, so the rows are buffered host-side and fused into the
        # finalize scatter instead — nothing reads a request's rows
        # before its decode starts, so the two modes are byte-identical.
        self.donate = _donates(self.pool.arena_k)
        if eager_kv_writes is None:
            eager_kv_writes = self.donate
        self.eager_kv_writes = eager_kv_writes
        self.store_refs: Dict[int, list] = {}
        self.last_stats: Dict[int, ENG.EngineStats] = {}
        self.prefill_states: Dict[int, PrefillState] = {}

    # ------------------------------ prefill --------------------------------
    def prefill(self, reqs: Sequence[BatchRequest], mode: str = "full") -> np.ndarray:
        """Prefill a batch; KV lands in the pool.  -> logits (N, V)."""
        if mode == "full":
            return self._prefill_full(reqs)
        if mode == "rcllm":
            if self.store is not None:
                return self._prefill_selective_shared(reqs)
            if self.batched_selective:
                return self._prefill_selective_batch(reqs)
            return np.stack([self._prefill_selective(r) for r in reqs])
        raise ValueError(mode)

    def admission_pages(self, r: BatchRequest) -> tuple:
        """(private-page bound, possible inserts) for one request — the
        batcher's `can_admit` accounting under cross-request reuse."""
        return BS.admission_pages(
            self.pool,
            self.store,
            r.plan,
            r.have,
            self.sel,
            r.reuse,
            r.n_reserve,
            bucket=self.bucket,
        )

    def _prefill_full(self, reqs: Sequence[BatchRequest]) -> np.ndarray:
        lens = [len(r.tokens) for r in reqs]
        S = max(self.bucket, -(-max(lens) // self.bucket) * self.bucket)
        # batch dim is a traced shape too: pad it to a bucket so varying
        # batch compositions reuse compiled steps (pad rows: one PAD
        # token at position 0, logits discarded, nothing pooled)
        N = -(-len(reqs) // self.decode_bucket) * self.decode_bucket
        toks = np.zeros((N, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, : lens[i]] = r.tokens
        last = np.zeros(N, np.int32)
        last[: len(reqs)] = [n - 1 for n in lens]
        logits, k, v = ENG._jit_batched_prefill(
            self.params, jnp.asarray(toks), jnp.asarray(last), self.cfg
        )
        k = np.asarray(k, np.float32)
        v = np.asarray(v, np.float32)
        for i, r in enumerate(reqs):
            self.pool.alloc(r.rid, lens[i] + r.n_reserve)
            self.pool.write_prompt(r.rid, k[i, : lens[i]], v[i, : lens[i]])
        return np.asarray(logits, np.float32)[: len(reqs)]

    @staticmethod
    def _check_plan(r: BatchRequest) -> None:
        if r.plan is None:
            raise ValueError(f"request {r.rid}: rcllm prefill needs a plan")

    @staticmethod
    def _selective_rows(r: BatchRequest, stats: ENG.EngineStats, k_all, v_all):
        """Final pool rows for one selectively-prefilled request.

        Block-granular semantics with host-side merging: cached span
        values first (one contiguous run per plan span), then the
        recomputed tokens' fresh KV overwriting them — resolved *before*
        the arena scatter so the fused write sees unique positions
        (duplicate slots in one XLA scatter have undefined order).
        -> (positions, k rows, v rows).
        """
        plan = r.plan
        write = np.zeros(plan.n, bool)
        for s in plan_spans(plan):
            if s.source != RECOMPUTE:
                write[s.start : s.end] = True
        kw = np.array(r.cached_k, np.float32)
        vw = np.array(r.cached_v, np.float32)
        rec = stats.recompute_mask
        kw[rec] = k_all[rec]
        vw[rec] = v_all[rec]
        write |= rec
        pos = np.where(write)[0]
        return pos, kw[pos], vw[pos]

    def _insert_selective(
        self,
        r: BatchRequest,
        stats: ENG.EngineStats,
        k_all: np.ndarray,
        v_all: np.ndarray,
    ) -> None:
        """Pool insertion for one selectively-prefilled request: one
        fused scatter for cached spans + recomputed KV, and one for the
        always-fresh layer-0 plane (HH identification runs layer 0 in
        full, so its KV is exact for every token)."""
        self.last_stats[r.rid] = stats
        n = r.plan.n
        self.pool.alloc(r.rid, n + r.n_reserve)
        pos, kw, vw = self._selective_rows(r, stats, k_all, v_all)
        self.pool.write_at(r.rid, pos, kw, vw)
        self.pool.write_at(
            r.rid, np.arange(n), k_all[:, 0], v_all[:, 0], layer=0
        )

    def _prefill_selective_batch(self, reqs: Sequence[BatchRequest]) -> np.ndarray:
        """Batched rcllm prefill: bucketed stacked requests, one jitted
        selective step per bucket (`engine.selective_prefill_batch`),
        then ONE fused pool scatter for the whole batch (plus one for
        the layer-0 planes) instead of per-request arena copies."""
        for r in reqs:
            self._check_plan(r)
        results = ENG.selective_prefill_batch(
            self.params,
            self.cfg,
            [(r.plan, r.cached_k, r.cached_v, r.have) for r in reqs],
            self.sel,
            bucket=self.bucket,
        )
        out = []
        entries, entries_l0 = [], []
        for r, (logits, stats, k_all, v_all) in zip(reqs, results):
            self.last_stats[r.rid] = stats
            n = r.plan.n
            self.pool.alloc(r.rid, n + r.n_reserve)
            pos, kw, vw = self._selective_rows(r, stats, k_all, v_all)
            entries.append((r.rid, pos, kw, vw))
            entries_l0.append((r.rid, np.arange(n), k_all[:, 0], v_all[:, 0]))
            out.append(logits)
        self.pool.write_at_batch(entries)
        self.pool.write_at_batch(entries_l0, layer=0)
        return np.stack(out)

    def _prefill_selective(self, r: BatchRequest) -> np.ndarray:
        """Legacy one-request-at-a-time selective prefill (parity and
        benchmark reference for the batched path)."""
        self._check_plan(r)
        logits, stats, k_all, v_all = ENG.selective_prefill_with_kv(
            self.params,
            self.cfg,
            r.plan,
            r.cached_k,
            r.cached_v,
            r.have,
            self.sel,
            bucket=self.bucket,
        )
        self._insert_selective(r, stats, k_all, v_all)
        return logits

    # --------------------------- shared insertion ---------------------------
    def _prefix_full_key(self, r: BatchRequest):
        """The prefix tier's content key for one request: instruction
        digest + the (n_pad, r_pad) jit bucket its rows came out of
        (computed from the *original* plan shape, so hit and miss
        requests derive the same key)."""
        reuse = r.reuse
        if reuse is None or reuse.prefix_key is None or not reuse.prefix_len:
            return None
        return reuse.prefix_key + BS.shape_bucket(
            r.plan, r.have, self.sel, self.bucket
        )

    def _prefill_selective_shared(self, reqs: Sequence[BatchRequest]) -> np.ndarray:
        """rcllm prefill against the shared block store.

        A **prefix-tier hit** is injected *before* compute: the stored
        instruction rows — byte-for-byte what this request's selective
        pass would recompute — are handed to the engine as cached KV
        with `have` set, so the instruction drops out of the recompute
        set entirely (real FLOP savings, not just skipped writes).  For
        every other tier the compute is identical to the private path;
        pool insertion then maps store-resident blocks instead of
        re-writing their bytes.
        """
        for r in reqs:
            self._check_plan(r)
        store = self.store
        prefix_hits: Dict[int, tuple] = {}
        items_in = []
        for r in reqs:
            ck, cv, have = r.cached_k, r.cached_v, r.have
            key = self._prefix_full_key(r)
            blk = store.get(key) if key is not None else None
            if blk is not None:
                # held until release(rid); recorded via prefix_hits
                blk.refcount += 1
                prefix_hits[r.rid] = (key, blk)
                npfx = min(blk.n_tokens, r.plan.n)
                ck = np.array(ck, np.float32)
                cv = np.array(cv, np.float32)
                have = have.copy()
                ck[:npfx] = blk.host_k[:npfx]
                cv[:npfx] = blk.host_v[:npfx]
                have[:npfx] = True
            items_in.append((r.plan, ck, cv, have))
        if self.batched_selective:
            results = ENG.selective_prefill_batch(
                self.params, self.cfg, items_in, self.sel, bucket=self.bucket
            )
        else:
            results = [
                ENG.selective_prefill_with_kv(
                    self.params, self.cfg, *item, self.sel, bucket=self.bucket
                )
                for item in items_in
            ]
        return self._insert_batch_shared(reqs, results, prefix_hits)

    def _insert_batch_shared(self, reqs, results, prefix_hits=None) -> np.ndarray:
        """Map store hits, insert missing blocks, write the private rest.

        Phase A acquires a reference on every resident block any request
        in the batch will map, *before* any insertion can trigger LRU
        eviction — so a block one batch member counts on can never be
        evicted to make room for another's insert.  Phase B then, per
        request: inserts missing blocks (optional — gated so the batch's
        remaining mandatory private allocations keep their pages), maps
        the hit positions that survived recompute selection, allocates
        the private remainder and stages its rows for the fused scatter.
        """
        store = self.store
        prefix_hits = prefix_hits if prefix_hits is not None else {}
        held: Dict[int, list] = {r.rid: [] for r in reqs}
        blocks: Dict[int, dict] = {r.rid: {} for r in reqs}
        # prefix refs were already taken pre-compute (the hit changed the
        # recompute set); record them so release(rid) drops them too
        for rid, (key, blk) in prefix_hits.items():
            held[rid].append(key)
            blocks[rid][key] = blk
        # phase A: silently acquire refs on resident blocks, batch-wide,
        # before any insertion can evict (hit/miss accounting happens at
        # resolution time in phase B, where same-batch inserts count as
        # the hits they are)
        for r in reqs:
            reuse = r.reuse if r.reuse is not None else BS.RequestReuse()
            keys = [ref.key for ref in reuse.blocks]
            if reuse.user_key is not None and len(
                BS.user_reuse_positions(r.plan, r.have, reuse.prefix_end)
            ):
                keys.append(reuse.user_key)
            for key in keys:
                blk = store.get(key)
                if blk is not None:
                    blk.refcount += 1
                    held[r.rid].append(key)
                    blocks[r.rid][key] = blk
        # private-page demand still owed to unprocessed batch members:
        # optional inserts must never eat into it
        bounds = {r.rid: self.admission_pages(r)[0] for r in reqs}
        remaining = sum(bounds.values())
        out = []
        entries, entries_l0 = [], []
        for r, (logits, stats, k_all, v_all) in zip(reqs, results):
            self.last_stats[r.rid] = stats
            n = r.plan.n
            rec = stats.recompute_mask
            reuse = r.reuse if r.reuse is not None else BS.RequestReuse()
            pos_parts, slot_parts = [], []
            # --- prefix tier: the instruction's recomputed rows, shared
            # by every request in this (n_pad, r_pad) bucket, pinned ---
            key = self._prefix_full_key(r)
            if key is not None:
                pblk = None
                if r.rid in prefix_hits:
                    pblk = prefix_hits[r.rid][1]
                    store.count_hit(pblk)
                else:
                    pblk = store.acquire(key)
                    if pblk is not None:
                        held[r.rid].append(key)
                    else:
                        # this request recomputed the instruction rows
                        # itself — they become the shared block
                        npfx = min(reuse.prefix_len, n)
                        pblk = store.insert(
                            key,
                            BS.PREFIX_TIER,
                            k_all[:npfx],
                            v_all[:npfx],
                            pinned=True,
                            keep_free=remaining,
                            defer_write=True,
                        )
                        if pblk is not None:
                            pblk.refcount += 1
                            held[r.rid].append(key)
                if pblk is not None:
                    npfx = min(pblk.n_tokens, n)
                    pos_parts.append(np.arange(npfx))
                    slot_parts.append(pblk.slots[:npfx])
            # --- item tier: offline block bytes, LRU-evictable ---
            for ref in reuse.blocks:
                blk = blocks[r.rid].get(ref.key)
                if blk is not None:
                    store.count_hit(blk)
                else:
                    # an earlier request in this batch may have inserted
                    # it since phase A — that is a hit too
                    blk = store.acquire(ref.key)
                    if blk is not None:
                        held[r.rid].append(ref.key)
                    elif ref.k is not None:
                        blk = store.insert(
                            ref.key,
                            BS.ITEM_TIER,
                            ref.k,
                            ref.v,
                            tokens=ref.tokens,
                            keep_free=remaining,
                            defer_write=True,
                        )
                        if blk is not None:
                            blk.refcount += 1
                            held[r.rid].append(ref.key)
                if blk is None:
                    continue
                use = ~rec[ref.positions]
                pos_parts.append(ref.positions[use])
                slot_parts.append(blk.slots[ref.offsets[use]])
            # --- user tier: fresh layer-0 + semantic deep layers, pinned ---
            u_pos = None
            if reuse.user_key is not None:
                u_pos = BS.user_reuse_positions(r.plan, r.have, reuse.prefix_end)
            if u_pos is not None and len(u_pos):
                ublk = blocks[r.rid].get(reuse.user_key)
                if ublk is not None:
                    store.count_hit(ublk)
                else:
                    ublk = store.acquire(reuse.user_key)
                    if ublk is not None:
                        held[r.rid].append(reuse.user_key)
                    else:
                        ku = np.concatenate(
                            [k_all[u_pos, :1], r.cached_k[u_pos, 1:]], axis=1
                        )
                        vu = np.concatenate(
                            [v_all[u_pos, :1], r.cached_v[u_pos, 1:]], axis=1
                        )
                        ublk = store.insert(
                            reuse.user_key,
                            BS.USER_TIER,
                            ku,
                            vu,
                            positions=u_pos,
                            pinned=True,
                            keep_free=remaining,
                            defer_write=True,
                        )
                        if ublk is not None:
                            ublk.refcount += 1
                            held[r.rid].append(reuse.user_key)
                if ublk is not None:
                    common = np.intersect1d(u_pos, ublk.positions)
                    common = common[~rec[common]]
                    pos_parts.append(common)
                    slot_parts.append(
                        ublk.slots[np.searchsorted(ublk.positions, common)]
                    )
            mapped_pos = (
                np.concatenate(pos_parts)
                if pos_parts
                else np.zeros(0, np.int64)
            )
            mapped_slots = (
                np.concatenate(slot_parts)
                if slot_parts
                else np.zeros(0, np.int64)
            )
            cap = self.pool.pages_for(n + r.n_reserve) * self.pool.page_size
            need = -(-(cap - len(mapped_pos)) // self.pool.page_size)
            if self.pool.free_pages < need:
                store.evict_for(need)
            self.pool.alloc_mapped(r.rid, n + r.n_reserve, mapped_pos, mapped_slots)
            remaining -= bounds[r.rid]
            self.store_refs[r.rid] = held[r.rid]
            mapped_mask = np.zeros(n, bool)
            mapped_mask[mapped_pos] = True
            pos, kw, vw = self._selective_rows(r, stats, k_all, v_all)
            keep = ~mapped_mask[pos]
            entries.append((r.rid, pos[keep], kw[keep], vw[keep]))
            l0_pos = np.where(~mapped_mask)[0]
            entries_l0.append((r.rid, l0_pos, k_all[l0_pos, 0], v_all[l0_pos, 0]))
            out.append(logits)
        store.flush_writes()
        self.pool.write_at_batch(entries)
        self.pool.write_at_batch(entries_l0, layer=0)
        return np.stack(out)

    # ------------------------ chunk-resumable prefill ------------------------
    def begin_prefill(self, r: BatchRequest) -> None:
        """Admit one request into chunk-resumable prefill.

        Resolves the shared block store *now* (a prefix-tier hit is
        injected before any compute, exactly like the wave path, so
        Eq. 3 selection later drops the instruction from the recompute
        set; item/user hits map their positions at store slots) and
        claims the request's full admission-bound private pages up
        front, so neither the incremental chunk writes nor the finalize
        remap can hit `PoolExhausted` mid-prefill.
        """
        self._check_plan(r)
        if r.rid in self.prefill_states:
            raise KeyError(f"request {r.rid} already prefilling")
        plan, n = r.plan, r.plan.n
        ck, cv, have = r.cached_k, r.cached_v, r.have
        store = self.store
        held: List = []
        pos_parts, slot_parts = [], []
        pending_prefix = pending_user = None
        if store is not None:
            reuse = r.reuse if r.reuse is not None else BS.RequestReuse()
            # --- prefix tier: inject a hit before compute ---
            key = self._prefix_full_key(r)
            if key is not None:
                pblk = store.acquire(key)
                if pblk is not None:
                    held.append(key)
                    npfx = min(pblk.n_tokens, n)
                    ck = np.array(ck, np.float32)
                    cv = np.array(cv, np.float32)
                    have = have.copy()
                    ck[:npfx] = pblk.host_k[:npfx]
                    cv[:npfx] = pblk.host_v[:npfx]
                    have[:npfx] = True
                    pos_parts.append(np.arange(npfx))
                    slot_parts.append(pblk.slots[:npfx])
                else:
                    pending_prefix = key
            # --- item tier: offline bytes exist now, so misses insert
            # at admission (later arrivals hit them; this request keeps
            # its own private rows — the bytes are identical either way)
            for ref in reuse.blocks:
                blk = store.acquire(ref.key)
                if blk is None and ref.k is not None:
                    blk = store.insert(
                        ref.key,
                        BS.ITEM_TIER,
                        ref.k,
                        ref.v,
                        tokens=ref.tokens,
                        defer_write=True,
                    )
                    if blk is not None:
                        blk.refcount += 1
                if blk is not None:
                    held.append(ref.key)
                    pos_parts.append(ref.positions)
                    slot_parts.append(blk.slots[ref.offsets])
            # --- user tier (fresh bytes needed: miss inserts at finalize)
            if reuse.user_key is not None:
                u_pos = BS.user_reuse_positions(plan, r.have, reuse.prefix_end)
                if len(u_pos):
                    ublk = store.acquire(reuse.user_key)
                    if ublk is not None:
                        held.append(reuse.user_key)
                        common = np.intersect1d(u_pos, ublk.positions)
                        pos_parts.append(common)
                        slot_parts.append(
                            ublk.slots[np.searchsorted(ublk.positions, common)]
                        )
                    else:
                        pending_user = (reuse.user_key, u_pos)
        mapped_pos = np.concatenate(pos_parts) if pos_parts else np.zeros(0, np.int64)
        mapped_slots = (
            np.concatenate(slot_parts) if slot_parts else np.zeros(0, np.int64)
        )
        # claim the full admission bound: the pages actually needed now,
        # plus spare headroom covering the worst-case finalize remap
        bound, _ = self.admission_pages(r)
        total_slots = self.pool.pages_for(n + r.n_reserve) * self.pool.page_size
        n_priv = max(total_slots - len(mapped_pos), 0)
        begin_need = -(-n_priv // self.pool.page_size)
        extra = max(bound - begin_need, 0)
        if store is not None and self.pool.free_pages < begin_need + extra:
            store.evict_for(begin_need + extra)
        try:
            self.pool.alloc_mapped(
                r.rid, n + r.n_reserve, mapped_pos, mapped_slots,
                extra_pages=extra,
            )
        except PoolExhausted:
            if store is not None:
                store.release_all(held)
            raise
        if store is not None:
            self.store_refs[r.rid] = held
        mapped_mask = np.zeros(n, bool)
        mapped_mask[mapped_pos[mapped_pos < n].astype(np.int64)] = True
        cp = ENG.ChunkedPrefill(
            self.params, self.cfg, plan, ck, cv, have, self.sel,
            chunk_tokens=self.chunk_tokens, bucket=self.bucket,
        )
        self.prefill_states[r.rid] = PrefillState(
            req=r,
            cp=cp,
            mapped_mask=mapped_mask,
            pending_prefix=pending_prefix,
            pending_user=pending_user,
        )

    def abort_prefill(self, rid: int) -> None:
        """Roll back a mid-prefill preemption: drop the chunk state and
        release pages + store refs.  The caller keeps the plan, so the
        victim can re-prefill from scratch (greedy decode regenerates
        the same tokens)."""
        self.prefill_states.pop(rid, None)
        self.release(rid)

    # ------------------------------ migration ------------------------------
    def export_request_kv(self, rid: int) -> RequestKV:
        """Snapshot one request (finished OR chunk-partial prefill) as a
        `RequestKV` handoff record.  Read-only: the source engine keeps
        serving the request until the destination's import succeeds,
        after which the caller evacuates it here (`abort_prefill` /
        `release`)."""
        export = self.pool.export_request(rid)
        held = list(self.store_refs.get(rid, []))
        payloads: Dict[tuple, BS.BlockPayload] = {}
        if self.store is not None:
            for key in held:
                if key not in payloads:
                    payload = self.store.export_payload(key)
                    if payload is not None:
                        payloads[key] = payload
        return RequestKV(
            rid=rid,
            export=export,
            held=held,
            payloads=payloads,
            stats=self.last_stats.get(rid),
            prefill=self.prefill_states.get(rid),
        )

    def import_request_kv(self, rec: RequestKV) -> Dict[str, int]:
        """Materialize a migrated request in THIS engine without any
        recompute.

        Store payloads resolve first (digest hit -> zero bytes moved;
        miss -> insert under the original key; budget refusal -> the
        referenced rows are privatized into fresh pages), building the
        shared-slot translation map the pool import needs.  Transactional:
        a `PoolExhausted` anywhere rolls back every page and store
        reference this call took, so the caller can retry on another
        worker and `check_partition` holds on both sides either way.

        -> counters: pages/bytes moved, digest fast-path hits.
        """
        rid, export = rec.rid, rec.export
        store = self.store
        fmap: Dict[int, int] = {}
        held_new: List[tuple] = []
        raw_pages: List[int] = []
        refused: Dict[tuple, BS.BlockPayload] = {}
        counters = {
            "pages": export.n_pages,
            "bytes": export.nbytes,
            "digest_hits": 0,
        }
        foreign = set(
            int(s) for s in export.foreign_slots[export.owner_page < 0]
        )
        priv_old: set = set()
        try:
            if store is not None:
                seen: set = set()
                for key in rec.held:
                    payload = rec.payloads.get(key)
                    if payload is None:
                        continue
                    if key in refused:
                        continue
                    blk, hit = store.import_payload(
                        payload, keep_free=export.n_pages
                    )
                    if blk is None:
                        refused[key] = payload
                        continue
                    held_new.append(key)
                    if key in seen:
                        continue
                    seen.add(key)
                    if hit:
                        counters["digest_hits"] += 1
                    else:
                        counters["bytes"] += payload.nbytes
                    for old, new in zip(payload.slots, blk.slots):
                        fmap[int(old)] = int(new)
                # budget-refused payloads: privatize the rows the slot
                # table actually references (fresh pages owned by the
                # request; the bytes travel like a payload miss)
                for payload in refused.values():
                    rows = [
                        i
                        for i, s in enumerate(payload.slots)
                        if int(s) in foreign and int(s) not in fmap
                    ]
                    if not rows:
                        continue
                    pages = self.pool.alloc_pages(
                        self.pool.pages_for(len(rows))
                    )
                    raw_pages.extend(pages)
                    slots = self.pool.page_slots(pages)[: len(rows)]
                    for i, s in zip(rows, slots):
                        fmap[int(payload.slots[i])] = int(s)
                        priv_old.add(int(payload.slots[i]))
                    self.pool.write_slots(
                        slots, payload.host_k[rows], payload.host_v[rows]
                    )
                    counters["bytes"] += (
                        payload.host_k[rows].nbytes
                        + payload.host_v[rows].nbytes
                    )
                    counters["pages"] += len(pages)
            self.pool.import_request(export, fmap)
        except PoolExhausted:
            if raw_pages:
                self.pool.release_pages(raw_pages)
            if store is not None:
                store.release_all(held_new)
            raise
        if raw_pages:
            self.pool.page_tables[rid].extend(raw_pages)
        if store is not None:
            self.store_refs[rid] = held_new
            store.flush_writes()
        if rec.stats is not None:
            self.last_stats[rid] = rec.stats
        if rec.prefill is not None:
            st = rec.prefill
            if priv_old:
                # privatized positions are no longer store-mapped: clear
                # the mask so finalize writes (not remaps) them
                for pos in np.where(export.owner_page < 0)[0]:
                    if (
                        int(export.foreign_slots[pos]) in priv_old
                        and pos < len(st.mapped_mask)
                    ):
                        st.mapped_mask[pos] = False
            self.prefill_states[rid] = st
        return counters

    def _finalize_store(self, st: PrefillState, k_all, v_all, rec) -> np.ndarray:
        """Store bookkeeping for one finalizing request: insert the
        fresh-byte tiers whose keys missed at admission, then un-share
        every mapped position Eq. 3 selected for recomputation (its
        fresh KV must land privately — writing through the shared slot
        would corrupt the store's block).  -> remapped positions."""
        store, r = self.store, st.req
        n = st.cp.n
        reuse = r.reuse if r.reuse is not None else BS.RequestReuse()
        held = self.store_refs.setdefault(r.rid, [])
        if st.pending_prefix is not None:
            npfx = min(reuse.prefix_len, n)
            pblk = store.insert(
                st.pending_prefix,
                BS.PREFIX_TIER,
                k_all[:npfx],
                v_all[:npfx],
                pinned=True,
                defer_write=True,
            )
            if pblk is not None:
                pblk.refcount += 1
                held.append(st.pending_prefix)
        if st.pending_user is not None:
            ukey, u_pos = st.pending_user
            ku = np.concatenate([k_all[u_pos, :1], r.cached_k[u_pos, 1:]], axis=1)
            vu = np.concatenate([v_all[u_pos, :1], r.cached_v[u_pos, 1:]], axis=1)
            ublk = store.insert(
                ukey,
                BS.USER_TIER,
                ku,
                vu,
                positions=u_pos,
                pinned=True,
                defer_write=True,
            )
            if ublk is not None:
                ublk.refcount += 1
                held.append(ukey)
        remap = np.where(st.mapped_mask & rec)[0]
        self.pool.remap_private(r.rid, remap)
        st.mapped_mask[remap] = False
        return remap

    def finalize_prefill(self, rids: Sequence[int]) -> Dict[int, np.ndarray]:
        """Selective layers + pool insertion for fully-scanned requests.

        One bucketed batched dispatch (`engine.selective_layers_batch`
        — the same kernel the wave path uses, so chunked and monolithic
        prefill decode bitwise-identical tokens), then one fused
        deep-layer pool scatter for the whole batch; the layer-0 plane
        already landed incrementally as chunks completed.
        """
        states = [self.prefill_states[rid] for rid in rids]
        sel_out = ENG.selective_layers_batch(
            self.params, self.cfg, [st.cp.sel_item() for st in states]
        )
        out: Dict[int, np.ndarray] = {}
        entries_deep, entries_l0 = [], []
        for st, (logits, k_rest, v_rest) in zip(states, sel_out):
            r, cp = st.req, st.cp
            n = cp.n
            stats = cp.stats
            self.last_stats[r.rid] = stats
            k_all = np.concatenate([cp.k0_full()[:, None], k_rest[:n]], axis=1)
            v_all = np.concatenate([cp.v0_full()[:, None], v_rest[:n]], axis=1)
            rec = stats.recompute_mask
            for positions, k0, v0 in st.l0_buf:  # lazy-mode chunk rows
                entries_l0.append((r.rid, positions, k0, v0))
            if self.store is not None:
                remap = self._finalize_store(st, k_all, v_all, rec)
                if len(remap):
                    # un-shared positions never got the incremental
                    # layer-0 write (they were mapped then) — their
                    # fresh plane lands with the finalize scatter
                    entries_l0.append((r.rid, remap, k_all[remap, 0], v_all[remap, 0]))
            pos, kw, vw = self._selective_rows(r, stats, k_all, v_all)
            keep = ~st.mapped_mask[pos]
            entries_deep.append((r.rid, pos[keep], kw[keep][:, 1:], vw[keep][:, 1:]))
            out[r.rid] = logits
            del self.prefill_states[r.rid]
        if self.store is not None:
            self.store.flush_writes()
        self.pool.write_at_batch(entries_deep, deep=True)
        self.pool.write_at_batch(entries_l0, layer=0)
        return out

    def step(
        self,
        budget: int,
        decode_rids: Sequence[int],
        decode_tokens: Sequence[int],
        prefill_rids: Sequence[int],
    ) -> StepReport:
        """One unified serving tick under a global token budget.

        Decode always runs first (one token per running request — and
        first so a `PoolExhausted` preemption can retry before any
        prefill work executes); the remaining budget packs prefill work
        over `prefill_rids` in admission order: requests whose scan is
        complete finalize (charged their padded recompute budget),
        everyone else gets layer-0 chunks round-robin — one chunk per
        request per cycle, so a short prompt admitted behind a long one
        finishes scanning in proportion to its own length instead of
        waiting out the long scan (the head-of-line fix).  When nothing
        fits the remaining budget, the single head work item runs
        anyway (`oversized` tick) — an indivisible selective finalize
        can exceed any fixed budget and must not starve.
        """
        rep = StepReport()
        if self.store is not None:
            # drain router-hinted spill promotions (budgeted demand-swap:
            # LRU refcount-0 victims demote to the spill tier to make
            # room; a no-op unless store.prefetch_pages_per_tick>0),
            # then land their deferred writes with this tick's flush
            self.store.prefetch()
            self.store.flush_writes()
        if decode_rids:
            rep.decode_logits = self.decode(decode_rids, decode_tokens)
            rep.charge_decode = len(decode_rids)
        left = budget - rep.charge_decode
        active = [rid for rid in prefill_rids if rid in self.prefill_states]
        packed = False
        finalize: List[int] = []
        l0_entries: List[tuple] = []

        def try_finalize(rid) -> None:
            nonlocal left, packed
            fc = self.prefill_states[rid].cp.finalize_charge()
            if fc <= left or (not packed and not decode_rids):
                if fc > left:
                    rep.oversized = True
                finalize.append(rid)
                rep.charge_finalize += fc
                left -= fc
                packed = True

        # pass 1 (admission order): fully-scanned requests finalize first
        for rid in active:
            if self.prefill_states[rid].cp.scan_done:
                try_finalize(rid)
        # pass 2: round-robin chunks; a request finishing its scan gets
        # to finalize in the same tick if the budget still allows
        progress = True
        while progress:
            progress = False
            for rid in active:
                st = self.prefill_states[rid]
                if st.cp.scan_done:
                    continue
                c = st.cp.next_chunk_tokens()
                if c > left and (packed or decode_rids):
                    continue
                if c > left:
                    rep.oversized = True
                positions, k0, v0 = st.cp.run_chunk()
                keep = ~st.mapped_mask[positions]
                if self.eager_kv_writes:
                    l0_entries.append((rid, positions[keep], k0[keep], v0[keep]))
                else:
                    st.l0_buf.append((positions[keep], k0[keep], v0[keep]))
                rep.charge_chunks += c
                left -= c
                packed = True
                progress = True
                if not st.started:
                    st.started = True
                    rep.started.append(rid)
                if rid not in rep.chunked:
                    rep.chunked.append(rid)
                if st.cp.scan_done and rid not in finalize:
                    try_finalize(rid)
        self.pool.write_at_batch(l0_entries, layer=0)
        if finalize:
            rep.finalized = self.finalize_prefill(finalize)
        return rep

    # ------------------------------- decode --------------------------------
    def decode(self, rids: Sequence[int], last_tokens: Sequence[int]) -> np.ndarray:
        """One token for each running request.  -> logits (N, V)."""
        n = len(rids)
        n_pad = -(-n // self.decode_bucket) * self.decode_bucket
        tables, lens = self.pool.batch_tables(rids)
        pages, slots = self.pool.append_slots(rids)
        toks = np.zeros(n_pad, np.int32)
        toks[:n] = np.asarray(last_tokens, np.int32)
        tables_p = np.zeros((n_pad, tables.shape[1]), np.int32)
        tables_p[:n] = tables
        lens_p = np.zeros(n_pad, np.int32)
        lens_p[:n] = lens
        pages_p = np.zeros(n_pad, np.int32)  # pad rows: scratch page 0
        slots_p = np.zeros(n_pad, np.int32)
        pages_p[:n], slots_p[:n] = pages, slots
        if ENG.decode_uses_paged(self.cfg):
            pg_ids, sl_pos = page_views(
                tables_p, lens_p, pages_p, slots_p, self.pool.page_size
            )
        else:
            # dead inputs on the gather path; keep them tiny and
            # shape-stable so they never force a retrace
            pg_ids = np.zeros((n_pad, 1), np.int32)
            sl_pos = np.full((n_pad, 1, self.pool.page_size), -1, np.int32)
        logits, ak, av = _decode_step_jit(self.donate)(
            self.params,
            jnp.asarray(toks),
            jnp.asarray(tables_p),
            jnp.asarray(lens_p),
            jnp.asarray(pages_p),
            jnp.asarray(slots_p),
            jnp.asarray(pg_ids),
            jnp.asarray(sl_pos),
            self.pool.arena_k,
            self.pool.arena_v,
            self.cfg,
        )
        self.pool.update_arenas(ak, av)
        return np.asarray(logits, np.float32)[:n]

    def release(self, rid: int) -> None:
        """Free a request's private pages and drop its shared-block
        references.  Idempotent — releasing an unknown or already-freed
        rid is a no-op (a duplicate `finish()` must not crash the loop)."""
        self.pool.free(rid)
        if self.store is not None:
            self.store.release_all(self.store_refs.pop(rid, []))
        self.last_stats.pop(rid, None)
