"""RcLLM system façade: offline build (both cache pools + placement) and
online ranking (full / rcllm / cacheblend / epic paths).

This is the public API the examples and accuracy benchmarks drive; the
distributed latency path is `repro.core.simulator`.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.configs.base import LMConfig
from repro.core import assembly as ASM
from repro.core import baselines as BASE
from repro.core import engine as ENG
from repro.core import item_cache as IC
from repro.core import placement as PL
from repro.core import semantic_cache as SC
from repro.core.engine import SelectiveConfig
from repro.data import synth as SY


@dataclass
class RcLLMSystem:
    cfg: LMConfig
    params: Dict
    catalog: SY.Catalog
    instruction: np.ndarray
    token_embed: np.ndarray
    semantic: Optional[SC.SemanticCache]
    item_store: Optional[IC.ItemKVStore]
    placement: PL.Placement

    # ----------------------------- offline -----------------------------
    @staticmethod
    def build(params, cfg: LMConfig, catalog: SY.Catalog,
              review_corpus: List[np.ndarray], history_requests,
              k_instances: int = 4, n_instruction: int = 207,
              item_coverage: float = 1.0, lsh_bits: int = 12,
              seed: int = 0) -> "RcLLMSystem":
        instruction = SY.make_instruction(n_instruction, catalog.vocab_size)
        token_embed = np.asarray(params["embed"], np.float32)

        # placement from the historical request log (Algorithm 1)
        req_items = [r.candidate_items for r in history_requests]
        placement = PL.place(catalog.n_items, req_items, k_instances)

        # batched, length-bucketed offline KV materialization
        corpus_kv = ENG.precompute_kv_batch(params, cfg, review_corpus)
        corpus_lookup = lambda i: corpus_kv[i]

        semantic = SC.build_semantic_cache(
            review_corpus, token_embed, n_bits=lsh_bits, seed=seed)
        SC.materialize_kv(semantic, review_corpus,
                          lambda toks, _i=None: None,
                          kv_by_doc=corpus_lookup)

        item_docs = [np.concatenate([[SY.ITEM_SEP], t]).astype(np.int32)
                     for t in catalog.item_tokens]
        item_kv = ENG.precompute_kv_batch(params, cfg, item_docs)
        item_store = IC.build_item_store(
            item_docs, placement,
            kv_of_sequence=None, kv_list=item_kv,
            coverage=item_coverage, seed=seed)
        return RcLLMSystem(cfg=cfg, params=params, catalog=catalog,
                           instruction=instruction, token_embed=token_embed,
                           semantic=semantic, item_store=item_store,
                           placement=placement)

    # ----------------------------- online ------------------------------
    def plan_for(self, request: SY.Request, instance: int = 0
                 ) -> ASM.AssemblyPlan:
        tokens, kind, ids = request.prompt_segments(self.catalog,
                                                    self.instruction)
        n_instr = len(self.instruction)
        marker = np.zeros(len(tokens), bool)
        hist_start = n_instr
        hm = request.history_marker_mask
        marker[hist_start:hist_start + len(hm)] = hm
        return ASM.build_plan(
            tokens, kind, ids,
            marker_mask=hm, item_store=self.item_store,
            semantic=self.semantic, token_embed=self.token_embed,
            instance=instance)

    def cached_kv(self, plan: ASM.AssemblyPlan, instance: int = 0):
        """Materialized assembled (k, v, have) for a plan on one instance."""
        return ASM.gather_cached_kv(
            plan, self.item_store, self.semantic, instance,
            self.cfg.n_layers, self.cfg.n_kv_heads,
            self.cfg.resolved_head_dim)

    _cached_kv = cached_kv                  # backward-compatible alias

    def best_instance(self, request: SY.Request) -> int:
        """Affinity routing (idle cluster → pure cache affinity)."""
        from repro.core import scheduler as SCH
        return int(np.argmax(SCH.hit_vector(request.candidate_items,
                                            self.placement)))

    def rank(self, request: SY.Request, method: str = "rcllm",
             sel: Optional[SelectiveConfig] = None,
             instance: Optional[int] = None
             ) -> Tuple[np.ndarray, Optional[ENG.EngineStats]]:
        """-> (scores over the request's candidate slots, stats)."""
        sel = sel or SelectiveConfig()
        if instance is None:
            instance = self.best_instance(request)
        n_cand = len(request.candidate_items)
        tokens, kind, ids = request.prompt_segments(self.catalog,
                                                    self.instruction)
        if method == "full":
            logits = ENG.full_prefill_logits(self.params, self.cfg, tokens)
            return logits[SY.SLOT_BASE:SY.SLOT_BASE + n_cand], None

        plan = self.plan_for(request, instance)
        ck, cv, have = self._cached_kv(plan, instance)
        if method == "rcllm":
            logits, stats = ENG.selective_prefill_logits(
                self.params, self.cfg, plan, ck, cv, have, sel)
        elif method == "cacheblend":
            logits, stats = BASE.cacheblend_prefill_logits(
                self.params, self.cfg, plan, ck, cv, have,
                r=(sel.r_item + sel.r_rev) / 2)
        elif method == "epic":
            logits, stats = BASE.epic_prefill_logits(
                self.params, self.cfg, plan, ck, cv, have)
        else:
            raise ValueError(method)
        return logits[SY.SLOT_BASE:SY.SLOT_BASE + n_cand], stats


CATALOG_VOCAB = 4096   # token ids of the synthetic catalog and reviews


def tiny_lm_config(n_layers: int = 4, d_model: int = 64, n_heads: int = 4,
                   n_kv_heads: int = 2) -> LMConfig:
    """The small float32 model the CPU tests and benchmarks serve."""
    return LMConfig(name="rcllm-tiny", n_layers=n_layers, d_model=d_model,
                    n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=16,
                    d_ff=128, vocab_size=CATALOG_VOCAB, mlp_type="swiglu",
                    dtype="float32", attn_q_chunk=64, attn_kv_chunk=64,
                    remat=False)


def make_tiny_system(profile_name: str = "amazon", n_items: int = 300,
                     k_instances: int = 4, n_requests_hist: int = 200,
                     seed: int = 0, n_layers: int = 4, d_model: int = 64,
                     item_coverage: float = 1.0, n_heads: int = 4,
                     n_kv_heads: int = 2, cfg: Optional[LMConfig] = None):
    """A small synthetic catalog and trace history, served by the model
    `cfg` with random weights from `seed`.

    Without `cfg` the model is `tiny_lm_config` at the given widths
    (``n_heads``/``n_kv_heads`` are overridable so the mesh parity tests
    can build a model whose head counts divide higher tp degrees).  Any
    `LMConfig` whose vocabulary covers the catalog's token ids works —
    the chip smoke serves Qwen3-8B widths through this builder."""
    from repro.models import transformer as T

    if cfg is None:
        cfg = tiny_lm_config(n_layers, d_model, n_heads, n_kv_heads)
    if cfg.vocab_size < CATALOG_VOCAB:
        raise ValueError(
            f"vocab_size={cfg.vocab_size} cannot hold the synthetic "
            f"catalog's token ids (< {CATALOG_VOCAB})")
    prof = dataclasses.replace(SY.PROFILES[profile_name], n_items=n_items,
                               n_clusters=max(6, n_items // 50),
                               mean_item_tokens=24, mean_review_tokens=20)
    catalog = SY.make_catalog(prof, vocab_size=CATALOG_VOCAB, seed=seed)
    pool = SY.make_review_pool(vocab_size=CATALOG_VOCAB, n_phrases=120,
                               seed=seed + 1)
    hist = SY.make_trace(catalog, pool, prof, n_requests=n_requests_hist,
                         qps=10.0, n_users=40, n_candidates=8,
                         reviews_per_user=2, seed=seed + 2)
    corpus = []
    seen = set()
    for r in hist:
        if r.user_id not in seen:
            corpus.append(r.history_tokens)
            seen.add(r.user_id)

    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    system = RcLLMSystem.build(params, cfg, catalog, corpus, hist,
                               k_instances=k_instances,
                               item_coverage=item_coverage, seed=seed)
    return system, pool, prof, hist
