"""Ranking traffic for the chip benchmark: one general generator, many mixes.

A traffic mix is a JSON file under ``traffic/`` naming its parameters
(catalog, users, reviews, candidates, rate, reuse).  This module turns
one mix and a ``--seed`` into the exact request stream a run serves.

The generators are copied from the program's synthetic corpus
(``repro.data.synth``: catalog, phrase pool, reviews, Zipf candidates)
so that a change to the program cannot move the yardstick.  They follow
the RcLLM paper's published statistics: items of about 87 tokens,
reviews of about 80, a 207-token instruction, Zipf(1.1) item popularity
with co-occurrence clusters, and about 2.2K-token ranking prompts.

Steadiness.  The mix's own ``structure_seed`` fixes everything that sets
the amount of work: the catalog, the users and their histories, each
request's candidates and the arrival times (Poisson, drawn once).
``--seed`` only orders the window's requests over those arrival times,
and draws the weights (elsewhere).  Every seed therefore serves the same
prompts at the same instants, in another order, so runs with different
seeds measure the same work and meet the same bursts.

The warm-up is drawn from the window's stream, each request from another
user (`other_user`): the same lengths and candidates, so it meets the
program shapes the window will (the harness serves one request of each).
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# token-id layout (the program's synthetic vocabulary uses the same one)
BOS, ITEM_SEP, REVIEW_SEP, RANK_QUERY = 1, 2, 3, 4
SLOT_BASE = 8  # slot tokens 8..8+64 name "candidate #k"
N_SPECIAL = SLOT_BASE + 64
INSTRUCTION_WORDS = 200  # the instruction's ids: N_SPECIAL + [0, 200)


def load_mix(name: str, root: str = HERE) -> dict:
    """A mix by name, with its ``extends`` chain resolved (later keys win)."""
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    base = mix.pop("extends", None)
    if base is None:
        return mix
    out = load_mix(base, root)
    for key, val in mix.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = {**out[key], **val}
        else:
            out[key] = val
    return out


def token_id_bound(mix: dict) -> int:
    """One past the largest token id `make_traffic` can draw from `mix`:
    special and instruction ids, items, reviews, and a new user's reviews
    where the mix has them (``users.unique``)."""
    v = mix["vocab"]
    regions = [N_SPECIAL + INSTRUCTION_WORDS, v["item_hi"], v["review_hi"]]
    if mix["users"].get("unique"):
        regions.append(v["cold_review_hi"])
    return max(regions)


def rng_for(*parts: int) -> np.random.Generator:
    """A numpy generator from any number of non-negative integers (seeds
    may exceed 32 bits)."""
    return np.random.default_rng([int(p) for p in parts])


# ------------------------------------------------------------- corpus
@dataclass
class Catalog:
    n_items: int
    item_tokens: List[np.ndarray]
    popularity: np.ndarray
    cluster_of: np.ndarray
    vocab_size: int


def make_catalog(n_items: int, mean_tokens: int, n_clusters: int, zipf_a: float,
                 lo: int, hi: int, rng: np.random.Generator) -> Catalog:
    """Items whose tokens come from per-cluster sub-pools of [lo, hi):
    co-occurring items look alike.  Popularity is Zipf over a random order."""
    cluster_of = rng.integers(0, n_clusters, n_items).astype(np.int32)
    lens = np.maximum(8, rng.poisson(mean_tokens, n_items))
    region = hi - lo
    per_cluster = min(400, max(32, region // 8))
    items = []
    for i in range(n_items):
        base = lo + (int(cluster_of[i]) * 37) % (region - per_cluster)
        items.append((base + rng.integers(0, per_cluster, lens[i])).astype(np.int32))
    ranks = rng.permutation(n_items) + 1
    popularity = 1.0 / ranks ** zipf_a
    return Catalog(n_items, items, popularity, cluster_of, hi)


def make_phrase_pool(n_phrases: int, lo: int, hi: int, rng: np.random.Generator):
    """Review phrases in 5 sentiment bands of [lo, hi): reviews built from a
    limited phrase pool repeat themselves (the paper's Insight 1)."""
    band = (hi - lo) // 5
    phrases, sentiment = [], []
    for p in range(n_phrases):
        s = p % 5
        ln = rng.integers(3, 9)
        phrases.append((lo + s * band + rng.integers(0, max(band - 8, 8), ln)).astype(np.int32))
        sentiment.append(s)
    return phrases, np.asarray(sentiment)


def make_review(pool, mean_tokens: int, rng: np.random.Generator) -> np.ndarray:
    phrases, sentiment_of = pool
    toks, total = [], 0
    sentiment = rng.integers(0, 5)
    while total < mean_tokens:
        if rng.random() < 0.8:
            cands = np.where(sentiment_of == sentiment)[0]
        else:
            cands = np.arange(len(phrases))
        ph = phrases[rng.choice(cands)]
        toks.append(ph)
        total += len(ph)
    return np.concatenate(toks)[: int(mean_tokens * 1.5)]


def make_history(pool, n_reviews: int, mean_tokens: int, rng: np.random.Generator):
    """One user's fixed history: reviews behind REVIEW_SEP markers.
    -> (tokens, marker mask: True at the instance-specific separators)."""
    revs, marks = [], []
    for _ in range(n_reviews):
        r = make_review(pool, mean_tokens, rng)
        m = np.zeros(len(r) + 1, bool)
        m[0] = True
        revs.append(np.concatenate([[REVIEW_SEP], r]).astype(np.int32))
        marks.append(m)
    return np.concatenate(revs), np.concatenate(marks)


def sample_candidates(catalog: Catalog, n: int, rng: np.random.Generator,
                      cluster_bias: float) -> np.ndarray:
    """Candidate sets co-occur: most come from the anchor item's cluster."""
    p = catalog.popularity / catalog.popularity.sum()
    anchor = rng.choice(catalog.n_items, p=p)
    same = np.where(catalog.cluster_of == catalog.cluster_of[anchor])[0]
    out = [int(anchor)]
    while len(out) < n:
        if rng.random() < cluster_bias:
            pick = int(rng.choice(same))
        else:
            pick = int(rng.choice(catalog.n_items, p=p))
        if pick not in out:
            out.append(pick)
    return np.asarray(out, np.int32)[rng.permutation(n)]


def make_instruction(n_tokens: int, rng: np.random.Generator) -> np.ndarray:
    words = N_SPECIAL + rng.integers(0, INSTRUCTION_WORDS, n_tokens - 1)
    return np.concatenate([[BOS], words]).astype(np.int32)


@dataclass
class Request:
    """One ranking request, in the shape the program's assembly reads
    (`prompt_segments`, `history_tokens`, `history_marker_mask`,
    `candidate_items`)."""

    user_id: int
    history_tokens: np.ndarray
    history_marker_mask: np.ndarray
    candidate_items: np.ndarray
    catalog: Catalog
    instruction: np.ndarray
    arrival_s: float = 0.0

    def prompt_segments(self, _catalog=None, _instruction=None):
        """-> (tokens, seg_kind, seg_id): kind 0 instruction/markers,
        1 history, 2 item; seg_id is the item id on item tokens.  The
        request's own catalog and instruction define the prompt."""
        parts = [self.instruction, self.history_tokens]
        kinds = [np.zeros(len(self.instruction), np.int32),
                 np.ones(len(self.history_tokens), np.int32)]
        ids = [np.full(len(self.instruction) + len(self.history_tokens), -1, np.int32)]
        for slot, it in enumerate(self.candidate_items):
            item = np.concatenate([[ITEM_SEP], self.catalog.item_tokens[it]]).astype(np.int32)
            parts += [np.asarray([SLOT_BASE + slot], np.int32), item]
            kinds += [np.zeros(1, np.int32), np.full(len(item), 2, np.int32)]
            ids += [np.full(1, -1, np.int32), np.full(len(item), it, np.int32)]
        parts.append(np.asarray([RANK_QUERY], np.int32))
        kinds.append(np.zeros(1, np.int32))
        ids.append(np.full(1, -1, np.int32))
        return np.concatenate(parts), np.concatenate(kinds), np.concatenate(ids)

    @property
    def tokens(self) -> np.ndarray:
        return self.prompt_segments()[0]


# ------------------------------------------------------------- streams
@dataclass
class Traffic:
    """Everything a run serves, made from one mix and one seed."""

    catalog: Catalog
    instruction: np.ndarray
    corpus: List[np.ndarray]        # offline review corpus (semantic cache)
    offline_requests: List[Request]  # the offline log (item placement)
    warmup: List[Request]           # the window's requests from other users
    window: List[Request]           # arrival_s = offset from the window start
    rate_per_s: float
    max_tokens: int
    item_coverage: float
    offline: bool                   # whether the program builds offline caches


def _users(mix: dict, pool, n: int, rng) -> Dict[int, tuple]:
    u = mix["users"]
    return {i: make_history(pool, u["reviews"], u["review_tokens"], rng) for i in range(n)}


def _requests(mix: dict, catalog, instruction, hist, user_ids, rng) -> List[Request]:
    c = mix["candidates"]
    out = []
    for uid in user_ids:
        toks, mask = hist[int(uid)]
        cands = sample_candidates(catalog, c["n"], rng, c["cluster_bias"])
        out.append(Request(int(uid), toks, mask, cands, catalog, instruction))
    return out


def other_user(rq: Request, lo: int, hi: int, user_id: int) -> Request:
    """The same request from another user: every review token moved one
    id along its region [lo, hi), so the prompt keeps its lengths, its
    markers and its candidates and no user block matches the original."""
    toks = rq.history_tokens.copy()
    words = toks != REVIEW_SEP
    toks[words] = lo + (toks[words] - lo + 1) % (hi - lo)
    return Request(user_id, toks, rq.history_marker_mask.copy(), rq.candidate_items.copy(),
                   rq.catalog, rq.instruction, rq.arrival_s)


def make_traffic(mix: dict, seed: int, seconds: float,
                 rate: Optional[float] = None) -> Traffic:
    """The run's request stream (see the module docstring for which
    parts the seed moves).  `rate` overrides the mix's offered rate (the
    knee sweep)."""
    s0 = int(mix["structure_seed"])
    rate = float(mix["rate_per_s"] if rate is None else rate)
    rng = rng_for(s0, 1)
    cat = mix["catalog"]
    vocab = mix["vocab"]
    catalog = make_catalog(cat["n_items"], cat["mean_tokens"], cat["n_clusters"],
                           cat["zipf_a"], vocab["item_lo"], vocab["item_hi"], rng)
    instruction = make_instruction(mix["instruction_tokens"], rng_for(s0, 2))
    corpus_pool = make_phrase_pool(mix["phrases"], vocab["review_lo"], vocab["review_hi"],
                                   rng_for(s0, 3))
    u = mix["users"]
    # the offline corpus: histories of users outside the served population
    rng_c = rng_for(s0, 4)
    corpus = [make_history(corpus_pool, u["reviews"], u["review_tokens"], rng_c)[0]
              for _ in range(mix["corpus_users"])]
    offline = _requests(mix, catalog, instruction,
                        {i: (h, np.zeros(len(h), bool)) for i, h in enumerate(corpus)},
                        np.arange(len(corpus)), rng_for(s0, 5))
    # the served population: returning Zipf users with fixed histories,
    # or (``users.unique``) a new user for every request whose reviews
    # come from a phrase region the corpus never saw
    n_window = max(1, int(round(rate * seconds)))
    if u.get("unique"):
        lo, hi = vocab["cold_review_lo"], vocab["cold_review_hi"]
        served_pool = make_phrase_pool(mix["phrases"], lo, hi, rng_for(s0, 6))
        hist = _users(mix, served_pool, n_window, rng_for(s0, 7))
        window_ids = np.arange(n_window)
    else:
        lo, hi = vocab["review_lo"], vocab["review_hi"]
        hist = _users(mix, corpus_pool, u["n"], rng_for(s0, 7))
        p = np.arange(1, u["n"] + 1, dtype=np.float64) ** -float(u["zipf_a"])
        p /= p.sum()
        order = rng_for(s0, 8).permutation(u["n"])  # popularity rank -> user id
        window_ids = order[rng_for(s0, 10).choice(u["n"], n_window, p=p)]
    window = _requests(mix, catalog, instruction, hist, window_ids, rng_for(s0, 12))
    # Poisson arrivals conditioned on n_window in the window (sorted
    # uniform points), fixed by the mix; the seed orders the requests
    pts = np.sort(rng_for(s0, 13).uniform(0.0, seconds, n_window))
    window = [window[i] for i in rng_for(seed, 14).permutation(n_window)]
    for rq, ts in zip(window, pts):
        rq.arrival_s = float(ts)
    # the warm-up: the same stream from other users (ids past the window's)
    first = (n_window if u.get("unique") else u["n"]) + 1
    warmup = [other_user(rq, lo, hi, first + i) for i, rq in enumerate(window)]
    return Traffic(catalog, instruction, corpus, offline, warmup, window,
                   rate, int(mix["max_tokens"]),
                   float(mix.get("item_coverage", 1.0)), bool(mix.get("offline", True)))


def correctness_sample(traffic: Traffic, finished: List[int], seed: int, n: int) -> List[int]:
    """Indices into `traffic.window` for the output check: the longest
    finished prompt, plus `n - 1` others drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (len(traffic.window[i].tokens), -i))
    rest = [i for i in finished if i != longest]
    rs = rng_for(seed, 15)
    pick = rs.choice(len(rest), min(n - 1, len(rest)), replace=False) if rest else []
    return [longest] + sorted(rest[int(j)] for j in pick)
