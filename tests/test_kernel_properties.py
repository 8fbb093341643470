"""Property-based kernel-parity harness: every Pallas kernel family vs
its pure-jnp `ref.py` oracle over randomized shapes.

Two drivers per family share one check function:

* a seeded-random sweep (`pytest.mark.parametrize` over fixed seeds) —
  always runs, so CI exercises randomized shapes even without
  hypothesis installed;
* a hypothesis `@given` explorer over the seed space — skips itself via
  `_hypothesis_compat` when hypothesis is absent.

Randomization covers what the fixed-shape sweeps in `test_kernels.py`
cannot: ragged `kv_valid` patterns (arbitrary interleaved dead slots,
not just padded tails), GQA group factors 1/2/4, pow2-padded batch
sizes, and page views whose slots scatter logical positions across
physical pages at arbitrary alignment — the layouts cross-request
sharing actually produces.

Every masked oracle relies on the same exactness property: a dead slot
scores `NEG_INF`, whose softmax weight underflows to exactly 0.0 in
fp32, and adding 0.0 terms never perturbs a float reduction — so a
masked computation equals the oracle run on the compacted live keys.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st  # optional dep

from repro.kernels.block_gather.ops import assemble_kv
from repro.kernels.block_gather.ref import block_gather_ref
from repro.kernels.embedding_bag.ops import bag_sum
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import mha_flash
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.ops import paged_decode_mha
from repro.kernels.paged_attention.ref import (
    NEG_INF,
    masked_decode_attention_ref,
    paged_decode_ref,
)
from repro.kernels.selective_attention.ops import selective_mha
from repro.kernels.selective_attention.ref import selective_attention_ref
from repro.serving.kv_pool import page_views

SWEEP_SEEDS = range(6)
GQA_GROUPS = (1, 2, 4)


# ------------------------------ flash ----------------------------------
def _check_flash(seed: int) -> None:
    rng = np.random.default_rng(seed)
    B = int(2 ** rng.integers(0, 3))              # pow2-padded batch
    G = int(rng.choice(GQA_GROUPS))
    Hkv = int(rng.choice([1, 2]))
    D = int(rng.choice([8, 16, 32]))
    Sq = int(rng.integers(1, 80))
    Skv = int(rng.integers(1, 120))
    causal = bool(rng.integers(0, 2)) and Sq <= Skv
    dtype = jnp.bfloat16 if rng.integers(0, 4) == 0 else jnp.float32
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    Hq = G * Hkv
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), dtype)
    out = mha_flash(q, k, v, causal=causal, q_block=16, kv_block=32, interpret=True)
    kk = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * Hq, Skv, D)
    vv = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * Hq, Skv, D)
    qq = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D)
    ref = flash_attention_ref(qq, kk, vv, causal=causal)
    ref = ref.reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def _check_flash_ragged(seed: int) -> None:
    """Arbitrary interleaved `kv_valid` patterns (not just padded tails):
    the masked kernel must equal the oracle run on each row's compacted
    live keys."""
    rng = np.random.default_rng(seed)
    B = int(2 ** rng.integers(0, 3))
    G = int(rng.choice(GQA_GROUPS))
    Hkv = int(rng.choice([1, 2]))
    D = int(rng.choice([8, 16]))
    Sq = int(rng.integers(1, 40))
    Skv = int(rng.integers(2, 100))
    Hq = G * Hkv
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Skv, Hkv, D)), jnp.float32)
    valid = rng.random((B, Skv)) < rng.uniform(0.2, 0.9)
    valid[np.arange(B), rng.integers(0, Skv, B)] = True  # >=1 live key
    out = mha_flash(
        q,
        k,
        v,
        kv_valid=jnp.asarray(valid),
        causal=False,
        q_block=16,
        kv_block=32,
        interpret=True,
    )
    for b in range(B):
        kb = jnp.repeat(k[b, valid[b]], G, 1).transpose(1, 0, 2)
        vb = jnp.repeat(v[b, valid[b]], G, 1).transpose(1, 0, 2)
        qb = q[b].transpose(1, 0, 2)
        ref = flash_attention_ref(qb, kb, vb, causal=False)
        np.testing.assert_allclose(
            np.asarray(out[b]),
            np.asarray(ref.transpose(1, 0, 2)),
            atol=1e-5,
            rtol=1e-5,
        )


# ---------------------------- selective --------------------------------
def _check_selective(seed: int) -> None:
    rng = np.random.default_rng(seed)
    B, Hkv, D = 1, int(rng.choice([1, 2])), 32
    G = int(rng.choice([1, 2]))
    Hq = G * Hkv
    S = int(rng.integers(32, 200))
    R_ = int(rng.integers(1, min(S, 48) + 1))
    window = int(rng.choice([8, 24, 64]))
    q = jnp.asarray(rng.normal(size=(B, R_, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    qpos = jnp.asarray(np.sort(rng.choice(S, R_, replace=False)), jnp.int32)
    hh = (rng.random(S) < rng.uniform(0, 0.3)).astype(np.int8)
    out = selective_mha(
        q,
        qpos,
        k,
        v,
        jnp.asarray(hh),
        window=window,
        q_block=16,
        kv_block=32,
        interpret=True,
    )
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, R_, D)
    kf = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    vf = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
    ref = selective_attention_ref(qf, qpos, kf, vf, jnp.asarray(hh), window=window)
    ref = ref.reshape(B, Hq, R_, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


# --------------------------- block gather ------------------------------
def _check_block_gather(seed: int) -> None:
    rng = np.random.default_rng(seed)
    npages = int(rng.integers(4, 48))
    page = int(rng.choice([4, 8, 16]))
    d = int(rng.choice([16, 32, 64]))
    n_logical = int(rng.integers(1, npages + 1))
    rotate = bool(rng.integers(0, 2))
    pk = jnp.asarray(rng.normal(size=(npages, page, d)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(npages, page, d)), jnp.float32)
    bt = jnp.asarray(rng.choice(npages, n_logical, replace=False), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 4096, (n_logical, page)), jnp.int32)
    ko, vo = assemble_kv(
        pk,
        pv,
        bt,
        pos,
        rope_theta=1e4,
        rotate=rotate,
        interpret=True,
    )
    kr, vr = block_gather_ref(pk, pv, bt, pos, rope_theta=1e4, rotate=rotate)
    np.testing.assert_allclose(np.asarray(ko), np.asarray(kr), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), atol=1e-6)


# --------------------------- embedding bag -----------------------------
def _check_embedding_bag(seed: int) -> None:
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(8, 600))
    d = int(rng.choice([8, 16, 32, 64]))
    B = int(2 ** rng.integers(0, 5))
    F = int(rng.integers(1, 16))
    dtype = jnp.bfloat16 if rng.integers(0, 4) == 0 else jnp.float32
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    table = jnp.asarray(rng.normal(size=(rows, d)), dtype)
    ids = jnp.asarray(rng.integers(0, rows, (B, F)), jnp.int32)
    out = bag_sum(table, ids, interpret=True)
    ref = embedding_bag_ref(table, ids)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


# --------------------------- paged decode ------------------------------
def _random_layout(rng, n_pages, page, n_rows, max_len):
    """Random per-row slot tables the way serving produces them: each
    row's logical positions land in arbitrary (possibly shared, never
    page-aligned) physical slots, plus one freshly claimed decode slot.
    -> (tables (N, S), lens (N,), new_pages (N,), new_slots (N,))."""
    lens = rng.integers(0, max_len, n_rows)
    S = max(int(lens.max()) + 1, 1)
    tables = np.zeros((n_rows, S), np.int64)
    new_pages = np.zeros(n_rows, np.int64)
    new_slots = np.zeros(n_rows, np.int64)
    for i in range(n_rows):
        # slots off the scratch page, distinct within the row, arbitrary
        # alignment (a draw may interleave any pages at any offsets)
        slots = rng.choice(
            np.arange(page, n_pages * page), int(lens[i]) + 1, replace=False
        )
        tables[i, : lens[i]] = slots[:-1]
        new_pages[i] = slots[-1] // page
        new_slots[i] = slots[-1] % page
    return tables, lens.astype(np.int64), new_pages, new_slots


def _check_paged_decode(seed: int) -> None:
    rng = np.random.default_rng(seed)
    page = int(rng.choice([4, 8, 16]))
    n_pages = int(rng.integers(6, 24))
    L = int(rng.integers(1, 3))
    Hkv = int(rng.choice([1, 2]))
    G = int(rng.choice(GQA_GROUPS))
    D = int(rng.choice([8, 16, 32]))
    N = int(2 ** rng.integers(0, 4))              # pow2-padded batch
    Hq = G * Hkv
    max_len = min(n_pages * page - page - 1, int(rng.integers(2, 40)))
    tables, lens, new_pages, new_slots = _random_layout(rng, n_pages, page, N, max_len)
    pg_ids, sl_pos = page_views(tables, lens, new_pages, new_slots, page)
    ak = jnp.asarray(rng.normal(size=(n_pages, L, Hkv, page, D)), jnp.float32)
    av = jnp.asarray(rng.normal(size=(n_pages, L, Hkv, page, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(N, Hq, D)), jnp.float32)
    for layer in range(L):
        out = paged_decode_mha(
            q,
            ak,
            av,
            jnp.asarray(pg_ids),
            jnp.asarray(sl_pos),
            layer=layer,
            rope_theta=1e4,
            interpret=True,
        )
        ref = paged_decode_ref(
            q,
            ak,
            av,
            jnp.asarray(pg_ids),
            jnp.asarray(sl_pos),
            layer=layer,
            rope_theta=1e4,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )


def _check_page_views(seed: int) -> None:
    """Structural invariants of the page view itself: every logical
    position 0..len appears exactly once, tagged at the physical slot
    the table maps it to; everything else is dead (-1); pad columns
    reference the scratch page."""
    rng = np.random.default_rng(seed)
    page = int(rng.choice([2, 4, 8, 16]))
    n_pages = int(rng.integers(4, 32))
    N = int(rng.integers(1, 9))
    max_len = min(n_pages * page - page - 1, int(rng.integers(1, 50)))
    tables, lens, new_pages, new_slots = _random_layout(rng, n_pages, page, N, max_len)
    pg_ids, sl_pos = page_views(tables, lens, new_pages, new_slots, page)
    assert pg_ids.shape[1] % 4 == 0
    assert sl_pos.shape == pg_ids.shape + (page,)
    for i in range(N):
        ln = int(lens[i])
        live = {}
        for j in range(pg_ids.shape[1]):
            for t in range(page):
                p = int(sl_pos[i, j, t])
                if p >= 0:
                    assert p not in live, "logical position served twice"
                    live[p] = int(pg_ids[i, j]) * page + t
        assert sorted(live) == list(range(ln + 1))
        for p in range(ln):
            assert live[p] == tables[i, p]
        assert live[ln] == new_pages[i] * page + new_slots[i]
        # pad view columns reference the scratch page, fully dead
        n_used = len({tables[i, p] // page for p in range(ln)} | {int(new_pages[i])})
        assert (pg_ids[i, n_used:] == 0).all()
        assert (sl_pos[i, n_used:] == -1).all()


_FAMILIES = {
    "flash": _check_flash,
    "flash_ragged": _check_flash_ragged,
    "selective": _check_selective,
    "block_gather": _check_block_gather,
    "embedding_bag": _check_embedding_bag,
    "paged_decode": _check_paged_decode,
    "page_views": _check_page_views,
}


@pytest.mark.parametrize("family", sorted(_FAMILIES))
@pytest.mark.parametrize("seed", SWEEP_SEEDS)
def test_kernel_parity_sweep(family, seed):
    """Seeded-random sweep — the always-on harness (CI runs this even
    without hypothesis)."""
    _FAMILIES[family](seed)


@pytest.mark.slow
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_kernel_parity_hypothesis(family):
    """Hypothesis-driven seed exploration (skips without hypothesis)."""

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def explore(seed):
        _FAMILIES[family](seed)

    explore()


# ------------------------ oracle-drift regression -----------------------
def test_decode_oracles_cannot_drift():
    """`batch_engine._decode_attn` (the gather path) and the paged
    kernel's oracle must share one attention body: identical inputs ->
    bitwise-identical outputs, and the masking constant stays pinned."""
    from repro.serving.batch_engine import _decode_attn

    assert NEG_INF == -1e30
    rng = np.random.default_rng(7)
    N, T, Hkv, G, D = 4, 33, 2, 2, 16
    q = jnp.asarray(rng.normal(size=(N, G * Hkv, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(N, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(N, T, Hkv, D)), jnp.float32)
    valid = rng.random((N, T)) < 0.6
    valid[:, -1] = True
    a = _decode_attn(q, k, v, jnp.asarray(valid))
    b = masked_decode_attention_ref(q, k, v, jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decode_kernel_config_resolution():
    """`decode_kernel` plumbing: auto follows the backend, gather/paged
    pin either path, anything else is rejected."""
    from repro.configs.base import LMConfig
    from repro.core.engine import decode_uses_paged

    cfg = LMConfig(
        name="t",
        n_layers=1,
        d_model=32,
        n_heads=4,
        n_kv_heads=2,
        d_ff=64,
        vocab_size=64,
    )
    assert not decode_uses_paged(cfg)  # jnp + auto
    assert decode_uses_paged(dataclasses.replace(cfg, attn_backend="pallas"))
    assert decode_uses_paged(dataclasses.replace(cfg, decode_kernel="paged"))
    assert not decode_uses_paged(
        dataclasses.replace(cfg, attn_backend="pallas", decode_kernel="gather")
    )
    with pytest.raises(ValueError, match="decode_kernel"):
        decode_uses_paged(dataclasses.replace(cfg, decode_kernel="bogus"))
