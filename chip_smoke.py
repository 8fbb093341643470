"""Chip smoke: the serving path end to end on TPU at Qwen3-8B widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip paths, and nothing else

One chip.  An 8-layer cut of `configs/rcllm_qwen3_8b` — published
widths (d_model 4096, 32/8 heads of 128, d_ff 12288, vocab 151936), bf16
weights drawn from ``--seed`` — serves 8 requests through the public
path `api.ServeConfig` -> `ClusterEngine` (k=1, mode=rcllm,
sched=chunked, kv_reuse=on), once with attn_backend=jnp and once with
attn_backend=pallas (real Mosaic kernels: interpret mode is refused).
Each backend serves the trace twice on fresh engines: the first pass
compiles (reported as set-up), the second is timed and must decode the
same tokens.  Checks, by stated tolerances and never bitwise:

* every request finishes with the tokens it asked for;
* the engine's full-prefill last-token logits for one request, under
  each backend, against `models.transformer.forward` run in float32
  under ``jax.default_matmul_precision("highest")`` on the same weights
  (`REF_REL_L2`);
* pallas first-token logits against jnp on the same trace
  (`BACKEND_REL_L2`).

Four chips (``--chips 4``), a 4-layer float32 cut at the same widths,
every matmul at "highest" precision:

* a k=4 `ClusterEngine` with each worker's params, KV arena and block
  store on its own chip must decode the tokens the same cluster decodes
  with all four workers on one chip;
* one engine at ``mesh.tp=4`` against the same engine unsharded on one
  chip: equal tokens, first-token logits within `TP_REL_L2`.

Every phase prints one JSON line; the last line is the verdict
``{"ok": true, "device": {...}}``.  A failed check raises, so the script
exits non-zero and prints no verdict.  It runs only where JAX's default
device is a TPU, in this one process (no child ever touches the chip).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import LMConfig  # noqa: E402
from repro.configs.rcllm_qwen3_8b import CONFIG as QWEN3_8B  # noqa: E402
from repro.core.rcllm import make_tiny_system  # noqa: E402
from repro.data import synth as SY  # noqa: E402
from repro.kernels import default_interpret  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import api as API  # noqa: E402
from repro.serving.batch_engine import BatchRequest  # noqa: E402
from repro.serving.cluster import ClusterEngine  # noqa: E402
from repro.serving.workload import rcllm_batch_requests  # noqa: E402

# Tolerances, as relative L2 error ||a - b|| / ||b|| over a logits row.
# bf16 serving against the float32 reference: bf16 keeps 8 mantissa bits
# (relative rounding 2^-9), and each of the cut's layers re-rounds the
# residual stream, attention and MLP outputs; a few percent covers that
# and still fails a wrong mask, position or layer (those move the row by
# O(1)).
REF_REL_L2 = 0.05
# pallas against jnp, both bf16: the same rounding points, different
# accumulation order inside attention
BACKEND_REL_L2 = 0.05
# tp=4 against unsharded, float32 at "highest": only the all-reduce
# summation order differs
TP_REL_L2 = 1e-4

ARENA_BYTES = 2 << 30        # the paged KV arena, K and V together
PAGE = API.ServeConfig.page_size   # the serving default every phase uses
ONE_CHIP_LAYERS = 8
FOUR_CHIP_LAYERS = 2


def chip_config(n_layers: int, dtype: str) -> LMConfig:
    """Qwen3-8B at its published widths, cut to `n_layers`."""
    return dataclasses.replace(QWEN3_8B, n_layers=n_layers, dtype=dtype, remat=False)


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _device_record() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def _peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _require_platform(platform: str, n_devices: int) -> None:
    devs = jax.devices()
    _check(devs[0].platform == platform,
           f"default device is {devs[0].platform!r}, this run needs {platform!r}")
    _check(len(devs) >= n_devices,
           f"{len(devs)} {platform} device(s) visible, this run needs {n_devices}")
    if platform == "tpu":
        _check(not default_interpret(), "Pallas kernels would run in interpret mode")


def _token_bytes(cfg: LMConfig) -> int:
    """float32 K+V bytes of one token row across all layers."""
    return 2 * 4 * cfg.n_layers * cfg.n_kv_heads * cfg.resolved_head_dim


def _pages_for(cfg: LMConfig, arena_bytes: int) -> int:
    """Pages of a float32 K+V arena of about `arena_bytes`."""
    return max(arena_bytes // (_token_bytes(cfg) * PAGE), 2)


def _build(cfg: LMConfig, *, seed: int, k: int, n_items: int, n_requests: int):
    """The system (offline item and history KV at `cfg`) and a trace whose
    requests all arrive at t=0, so batching never depends on wall time."""
    system, pool_rv, prof, _ = make_tiny_system(
        n_items=n_items, n_requests_hist=40, k_instances=k, seed=seed, cfg=cfg)
    trace = SY.make_trace(system.catalog, pool_rv, prof, n_requests, qps=1.0,
                          n_users=n_requests, n_candidates=8,
                          reviews_per_user=2, seed=seed + 3)
    for rq in trace:
        rq.arrival_s = 0.0
    return system, trace


def _serve(system, trace, config: API.ServeConfig, decode_steps: int,
           devices=None):
    """One pass of the trace through a fresh `ClusterEngine`.
    -> (tokens by rid, first-token logits by rid, seconds, engines)."""
    cluster = ClusterEngine(system, config, devices=devices)
    first = {}
    for backend in cluster.backends:
        engine = backend.engine
        finalize = engine.finalize_prefill

        def record(rids, _finalize=finalize):
            out = _finalize(rids)
            first.update(out)
            return out

        engine.finalize_prefill = record
    t0 = time.perf_counter()
    report = cluster.run(trace, decode_steps=decode_steps)
    engines = [b.engine for b in cluster.backends]
    jax.block_until_ready([(e.pool.arena_k, e.pool.arena_v) for e in engines])
    dt = time.perf_counter() - t0
    _check(len(report.completions) == len(trace),
           f"{len(report.completions)} of {len(trace)} requests completed")
    for rid in range(len(trace)):
        got = report.generated.get(rid, [])
        _check(len(got) == decode_steps,
               f"request {rid} finished with {len(got)} tokens, asked {decode_steps}")
    tokens = {rid: [int(t) for t in report.generated[rid]] for rid in range(len(trace))}
    return tokens, first, dt, engines


def smoke(cfg: LMConfig, platform: str, *, seed: int = 0, n_requests: int = 8,
          decode_steps: int = 4, n_items: int = 256,
          arena_bytes: int = ARENA_BYTES) -> dict:
    """The one-chip run (see the module docstring).  -> summary."""
    _require_platform(platform, 1)
    t0 = time.perf_counter()
    system, trace = _build(cfg, seed=seed, k=1, n_items=n_items, n_requests=n_requests)
    jax.block_until_ready(system.params)
    n_pages = _pages_for(cfg, arena_bytes)
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(system.params))
    _emit({"phase": "build", "model": cfg.name, "n_layers": cfg.n_layers,
           "d_model": cfg.d_model, "n_heads": cfg.n_heads,
           "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.resolved_head_dim,
           "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "dtype": cfg.dtype,
           "cut": f"depth {cfg.n_layers} of {QWEN3_8B.n_layers} layers",
           "param_bytes": param_bytes,
           "arena_pages": n_pages, "arena_bytes": n_pages * PAGE * _token_bytes(cfg),
           "requests": len(trace),
           "prompt_tokens": [int(system.plan_for(rq, 0).n) for rq in trace],
           "setup_s": time.perf_counter() - t0})

    # ---- serve the trace under each attention backend ----
    tokens, first = {}, {}
    for backend in ("jnp", "pallas"):
        config = API.ServeConfig(engine="jax", k=1, mode="rcllm", sched="chunked",
                                 kv_reuse=True, attn_backend=backend,
                                 n_pages=n_pages, decode_steps=decode_steps)
        cold_tokens, _, cold_s, engines = _serve(system, trace, config, decode_steps)
        del engines
        gc.collect()
        tokens[backend], first[backend], run_s, engines = _serve(
            system, trace, config, decode_steps)
        del engines
        gc.collect()
        _check(tokens[backend] == cold_tokens,
               f"{backend}: the timed pass decoded other tokens than the first")
        _emit({"phase": "serve", "backend": backend, "requests": len(trace),
               "decode_steps": decode_steps,
               "first_pass_s": cold_s, "run_s": run_s,
               "compile_s": max(cold_s - run_s, 0.0),
               "peak_bytes_in_use": _peak_bytes()})

    worst = max(rel_l2(first["pallas"][r], first["jnp"][r]) for r in first["jnp"])
    _check(worst <= BACKEND_REL_L2,
           f"pallas first-token logits off jnp by rel L2 {worst} > {BACKEND_REL_L2}")
    same = sum(tokens["pallas"][r] == tokens["jnp"][r] for r in tokens["jnp"])
    _emit({"phase": "backend_parity", "first_token_rel_l2_max": worst,
           "tol": BACKEND_REL_L2, "requests_with_equal_tokens": same,
           "requests": len(trace)})

    # ---- full prefill of one request against the float32 reference ----
    toks = np.asarray(system.plan_for(trace[0], 0).tokens, np.int32)
    ref_cfg = dataclasses.replace(cfg, dtype="float32")
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: T.forward(p, x, ref_cfg)[0][0, -1])(
            system.params, jnp.asarray(toks[None]))
        ref = np.asarray(jax.block_until_ready(ref), np.float32)
    ref_s = time.perf_counter() - t0
    for backend in ("jnp", "pallas"):
        config = API.ServeConfig(engine="jax", attn_backend=backend, mode="full",
                                 n_pages=_pages_for(cfg, 64 << 20))
        engine = API.build_engine(system.params, system.cfg, config)
        t0 = time.perf_counter()
        got = engine.prefill([BatchRequest(rid=0, tokens=toks)], mode="full")[0]
        dt = time.perf_counter() - t0
        err = rel_l2(got, ref)
        _emit({"phase": "reference", "backend": backend, "prompt_tokens": len(toks),
               "rel_l2": err, "tol": REF_REL_L2,
               "max_abs": float(np.max(np.abs(got - ref))),
               "ref_max_abs": float(np.max(np.abs(ref))),
               "top1_equal": int(np.argmax(got)) == int(np.argmax(ref)),
               "prefill_s_with_compile": dt, "reference_s_with_compile": ref_s})
        _check(err <= REF_REL_L2,
               f"{backend} full-prefill logits off the float32 reference by "
               f"rel L2 {err} > {REF_REL_L2}")
        del engine
    return {"peak_bytes_in_use": _peak_bytes()}


def smoke_four(cfg: LMConfig, platform: str, *, seed: int = 0, n_requests: int = 8,
               decode_steps: int = 4, n_items: int = 256,
               arena_bytes: int = 256 << 20) -> dict:
    """The four-chip paths (see the module docstring).  -> summary."""
    _require_platform(platform, 4)
    devs = jax.devices()[:4]
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        system, trace = _build(cfg, seed=seed, k=4, n_items=n_items,
                               n_requests=n_requests)
        jax.block_until_ready(system.params)
        n_pages = _pages_for(cfg, arena_bytes)
        _emit({"phase": "build", "model": cfg.name, "n_layers": cfg.n_layers,
               "d_model": cfg.d_model, "dtype": cfg.dtype, "arena_pages": n_pages,
               "requests": len(trace), "setup_s": time.perf_counter() - t0})

        # ---- k=4 replicas: one chip each vs all on one chip ----
        config = API.ServeConfig(engine="jax", k=4, mode="rcllm", sched="chunked",
                                 kv_reuse=True, n_pages=n_pages,
                                 decode_steps=decode_steps)
        one_tokens, _, one_s, engines = _serve(system, trace, config, decode_steps,
                                               devices=devs[:1])
        del engines
        gc.collect()
        four_tokens, _, four_s, engines = _serve(system, trace, config, decode_steps,
                                                 devices=devs)
        homes = []
        for e in engines:
            leaves = jax.tree_util.tree_leaves(e.params)
            placed = {d for x in leaves for d in x.devices()}
            placed |= e.pool.arena_k.devices() | e.pool.arena_v.devices()
            _check(len(placed) == 1, f"a worker spans devices {placed}")
            homes.append(placed.pop())
        del engines
        gc.collect()
        _check(len(set(homes)) == 4, f"workers sit on {len(set(homes))} devices, not 4")
        _check(four_tokens == one_tokens,
               "k=4 on four chips decoded other tokens than on one chip")
        _emit({"phase": "cluster_k4", "worker_devices": [str(d) for d in homes],
               "tokens_equal_one_chip": True, "one_chip_s_with_compile": one_s,
               "four_chips_s_with_compile": four_s})

        # ---- tensor parallel: one engine at tp=4 vs unsharded ----
        brs = rcllm_batch_requests(system, trace, n_reserve=decode_steps - 1)
        runs = {}
        for name, mesh in (("tp1", API.MeshConfig()), ("tp4", API.MeshConfig(tp=4))):
            config = API.ServeConfig(engine="jax", mode="rcllm", n_pages=n_pages,
                                     mesh=mesh)
            engine = API.build_engine(system.params, system.cfg, config)
            t0 = time.perf_counter()
            logits = engine.prefill(brs, mode="rcllm")
            last = [int(np.argmax(lg)) for lg in logits]
            seqs = [[t] for t in last]
            rids = [r.rid for r in brs]
            for _ in range(decode_steps - 1):
                out = engine.decode(rids, last)
                last = [int(np.argmax(lg)) for lg in out]
                for s, t in zip(seqs, last):
                    s.append(t)
            runs[name] = (np.asarray(logits), seqs, time.perf_counter() - t0,
                          len(engine.pool.arena_k.devices()))
            del engine
            gc.collect()
        err = max(rel_l2(a, b) for a, b in zip(runs["tp4"][0], runs["tp1"][0]))
        _check(runs["tp4"][3] == 4, f"tp=4 arena spans {runs['tp4'][3]} devices")
        _check(runs["tp4"][1] == runs["tp1"][1], "tp=4 decoded other tokens than tp=1")
        _check(err <= TP_REL_L2,
               f"tp=4 first-token logits off by rel L2 {err} > {TP_REL_L2}")
        _emit({"phase": "tp4", "tokens_equal_tp1": True, "first_token_rel_l2_max": err,
               "tol": TP_REL_L2, "tp1_s_with_compile": runs["tp1"][2],
               "tp4_s_with_compile": runs["tp4"][2]})
    return {"peak_bytes_in_use": [_peak_bytes(d) for d in devs]}


def run(cfg: LMConfig, platform: str, *, four: bool = False, **kw) -> None:
    """Run the one-chip smoke (or, with `four`, the four-chip paths) on
    the expected `platform` and print the verdict as the last line."""
    _emit({"phase": "start", "device": _device_record(), "jax": jax.__version__})
    out = (smoke_four if four else smoke)(cfg, platform, **kw)
    _emit({"phase": "done", **out})
    print(json.dumps({"ok": True, "device": _device_record()}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU (default device is {jax.devices()[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    _emit({"phase": "compile_cache", "dir": use_compile_cache()})
    if args.chips == 4:
        run(chip_config(FOUR_CHIP_LAYERS, "float32"), "tpu", four=True, seed=args.seed)
    else:
        run(chip_config(ONE_CHIP_LAYERS, "bfloat16"), "tpu", seed=args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
