"""Serving launcher: cluster simulation or the real batched JAX engine.

    # distributed cluster simulation (analytic cost model, K instances)
    PYTHONPATH=src python -m repro.launch.serve --config engine=sim,k=40 \\
        --qps 120

    # real hardware: continuous batching + paged KV pool on one instance
    PYTHONPATH=src python -m repro.launch.serve --config engine=jax \\
        --requests 8

    # real hardware, K instances: affinity-scheduled cluster of JAX
    # engines over sharded item caches (per-request TTFT, per-worker
    # hit rates, explicit cross-shard transfers)
    PYTHONPATH=src python -m repro.launch.serve --config engine=jax,k=4 \\
        --requests 12

    # unified token-budget scheduler: chunk-resumable selective prefill
    # mixed with decode in every tick (no whole-prefill waves)
    PYTHONPATH=src python -m repro.launch.serve \\
        --config engine=jax,sched=chunked,chunk_tokens=128 \\
        --requests 12 --long-prompt-frac 0.2

    # the asyncio session server: the same trace as live streaming
    # sessions (per-tick online metrics in the output's "online" key)
    PYTHONPATH=src python -m repro.launch.serve --server \\
        --config engine=jax,sched=chunked,kv_reuse=on --requests 12

    # tensor-parallel serving on a real jax mesh (2 devices on the model
    # axis; on CPU, force host devices before the first jax import)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.serve \\
        --config mesh.tp=2,sched=chunked --requests 8

Serving knobs live in ONE typed object — `serving.api.ServeConfig` —
passed as ``--config key=value[,key=value...]`` and validated up front
(invalid combos like ``decode_kernel=paged`` with ``engine=sim`` fail
with a message naming both knobs).  The historical per-knob flags
(``--engine --k --sched --kv-reuse ...``) still work: they fold into
the same dataclass through `ServeConfig.from_args` with a single
`DeprecationWarning`.  Workload shape (``--requests --qps --zipf-users
--long-prompt-frac``) and launcher behaviour (``--warmup --server
--speed``) stay first-class flags — they describe the experiment, not
the serving stack.

All paths drive the *same* batching loop; ``engine`` picks the backend
behind its seam (`serving.batching.EngineBackend`) and ``k`` with
``engine=jax`` picks single-instance vs the `serving.cluster` path.
``sched`` picks the scheduling discipline: ``wave`` (whole-prefill
batches, prefill-prioritized — the default) or ``chunked`` (every tick
packs decode tokens plus fixed-size prefill chunks under a global token
budget; decoded tokens are bitwise identical either way).  With
``mode=rcllm`` each prompt goes through decomposition → assembly
plan → beyond-prefix cache insertion → selective recompute → paged
decode; ``mode=full`` is the Full-Recompute reference.  ``--server``
re-expresses the trace-driven run as a thin client of the asyncio
session server (`serving.server`): identical output schema (and, with
``--speed 0``, bitwise-identical decoded tokens) plus the server's
rolling online metrics.  This entry point emits machine-readable JSON,
including a per-request latency split (queue-wait vs prefill-compute vs
decode) and time-between-tokens percentiles so scheduler changes are
attributable from bench artifacts.
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from repro.configs import registry as REG
from repro.core import cost_model as CM
from repro.core import simulator as SIM
from repro.launch.compile_cache import use_compile_cache
from repro.serving.api import ServeConfig, SubmitRequest


def run_sim(config: ServeConfig, args) -> dict:
    qps = args.qps if args.qps is not None else 3.0 * config.k
    cfg = REG.ARCHS[args.model]
    reqs, placement, _ = SIM.make_sim_setup(
        k=config.k, n_requests=args.requests, qps=qps, n_items=8000, seed=1
    )
    res = SIM.simulate(
        cfg,
        CM.V5E_1,
        reqs,
        placement,
        SIM.SimConfig(
            mode=config.mode,
            policy=config.policy,
            r_item=config.r_item,
            r_rev=config.r_rev,
        ),
    )
    return {
        "engine": "sim",
        "k": config.k,
        "qps": qps,
        "mode": config.mode,
        "policy": config.policy,
        **res.summary(),
    }


def _percentiles(xs, qs=(50, 90, 99)) -> dict:
    xs = np.asarray(list(xs), np.float64)
    if len(xs) == 0:
        return {f"p{q}_s": None for q in qs}
    return {f"p{q}_s": float(np.percentile(xs, q)) for q in qs}


def _latency_split(completions) -> dict:
    """Per-request latency attribution + aggregates from completions."""
    done = sorted(completions, key=lambda c: c.rid)
    if not done:
        # every session was rejected/cancelled before producing a token
        # (the server path degrades per-request instead of raising)
        keys = ("ttft_p50_s", "ttft_p90_s", "ttft_p99_s", "ttft_mean_s")
        out = {k: None for k in keys}
        out.update(queue_wait_mean_s=None, prefill_mean_s=None, decode_mean_s=None)
        out["per_request"] = []
        return out
    ttft = np.asarray([c.first_token_s - c.arrival_s for c in done])
    return {
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "ttft_p90_s": float(np.percentile(ttft, 90)),
        "ttft_p99_s": float(np.percentile(ttft, 99)),
        "ttft_mean_s": float(ttft.mean()),
        "queue_wait_mean_s": float(np.mean([c.queue_wait_s for c in done])),
        "prefill_mean_s": float(np.mean([c.prefill_s for c in done])),
        "decode_mean_s": float(np.mean([c.decode_s for c in done])),
        "per_request": [
            {
                "rid": c.rid,
                "ttft_s": round(float(c.first_token_s - c.arrival_s), 4),
                "queue_wait_s": round(float(c.queue_wait_s), 4),
                "prefill_s": round(float(c.prefill_s), 4),
                "decode_s": round(float(c.decode_s), 4),
            }
            for c in done
        ],
    }


def _mesh_info(config: ServeConfig):
    """The mesh the run actually used, for the output JSON (None when
    the config runs the classic unsharded path)."""
    if not config.mesh.enabled:
        return None
    import jax

    return {
        "tp": config.mesh.tp,
        "dp": config.mesh.dp,
        "shape": list(config.mesh.resolved_shape),
        "axis_names": list(config.mesh.axis_names),
        "host_devices": len(jax.devices()),
    }


def _tbt_stats(workers) -> dict:
    samples = [dt for w in workers for dt in w.tbt]
    out = {f"tbt_{k}": v for k, v in _percentiles(samples).items()}
    out["tbt_samples"] = len(samples)
    return out


def _tick_stats(workers) -> dict:
    ticks = [t for w in workers for t in w.ticks]
    if not ticks:
        return {}
    return {
        "ticks": len(ticks),
        "oversized_ticks": sum(1 for t in ticks if t.oversized),
        "mean_tick_tokens": float(
            np.mean(
                [t.decode_tokens + t.chunk_tokens + t.finalize_tokens for t in ticks]
            )
        ),
    }


def run_jax_cluster(config: ServeConfig, args) -> dict:
    """K real engine workers behind the Eq. 2 scheduler (serving.cluster)."""
    from repro.core.rcllm import make_tiny_system
    from repro.data import synth as SY
    from repro.serving.cluster import ClusterEngine

    qps = args.qps if args.qps is not None else 8.0
    system, pool_rv, prof, _ = make_tiny_system(
        n_items=80, n_requests_hist=40, k_instances=config.k,
        n_layers=2, d_model=32,
    )
    trace = SY.make_trace(
        system.catalog,
        pool_rv,
        prof,
        args.requests,
        qps=qps,
        n_users=max(3, args.requests // 2),
        n_candidates=8,
        reviews_per_user=1,
        seed=2,
        user_zipf_a=args.zipf_users,
        long_prompt_frac=args.long_prompt_frac,
    )

    if args.warmup:
        ClusterEngine(system, config).run(trace, decode_steps=config.decode_steps)
    cluster = ClusterEngine(system, config)
    rep = cluster.run(trace, decode_steps=config.decode_steps)

    ttft = rep.ttft()
    return {
        "engine": "jax-cluster",
        "k": config.k,
        "mode": config.mode,
        "sched": config.sched,
        "attn_backend": config.attn_backend,
        "decode_kernel": config.decode_kernel,
        "kv_reuse": "on" if config.kv_reuse else "off",
        "mesh": _mesh_info(config),
        "disagg": (
            {
                "prefill_workers": config.disagg.prefill_workers,
                "decode_workers": config.disagg.decode_workers,
                "mig_gamma": config.disagg.mig_gamma,
            }
            if config.disagg.enabled
            else None
        ),
        "store": (
            {
                "kv_store_dtype": config.store.kv_store_dtype,
                "spill_mb": config.store.spill_mb,
                "prefetch_pages_per_tick": config.store.prefetch_pages_per_tick,
            }
            if config.store.enabled
            else None
        ),
        "policy": rep.policy,
        "requests": len(rep.completions),
        "decode_steps": config.decode_steps,
        "includes_jit_compile": not args.warmup,
        "per_request_ttft_s": [round(float(x), 4) for x in ttft],
        **_latency_split(rep.completions),
        **_tbt_stats(cluster.batcher.workers),
        **_tick_stats(cluster.batcher.workers),
        "mean_hit_rate": rep.mean_hit_rate(),
        "per_worker": [
            {
                "worker": w.worker,
                "role": (
                    config.disagg.role_of(w.worker)
                    if config.disagg.enabled
                    else "unified"
                ),
                "requests": w.n_requests,
                "mean_hit_rate": (
                    round(w.mean_hit_rate, 4)
                    if w.mean_hit_rate is not None
                    else None
                ),
                "transfer_blocks": w.transfer_blocks,
                "transfer_tokens": w.transfer_tokens,
                "transfer_mbytes": round(w.transfer_bytes / 1e6, 3),
                "transfer_seconds": round(w.transfer_seconds, 6),
                "pool_peak_pages": w.pool_peak_pages,
                "busy_seconds": round(w.busy_seconds, 4),
                "preempted": w.preempted,
                "migrations": w.migrations,
                "migrated_out": w.migrated_out,
                "migrated_pages": w.migrated_pages,
                "migration_mbytes": round(w.migration_bytes / 1e6, 3),
                "migration_s": round(w.migration_s, 6),
                "migration_digest_hits": w.migration_digest_hits,
                "device_blocks": w.device_blocks,
                "spill_blocks": w.spill_blocks,
                "spill_hits": w.spill_hits,
                "prefetch_promotions": w.prefetch_promotions,
                "dequant_s": round(w.dequant_s, 6),
                "kv_reuse": w.kv_reuse,
            }
            for w in rep.workers
        ],
    }


def _jax_workload(config: ServeConfig, args):
    """Build (params, lm_cfg, requests, plans, reuse) for the single-
    instance jax paths — shared by the closed-loop runner and the
    session server so both serve the exact same trace."""
    from repro.serving.batching import PendingRequest
    from repro.serving.workload import rcllm_workload

    if args.zipf_users is not None and config.mode != "rcllm":
        raise SystemExit(
            "--zipf-users shapes the rcllm trace; it has no "
            "effect on mode=full prompts"
        )
    qps = args.qps if args.qps is not None else 8.0
    rng = np.random.default_rng(1)
    plans = {}
    reuse = None

    if config.mode == "rcllm":
        # full RcLLM stack: tiny model + both cache pools + placement
        from repro.core.rcllm import make_tiny_system
        from repro.data import synth as SY
        from repro.serving.workload import rcllm_reuse_info

        system, pool_rv, prof, _ = make_tiny_system(
            n_items=80, n_requests_hist=40, k_instances=max(config.k, 1),
            n_layers=2, d_model=32,
        )
        params, cfg = system.params, system.cfg
        # one trace producer for every flag combination: --zipf-users
        # changes ONLY the user-id distribution and --long-prompt-frac
        # ONLY the history-length tail, so scheduler / reuse comparisons
        # are not confounded by trace shape
        trace = SY.make_trace(
            system.catalog,
            pool_rv,
            prof,
            args.requests,
            qps=qps,
            n_users=max(3, args.requests // 2),
            n_candidates=8,
            reviews_per_user=1,
            seed=2,
            user_zipf_a=args.zipf_users,
            long_prompt_frac=args.long_prompt_frac,
        )
        reqs, plans = rcllm_workload(system, trace, decode_steps=config.decode_steps)
        if config.kv_reuse:
            reuse = rcllm_reuse_info(system, trace, plans)
    else:
        # Full-Recompute reference on random prompts
        import jax

        from repro.configs.base import LMConfig
        from repro.models import transformer as T

        cfg = LMConfig(
            name="serve-tiny",
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=2,
            head_dim=16,
            d_ff=128,
            vocab_size=512,
            mlp_type="swiglu",
            dtype="float32",
            attn_q_chunk=64,
            attn_kv_chunk=64,
            remat=False,
        )
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        if args.prompt_tokens < 16:
            raise SystemExit("--prompt-tokens must be >= 16")
        lo = min(48, args.prompt_tokens)
        arrivals = np.cumsum(rng.exponential(1.0 / qps, args.requests))
        reqs = []
        for rid in range(args.requests):
            n = int(rng.integers(lo, args.prompt_tokens + 1))
            reqs.append(
                PendingRequest(
                    arrival_s=float(arrivals[rid]),
                    rid=rid,
                    n_tokens=n,
                    decode_steps=config.decode_steps,
                    tokens=rng.integers(1, cfg.vocab_size, n).astype(np.int32),
                )
            )
    return params, cfg, reqs, plans, reuse


def _engine_report(config: ServeConfig, args, engine, backend, done) -> dict:
    total = max((c.done_s for c in done), default=0.0)
    n_toks = sum(len(backend.generated[c.rid]) for c in done)
    stats = engine.pool.stats()
    out = {
        "engine": "jax",
        "mode": config.mode,
        "sched": config.sched,
        "attn_backend": backend.attn_backend,
        "decode_kernel": config.decode_kernel,
        "requests": len(done),
        "kv_reuse": "on" if config.kv_reuse else "off",
        "mesh": _mesh_info(config),
        "decode_steps": config.decode_steps,
        "includes_jit_compile": not args.warmup,
        **_latency_split(done),
        "decode_tokens": int(n_toks),
        "throughput_tok_s": float(n_toks / max(total, 1e-9)),
        "pool_peak_pages": engine.pool.peak_pages,
        "pool_peak_utilization": round(
            engine.pool.peak_pages / max(stats.n_pages - 1, 1), 4
        ),
    }
    if engine.store is not None:
        out["block_store"] = engine.store.stats()
    return out


def run_jax(config: ServeConfig, args) -> dict:
    """Continuous batching over the real engine on this host's devices."""
    from repro.core import engine as ENG
    from repro.serving import api as API

    params, cfg, reqs, plans, reuse = _jax_workload(config, args)
    sel = ENG.SelectiveConfig(r_item=config.r_item, r_rev=config.r_rev, window=16)

    def make_batcher():
        engine = API.build_engine(params, cfg, config, sel=sel)
        backend = API.build_backend(engine, config, plans=plans, reuse=reuse)
        return engine, backend, API.build_batcher(backend, config)

    if args.warmup:
        # throwaway pass to fill the jit caches, so the reported times
        # are step times rather than trace/compile times
        make_batcher()[2].run(list(reqs))
    engine, backend, batcher = make_batcher()
    done = sorted(batcher.run(reqs), key=lambda c: c.rid)

    out = _engine_report(config, args, engine, backend, done)
    out.update(_tbt_stats(batcher.workers))
    out.update(_tick_stats(batcher.workers))
    return out


def run_jax_server(config: ServeConfig, args) -> dict:
    """The same single-instance trace served through the asyncio session
    server: streaming sessions over the identical scheduling loop, plus
    rolling online metrics.  ``--speed 0`` replays the trace's arrival
    stamps deterministically (decoded tokens bitwise-identical to
    `run_jax`); ``--speed > 0`` turns it into open-loop wall-clock
    traffic."""
    from repro.core import engine as ENG
    from repro.serving import api as API
    from repro.serving.server import AsyncSessionServer, serve_trace

    params, cfg, reqs, plans, reuse = _jax_workload(config, args)
    sel = ENG.SelectiveConfig(r_item=config.r_item, r_rev=config.r_rev, window=16)
    submits = [
        (
            r.arrival_s,
            SubmitRequest(
                rid=r.rid,
                tokens=r.tokens,
                max_tokens=r.decode_steps,
                context=plans.get(r.rid),
                reuse=(reuse or {}).get(r.rid),
            ),
        )
        for r in reqs
    ]

    def make_server():
        engine = API.build_engine(params, cfg, config, sel=sel)
        backend = API.build_backend(engine, config)
        return engine, backend, AsyncSessionServer(backend, config)

    if args.warmup:
        import asyncio

        from repro.serving.server import replay

        engine, backend, server = make_server()
        asyncio.run(replay(server, submits, speed=args.speed))
    engine, backend, _ = make_server()
    completions, server = serve_trace(backend, config, submits, speed=args.speed)
    # the worker's completion records carry the same virtual-clock
    # latency split the closed-loop runner reports
    done = sorted(server.worker.done, key=lambda c: c.rid)

    out = _engine_report(config, args, engine, backend, done)
    out.update(_tbt_stats([server.worker]))
    out.update(_tick_stats([server.worker]))
    out["server"] = True
    out["speed"] = args.speed
    out["finish_reasons"] = {
        reason: sum(1 for c in completions.values() if c.reason == reason)
        for reason in sorted({c.reason for c in completions.values()})
    }
    out["online"] = server.metrics_snapshot()
    return out


def build_config(args) -> ServeConfig:
    """``--config`` + legacy per-knob flags -> one validated ServeConfig."""
    if args.config is not None:
        base = ServeConfig.parse(args.config)
    else:
        # historical defaults: engine=sim with 40 simulated instances;
        # --engine jax serves one real instance unless --k asks for more
        eng = args.engine if args.engine is not None else "sim"
        k = args.k if args.k is not None else (1 if eng == "jax" else 40)
        base = ServeConfig(engine=eng, k=k)
    return ServeConfig.from_args(args, base=base)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--config",
        default=None,
        help="serving stack as key=value[,key=value...] over "
        "serving.api.ServeConfig — e.g. "
        "engine=jax,k=2,sched=chunked,kv_reuse=on.  The typed "
        "replacement for the per-knob flags below",
    )
    ap.add_argument(
        "--server",
        action="store_true",
        help="drive the trace through the asyncio session server "
        "(serving.server; engine=jax, k=1): streaming sessions over "
        "the same scheduling loop, online metrics in the output's "
        "'online' key.  Identical decoded tokens at --speed 0",
    )
    ap.add_argument(
        "--speed",
        type=float,
        default=0.0,
        help="--server arrival pacing: 0 = deterministic replay of "
        "the trace's arrival stamps; >0 = open-loop wall-clock "
        "arrivals at trace-time / speed",
    )
    # ------- workload / launcher flags (first-class, not deprecated) -------
    ap.add_argument("--qps", type=float, default=None)
    ap.add_argument("--requests", type=int, default=1500)
    ap.add_argument("--model", default="rcllm-qwen3-8b")
    ap.add_argument(
        "--zipf-users",
        type=float,
        default=None,
        help="rcllm trace: draw user ids Zipf(a) instead of "
        "uniformly — heavy repeat users, the workload "
        "where kv_reuse pays (e.g. 1.4)",
    )
    ap.add_argument(
        "--long-prompt-frac",
        type=float,
        default=0.0,
        help="rcllm trace: fraction of users carrying a lognormal "
        "heavy tail of extra reviews — long-prompt head-of-line "
        "interference, the workload where sched=chunked pays "
        "(e.g. 0.2)",
    )
    ap.add_argument("--prompt-tokens", type=int, default=160)
    ap.add_argument(
        "--warmup",
        action="store_true",
        help="run a throwaway pass first so reported times "
        "exclude jit compilation",
    )
    # ---- legacy per-knob serving flags (deprecated: they fold into the ----
    # ---- ServeConfig with one DeprecationWarning; prefer --config) -------
    ap.add_argument("--engine", default=None, choices=["sim", "jax"])
    ap.add_argument("--k", type=int, default=None)
    ap.add_argument("--mode", default=None, choices=["rcllm", "prefix", "full"])
    ap.add_argument("--attn-backend", default=None, choices=["jnp", "pallas"])
    ap.add_argument(
        "--decode-kernel", default=None, choices=["auto", "gather", "paged"]
    )
    ap.add_argument("--kv-reuse", default=None, choices=["off", "on"])
    ap.add_argument("--sched", default=None, choices=["wave", "chunked"])
    ap.add_argument("--chunk-tokens", type=int, default=None)
    ap.add_argument("--step-tokens", type=int, default=None)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--r-item", type=float, default=None)
    ap.add_argument("--r-rev", type=float, default=None)
    ap.add_argument("--decode-steps", type=int, default=None)
    ap.add_argument("--max-batch-tokens", type=int, default=None)
    ap.add_argument("--page-size", type=int, default=None)
    ap.add_argument("--pages", type=int, default=None)
    args = ap.parse_args(argv)
    use_compile_cache()

    try:
        config = build_config(args)
    except ValueError as e:
        raise SystemExit(str(e))

    if args.server:
        if config.engine != "jax":
            raise SystemExit("--server drives the real engine: engine=jax")
        if config.k != 1:
            raise SystemExit(
                "--server runs a single-worker session server (k=1); "
                "multi-worker serving is the closed-loop cluster path"
            )
        out = run_jax_server(config, args)
    elif config.engine == "jax":
        out = run_jax_cluster(config, args) if config.k > 1 else run_jax(config, args)
    else:
        out = run_sim(config, args)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
