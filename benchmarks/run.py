"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks sweeps (CI);
default sizes reproduce the paper's structure in full.

  fig6        TTFT distributions, K=40, RcLLM vs Prefix vs Full (8B + 72B)
  fig8_9      speedup / hit-rate / footprint vs cluster size K
  fig10       scheduling policies under rising load
  fig11       recompute budget r vs TTFT
  tableIII    ranking accuracy: Full vs RcLLM vs CacheBlend vs EPIC
  kernels     Pallas kernel probes + analytic FLOP reductions
  serving     continuous batching: sim-engine vs real jax-engine TTFT
  cluster     K real engines + sharded item caches: dispatch policies
  attn_backend  jnp vs pallas attention; batched vs per-request prefill
  reuse       cross-request KV reuse (shared block store) off vs on
  chunked     unified token-budget scheduler: wave vs chunked prefill
  paged_decode  fused paged-attention decode kernel vs jnp gather
  openloop    async session server: Poisson wall-clock arrivals, SLO curve
  mesh        tensor-parallel serving on forced host devices: TTFT vs tp
  disagg      disaggregated prefill/decode: KV migration vs re-prefill
  tiered      tiered quantized store: host-RAM spill vs drop-on-evict

Each entry also writes a JSON artifact into ``--out`` (see
docs/benchmarks.md for the full flag and output reference).
"""
from __future__ import annotations

import argparse
import functools
import time

from repro.launch.compile_cache import use_compile_cache

print = functools.partial(print, flush=True)   # keep CSV ordered through pipes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma-separated subset of fig6|fig8_9|fig10|fig11|"
                         "tableIII|kernels|serving|cluster|attn_backend|"
                         "reuse|chunked|paged_decode|openloop|mesh|disagg|"
                         "tiered, or all")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--planted", action="store_true",
                    help="tableIII: train the planted-preference ranker")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args(argv)
    use_compile_cache()

    print("name,us_per_call,derived")
    t0 = time.time()
    jobs = {
        "fig6": lambda: __import__(
            "benchmarks.bench_ttft", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "fig8_9": lambda: __import__(
            "benchmarks.bench_scalability", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "fig10": lambda: __import__(
            "benchmarks.bench_scheduling", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "fig11": lambda: __import__(
            "benchmarks.bench_recompute", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "tableIII": lambda: __import__(
            "benchmarks.bench_accuracy", fromlist=["run"]).run(
                args.out, quick=args.quick, planted=args.planted),
        "kernels": lambda: __import__(
            "benchmarks.bench_kernels", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "serving": lambda: __import__(
            "benchmarks.bench_serving", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "cluster": lambda: __import__(
            "benchmarks.bench_cluster", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "attn_backend": lambda: __import__(
            "benchmarks.bench_attn_backend", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "reuse": lambda: __import__(
            "benchmarks.bench_reuse", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "chunked": lambda: __import__(
            "benchmarks.bench_chunked", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "paged_decode": lambda: __import__(
            "benchmarks.bench_paged_decode", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "openloop": lambda: __import__(
            "benchmarks.bench_openloop", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "mesh": lambda: __import__(
            "benchmarks.bench_mesh", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "disagg": lambda: __import__(
            "benchmarks.bench_disagg", fromlist=["run"]).run(
                args.out, quick=args.quick),
        "tiered": lambda: __import__(
            "benchmarks.bench_tiered", fromlist=["run"]).run(
                args.out, quick=args.quick),
    }
    only = {s.strip() for s in args.only.split(",") if s.strip()}
    unknown = only - set(jobs) - {"all"}
    if unknown:
        ap.error(f"unknown --only entries {sorted(unknown)}; "
                 f"choose from {['all', *jobs]}")
    for name, job in jobs.items():
        if "all" not in only and name not in only:
            continue
        job()
    print(f"# total_bench_seconds,{time.time() - t0:.1f},")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
