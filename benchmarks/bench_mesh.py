"""Tensor-parallel serving benchmark: TTFT / decode step time vs mesh.tp.

The same heavy-tail trace streams through the chunked-scheduler jax
engine unsharded and then on a real mesh at each tensor-parallel degree
(``--config mesh.tp=N``), with decoded tokens compared against the
unsharded run (``token_parity`` — gated at 1.0-ish by
``check_regression``; tp=1 on an explicit (1, 1) mesh must be bitwise).

Honesty note: these numbers come from FORCED HOST DEVICES — one CPU
carved into 8 XLA devices.  Every "device" shares the same socket, so
tp>1 pays GSPMD's all-reduces without any extra FLOP throughput and is
*expected to be slower* than tp=1 here.  The benchmark pins the cost
surface and the token-parity invariant, not a speedup: on a real
multi-chip backend the same config is where the TP win would appear.

It runs in this process over the devices JAX already sees and fails
with a clear message when there are too few: a child process could not
open a chip its parent holds.  On CPU, force host devices in the
environment before Python starts
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, as CI does).

Emits the standard ``name,us_per_call,derived`` CSV rows plus
``mesh.json`` in `out_dir`; ``--quick`` shrinks the sweep (CI).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

POOL_PAGES = 512
DECODE_STEPS = 4
LONG_PROMPT_FRAC = 0.3


def _stats(ttfts, tbts, wall):
    ttft = np.concatenate(ttfts)
    tbt = np.asarray(tbts)
    return {
        "ttft_mean_s": float(ttft.mean()),
        "ttft_p50_s": float(np.percentile(ttft, 50)),
        "ttft_p99_s": float(np.percentile(ttft, 99)),
        "decode_step_mean_s": float(tbt.mean()) if tbt.size else None,
        "decode_step_p99_s": float(np.percentile(tbt, 99)) if tbt.size else None,
        "wall_s_per_pass": float(np.mean(wall)),
    }


def _serve(system, pend, plans, mesh_cfg, measured):
    """1 warm + `measured` passes of the trace on one engine. -> (stats,
    decoded tokens as plain ints)."""
    from repro.serving import api as API

    scfg = API.ServeConfig(
        engine="jax",
        sched="chunked",
        n_pages=POOL_PAGES,
        decode_steps=DECODE_STEPS,
        mesh=mesh_cfg,
    )
    engine = API.build_engine(system.params, system.cfg, scfg)
    backend = API.build_backend(engine, scfg, plans=plans)
    ttfts, tbts, wall = [], [], []
    for i in range(1 + measured):
        batcher = API.build_batcher(backend, scfg)
        t0 = time.perf_counter()
        done = batcher.run(list(pend))
        dt = time.perf_counter() - t0
        if i == 0:
            continue
        done = sorted(done, key=lambda c: c.rid)
        ttfts.append(np.asarray([c.first_token_s - c.arrival_s for c in done]))
        tbts.extend(batcher.workers[0].tbt)
        wall.append(dt)
    gen = {rid: [int(t) for t in toks] for rid, toks in backend.generated.items()}
    return _stats(ttfts, tbts, wall), gen


def _measure(out_dir: str, quick: bool) -> None:
    import jax

    from benchmarks.common import emit
    from repro.core.rcllm import make_tiny_system
    from repro.serving.api import MeshConfig
    from repro.serving.workload import heavy_tail_trace, rcllm_workload

    tps = [1, 2] if quick else [1, 2, 4]
    n_req = 8 if quick else 16
    measured = 1 if quick else 2
    if len(jax.devices()) < max(tps):
        raise RuntimeError(
            f"bench_mesh needs {max(tps)} devices for tp={max(tps)}, JAX sees "
            f"{len(jax.devices())} ({jax.devices()[0].platform}); on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before starting")

    system, pool_rv, prof, _ = make_tiny_system(
        n_items=60,
        n_requests_hist=30,
        k_instances=2,
        n_layers=2,
        d_model=32,
        n_heads=4,
        n_kv_heads=4,
    )
    trace = heavy_tail_trace(
        system.catalog,
        pool_rv,
        prof,
        n_req,
        qps=60.0,
        n_users=n_req,
        long_prompt_frac=LONG_PROMPT_FRAC,
        long_prompt_reviews=6,
        seed=5,
    )
    pend, plans = rcllm_workload(system, trace, decode_steps=DECODE_STEPS)

    ref_stats, ref_gen = _serve(system, pend, plans, MeshConfig(), measured)
    emit(
        "mesh/unsharded",
        ref_stats["ttft_mean_s"] * 1e6,
        f"ttft_p99={ref_stats['ttft_p99_s']:.4f}s",
    )

    per_tp = {}
    parities = []
    for tp in tps:
        mesh_cfg = MeshConfig(mesh_shape=(1, 1)) if tp == 1 else MeshConfig(tp=tp)
        stats, gen = _serve(system, pend, plans, mesh_cfg, measured)
        parity = float(np.mean([gen[r] == ref_gen[r] for r in ref_gen]))
        stats["token_parity"] = parity
        stats["ttft_vs_unsharded"] = stats["ttft_mean_s"] / max(
            ref_stats["ttft_mean_s"], 1e-9
        )
        per_tp[str(tp)] = stats
        parities.append(parity)
        emit(
            f"mesh/tp{tp}",
            stats["ttft_mean_s"] * 1e6,
            f"ttft_p99={stats['ttft_p99_s']:.4f}s "
            f"vs_unsharded={stats['ttft_vs_unsharded']:.2f}x "
            f"token_parity={parity:.2f}",
        )

    out = {
        "requests": n_req,
        "decode_steps": DECODE_STEPS,
        "measured_passes": measured,
        "host_devices": len(jax.devices()),
        "backend": jax.devices()[0].platform,
        "note": "forced host devices share one CPU: tp>1 pays GSPMD "
        "all-reduces with no added FLOP throughput, so slowdowns vs "
        "tp=1 are expected here; the gates pin cost + token parity, "
        "not a speedup",
        "unsharded": ref_stats,
        "tp": per_tp,
        "token_parity": min(parities),
    }
    assert out["token_parity"] == 1.0, (
        f"sharding changed decoded tokens (parity={out['token_parity']})"
    )
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "mesh.json"), "w") as f:
        json.dump(out, f, indent=1)


def run(out_dir: str = "results/bench", quick: bool = False) -> None:
    """Entry point for ``benchmarks.run``: the sweep, in this process."""
    _measure(out_dir, quick)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)
    _measure(args.out, args.quick)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
