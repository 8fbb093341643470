import os

if __name__ == "__main__":
    # 512 host devices for the production meshes, before JAX's first
    # backend use; importing this module sets nothing
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb driver — three cells, hypothesis → change → measure.

Cells (per the selection rules):
  A nemotron-4-15b × prefill_32k  — most representative of the paper
    (TTFT-critical prefill; the paper's selective recomputation applies)
  B kimi-k2-1t-a32b × train_4k    — most collective-bound (9.5 s TP wire)
  C moonshot-v1-16b-a3b × train_4k — worst roofline fraction (0.35)

Each iteration records hypothesis, the analytic roofline delta, and —
where the change alters the compiled artifact — the measured HLO evidence
(collective op counts/bytes, temp memory).  Results land in results/perf/.
"""
import dataclasses          # noqa: E402
import json                 # noqa: E402
import time                 # noqa: E402

import jax                  # noqa: E402

from repro.configs import registry as R                        # noqa: E402
from repro.launch import steps as STEPS                        # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.launch.roofline import collective_bytes_from_hlo    # noqa: E402
from repro.launch.roofline_analytic import lm_analytic         # noqa: E402


def compile_probe(arch, shape, mesh=None, cfg_override=None):
    """Lower+compile a (possibly modified) cell; return HLO evidence."""
    mesh = mesh or make_production_mesh(shape=(16, 16))  # the fixed v5e pod
    if cfg_override is not None:
        old = R.ARCHS[arch]
        R.ARCHS[arch] = cfg_override
    try:
        t0 = time.time()
        fn, args, in_sh, out_sh = STEPS.build(arch, shape, mesh)
        with mesh:
            compiled = jax.jit(fn, in_shardings=in_sh,
                               out_shardings=out_sh).lower(*args).compile()
        mem = compiled.memory_analysis()
        coll = collective_bytes_from_hlo(compiled.as_text())
        return {"compile_s": round(time.time() - t0, 1),
                "temp_gb": round(mem.temp_size_in_bytes / 1e9, 2),
                "arg_gb": round(mem.argument_size_in_bytes / 1e9, 2),
                "collective_counts": coll["counts"],
                "collective_bytes_hlo": coll["total_bytes"]}
    finally:
        if cfg_override is not None:
            R.ARCHS[arch] = old


def fmt(t):
    return (f"comp={t['compute_s']:.3f}s mem={t['memory_s']:.3f}s "
            f"coll={t['collective_s']:.3f}s -> step≈{t['overlapped_s']:.3f}s "
            f"[{t['bottleneck']}]")


def cell_A(out, probe: bool):
    arch, shape = "nemotron-4-15b", "prefill_32k"
    cfg = R.ARCHS[arch]
    dims = R.shapes_of(arch)[shape].dims
    log = []
    base = lm_analytic(cfg, "prefill", dims)
    log.append({"iter": 0, "name": "baseline (full prefill, masked tiles)",
                "terms": base})

    t1 = lm_analytic(cfg, "prefill", dims, selective_recompute=0.37)
    log.append({
        "iter": 1, "name": "RcLLM selective recomputation (paper, r=0.37)",
        "hypothesis": "layers 1..L-1 run dense+attention only for the "
                      "recompute set (instr+HH+window+misses ≈ 37% of "
                      "tokens); compute term ≈ (1 + 0.37·(L-1))/L ≈ 0.39×",
        "terms": t1,
        "confirmed": t1["compute_s"] / base["compute_s"] < 0.45})

    t2 = lm_analytic(cfg, "prefill", dims, selective_recompute=0.37,
                     causal_block_pairing=True)
    log.append({
        "iter": 2, "name": "+ causal block pairing (beyond-paper)",
        "hypothesis": "the baseline masks acausal tiles but still computes "
                      "them; enumerating live (q,kv) tile pairs cuts "
                      "attention-score flops to ~0.55× (diag + lower tiles)",
        "terms": t2,
        "confirmed": t2["compute_s"] < t1["compute_s"]})

    t3 = lm_analytic(cfg, "prefill", dims, selective_recompute=0.37,
                     causal_block_pairing=True, seq_parallel=True,
                     overlap_collectives=True)
    log.append({
        "iter": 3, "name": "+ SP boundaries + comm/compute overlap",
        "hypothesis": "prefill TP all-reduces become RS/AG over "
                      "seq-sharded boundaries (0.5× wire) and overlap the "
                      "per-layer matmuls; step ≈ max(comp, coll)",
        "terms": t3,
        "confirmed": t3["overlapped_s"] < t2["serial_s"]})
    if probe:
        cfg_bp = dataclasses.replace(cfg, causal_block_pairing=True,
                                     attn_q_chunk=2048, attn_kv_chunk=2048)
        log.append({"iter": "evidence",
                    "name": "compile probe: block-pairing lowers (2048-tiles)",
                    "probe": compile_probe(arch, shape, cfg_override=cfg_bp)})
    out["A_nemotron_prefill_32k"] = {
        "selection": "most representative of the paper's technique",
        "final_speedup_vs_baseline":
            base["serial_s"] / log[3]["terms"]["overlapped_s"],
        "iterations": log}


def cell_B(out, probe: bool):
    arch, shape = "kimi-k2-1t-a32b", "train_4k"
    cfg = R.ARCHS[arch]
    dims = R.shapes_of(arch)[shape].dims
    log = []
    base = lm_analytic(cfg, "train", dims)
    log.append({"iter": 0, "name": "baseline", "terms": base})

    t1 = lm_analytic(cfg, "train", dims, seq_parallel=True)
    log.append({
        "iter": 1, "name": "sequence-parallel TP boundaries",
        "hypothesis": "TP wire dominates (4 AR of (B_loc,S,D) per layer = "
                      "458 GB/dev/step); RS+AG over seq-sharded residuals "
                      "halves wire bytes → collective term ×0.5",
        "terms": t1,
        "confirmed": abs(t1["collective_s"] / base["collective_s"] - 0.5
                         - 0.0) < 0.2})

    t2 = lm_analytic(cfg, "train", dims, seq_parallel=True,
                     overlap_collectives=True)
    log.append({
        "iter": 2, "name": "+ async collectives overlapped with compute",
        "hypothesis": "remaining 4.8 s of wire can hide behind the 5.5 s "
                      "of expert GEMMs (XLA latency-hiding scheduler); "
                      "step time → max(comp, coll) ≈ comp",
        "terms": t2,
        "confirmed": t2["overlapped_s"] <= t1["serial_s"] * 0.65})

    t3 = lm_analytic(cfg, "train", dims, seq_parallel=True,
                     overlap_collectives=True, causal_block_pairing=True)
    log.append({
        "iter": 3, "name": "+ causal block pairing",
        "hypothesis": "with wire hidden, compute is dominant again; "
                      "attention tiles are ~23% of train flops at S=4096 → "
                      "expect ~10% off the compute term",
        "terms": t3,
        "confirmed": t3["compute_s"] < t2["compute_s"]})
    if probe:
        log.append({"iter": "evidence",
                    "name": "compile probe: baseline collective schedule",
                    "probe": compile_probe(arch, shape)})
    out["B_kimi_train_4k"] = {
        "selection": "most collective-bound (9.53 s wire/step at baseline)",
        "final_speedup_vs_baseline":
            base["serial_s"] / log[3]["terms"]["overlapped_s"],
        "iterations": log}


def cell_C(out, probe: bool):
    arch, shape = "moonshot-v1-16b-a3b", "train_4k"
    cfg = R.ARCHS[arch]
    dims = R.shapes_of(arch)[shape].dims
    log = []
    base = lm_analytic(cfg, "train", dims)
    log.append({"iter": 0, "name": "baseline mesh (16,16)", "terms": base})

    t1 = lm_analytic(cfg, "train", dims, data_par=64)
    log.append({
        "iter": 1, "name": "mesh reshape (16,16) -> (64,4)",
        "hypothesis": "d_model=2048 is too small for TP=16 (128 cols/shard "
                      "starves the MXU and the per-layer AR volume is paid "
                      "16× over); TP=4/DP=64 cuts activation wire 4× while "
                      "experts (64) still shard over model=4",
        "terms": t1,
        "confirmed": t1["collective_s"] < base["collective_s"] * 0.3})

    t2 = lm_analytic(cfg, "train", dims, data_par=64, seq_parallel=True)
    log.append({
        "iter": 2, "name": "+ sequence-parallel boundaries",
        "hypothesis": "remaining TP wire halves again",
        "terms": t2, "confirmed": t2["collective_s"] < t1["collective_s"]})

    t3 = lm_analytic(cfg, "train", dims, data_par=64, seq_parallel=True,
                     overlap_collectives=True, causal_block_pairing=True)
    log.append({
        "iter": 3, "name": "+ overlap + block pairing",
        "terms": t3,
        "confirmed": t3["overlapped_s"] < t2["serial_s"]})
    if probe:
        mesh64 = make_production_mesh(shape=(64, 4))
        log.append({"iter": "evidence",
                    "name": "compile probe: (64,4) mesh lowers + memory",
                    "probe": compile_probe(arch, shape, mesh=mesh64)})
    out["C_moonshot_train_4k"] = {
        "selection": "worst roofline fraction (0.35 at baseline)",
        "final_speedup_vs_baseline":
            base["serial_s"] / log[3]["terms"]["overlapped_s"],
        "iterations": log}


def cell_D(out):
    """Bonus cell (beyond the required three): gemma-7b × long_500k — the
    paper's selective read set applied to long-context decode."""
    arch, shape = "gemma-7b", "long_500k"
    cfg = R.ARCHS[arch]
    dims = R.shapes_of(arch)[shape].dims
    log = []
    base = lm_analytic(cfg, "decode", dims)
    log.append({"iter": 0, "name": "baseline (full KV read)", "terms": base})
    rd = (256 + int(0.05 * dims["seq"])) / dims["seq"]   # window ∪ 5% HH
    t1 = lm_analytic(cfg, "decode", dims, selective_decode_read=rd)
    log.append({
        "iter": 1,
        "name": f"RcLLM selective read set (window 256 + 5% HH, rd={rd:.3f})",
        "hypothesis": "decode at B=1/S=524288 is KV-read-bound (cache "
                      "dwarfs params at this config); restricting reads to "
                      "window ∪ heavy hitters cuts the kv term ~20×, "
                      "leaving the param-read floor",
        "terms": t1,
        "confirmed": t1["memory_s"] < base["memory_s"] * 0.5})
    out["D_gemma_long_500k"] = {
        "selection": "bonus: paper technique on the long-context decode cell",
        "final_speedup_vs_baseline": base["serial_s"] / t1["serial_s"],
        "iterations": log}


def main(probe: bool = True):
    out = {}
    cell_A(out, probe)
    cell_B(out, probe)
    cell_C(out, probe)
    cell_D(out)
    os.makedirs("results/perf", exist_ok=True)
    with open("results/perf/hillclimbs.json", "w") as f:
        json.dump(out, f, indent=1, default=str)
    for cell, rec in out.items():
        print(f"== {cell} ({rec['selection']}) ==")
        for it in rec["iterations"]:
            if "terms" in it:
                print(f"  [{it['iter']}] {it['name']}: {fmt(it['terms'])}"
                      + (f"  confirmed={it['confirmed']}"
                         if "confirmed" in it else ""))
            else:
                print(f"  [{it['iter']}] {it['name']}: {it['probe']}")
        print(f"  final speedup vs baseline: "
              f"{rec['final_speedup_vs_baseline']:.2f}x")
    return out


if __name__ == "__main__":
    import sys
    main(probe="--no-probe" not in sys.argv)
