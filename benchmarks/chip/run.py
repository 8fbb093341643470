"""Chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 benchmarks/chip/run.py --workload qwen3-8b.rec-cold \\
        --seed 7 --seconds 30 --trace 0

Runs only where JAX's devices are TPUs, at least as many as the cell
asks for, and the device kind is in ``peaks.json``; anywhere else it
says why on stderr and exits 2 without a result.  So does a cell whose
traffic draws token ids the configuration's vocabulary lacks.
Everything the run needs is found by name: the cell in
``BENCHMARK.json`` at the checkout's root, its configuration in
``configs/<config>.json``, the configuration's architecture
(``archs/<reference>.py``) and plain reference
(``references/<reference>.py``), its traffic mix in
``traffic/<mix>.json``, its output limits in ``limits/<cell>.json``
and each metric's reader in ``metrics/<metric>.py``.  A later cell,
mix, metric or architecture is new files and new entries, never an
edit here: what a model's shape makes of its keys, its parameter tree
and its operation counts is its architecture module's (`archs`).

One run: weights from ``--seed`` on the device, the program's offline
caches, the one-chip wall-clock session server, a warm-up over the
cell's program shapes, then ``--seconds`` of open-loop traffic
(`serve.py`).
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read with the profiler on over
the whole window.  After the window closes and
the program's state is freed, a sample of the served tokens is checked
against the plain reference (`check.py`), which decides ``correct``.

The last line of standard output is the result (JSON); the numbers
compared for ``correct`` come last in it, under ``compared``, and are
also the last lines of standard error.  JAX's persistent compilation
cache lives in ``.jax_cache/`` at the checkout's root.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import archs  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SAMPLE_REQUESTS = 32  # requests whose served tokens the reference checks


class NoChip(RuntimeError):
    pass


class BadCell(RuntimeError):
    pass


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_of(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics (trace 0) or per-layer ones (trace 1)."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _flat(c: dict, prefix: str = "") -> dict:
    """Nested groups as dotted keys, lists as tuples: flat and hashable."""
    out = {}
    for k, v in c.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = tuple(v) if isinstance(v, list) else v
    return out


def load_config(name: str, root: str = HERE) -> dict:
    """``<root>/configs/<name>.json``, mapped onto the harness's keys by its
    architecture (``harness_keys``), with every other key of the file kept
    beside them (nested ones as ``group.key``).  The architecture and the
    reference are looked for under `root` first, then in the harness."""
    c = load_json(root, "configs", f"{name}.json")
    arch = c.get("reference", archs.DEFAULT)
    root = os.path.abspath(root)
    return {**_flat(c), **archs.find("archs", arch, root).harness_keys(c),
            "reference": arch, "root": root}


def refuse_unknown_tokens(cfg: dict, mix: dict, mix_name: str) -> None:
    """`BadCell` where the mix draws token ids at or above the vocabulary:
    the embedding's gather would clamp them without a word."""
    import traffic as TR

    top = TR.token_id_bound(mix) - 1
    if top >= cfg["vocab_size"]:
        raise BadCell(f"traffic {mix_name!r} draws token ids up to {top}, at or above "
                      f"vocab_size {cfg['vocab_size']} of configuration {cfg['name']!r}")


class RunView:
    """What a metric reader sees of one run."""

    def __init__(self, cfg, served, seconds, setup_s, peak, trace):
        self.cfg = cfg
        self.arch = archs.of(cfg)  # its operation counts, for the readers
        self.served = served
        self.reqs = served.reqs
        self.ticks = served.ticks
        self.stats = served.stats
        self.seconds = seconds
        self.setup_s = setup_s
        self.peak = peak
        self.trace = trace

    @staticmethod
    def percentile(xs, q: float) -> float:
        return float(np.percentile(np.asarray(xs, np.float64), q))

    def ttft_s(self) -> list:
        """First token minus due time; a request that never produced one
        counts as waiting until the run stopped waiting for it."""
        drained = self.served.drained_at
        return [(r.token_t[0] if r.token_t else drained) - r.due for r in self.reqs]

    def tbt_s(self) -> list:
        return [b - a for r in self.reqs for a, b in zip(r.token_t, r.token_t[1:])]

    def completed_in_window(self) -> int:
        s = self.served
        return sum(1 for r in self.reqs
                   if r.finished and r.done_t is not None and s.t0 <= r.done_t <= s.t1)

    def flops_in_window(self) -> float:
        """Operations done in the window (`flops.py`): each request's
        prefill at its first token, each decoded token at its publish time."""
        s, cfg, arch, total = self.served, self.cfg, self.arch, 0
        for r in self.reqs:
            st = self.stats.get(r.idx)
            for i, t in enumerate(r.token_t):
                if not s.t0 <= t <= s.t1:
                    continue
                if i == 0:
                    if st is not None:
                        total += arch.prefill(cfg, st["n_tokens"], st["n_recomputed"],
                                              st["recomputed_ctx"])
                else:
                    total += arch.decode(cfg, r.n_prompt + i - 1)
        return float(total)


def arena_bytes(pool) -> int:
    """Bytes of the KV pool's arena arrays (``arena*``), however many
    planes it keeps."""
    return sum(int(a.nbytes) for k, a in vars(pool).items()
               if k.startswith("arena") and hasattr(a, "nbytes"))


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def require_devices(chips: int, peaks: dict):
    """The cell's chips, or `NoChip`: a run never falls back to the CPU."""
    import jax

    from repro.kernels import default_interpret

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"default device is {devs[0].platform!r}; this benchmark runs on TPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} TPU device(s) visible, the cell needs {chips}")
    if default_interpret():
        raise NoChip("Pallas kernels would run in interpret mode")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"device kind {devs[0].device_kind!r} is not in peaks.json")
    return devs[:chips]


def use_cache(path: str = CACHE_DIR) -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def window_bounds(host) -> dict:
    """The traced window on the profiler's clock: the host span the
    server opened over the measured window."""
    import serve

    spans = [(s, e) for name, s, e in host if name == serve.WINDOW_SPAN]
    if not spans:
        raise RuntimeError(f"no {serve.WINDOW_SPAN!r} span in the trace")
    return {"lo_ns": spans[0][0], "hi_ns": spans[0][1]}


def run_cell(cell: str, cfg: dict, mix: dict, limits: dict, metrics: list, *, seed: int,
             seconds: float, trace: bool, devices, peak, controls=()):
    """One run of one cell on `devices`.  `controls` are precisions in
    which `check.readings` also reads the control (never in a benchmark
    run).  -> (result, info lines, compared)."""
    import jax

    import check
    import serve
    import traffic as TR
    import weights as W
    import xplane

    dev = device_record(devices)
    phases = {"imports": time.perf_counter() - PROCESS_T0}
    t = time.perf_counter()
    params = W.make_params(cfg, seed)
    jax.block_until_ready(params)
    param_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    tr = TR.make_traffic(mix, seed, seconds)
    phases["traffic"] = time.perf_counter() - t
    t = time.perf_counter()
    system = serve.System(cfg, params, tr, int(mix["structure_seed"]))
    del params
    phases["offline_caches_and_engine"] = time.perf_counter() - t
    t_warm = time.perf_counter()
    counter = serve.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    window_start = {}

    def on_window_start(t):
        window_start["t"] = t

    served = serve.serve(system, tr, seconds, counter, trace_dir=trace_dir,
                         on_window_start=on_window_start, annotate=trace)
    setup_s = window_start["t"] - PROCESS_T0
    phases["warmup_and_contexts"] = window_start["t"] - t_warm
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    mem_limit = min(int(s.get("bytes_limit", 0)) for s in stats)
    arena = arena_bytes(system.engine.pool)
    reduced = None
    if trace:
        dev_events, host, programs = xplane.read_events(xplane.find_xplane(trace_dir))
        bounds = window_bounds(host)
        host = [h for h in host if h[0] != serve.WINDOW_SPAN]
        reduced = xplane.reduce(dev_events, host, **bounds)
        program_s = xplane.time_by_name(programs, bounds["lo_ns"], bounds["hi_ns"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    view = RunView(cfg, served, seconds, setup_s, peak, reduced)
    values = {}
    for m in metrics:
        v = load_reader(m["name"])(view)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---- output check, with the program's state freed ----
    finished = [r.idx for r in served.reqs if r.finished and len(r.tokens) == tr.max_tokens]
    failed = len(served.reqs) - len(finished)
    del system
    gc.collect()
    t_ref = time.perf_counter()
    pick = TR.correctness_sample(tr, finished, seed, SAMPLE_REQUESTS)
    prompts = [tr.window[i].tokens for i in pick]
    got = (check.readings(cfg, seed, prompts, [served.reqs[i].tokens for i in pick], controls)
           if pick else {})
    ref_s = time.perf_counter() - t_ref
    limit = limits["max_gap"]["limit"]
    compared = {
        "max_gap": {"value": got.get("max_gap"), "limit": limit},
        "failed": {"value": failed, "limit": 0},
    }
    correct = bool(pick) and failed == 0 and got["max_gap"] <= limit

    dev["memory_peak_bytes"] = mem_peak
    result = {"correct": correct, "attempted": len(served.reqs), "failed": failed,
              "metrics": values, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.gap_totals(10)}
    lat = view.ttft_s()
    info = {
        "phase": "run", "cell": cell, "seed": seed, "seconds": seconds, "trace": trace,
        "offered_rate_per_s": tr.rate_per_s, "requests_due": len(served.reqs),
        "requests_finished": len(finished), "completed_in_window": view.completed_in_window(),
        "warmup_requests": len(serve.warmup_plan(tr.warmup, tr.max_tokens)), "compiles_in_window": served.compiles,
        "setup_s": setup_s, "setup_phases_s": phases, "param_bytes": param_bytes, "arena_bytes": arena,
        "memory_peak_bytes": mem_peak, "memory_limit_bytes": mem_limit,
        "ttft_max_ms": max(lat) * 1e3 if lat else None,
        # every latency and rate, whichever the cell reports
        "ttft_p50_ms": view.percentile(lat, 50) * 1e3 if lat else None,
        "ttft_p90_ms": view.percentile(lat, 90) * 1e3 if lat else None,
        "tbt_p90_ms": view.percentile(view.tbt_s(), 90) * 1e3 if view.tbt_s() else None,
        "tbt_p95_ms": view.percentile(view.tbt_s(), 95) * 1e3 if view.tbt_s() else None,
        "tbt_gaps": len(view.tbt_s()),
        "req_per_s": view.completed_in_window() / seconds,
        "gen_lag_max_ms": max((r.submit - r.due for r in served.reqs
                               if r.submit is not None), default=0.0) * 1e3,
        "reference_s": ref_s, "reference_requests": len(pick),
        **({"device_programs_s": sorted(program_s.items(), key=lambda kv: -kv[1])[:12],
            "custom_calls_s": [[k[:300], v] for k, v in reduced.top_ops(10 ** 6)
                               if "custom" in k][:12]} if trace else {}),
        **{k: v for k, v in got.items() if k != "max_gap"},
    }
    result["compared"] = compared
    return result, [info], compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json(ROOT, "BENCHMARK.json")
    cell = cell_of(spec, args.workload)
    peaks = load_json(HERE, "peaks.json")["devices"]
    import traffic as TR

    cfg, mix = load_config(cell["config"]), TR.load_mix(cell["traffic"])
    try:
        refuse_unknown_tokens(cfg, mix, cell["traffic"])
        devices = require_devices(int(cell["chips"]), peaks)
    except (BadCell, NoChip) as e:
        print(f"chip benchmark: {e}; nothing was run", file=sys.stderr)
        return 2
    use_cache()
    result, info, compared = run_cell(
        cell["name"], cfg, mix, load_json(HERE, "limits", f"{cell['name']}.json"),
        metrics_for(spec, cell["name"], bool(args.trace)),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace), devices=devices,
        peak=peaks[devices[0].device_kind])
    for line in info:
        print(json.dumps(line), flush=True)
    for name, c in compared.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
