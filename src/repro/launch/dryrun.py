import argparse
import json
import os
import time
import traceback

import jax

from repro.configs import registry as R
from repro.launch import steps as STEPS
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_bytes_from_hlo, roofline_terms


def force_host_devices() -> None:
    """Give XLA:CPU the 512 host devices the production meshes need.

    XLA reads the flag once, at JAX's first use of a backend, so a
    launcher calls this before it touches any device.  Importing this
    module sets nothing: the flag stays off every chip path."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             skip_existing: bool = True) -> dict:
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    out_path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
    if skip_existing and os.path.exists(out_path):
        with open(out_path) as f:
            rec = json.load(f)
        if rec.get("ok"):
            print(f"[skip] {arch} × {shape} × {mesh_name} (cached)")
            return rec

    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "ok": False}
    t0 = time.time()
    try:
        # the dry-run models the paper's fixed v5e topology, not this
        # host: name the shape explicitly (auto-factoring would size the
        # mesh to the 512 forced host devices instead)
        mesh = make_production_mesh(
            multi_pod=multi_pod,
            shape=(2, 16, 16) if multi_pod else (16, 16))
        fn, args, in_sh, out_sh = STEPS.build(arch, shape, mesh)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            print(mem)                     # proves it fits (bytes per device)
            cost = compiled.cost_analysis()
            print({k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed")})
            hlo = compiled.as_text()
            coll = collective_bytes_from_hlo(hlo)

        n_chips = 512 if multi_pod else 256
        rec.update(
            ok=True,
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            flops_per_device=float(cost.get("flops", -1.0)),
            bytes_per_device=float(cost.get("bytes accessed", -1.0)),
            collectives=coll,
            memory={
                "argument_size": getattr(mem, "argument_size_in_bytes", None),
                "output_size": getattr(mem, "output_size_in_bytes", None),
                "temp_size": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_size": getattr(
                    mem, "generated_code_size_in_bytes", None),
            },
            n_chips=n_chips,
        )
        rec["roofline"] = roofline_terms(rec)
    except Exception as e:       # record the failure for triage, then re-raise
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch} × {shape} × {mesh_name}: {rec['error']}")
    os.makedirs(out_dir, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    status = "ok" if rec["ok"] else "FAIL"
    print(f"[{status}] {arch} × {shape} × {mesh_name} "
          f"({time.time() - t0:.0f}s)")
    return rec


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = list(R.cells())
    if args.arch != "all":
        cells = [c for c in cells if c[0] == args.arch]
    if args.shape != "all":
        cells = [c for c in cells if c[1] == args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            rec = run_cell(arch, shape, mp, args.out,
                           skip_existing=not args.force)
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
