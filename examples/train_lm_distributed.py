"""Distributed LM training driver: any assigned architecture (reduced or
full), the production sharding rules, the fault-tolerant loop, and
gradient compression on the DP axis.

    # CPU-feasible reduced config:
    PYTHONPATH=src python examples/train_lm_distributed.py \
        --arch gemma-7b --smoke --steps 20

    # full-config lowering check (no execution; dry-run proper lives in
    # repro.launch.dryrun):
    PYTHONPATH=src python examples/train_lm_distributed.py \
        --arch nemotron-4-15b --lower-only
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import registry as R
from repro.data.pipeline import BatchPipeline, lm_synthetic_batches
from repro.models import transformer as T
from repro.training.train_loop import TrainConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-7b", choices=list(R.ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--compression", default=None,
                    choices=[None, "int8", "topk"])
    ap.add_argument("--lower-only", action="store_true")
    args = ap.parse_args()

    if args.lower_only:
        from repro.launch.dryrun import force_host_devices, run_cell
        force_host_devices()
        run_cell(args.arch, "train_4k", multi_pod=False,
                 out_dir="results/dryrun", skip_existing=False)
        return

    cfg = R.get_config(args.arch, smoke=args.smoke)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {n_params / 1e6:.1f}M params "
          f"({'reduced' if args.smoke else 'full'})")

    loss_fn = lambda p, b: T.loss_fn(p, b["tokens"], b["labels"], cfg)[0]
    pipe = BatchPipeline(lm_synthetic_batches(cfg.vocab_size, args.batch,
                                              args.seq))
    t0 = time.time()
    _, _, hist = train(params, loss_fn, iter(pipe),
                       TrainConfig(steps=args.steps, ckpt_dir=args.ckpt,
                                   optimizer=cfg.optimizer, lr=1e-3,
                                   grad_compression=args.compression))
    pipe.close()
    dt = time.time() - t0
    print(f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"in {len(hist)} steps ({dt / len(hist):.2f}s/step)")


if __name__ == "__main__":
    main()
