"""Training launcher: --arch <id> [--smoke] [--steps N].

Reduced configs execute on CPU; full configs are lowered/compiled via the
dry-run (real execution requires the TPU pod this repo targets).

    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b --smoke

XLA latency-hiding flags for real TPU runs (comm/compute overlap — §Perf):
    LIBTPU_INIT_ARGS="--xla_tpu_enable_async_collective_fusion=true
        --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import registry as R


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(R.ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, runs on CPU")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--compression", default=None,
                    choices=["int8", "topk"])
    args = ap.parse_args()

    fam = R.family_of(args.arch) if args.arch in R.ASSIGNED else "lm"
    if not args.smoke:
        from repro.launch.dryrun import force_host_devices, run_cell
        force_host_devices()
        shape = {"lm": "train_4k", "recsys": "train_batch",
                 "gnn": "full_graph_sm"}[fam]
        run_cell(args.arch, shape, multi_pod=False,
                 out_dir="results/dryrun", skip_existing=False)
        return

    cfg = R.get_config(args.arch, smoke=True)
    from repro.training.train_loop import TrainConfig, train
    if fam == "lm":
        from repro.data.pipeline import BatchPipeline, lm_synthetic_batches
        from repro.models import transformer as T
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b: T.loss_fn(p, b["tokens"], b["labels"], cfg)[0]
        pipe = BatchPipeline(lm_synthetic_batches(cfg.vocab_size, args.batch,
                                                  args.seq))
        data = iter(pipe)
    elif fam == "recsys":
        from repro.recsys import models as RM
        rng = np.random.default_rng(0)
        params = RM.init_params(jax.random.PRNGKey(0), cfg)
        loss_fn = lambda p, b: RM.train_loss(p, b, cfg)

        def gen():
            import jax.numpy as jnp
            B = args.batch
            while True:
                if cfg.kind in ("wide_deep", "autoint"):
                    yield {"dense": jnp.ones((B, 13)),
                           "sparse_ids": jnp.asarray(
                               rng.integers(0, 100, (B, len(cfg.field_vocabs))),
                               jnp.int32),
                           "labels": jnp.asarray(rng.integers(0, 2, B),
                                                 jnp.float32)}
                elif cfg.kind == "dien":
                    T_ = cfg.seq_len
                    yield {"hist_items": jnp.zeros((B, T_), jnp.int32),
                           "hist_cates": jnp.zeros((B, T_), jnp.int32),
                           "hist_mask": jnp.ones((B, T_), bool),
                           "target_item": jnp.zeros((B,), jnp.int32),
                           "target_cate": jnp.zeros((B,), jnp.int32),
                           "labels": jnp.asarray(rng.integers(0, 2, B),
                                                 jnp.float32)}
                else:
                    T_ = cfg.seq_len
                    yield {"item_seq": jnp.zeros((B, T_), jnp.int32),
                           "seq_mask": jnp.ones((B, T_), bool),
                           "mlm_positions": jnp.zeros((B, 2), jnp.int32),
                           "mlm_labels": jnp.ones((B, 2), jnp.int32),
                           "neg_samples": jnp.arange(16, dtype=jnp.int32)}
        data = gen()
        pipe = None
    else:
        raise SystemExit("use tests/examples for GNN training demos")

    _, _, hist = train(params, loss_fn, data,
                       TrainConfig(steps=args.steps, ckpt_dir=args.ckpt,
                                   optimizer=getattr(cfg, "optimizer",
                                                     "adamw"),
                                   lr=1e-3,
                                   grad_compression=args.compression))
    if pipe is not None:
        pipe.close()
    print(f"{args.arch}: loss {hist[0]['loss']:.4f} -> "
          f"{hist[-1]['loss']:.4f} over {len(hist)} steps")


if __name__ == "__main__":
    main()
