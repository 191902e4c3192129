#!/usr/bin/env python
"""Standalone LArTPC semantic-segmentation training (reference
``run.py``, the fork-added L4 application that bypasses the task/CLI
layers — SURVEY §3.4).

Behavior reproduced TPU-natively:

- ``LAr_Perceiver`` config: 512×512 ImageInputAdapter (32 Fourier
  bands), 32×64 latents, 3 encoder layers, 3 self-attn layers/block,
  262,144 chunked output queries, zero-pixel pad mask
  (``run.py:72-112`` → ``perceiver_tpu.tasks.SegmentationTask``);
- occupancy-filtered dataset, shuffled train/val split with a held-out
  validation set (``run.py:121-133``);
- Adam(lr 1e-3, weight_decay 1e-4 — torch-Adam L2 semantics) with
  ReduceLROnPlateau(patience 5000, factor 0.1) stepped on the *train*
  loss each iteration (``run.py:135-136,245``), gradient clipping at
  global-norm 10 (``run.py:247``);
- per-iteration TensorBoard scalars ``loss``/``lr``/``train_acc``/
  ``train_acc1``/``train_acc2`` and per-epoch ``validation_loss``/
  ``val_acc`` (``run.py:186-197,242-243,271-276``);
- final checkpoint of model/optimizer/epoch (``run.py:278-281``).

The whole step (forward, weighted CE, backward, clip, Adam, plateau
scale) is one jitted, donated function — the plateau scheduler is
`optax.contrib.reduce_on_plateau`, carried in the optimizer state, so
LR adaptation happens on-device without host round-trips.

Real larcv ROOT inputs are supported when the larcv package is
installed (``--files *.root``); NPZ interchange files otherwise; with
no ``--files`` a synthetic track/shower generator runs the same code
path end to end (smoke-test scale defaults).
"""

from __future__ import annotations

import argparse
import os
import time
from functools import partial

import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--files", nargs="*", default=None,
                   help="larcv ROOT or NPZ event files (default: synthetic)")
    p.add_argument("--model", default="perceiver",
                   choices=["perceiver", "uresnet"],
                   help="perceiver = LAr_Perceiver config (run.py:72-103);"
                        " uresnet = the dense U-ResNet the reference "
                        "wires up but never runs")
    p.add_argument("--inplanes", type=int, default=16,
                   help="U-ResNet stem width (uresnet model only)")
    p.add_argument("--size", type=int, default=512,
                   help="image side (512 for real data)")
    p.add_argument("--num-synthetic", type=int, default=64)
    p.add_argument("--epochs", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--val-events", type=int, default=1000,
                   help="held-out validation events (run.py:133)")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--clip", type=float, default=10.0)
    p.add_argument("--plateau-patience", type=int, default=5000)
    p.add_argument("--plateau-factor", type=float, default=0.1)
    p.add_argument("--logdir", default="logs/lartpc")
    p.add_argument("--ckpt-dir", default="ckpt")
    p.add_argument("--precision", default="bf16", choices=["bf16", "32"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--accelerator", default="auto",
                   choices=["auto", "tpu", "cpu", "gpu"],
                   help="JAX platform (the env-var route is closed by "
                        "the container's early platform pin)")
    return p.parse_args()


def main():
    args = parse_args()

    import jax

    from perceiver_tpu.cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    import optax

    from perceiver_tpu.training.trainer import apply_accelerator
    apply_accelerator(args.accelerator)

    from perceiver_tpu.data.core import BatchIterator
    from perceiver_tpu.data.lartpc import load_lartpc
    from perceiver_tpu.ops.policy import Policy
    from perceiver_tpu.tasks.segmentation import (
        SegmentationTask,
        UResNetSegmentationTask,
    )
    from perceiver_tpu.training.checkpoint import save_params
    from perceiver_tpu.utils.tb import SummaryWriter

    use_uresnet = args.model == "uresnet"
    if use_uresnet:
        task = UResNetSegmentationTask(
            image_shape=(args.size, args.size, 1), inplanes=args.inplanes)
    else:
        task = SegmentationTask(image_shape=(args.size, args.size, 1))
    model = task.build()
    policy = Policy.bf16() if args.precision == "bf16" else Policy.fp32()

    dataset = load_lartpc(args.files, size=args.size,
                          num_synthetic=args.num_synthetic, seed=args.seed)
    n = len(dataset)
    print(f"num entries: {n}", flush=True)
    n_val = min(args.val_events, max(1, n // 8)) if args.val_events > 0 \
        else 0
    perm = np.random.default_rng(args.seed).permutation(n)
    train_ds = dataset.subset(perm[:n - n_val])
    val_ds = dataset.subset(perm[n - n_val:])
    train_it = BatchIterator(train_ds, args.batch_size, shuffle=True,
                             seed=args.seed, drop_last=True)
    val_it = BatchIterator(val_ds, args.batch_size, drop_last=True)
    if len(train_it) == 0:
        raise SystemExit(
            f"No training batches: {len(train_ds)} events after the "
            f"occupancy filter with batch_size={args.batch_size} "
            f"(drop_last). Lower --batch-size or provide more events.")

    if use_uresnet:
        params, aux = model.init(jax.random.key(args.seed))
    else:
        params, aux = model.init(jax.random.key(args.seed)), None
    # torch Adam's weight_decay is L2-on-gradients, hence decayed
    # weights added *before* the Adam moment update (not AdamW order)
    tx = optax.chain(
        optax.clip_by_global_norm(args.clip),
        optax.add_decayed_weights(args.weight_decay),
        optax.scale_by_adam(),
        optax.contrib.reduce_on_plateau(
            factor=args.plateau_factor, patience=args.plateau_patience),
        optax.scale_by_learning_rate(args.lr),
    )
    opt_state = tx.init(params)

    def compute(p, aux, batch, rng, train):
        """Unified (loss, metrics, new_aux): aux is the U-ResNet's
        BatchNorm running stats (threaded, never optimized) and None
        for the Perceiver."""
        if use_uresnet:
            return task.loss_and_metrics(model, (p, aux), batch,
                                         train=train, policy=policy)
        loss, metrics = task.loss_and_metrics(
            model, p, batch, rng=rng, deterministic=not train,
            policy=policy)
        return loss, metrics, aux

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, aux, opt_state, batch, rng):
        def loss_fn(p):
            loss, metrics, new_aux = compute(p, aux, batch, rng, True)
            return loss, (metrics, new_aux)

        (loss, (metrics, new_aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params,
                                       value=loss)
        # surface the plateau scale as a step OUTPUT: metrics are never
        # donated back in, so the host can read them lazily, whereas
        # opt_state buffers die at the next step's donation
        metrics["lr_scale"] = opt_state[3].scale  # chain idx 3 = plateau
        return (optax.apply_updates(params, updates), new_aux, opt_state,
                metrics)

    @jax.jit
    def eval_step(params, aux, batch):
        _, metrics, _ = compute(params, aux, batch, None, False)
        return metrics

    writer = SummaryWriter(args.logdir)
    key = jax.random.key(args.seed + 1)
    total_iter = 0
    t0 = time.perf_counter()

    # per-iteration scalars (reference run.py:186-197,242-243) without
    # per-iteration device syncs: buffer the metric futures and flush
    # every FLUSH_EVERY iters — by then those steps have long retired,
    # so float() is non-blocking and the device pipeline stays full
    FLUSH_EVERY = 10
    pending = []

    def flush():
        for it, m in pending:
            writer.add_scalar("loss", float(m["loss"]), it)
            writer.add_scalar("lr", args.lr * float(m["lr_scale"]), it)
            writer.add_scalar("train_acc", float(m["acc"]), it)
            writer.add_scalar("train_acc1", float(m["acc1"]), it)
            writer.add_scalar("train_acc2", float(m["acc2"]), it)
        if pending:
            it, m = pending[-1]
            print(f"iter {it} loss {float(m['loss']):.4f} "
                  f"acc {float(m['acc']):.3f} "
                  f"({time.perf_counter() - t0:.1f}s)", flush=True)
        pending.clear()

    for epoch in range(args.epochs):
        train_it.set_epoch(epoch)
        for batch in train_it:
            key, sub = jax.random.split(key)
            params, aux, opt_state, metrics = train_step(
                params, aux, opt_state,
                {k: jnp.asarray(v) for k, v in batch.items()}, sub)
            pending.append((total_iter, metrics))
            if len(pending) >= FLUSH_EVERY:
                flush()
            total_iter += 1
        flush()

        vlosses, vaccs = [], []
        for batch in val_it:
            m = eval_step(params, aux, {k: jnp.asarray(v)
                                        for k, v in batch.items()})
            vlosses.append(float(m["loss"]))
            vaccs.append(float(m["acc"]))
        if vlosses:
            print(f"validation loss: {np.mean(vlosses):.4f}", flush=True)
            writer.add_scalar("validation_loss", float(np.mean(vlosses)),
                              total_iter)
            writer.add_scalar("val_acc", float(np.mean(vaccs)), total_iter)

    os.makedirs(args.ckpt_dir, exist_ok=True)
    saved = {"params": params, "opt_state": opt_state,
             "epoch": args.epochs - 1}
    if aux is not None:
        saved["batch_stats"] = aux
    save_params(os.path.join(args.ckpt_dir, f"model_{args.epochs - 1}"),
                saved,
                hparams={"task": "segmentation", "model": args.model,
                         "size": args.size})
    writer.close()


if __name__ == "__main__":
    main()
