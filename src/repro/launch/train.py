"""Training driver: checkpointed, fault-tolerant, resumable.

Runs the same ``train_step`` the dry-run lowers, against the synthetic
deterministic data stream.  On CPU use ``--reduced`` (tiny same-family
config); on a pod the full config + production mesh applies.

  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --reduced --steps 50 --ckpt-dir /tmp/ckpt
  # kill it mid-run, rerun the same command: it resumes from the last
  # committed checkpoint and reproduces the uninterrupted run exactly.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt
from repro import configs as C
from repro.configs.base import ShapeConfig
from repro.data import pipeline as dp
from repro.models import lm
from repro.models import sharding as shd
from repro.optim import adamw, compression, schedules


def build_train_state(key, cfg):
    params = lm.init(key, cfg)
    opt = adamw.init(params)
    return {"params": params, "opt": opt}


def make_step(cfg, rules, *, peak_lr, total_steps, remat=True):
    def step(state, batch):
        def lf(p):
            return lm.loss_fn(cfg, p, batch, rules, remat=remat)

        (_, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
            state["params"]
        )
        lr = schedules.warmup_cosine(
            state["opt"].count, peak_lr=peak_lr,
            warmup_steps=max(total_steps // 20, 1), total_steps=total_steps,
        )
        new_params, new_opt, om = adamw.update(
            grads, state["opt"], state["params"], lr=lr
        )
        metrics.update(om)
        return {"params": new_params, "opt": new_opt}, metrics

    return step


def make_compressed_step(cfg, mesh, *, peak_lr, total_steps,
                         method="int8", topk_frac=0.01):
    """DP trainer with error-feedback compressed gradient all-reduce
    (shard_map over the data axis; params replicated — the compression
    applies where gradients cross devices)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def local_step(state, batch, key):
        def lf(p):
            return lm.loss_fn(cfg, p, batch, None, remat=False)

        (_, metrics), grads = jax.value_and_grad(lf, has_aux=True)(
            state["params"]
        )
        reduced, ef = compression.compressed_psum(
            grads, state["ef"], key, "data", method=method,
            topk_frac=topk_frac,
        )
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "data"), metrics)
        lr = schedules.warmup_cosine(
            state["opt"].count, peak_lr=peak_lr,
            warmup_steps=max(total_steps // 20, 1), total_steps=total_steps,
        )
        new_params, new_opt, om = adamw.update(
            reduced, state["opt"], state["params"], lr=lr
        )
        metrics.update(om)
        return {"params": new_params, "opt": new_opt, "ef": ef}, metrics

    rep = P()
    dat = P("data")
    state_spec = {"params": rep, "opt": rep, "ef": dat}
    batch_spec = jax.tree.map(lambda _: dat, {"tokens": 0, "targets": 0})
    return shard_map(
        local_step, mesh=mesh,
        in_specs=(state_spec, batch_spec, rep),
        out_specs=(state_spec, rep),
        check_vma=False,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = C.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    rules = None  # CPU path; production path goes through dryrun/mesh

    state = build_train_state(jax.random.PRNGKey(args.seed), cfg)
    start = 0
    last = ckpt.latest_step(args.ckpt_dir)
    if last is not None:
        state = ckpt.restore(args.ckpt_dir, last, state)
        start = last + 1
        print(f"resumed from step {last}")

    step_fn = jax.jit(make_step(cfg, rules, peak_lr=args.lr,
                                total_steps=args.steps, remat=False))
    writer = ckpt.AsyncCheckpointer(args.ckpt_dir)
    it = dp.Prefetcher(dp.stream(cfg, shape, args.seed, start_step=start))
    t0 = time.time()
    try:
        for step, batch in it:
            if step >= args.steps:
                break
            state, metrics = step_fn(state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                toks = (step - start + 1) * args.batch * args.seq
                rate = toks / max(time.time() - t0, 1e-9)
                print(f"step {step:5d}  loss {loss:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"{rate:,.0f} tok/s", flush=True)
            if (step + 1) % args.ckpt_every == 0 or step == args.steps - 1:
                writer.save(state, step)
    finally:
        writer.close()
        ckpt.gc_old(args.ckpt_dir, keep=3)
    print("done")


if __name__ == "__main__":
    main()
