"""End-to-end LM training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --steps 50 --reduced --batch 8 --seq 128

``--reduced`` trains the smoke-scale config on this CPU container; on a real
pod the same driver binds the production mesh.  Wires together: config
registry, synthetic data pipeline, sharded train step, fault-tolerant
Trainer (checkpoint/restart, straggler watchdog), optional n-TangentProp
Sobolev regularization (--ntp-order) -- the paper's technique as a
first-class LM-training feature.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_arch
from repro.configs.base import ShapeCfg
from repro.data.tokens import synthetic_batch
from repro.launch.sharding import build_train_step
from repro.models import init_model, train_loss
from repro.models.transformer import Knobs
from repro.optim import adam_init, adam_update
from repro.runtime import Trainer, TrainerConfig
from repro.runtime.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ntp-order", type=int, default=0,
                    help="add an order-n jet smoothness regularizer (dense archs)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        shape = ShapeCfg("custom", args.seq, args.batch, "train")
    else:
        shape = SHAPES[args.shape]

    key = jax.random.PRNGKey(0)
    params, _ = init_model(cfg, key)
    opt = adam_init(params)

    @jax.jit
    def step_fn(state, batch):
        params, opt = state
        def loss_fn(p):
            loss, metrics = train_loss(p, cfg, batch)
            if args.ntp_order > 0:
                from repro.launch.ntp_reg import ntp_smoothness
                loss = loss + 1e-4 * ntp_smoothness(p, cfg, batch, args.ntp_order)
            return loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt = adam_update(grads, opt, params, args.lr, grad_clip=1.0)
        return (params, opt), loss

    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir),
        step_fn,
        lambda step: synthetic_batch(cfg, shape, step),
        straggler_cb=lambda s, dt, ema: print(f"[straggler] step {s}: {dt:.2f}s vs ema {ema:.2f}s"),
    )
    t0 = time.perf_counter()
    (params, opt), report = trainer.run((params, opt))
    dt = time.perf_counter() - t0
    print(f"ran {report.steps_run} steps in {dt:.1f}s "
          f"({report.restarts} restarts, {report.stragglers} stragglers)")
    print("loss first->last:", report.losses[0], "->", report.losses[-1])


if __name__ == "__main__":
    main()
