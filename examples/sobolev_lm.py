"""n-TangentProp for transformers: Sobolev-regularized LM training.

    PYTHONPATH=src python examples/sobolev_lm.py --order 3 --steps 20

TangentProp (the 1991 original) penalized first derivatives along invariance
directions; the quasilinear n-jet makes ORDER-n smoothness penalties on a
*transformer* affordable: one extra forward pass carrying an (n+1)-deep
Taylor stack through attention/softmax/GeGLU, instead of n nested autodiff
sweeps.  This trains a small dense LM with loss

    CE + 1e-4 * || d^n h / dt^n ||^2,   t -> embeddings + t v

and prints both terms; watch the smoothness term fall while CE trains.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.configs.base import ShapeCfg
from repro.data.tokens import synthetic_batch
from repro.launch.ntp_reg import ntp_smoothness
from repro.models import init_model, train_loss
from repro.optim import adam_init, adam_update
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--order", type=int, default=3)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--coef", type=float, default=1e-4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_arch(args.arch).reduced()
    shape = ShapeCfg("sobolev", args.seq, args.batch, "train")
    params, _ = init_model(cfg, jax.random.PRNGKey(0))
    opt = adam_init(params)

    @jax.jit
    def step(params, opt, batch):
        def loss_fn(p):
            ce, metrics = train_loss(p, cfg, batch)
            smooth = ntp_smoothness(p, cfg, batch, args.order)
            return ce + args.coef * smooth, (ce, smooth)

        (loss, (ce, smooth)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt = adam_update(grads, opt, params, 1e-3, grad_clip=1.0)
        return params, opt, ce, smooth

    for i in range(args.steps):
        t0 = time.perf_counter()
        params, opt, ce, smooth = step(params, opt, synthetic_batch(cfg, shape, i))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  ce={float(ce):.4f}  "
                  f"||d^{args.order}h||^2={float(smooth):.4e}  "
                  f"({(time.perf_counter() - t0) * 1e3:.0f} ms)")


if __name__ == "__main__":
    main()
