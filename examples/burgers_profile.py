"""End-to-end driver: self-similar Burgers shock profiles with a PINN
(paper section IV-C + appendix A).

    PYTHONPATH=src python examples/burgers_profile.py --k 1 --adam 1500 --lbfgs 300
    PYTHONPATH=src python examples/burgers_profile.py --k 3 --engine ntp   # 7 derivatives!

Finds the k-th smooth profile (lambda = 1/2k) by the combined forward-inverse
procedure: constrain lambda to [1/(2k+1), 1/(2k-1)], penalize
|d^(2k+1) R / dX^(2k+1)| near the origin, train Adam -> L-BFGS.  ``--engine
autodiff`` runs the identical schedule with nested autodiff (the paper's
baseline) for a wall-clock comparison; k >= 3 is where autodiff becomes
untenable and n-TangentProp keeps going.

Runs in float32, the chip's precision; ``JAX_ENABLE_X64=1`` makes a CPU run
float64.
"""

import argparse

import jax
import numpy as np

from repro.core.ntp import mlp_apply
from repro.pinn import PINNRunConfig, exact_profile, profile_lambda, train
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=1, help="profile index (lam=1/2k)")
    ap.add_argument("--engine", choices=["ntp", "ntp/pallas", "autodiff"],
                    default="ntp", help="derivative-engine spec")
    ap.add_argument("--adam", type=int, default=1500)
    ap.add_argument("--lbfgs", type=int, default=300)
    ap.add_argument("--width", type=int, default=24)
    ap.add_argument("--depth", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = PINNRunConfig(k=args.k, engine=args.engine,
                        adam_steps=args.adam, lbfgs_steps=args.lbfgs,
                        width=args.width, depth=args.depth)
    print(f"profile k={args.k}: target lambda = {profile_lambda(args.k)} | "
          f"smoothness order = {cfg.k * 2 + 1} "
          f"(=> {cfg.k * 2 + 2} network derivatives) | engine={args.engine}")
    res = train(cfg)

    print(f"\nlambda learned = {res.lam:.6f}  (target {profile_lambda(args.k)})")
    print(f"adam {res.adam_time_s:.1f}s, lbfgs {res.lbfgs_time_s:.1f}s, "
          f"final loss {res.loss_history[-1]:.3e}")

    # accuracy vs the closed-form profile (C=1 normalization)
    xs = np.linspace(-cfg.domain, cfg.domain, 401)
    u_true = exact_profile(xs, args.k)
    u_net = np.asarray(mlp_apply(res.params, jax.numpy.asarray(xs)[:, None]))[:, 0]
    l2 = np.sqrt(np.mean((u_net - u_true) ** 2))
    print(f"L2 error vs exact profile: {l2:.3e}")
    print("lambda history:", [f"{l:.4f}" for l in res.lam_history[-8:]])


if __name__ == "__main__":
    main()
