"""Train a PINN on any registered differential operator.

    PYTHONPATH=src python examples/pde_operator.py --op heat --steps 2000
    PYTHONPATH=src python examples/pde_operator.py --op kdv --engine autodiff
    PYTHONPATH=src python examples/pde_operator.py --op poisson2d --engine ntp/pallas
    PYTHONPATH=src python examples/pde_operator.py --op advection-diffusion \
        --network fourier --fourier-features 32
    PYTHONPATH=src python examples/pde_operator.py --op navier-stokes   # 4th-order psi_xxyy
    PYTHONPATH=src python examples/pde_operator.py --op gray-scott      # d_out=2 system
    PYTHONPATH=src python examples/pde_operator.py --op heat --devices 4 \
        --grad-compression int8                 # data-parallel over 4 devices

Each operator carries a manufactured/exact solution: it supplies the
boundary/initial data during training and the L2 accuracy oracle at the end.
``--engine`` is a derivative-engine spec ("ntp", "ntp/pallas", "autodiff") --
``autodiff`` runs the identical objective through nested autodiff (the
paper's baseline); watch the per-step wall clock diverge as the operator's
derivative order grows (KdV needs u_xxx).  ``--network`` picks any
registered architecture: dense (paper), mlp, residual, fourier.

``--devices N`` shards collocation batches over an N-device "data" mesh
(``repro.parallel.jet_shard``); on a CPU-only host it forces N host
platform devices via XLA_FLAGS, which is why the heavy imports happen
*after* argument parsing.  ``--grad-compression int8|topk:F`` routes the
gradient all-reduce through the error-feedback compressors (off by
default: plain psum is exact).

Runs in float32, the chip's precision; ``JAX_ENABLE_X64=1`` makes a CPU run
float64.
"""

import argparse
import os


def parse_mask(text: str):
    """CLI spelling -> SelfAttention mask: none | causal | local:W."""
    text = text.strip().lower()
    if text in ("", "none"):
        return None
    if text == "causal":
        return "causal"
    if text.startswith("local:"):
        return ("local", int(text.split(":", 1)[1]))
    raise SystemExit(f"bad --mask {text!r}: expected none | causal | local:W")


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", default="heat")
    ap.add_argument("--engine", default="ntp",
                    help="engine spec: ntp | ntp/pallas | autodiff")
    ap.add_argument("--network", default="dense")
    ap.add_argument("--fourier-features", type=int, default=16,
                    help="embedding size for --network fourier")
    ap.add_argument("--heads", type=int, default=2,
                    help="attention heads for --network transformer "
                         "(--width must be divisible by it)")
    ap.add_argument("--mask", default="none",
                    help="attention mask for --network transformer: "
                         "none | causal | local:W (e.g. local:4)")
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--lbfgs", type=int, default=0)
    ap.add_argument("--width", type=int, default=32)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--activation", default="tanh")
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--devices", type=int, default=0,
                    help="shard collocation batches over this many devices "
                         "(0 = single-device; forces host-platform devices "
                         "on CPU)")
    ap.add_argument("--grad-compression", default=None,
                    help="gradient all-reduce compression with --devices: "
                         "int8 | topk:F (default: exact fp psum)")
    ap.add_argument("--points", type=int, default=1024,
                    help="collocation points per step (must divide "
                         "--devices)")
    return ap.parse_args()


def main():
    args = parse_args()
    if args.devices > 1:
        # must land before jax initializes its backend: on a CPU host this
        # is how N "devices" come to exist at all
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}").strip()

    from repro.core import network_names
    from repro.pinn import (OperatorRunConfig, get_operator, operator_names,
                            train_operator)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.op not in operator_names():
        raise SystemExit(f"unknown --op {args.op!r}; known: "
                         f"{', '.join(operator_names())}")
    if args.network not in network_names():
        raise SystemExit(f"unknown --network {args.network!r}; known: "
                         f"{', '.join(network_names())}")

    op = get_operator(args.op)
    print(f"operator {op.name}: {op.description}")
    print(f"  d_in={op.d_in}, d_out={op.d_out}, "
          f"max pure-derivative order={op.order}, "
          f"mixed partials={op.mixed or 'none'}, domain={op.domain}")
    print(f"  engine={args.engine}, network={args.network}, "
          f"devices={args.devices or 1}"
          + (f", grad_compression={args.grad_compression}"
             if args.grad_compression else ""))

    net_kwargs = {}
    if args.network == "fourier":
        net_kwargs["n_features"] = args.fourier_features
    elif args.network == "transformer":
        net_kwargs["n_heads"] = args.heads
        net_kwargs["mask"] = parse_mask(args.mask)
    cfg = OperatorRunConfig(op=args.op, engine=args.engine,
                            network=args.network, net_kwargs=net_kwargs,
                            adam_steps=args.steps, lbfgs_steps=args.lbfgs,
                            width=args.width, depth=args.depth,
                            activation=args.activation, adam_lr=args.lr,
                            n_domain=args.points,
                            data_parallel=args.devices,
                            grad_compression=args.grad_compression)
    res = train_operator(cfg)

    print(f"\nloss {res.loss_history[0]:.3e} -> {res.loss_history[-1]:.3e} "
          f"over {args.steps} Adam steps"
          + (f" + {args.lbfgs} L-BFGS steps" if args.lbfgs else ""))
    print(f"adam {res.adam_time_s:.1f}s, lbfgs {res.lbfgs_time_s:.1f}s, "
          f"{res.n_params} params")
    print(f"L2 error vs exact solution: {res.l2_error:.3e}")


if __name__ == "__main__":
    main()
