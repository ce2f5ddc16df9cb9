"""Serve a trained operator PINN: train -> checkpoint -> hot derivative API.

    PYTHONPATH=src python examples/serve_operator.py --op heat --steps 300
    PYTHONPATH=src python examples/serve_operator.py --op kdv --order 3
    PYTHONPATH=src python examples/serve_operator.py --clients 8 --points 40

The end-to-end inference path: ``train_operator`` fits the PDE, the
parameters go through ``ckpt.CheckpointManager`` (atomic step directory),
and a :class:`repro.serving.DerivativeServer` restores them and serves
``(x, order)`` / ``(x, axes)`` queries for EVERY registered engine spec --
concurrent clients coalesce into shape-bucketed launches, compiled
executables are cached per (engine, order, bucket), and each response
carries queue-wait/pad/cache metrics.  Served tables are checked against a
direct ``engine.grid`` call before the per-spec metrics print.

Runs in float32, the chip's precision; ``JAX_ENABLE_X64=1`` makes a CPU run
float64.
"""

import argparse
import tempfile
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.core.engines import DerivativeEngine
from repro.data.collocation import sample_box
from repro.pinn import (OperatorRunConfig, get_operator, operator_names,
                        train_operator)
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import DerivativeServer

# every registered engine spec; mirrors benchmarks/operators_bench.SPECS
SPECS = ("ntp", "ntp/pallas", "autodiff")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", default="heat", choices=list(operator_names()))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--order", type=int, default=None,
                    help="served derivative order (default: the operator's)")
    ap.add_argument("--clients", type=int, default=4,
                    help="concurrent client threads per engine spec")
    ap.add_argument("--points", type=int, default=24,
                    help="query points per client request")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: a temp dir)")
    args = ap.parse_args()
    enable_compile_cache()

    op = get_operator(args.op)
    order = args.order if args.order is not None else op.order
    print(f"training {op.name} (d_in={op.d_in}, d_out={op.d_out}) ...")
    cfg = OperatorRunConfig(op=args.op, width=args.width, depth=args.depth,
                            adam_steps=args.steps, log_every=max(args.steps // 4, 1))
    res = train_operator(cfg)
    net = res.net
    print(f"  trained: loss {res.loss_history[0]:.2e} -> "
          f"{res.loss_history[-1]:.2e}, L2 vs exact {res.l2_error:.2e}")

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="serve_operator_")
    CheckpointManager(ckpt_dir).save(args.steps, res.params, blocking=True)
    print(f"  checkpointed to {ckpt_dir}")

    key = jax.random.PRNGKey(7)
    queries = [sample_box(k, op.domain, args.points)
               for k in jax.random.split(key, args.clients)]

    for spec in SPECS:
        engine = DerivativeEngine.from_spec(spec)
        with DerivativeServer.from_checkpoint(
                ckpt_dir, net, engine=spec,
                flush_window_s=0.005) as server:
            results = [None] * args.clients

            def client(i, srv=server):
                results[i] = srv.grid(queries[i], order, timeout=120.0)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(args.clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

            # every served table must agree with a direct engine call
            worst = 0.0
            for x, table in zip(queries, results):
                direct = jax.jit(
                    lambda p, xx: engine.grid(net, p, xx, order)
                )(server.params, x)
                worst = max(worst, float(jnp.max(jnp.abs(table - direct))))
            mixed = None
            if op.d_in > 1:
                mixed = server.cross(queries[0], (0, 1), timeout=120.0)

            m = server.metrics()
            print(f"\nengine {spec}: served {m['requests']} requests in "
                  f"{m['batches']} launches "
                  f"(max |served - direct| = {worst:.1e}"
                  + (f"; u_xy head {np.asarray(mixed)[0]}" if mixed is not None
                     else "") + ")")
            print(f"  latency p50 {m['latency']['p50_us']:.0f}us "
                  f"p99 {m['latency']['p99_us']:.0f}us | queue wait p50 "
                  f"{m['queue_wait']['p50_us']:.0f}us | pad fraction "
                  f"{m['pad_fraction_mean']:.2f}")
            c = m["cache"]
            print(f"  executable cache: {c['hits']} hits, {c['misses']} "
                  f"misses, {c['evictions']} evictions, size {c['size']}")


if __name__ == "__main__":
    main()
