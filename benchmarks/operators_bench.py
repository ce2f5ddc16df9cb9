"""Operator-axis benchmark: residual evaluation cost per PDE x engine.

For every registered differential operator this times one jitted residual
evaluation over a collocation batch, for the quasilinear n-TangentProp engine
(``ntp`` and ``ntp/pallas`` specs) and the nested-autodiff baseline.  The
per-operator ratio autodiff/ntp is the paper's headline quantity generalized
beyond the Burgers workload: it grows with the operator's derivative order
(heat/wave: 2, KdV: 3) exactly as the O(M^n) vs O(n p(n) M) analysis
predicts.  ``network`` selects any registered architecture (the engine
surface is network-agnostic), so e.g. ``network="fourier"`` times the
random-feature embedding at zero extra benchmark code.  Vector-valued
systems ride the same sweep: the network is built with ``d_out=op.d_out``,
so ``gray-scott`` times the shared-table two-component residual and
``navier-stokes`` the 4th-order polarization crosses.

``network_axis`` adds a second sweep -- each named architecture (residual
and the attention/transformer trunk by default, :data:`NETWORK_AXIS`) timed
on one representative operator under every engine spec, rows suffixed
``_net-*``.  The smoke run carries it, and ``compare.py`` derives coverage
expectations from the same tuples, so a trunk whose jet path rots fails CI
the way a dropped operator does.  The ``transformer x ntp/pallas`` rows
(smoke and full) exercise the FUSED attention path -- SelfAttention routes
its score Cauchy product + softmax through ``kernels.ops.
jet_attention_scores`` and RMSNorm through ``jet_rms_norm`` -- and carry a
``fused_attn=`` tag in their derived field.
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.core.engines import DerivativeEngine, EngineSpec, NTPEngine
from repro.core.jet import float_dtype
from repro.core.network import make_network
from repro.data.collocation import sample_box
from repro.pinn.operators import get_operator, residual_values

from .common import axis_product, csv_row, time_fn

DEFAULT_OPS = ("burgers", "heat", "wave", "allen-cahn", "kdv", "poisson2d",
               "advection-diffusion", "navier-stokes", "gray-scott")

# the full engine sweep; compare.py derives its coverage expectations from
# this same tuple, so adding a spec here automatically widens the CI gate
SPECS = ("ntp", "ntp/pallas", "autodiff")

# the network axis: non-default architectures benchmarked (and coverage-
# gated, same mechanism as SPECS) on one representative operator per spec --
# the smoke run carries them so a trunk that stops jet-tracing fails the PR
NETWORK_AXIS = ("residual", "transformer")
NETWORK_AXIS_OP = "heat"

# the token-count scaling axis: the flash-jet attention kernel's reason to
# exist is that memory no longer grows with T^2, so the transformer trunk is
# timed at growing token counts (T = d_in coordinate tokens) under the
# fused pallas engine; rows are tagged ``flash=1`` and coverage-gated like
# every other axis
TOKEN_AXIS = (16, 64, 256)
TOKEN_AXIS_ORDER = 2

# the weak-scaling axis: the sharded jet engine (repro.parallel.jet_shard)
# timed at a FIXED per-device collocation batch while the host-device count
# grows, so the points/sec column reads as a weak-scaling curve.  Each
# device count needs its own interpreter (XLA_FLAGS must force the host
# platform device count before jax initializes), so every row is one
# subprocess -- which also keeps the benchmark process itself single-device
# like every other suite.  Rows are coverage-gated via compare.py like the
# operator and token axes.
DEVICE_AXIS = (1, 2, 4, 8)
WEAK_SCALE_OP = "heat"
WEAK_SCALE_SPEC = "ntp"


def spec_tag(spec: str) -> str:
    """CANONICAL engine spec -> the row-name tag used in benchmark output.
    Going through :class:`EngineSpec` keeps equivalent spellings ("ntp" vs
    "ntp/jnp") on one baseline row."""
    return str(EngineSpec.parse(spec)).replace("/", "_")


def row_name(op_name: str, spec: str, network: str = "dense") -> str:
    """Benchmark row name; non-default networks get a ``_net-`` suffix so
    the historical dense row names stay stable."""
    base = f"residual_{op_name}_{spec_tag(spec)}"
    return base if network == "dense" else f"{base}_net-{network}"


def _time_case(op, spec: str, network: str, n_pts: int, width: int,
               depth: int, trials: int) -> tuple:
    net = make_network(network, d_in=op.d_in, d_out=op.d_out, width=width,
                       depth=depth)
    engine = DerivativeEngine.from_spec(spec)
    params = net.init(jax.random.PRNGKey(0), dtype=float_dtype())
    x = sample_box(jax.random.PRNGKey(1), op.domain, n_pts)

    fn = jax.jit(functools.partial(
        lambda p, pts, _op, _eng, _net: residual_values(
            p, _op, pts, engine=_eng, net=_net),
        _op=op, _eng=engine, _net=net))
    t = time_fn(fn, params, x, trials=trials)
    derived = f"order={op.order};d_in={op.d_in};d_out={op.d_out};" \
              f"net={network}"
    if network == "transformer" and spec.endswith("pallas"):
        # records whether the fused flash-attention/rms_norm kernels were
        # REGISTERED for this run (capability registry at timing time).
        # Registry membership => actual module dispatch is enforced
        # separately by tests/test_parity.py's kernel-invocation guard, so
        # together the tag certifies the row timed the fused path.
        from repro.kernels import ops as kops
        fused = int("flash_attention" in kops.epilogues()
                    and "rms_norm" in kops.epilogues())
        derived += f";fused_attn={fused}"
    return t, derived


def token_row_name(tokens: int) -> str:
    return f"tokens_T{tokens}_transformer_{spec_tag('ntp/pallas')}"


def _time_token_case(tokens: int, width: int, trials: int) -> tuple:
    """One flash-path derivative pass on a transformer whose token count is
    ``tokens`` (coordinate tokens == d_in), timed via the engine surface the
    serving layer uses.  Depth 1 and a small batch keep the smoke run fast;
    the axis varies ONLY T, so the rows read as a scaling curve."""
    net = make_network("transformer", d_in=tokens, d_out=1, width=width,
                       depth=1)
    engine = NTPEngine("pallas")
    params = net.init(jax.random.PRNGKey(0), dtype=float_dtype())
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, tokens), float_dtype(),
                           -1.0, 1.0)
    fn = jax.jit(lambda p, pts: engine.derivs(net, p, pts, TOKEN_AXIS_ORDER))
    t = time_fn(fn, params, x, trials=trials)
    from repro.kernels import ops as kops
    flash = int("flash_attention" in kops.epilogues())
    return t, f"tokens={tokens};order={TOKEN_AXIS_ORDER};flash={flash}"


def weak_row_name(devices: int) -> str:
    return (f"weakscale_D{devices}_{WEAK_SCALE_OP}_"
            f"{spec_tag(WEAK_SCALE_SPEC)}")


def _time_weak_case(devices: int, pts_per_device: int, width: int,
                    depth: int, trials: int, timeout: int = 300) -> tuple:
    """One weak-scaling point: a subprocess with ``devices`` forced host
    devices times the sharded residual grid on ``devices * pts_per_device``
    collocation points (constant work per device).  Returns
    (median seconds/call, derived tag with the points/sec column)."""
    n_pts = devices * pts_per_device
    code = textwrap.dedent(f"""
        import json, time
        import jax, jax.numpy as jnp
        from repro.core.engines import DerivativeEngine
        from repro.core.network import make_network
        from repro.data.collocation import sample_box
        from repro.parallel.jet_shard import ShardedEngine, resolve_mesh
        from repro.pinn.operators import get_operator

        op = get_operator({WEAK_SCALE_OP!r})
        net = make_network("dense", d_in=op.d_in, d_out=op.d_out,
                           width={width}, depth={depth})
        eng = DerivativeEngine.from_spec({WEAK_SCALE_SPEC!r})
        mesh = resolve_mesh(data_parallel={devices})
        if mesh is not None:
            eng = ShardedEngine(eng, mesh)
        params = net.init(jax.random.PRNGKey(0), dtype=jnp.float32)
        x = sample_box(jax.random.PRNGKey(1), op.domain, {n_pts}, jnp.float32)
        fn = jax.jit(lambda p, xs: eng.grid(net, p, xs, op.order))
        for _ in range(2):
            jax.block_until_ready(fn(params, x))
        times = []
        for _ in range({trials}):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(params, x))
            times.append(time.perf_counter() - t0)
        times.sort()
        print(json.dumps({{"s_per_call": times[len(times) // 2]}}))
    """)
    env = dict(os.environ)
    # the children measure forced CPU host devices by design; pinning their
    # platform keeps them off an accelerator the parent process holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count"
                        f"={devices}").strip()
    src = os.path.normpath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"weak-scaling child (devices={devices}) failed:\n"
                           f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
    t = json.loads(out.stdout.strip().splitlines()[-1])["s_per_call"]
    derived = (f"devices={devices};points={n_pts};"
               f"points_per_s={n_pts / t:.1f}")
    return t, derived


def run(n_pts: int = 256, width: int = 24, depth: int = 3, trials: int = 3,
        operators=DEFAULT_OPS, include_pallas: bool = True,
        network: str = "dense", network_axis=(), token_axis=TOKEN_AXIS,
        device_axis=DEVICE_AXIS):
    """Main sweep: every operator x engine spec on ``network``.  When
    ``network_axis`` names extra architectures, each is additionally timed
    on :data:`NETWORK_AXIS_OP` under every spec (rows suffixed ``_net-*``).
    ``token_axis`` adds the flash-attention token-count scaling rows
    (pallas-only, so it rides ``include_pallas`` like the pallas specs).
    ``device_axis`` adds the weak-scaling rows: the sharded jet engine at
    ``n_pts`` collocation points *per device* for each host-device count
    (one subprocess per count -- see :func:`_time_weak_case`)."""
    # NOTE: deliberately no jax_enable_x64 flip here -- it is process-global
    # and would change the precision (and timings) of every suite after this
    # one.  Timing is dtype-uniform with the other suites instead.
    specs = SPECS if include_pallas \
        else tuple(s for s in SPECS if not s.endswith("pallas"))
    rows = []
    ntp_times = {}
    for case in axis_product(op=operators, spec=specs):
        op = get_operator(case["op"])
        spec = case["spec"]
        t, derived = _time_case(op, spec, network, n_pts, width, depth, trials)
        if spec == "ntp":
            ntp_times[op.name] = t
        if spec == "autodiff" and op.name in ntp_times:
            derived += f";vs_ntp_x={t / ntp_times[op.name]:.2f}"
        rows.append(csv_row(row_name(op.name, spec, network), t, derived))

    axis_op = get_operator(NETWORK_AXIS_OP)
    for case in axis_product(net=tuple(network_axis), spec=specs):
        t, derived = _time_case(axis_op, case["spec"], case["net"], n_pts,
                                width, depth, trials)
        rows.append(csv_row(row_name(axis_op.name, case["spec"], case["net"]),
                            t, derived))

    if include_pallas:
        for tokens in token_axis:
            t, derived = _time_token_case(tokens, width=8, trials=trials)
            rows.append(csv_row(token_row_name(tokens), t, derived))

    for devices in device_axis:
        t, derived = _time_weak_case(devices, pts_per_device=n_pts,
                                     width=width, depth=depth, trials=trials)
        rows.append(csv_row(weak_row_name(devices), t, derived))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
