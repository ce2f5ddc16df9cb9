"""The Pallas kernels compile for a TPU v5e, with no chip attached.

Everywhere else the suite runs the kernels in interpret mode on the CPU,
which cannot see what Mosaic (the TPU kernel compiler) refuses: scatters,
matmuls with more than one batch axis, tiles beyond VMEM, 64-bit types.
Here each kernel of the main path, and one jitted ``ntp/pallas`` grid, is
compiled for a described ``v5e:2x2`` topology at the pinn-pde widths (32
wide, 2 heads x 16) and at a lane-aligned width (128), in f32, at orders 2
and 4, and the compiled program must call the kernel (``tpu_custom_call``).
A small PINN train step compiled the same way must carry the layer scopes
(``repro.runtime.metrics.scope``) in its operations' ``op_name``.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library at a time, and every test worker
imports this file.  Keep these tests in this one file, so that one worker
loads it.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engines import NTPEngine
from repro.core.network import DenseMLP
from repro.kernels import ops
from repro.kernels.jet_attention import (flash_jet_bwd_pallas,
                                         jet_flash_attention_pallas,
                                         jet_rms_norm_pallas)
from repro.kernels.jet_dense import jet_dense_pallas
from repro.kernels.tanh_jet import act_jet_pallas
from repro.kernels.wave_jet import wave_jet_bwd_pallas, wave_jet_pallas
from repro.optim import adam_init
from repro.pinn import OperatorRunConfig, train_operator
from repro.pinn.operators import (Operator, get_operator, operator_names,
                                  register)

BATCH = 1024            # collocation rows (pinn-pde trains on 1024 points)
HEADS = 2
TOKENS = 2              # coordinate tokens: d_in of the (t, x) operators
WIDTHS = (32, 128)      # pinn-pde width, and a lane-aligned one
ORDERS = (2, 4)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:        # noqa: BLE001 -- any failure skips
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def compile_for_chip(fn, *shapes, sharding):
    """Compile ``fn`` in f32 (x64 off, as on the chip) for the described
    chip; returns the compiled program's text."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("width", WIDTHS)
def test_jet_dense_compiles(one_chip, width, order):
    text = compile_for_chip(
        lambda c, w, b: jet_dense_pallas(c, w, b, "tanh", interpret=False),
        (order + 1, BATCH, width), (width, width), (width,),
        sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("width", WIDTHS)
def test_act_jet_compiles(one_chip, width, order):
    text = compile_for_chip(
        lambda c: act_jet_pallas(c, "tanh", interpret=False),
        (order + 1, BATCH, width), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("width", WIDTHS)
def test_jet_rms_norm_compiles(one_chip, width, order):
    text = compile_for_chip(
        lambda c, g: jet_rms_norm_pallas(c, g, interpret=False),
        (order + 1, BATCH * TOKENS, width), (width,), sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("width", WIDTHS)
def test_jet_flash_attention_compiles(one_chip, width, order):
    qkv = (order + 1, BATCH, HEADS, TOKENS, width // HEADS)
    text = compile_for_chip(
        lambda q, k, v, wo: jet_flash_attention_pallas(
            q, k, v, wo, 0.25, interpret=False),
        qkv, qkv, qkv, (HEADS, width // HEADS, width), sharding=one_chip)
    assert "tpu_custom_call" in text


# PINNsFormer's wave widths: d_model 32, FF 256, head 512; token rows of a
# point set that fills no whole block, so the ragged last block compiles too
WAVE_WIDTHS = (32, 256, 512)
WAVE_ROWS = BATCH * 5 + 40
PALLAS_CALL = re.compile(r'^\s*(?:ROOT )?%(\S+) = .*'
                         r'custom_call_target="tpu_custom_call"', re.M)


# PINNsFormer's attention: 2 heads of 16 over d_model 32, 5 tokens; a batch
# of sequences that fills no whole block, so the masked weight sum compiles
PFNS_SEQS = 1250


@pytest.mark.parametrize("mask", ("none", "causal", "local"))
@pytest.mark.parametrize("order", (1, 2, 3, 4))
def test_flash_jet_bwd_compiles(one_chip, order, mask):
    """The one-tile flash-jet backward at PINNsFormer's shapes, one launch
    named after its ``pallas_call``."""
    qkv = (order + 1, PFNS_SEQS, HEADS, 5, 16)
    text = compile_for_chip(
        lambda q, k, v, wo, g: flash_jet_bwd_pallas(
            q, k, v, wo, g, 0.25, mask=mask, window=2, interpret=False),
        qkv, qkv, qkv, (HEADS, 16, 32), (order + 1, PFNS_SEQS, 5, 32),
        sharding=one_chip)
    calls = PALLAS_CALL.findall(text)
    assert [n.rsplit(".", 1)[0] for n in calls] == ["flash_jet_bwd"], calls


@pytest.mark.parametrize("order", (1, 2, 3, 4))
@pytest.mark.parametrize("width", WAVE_WIDTHS)
def test_wave_jet_compiles(one_chip, width, order):
    """Forward and backward wavelet-jet kernels, each one launch named
    after its ``pallas_call``."""
    stack = (order + 1, WAVE_ROWS, width)
    fwd = compile_for_chip(
        lambda c, w: wave_jet_pallas(c, w, interpret=False),
        stack, (2,), sharding=one_chip)
    bwd = compile_for_chip(
        lambda c, w, g: wave_jet_bwd_pallas(c, w, g, interpret=False),
        stack, (2,), stack, sharding=one_chip)
    for text, name in ((fwd, "wave_jet"), (bwd, "wave_jet_bwd")):
        calls = PALLAS_CALL.findall(text)
        assert [n.rsplit(".", 1)[0] for n in calls] == [name], calls


@pytest.mark.parametrize("order", ORDERS)
def test_ntp_pallas_grid_compiles(one_chip, order, monkeypatch):
    """The whole order-n derivative table of the pinn-pde dense model, as
    the engine dispatches it: the kernels must be chosen compiled, not
    interpreted (the dispatch asks ``ops._on_tpu``, which sees the CPU)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    net = DenseMLP(d_in=2, width=WIDTHS[0], depth=3, d_out=1)
    engine = NTPEngine("pallas")
    with jax.enable_x64(False):
        pshape = jax.eval_shape(
            lambda: net.init(jax.random.PRNGKey(0), jnp.float32))
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), pshape)
        x = jax.ShapeDtypeStruct((BATCH, net.d_in), jnp.float32,
                                 sharding=one_chip)
        text = jax.jit(lambda p, xx: engine.grid(net, p, xx, order)) \
            .lower(params, x).compile().as_text()
    # one fused dense+activation launch per layer
    assert text.count("tpu_custom_call") >= net.depth + 1


# the scopes a dense net's train step runs through (``pinn.setup`` is a
# host span outside the step; the dense kernel fuses the activation, so
# ``kernel.act_jet.bwd`` is compiled on its own below)
STEP_SCOPES = ("ntp.grid", "ntp.cross", "ntp.fold", "ntp.polarize",
               "kernel.jet_dense.bwd", "pinn.residual", "pinn.boundary",
               "optim.adam")
SCOPED_OP = "kdv-uxxt"
COMPILED_OP = re.compile(r"^\s*%(\S+) = .* (fusion|convolution|custom-call)\(")


def _kdv_uxxt_residual(x, d):
    return d(0, 1) + 6.0 * d(0, 0) * d(1, 1) + d(1, 3) + d.mixed(0, 1, 1)


def _names(op_name):
    """The path components of an ``op_name``, transforms unwrapped:
    ``jit(f)/transpose(jvp(ntp.grid))/mul`` -> {jit, f, transpose, jvp,
    ntp.grid, mul}."""
    return set(re.findall(r"[\w.]+", op_name))


def _all_names(text):
    """The path components of every ``op_name`` in a compiled program."""
    return _names(" ".join(re.findall(r'op_name="([^"]*)"', text)))


def _scoped_ops(text):
    """(instruction name, kind, op_name) of every compiled fusion,
    convolution and custom call that carries an ``op_name``."""
    out = []
    for line in text.splitlines():
        m, name = COMPILED_OP.match(line), re.search(r'op_name="([^"]*)"', line)
        if m and name:
            out.append((m.group(1), m.group(2), name.group(1)))
    return out


def _compiled_step(one_chip, monkeypatch, op, width, depth, n_domain=64):
    """The compiled text of ``op``'s ``ntp/pallas`` train step on a dense
    tanh net, for the described chip."""
    with jax.enable_x64(False):
        res = train_operator(OperatorRunConfig(
            op=op, width=width, depth=depth, n_domain=n_domain, n_bc=4,
            adam_steps=0, engine="ntp/pallas", eval_pts_per_axis=2))
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        d_in = get_operator(op).d_in
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (res.params, adam_init(res.params),
             jnp.zeros((n_domain, d_in), jnp.float32)))
        return res.train_step.lower(*args).compile().as_text()


def _forward_kernels(compiled):
    """Instruction names of the ``jet_dense`` Pallas custom calls (the
    forward kernels: the backward recomputes in jnp)."""
    return [n for n, kind, op in compiled
            if kind == "custom-call" and "jet_dense_pallas" in op]


def test_train_step_carries_the_layer_scopes(one_chip, monkeypatch):
    """Dense tanh net (width 8, depth 2), 64 points, ``ntp/pallas``, an
    order-3 operator with one mixed partial: every scope of the step names
    some operation (the polarization sum fuses into its neighbours, so it
    is looked for in every instruction's ``op_name``), at least 95% of the
    compiled fusions, convolutions and custom calls that carry an
    ``op_name`` lie under a scope, and the kernel's custom calls keep
    ``jet_dense`` in their instruction names (what the benchmark's kernel
    readers sum)."""
    if SCOPED_OP not in operator_names():
        kdv = get_operator("kdv")
        register(Operator(name=SCOPED_OP, d_in=2, order=3,
                          residual=_kdv_uxxt_residual, exact=kdv.exact,
                          domain=kdv.domain, mixed=((0, 1, 1),)))
    text = _compiled_step(one_chip, monkeypatch, SCOPED_OP, width=8, depth=2)
    named = _all_names(text)
    for name in STEP_SCOPES:
        assert name in named, name
    compiled = _scoped_ops(text)
    covered = sum(bool(_names(op) & set(STEP_SCOPES))
                  for _, _, op in compiled)
    assert covered >= 0.95 * len(compiled), (covered, len(compiled))
    kernels = _forward_kernels(compiled)
    assert len(kernels) == 3          # grid and cross share one pass, 3 layers
    assert all("jet_dense" in n for n in kernels), kernels


# Navier-Stokes-shaped: (x, y, t) -> (psi, p), order 3, the five mixed
# partials of Raissi et al.'s momentum residuals
NS_SHAPED_OP = "ns-shaped"
NS_MIXED = ((0, 1), (0, 2), (1, 2), (0, 0, 1), (0, 1, 1))


def _ns_shaped_residual(x, d):
    u_x, u_t, v_t = d.mixed(0, 1), d.mixed(1, 2), -d.mixed(0, 2)
    u_xx, v_yy = d.mixed(0, 0, 1), -d.mixed(0, 1, 1)
    f = u_t + d(1, 1) * u_x + d(0, 1, comp=1) - 0.01 * (u_xx + d(1, 3))
    g = v_t - d(0, 1) * u_x + d(1, 1, comp=1) - 0.01 * (v_yy - d(0, 3))
    return jnp.stack([f, g])


def _ns_shaped_exact(x):
    psi = -jnp.cos(x[:, 0]) * jnp.cos(x[:, 1]) * jnp.exp(-0.02 * x[:, 2])
    return jnp.stack([psi, 0.5 * psi], axis=1)


def test_mixed_table_is_one_jet_forward(one_chip, monkeypatch):
    """A train step whose derivative table holds five mixed partials
    (dense tanh net, width 20, depth 8) runs one jet forward for the whole
    table: one ``jet_dense`` launch per dense map, not one per engine
    call."""
    if NS_SHAPED_OP not in operator_names():
        register(Operator(name=NS_SHAPED_OP, d_in=3, d_out=2, order=3,
                          residual=_ns_shaped_residual,
                          exact=_ns_shaped_exact,
                          domain=((1.0, 8.0), (-2.0, 2.0), (0.0, 20.0)),
                          mixed=NS_MIXED))
    depth = 8
    text = _compiled_step(one_chip, monkeypatch, NS_SHAPED_OP, width=20,
                          depth=depth)
    assert len(_forward_kernels(_scoped_ops(text))) == depth + 1


def test_act_jet_backward_carries_its_scope(one_chip, monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    text = compile_for_chip(
        jax.grad(lambda c: jnp.sum(ops.act_jet(c, "tanh"))),
        (3, BATCH, WIDTHS[0]), sharding=one_chip)
    assert "kernel.act_jet.bwd" in _all_names(text)


# PINNsFormer (d_model 32, 2 heads of 16, as published; FF and head cut to
# 64 to keep the compile short) on the Navier-Stokes-shaped operator
PFNS_SCOPES = ("net.self_attn", "net.cross_attn", "net.wave", "net.seq",
               "kernel.jet_dense.bwd", "kernel.flash_attention.bwd",
               "kernel.wave_jet.bwd")
# Wave jets of a depth-1 PINNsFormer: two per encoder and decoder layer
# (before the attention and before the FF), two inside each FF, the
# encoder's and decoder's final ones, two in the head
PFNS_WAVES = 12


def _flash_bwd_dispatches():
    """(kernel, reference) dispatches of the flash-jet backward so far."""
    from repro.runtime import metrics
    counters = metrics.snapshot()["counter"]
    return [counters.get(name, (0, 0.0))[0] for name in
            ("kernel.flash_jet_bwd", "kernel.flash_jet_bwd.fallback")]


def test_pinnsformer_step_runs_both_kernels(one_chip, monkeypatch):
    """The ``ntp/pallas`` train step of a PINNsFormer calls the ``jet_dense``,
    ``jet_flash_attention`` and ``wave_jet`` kernels compiled (one
    ``wave_jet`` forward and one ``wave_jet_bwd`` launch per Wave jet, one
    ``flash_jet_bwd`` launch per attention block, counted at trace time), and
    its operations carry the network's scopes and the kernels' backward
    scopes."""
    if NS_SHAPED_OP not in operator_names():
        register(Operator(name=NS_SHAPED_OP, d_in=3, d_out=2, order=3,
                          residual=_ns_shaped_residual,
                          exact=_ns_shaped_exact,
                          domain=((1.0, 8.0), (-2.0, 2.0), (0.0, 20.0)),
                          mixed=NS_MIXED))
    n_domain = 16
    with jax.enable_x64(False):
        res = train_operator(OperatorRunConfig(
            op=NS_SHAPED_OP, network="pinnsformer", width=32, depth=1,
            activation="wave",
            net_kwargs={"n_heads": 2, "ff": 64, "head": 64, "tokens": 5},
            n_domain=n_domain, n_bc=4, adam_steps=0, engine="ntp/pallas",
            eval_pts_per_axis=2))
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (res.params, adam_init(res.params),
             jnp.zeros((n_domain, 3), jnp.float32)))
        before = _flash_bwd_dispatches()
        text = res.train_step.lower(*args).compile().as_text()
    # tracing the step dispatches the kernel once per attention block
    assert [a - b for a, b in zip(_flash_bwd_dispatches(), before)] == [2, 0]
    calls = [n for n, kind, _ in _scoped_ops(text) if kind == "custom-call"]
    # embed, 3 + 3 FF per layer, 3 head maps; one flash launch per layer
    assert sum(n.startswith("jet_dense") for n in calls) == 1 + 2 * 6 + 3
    assert sum(n.startswith("jet_flash_attention") for n in calls) == 2
    kernels = [n.rsplit(".", 1)[0] for n in PALLAS_CALL.findall(text)]
    # each attention block's backward is the one-tile kernel (T = 5)
    assert kernels.count("flash_jet_bwd") == 2
    assert kernels.count("wave_jet") == PFNS_WAVES
    assert kernels.count("wave_jet_bwd") == PFNS_WAVES
    named = _all_names(text)
    for name in PFNS_SCOPES:
        assert name in named, name


@pytest.mark.parametrize("engine,waves", [("ntp/pallas", PFNS_WAVES),
                                          ("ntp", 0)])
def test_pinnsformer_table_counts_its_wave_jets(one_chip, monkeypatch,
                                                engine, waves):
    """Lowering a PINNsFormer's derivative table for the chip counts
    ``net.wave_jets`` once per Wave jet under ``ntp/pallas`` (each a
    ``wave_jet`` kernel dispatch) and never under ``ntp`` (the jnp
    algebra)."""
    from repro.core.engines import DerivativeEngine
    from repro.core.network import make_network
    from repro.pinn.operators import build_table
    from repro.runtime import metrics

    if NS_SHAPED_OP not in operator_names():
        register(Operator(name=NS_SHAPED_OP, d_in=3, d_out=2, order=3,
                          residual=_ns_shaped_residual,
                          exact=_ns_shaped_exact,
                          domain=((1.0, 8.0), (-2.0, 2.0), (0.0, 20.0)),
                          mixed=NS_MIXED))
    op = get_operator(NS_SHAPED_OP)
    net = make_network("pinnsformer", d_in=3, d_out=2, width=32, depth=1,
                       activation="wave", n_heads=2, ff=64, head=64,
                       tokens=5)
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    counted = lambda: metrics.snapshot()["counter"].get("net.wave_jets",
                                                        (0, 0.0))[1]
    with jax.enable_x64(False):
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            (jax.eval_shape(lambda: net.init(jax.random.PRNGKey(0))),
             jax.ShapeDtypeStruct((16, 3), jnp.float32)))
        eng = DerivativeEngine.from_spec(engine)
        before = counted()
        text = jax.jit(lambda p, x: build_table(net, p, eng, op, x)._pure) \
            .lower(*shapes).as_text()
    assert counted() - before == waves
    assert text.count("wave_jet") >= waves
