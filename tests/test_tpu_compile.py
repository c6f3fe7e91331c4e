"""Compile the main path's device programs for a described TPU v5e.

Nothing runs here: each program is lowered and compiled by the TPU
compiler for a chip that is described, not attached, at the real
`sw_1000` / `ba_10000` widths.  That catches what the Pallas interpreter
cannot — block shapes the TPU lowering refuses, fast-memory overruns,
programs that do not fit the device — before any chip time is spent.

The topology is described inside a module-scoped fixture (never at
import) so that, under pytest-xdist, only the worker that runs this file
loads the TPU library.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import core
from repro.core.network import flows_carry_and_cost
from repro.core.scenarios import _mk_adj
from repro.core.sgp import BLOCK, _sgp_block, sgp_step_flows
from repro.kernels import ops

V5E_HBM = 16e9                 # bytes of device memory on one v5e chip
_REAL_PICK = ops._pick         # before conftest reroutes it to interpret


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written for a described chip cannot be
    # read back without one; keep these compiles out of any cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def chip(one_chip, monkeypatch):
    """The described chip, with impl="pallas" meaning the compiled kernel
    again (conftest sends it through the interpreter off TPU)."""
    monkeypatch.setattr(ops, "_pick", _REAL_PICK)
    return one_chip


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < V5E_HBM, f"{used / 1e9:.2f} GB does not fit a v5e"
    return compiled


@pytest.fixture(scope="module")
def sw_1000():
    net = core.make_scenario(core.TABLE_II["sw_1000"])
    nbrs = core.build_neighbors(net.adj)
    return net, nbrs, core.spt_phi_sparse(net, nbrs)


@pytest.fixture(scope="module")
def ba_10000_buckets():
    return core.build_buckets(_mk_adj(core.TABLE_II["ba_10000"]))


def test_tpu_defaults():
    """The flows fixed points run on XLA on TPU (the Pallas edge_rounds
    kernels are refused by the chip's compiler); the QP projection runs
    its Pallas kernel."""
    assert ops.default_impl("edge_rounds", "tpu") == "ref"
    assert ops.default_impl("edge_rounds_bucketed", "tpu") == "ref"
    assert ops.default_impl("simplex_project", "tpu") == "pallas"
    assert ops.default_impl("simplex_project", "cpu") == "ref"


def test_edge_rounds_sw_1000(chip, sw_1000):
    _, nbrs, phi = sw_1000
    impl = ops.default_impl("edge_rounds", "tpu")
    w = jax.ShapeDtypeStruct(phi.data.shape, jnp.float32, sharding=chip)
    inj = jax.ShapeDtypeStruct(phi.data.shape[:2], jnp.float32,
                               sharding=chip)
    tiles = _abstract((nbrs.out_nbr, nbrs.out_mask), chip)
    _compile(lambda w, b, n, m: ops.edge_rounds(w, b, n, m, impl=impl),
             w, inj, *tiles)


def test_edge_rounds_bucketed_ba_10000(chip, ba_10000_buckets):
    buckets = ba_10000_buckets
    S, V = core.TABLE_II["ba_10000"].S, buckets.V
    D = max(int(t.shape[1]) for t in buckets.out.nbr)   # tile width Dmax
    impl = ops.default_impl("edge_rounds_bucketed", "tpu")
    w = jax.ShapeDtypeStruct((S, V, D), jnp.float32, sharding=chip)
    inj = jax.ShapeDtypeStruct((S, V), jnp.float32, sharding=chip)
    _compile(lambda w, b, bk: ops.edge_rounds_bucketed(w, b, bk, impl=impl),
             w, inj, _abstract(buckets.out, chip))


def test_simplex_project_sw_1000(chip, sw_1000):
    net, nbrs, _ = sw_1000
    impl = ops.default_impl("simplex_project", "tpu")
    rows = (net.S * net.V, nbrs.Dmax + 1)      # [R, K], K not padded
    f = jax.ShapeDtypeStruct(rows, jnp.float32, sharding=chip)
    b = jax.ShapeDtypeStruct(rows, jnp.bool_, sharding=chip)
    compiled = _compile(
        lambda p, d, m, k: ops.simplex_project(p, d, m, k, impl=impl),
        f, f, f, b)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the trace finds the kernel by this instruction name (qp_roofline)
    assert re.search(r"%simplex_project(\.\d+)? = \S+ custom-call\(", text)


def test_simplex_project_ba_10000(chip, ba_10000_buckets):
    """The widest rows the repo projects, K = Dmax + 1 = 278: one
    [K, 8, 128] block must fit the kernel's VMEM."""
    spec = core.TABLE_II["ba_10000"]
    D = max(int(t.shape[1]) for t in ba_10000_buckets.out.nbr)
    rows = (spec.S * ba_10000_buckets.V, D + 1)
    f = jax.ShapeDtypeStruct(rows, jnp.float32, sharding=chip)
    b = jax.ShapeDtypeStruct(rows, jnp.bool_, sharding=chip)
    impl = ops.default_impl("simplex_project", "tpu")
    compiled = _compile(
        lambda p, d, m, k: ops.simplex_project(p, d, m, k, impl=impl),
        f, f, f, b)
    assert "tpu_custom_call" in compiled.as_text()


def test_sgp_step_flows_sw_1000(chip, sw_1000):
    net, nbrs, phi = sw_1000
    fl, T0 = jax.eval_shape(
        lambda n, p: flows_carry_and_cost(n, p, "sparse", nbrs=nbrs),
        net, phi)
    consts = jax.eval_shape(core.make_consts, net, T0)
    args = _abstract((net, phi, fl, consts, nbrs), chip)
    sigma = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    engine = ops.default_impl("edge_rounds", "tpu")
    proj = ops.default_impl("simplex_project", "tpu")

    def step(net, phi, fl, consts, nbrs, sigma):
        return sgp_step_flows(
            net, phi, fl, consts, method="sparse", sigma=sigma, kappa=0.0,
            engine_impl=engine, proj_impl=proj, nbrs=nbrs)

    compiled = _compile(step, *args, sigma)
    assert "tpu_custom_call" in compiled.as_text()   # the QP kernel


def test_sgp_block_sw_1000(chip, sw_1000):
    """The fused driver's block — the step and the accept select in an
    on-device loop of up to BLOCK iterations — at `sw_1000` widths."""
    net, nbrs, phi = sw_1000
    fl, T0 = jax.eval_shape(
        lambda n, p: flows_carry_and_cost(n, p, "sparse", nbrs=nbrs),
        net, phi)
    consts = jax.eval_shape(core.make_consts, net, T0)
    args = _abstract((net, phi, fl, consts, nbrs), chip)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    flag = jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)
    engine = ops.default_impl("edge_rounds", "tpu")
    proj = ops.default_impl("simplex_project", "tpu")

    def block(net, phi, fl, consts, nbrs, sigma, prev, n_costs, n_rej,
              stopped, tol, n):
        return _sgp_block(
            net, phi, fl, consts, sigma, prev, n_costs, n_rej, stopped,
            tol, n, step=sgp_step_flows, adaptive=True, method="sparse",
            kappa=0.0, engine_impl=engine, proj_impl=proj, nbrs=nbrs)

    compiled = _compile(block, *args, f32, f32, i32, i32, flag, f32, i32)
    text = compiled.as_text()
    assert "tpu_custom_call" in text                  # the QP kernel
    assert "while" in text                            # the block's loop
    assert jax.eval_shape(block, *args, f32, f32, i32, i32, flag, f32,
                          i32)[1][0].shape == (BLOCK,)
