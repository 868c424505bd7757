"""Compile every kernel the TPU's ``impl="auto"`` reaches, at the paper's
widths, for a described TPU v5e — no chip needed: the TPU compiler is
installed and compiles for a topology that is described, not attached.

Each test compiles one op's forward and its VJP under ``jax.vmap`` over
T=4 trainers (the trainer's stacked step) and checks that a Mosaic kernel
(``tpu_custom_call``) is in the program.  ``sparse_adam`` is the
exception: it updates one KVStore shard on the host's behalf, outside any
vmap or gradient, so its two programs are compiled as called.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
import every test file.  Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (fused_edge_softmax_aggregate,
                           fused_gather_aggregate, segment_sum)
from repro.kernels.fused_edge_softmax_aggregate import ops as fesa_ops
from repro.kernels.fused_gather_aggregate import ops as fga_ops
from repro.kernels.segment_sum import ops as seg_ops
from repro.kernels.sparse_adam.kernel import sparse_adam_pallas

T = 4   # trainers vmapped in one step (2 machines x 2 trainers)


@pytest.fixture(scope="module")
def topo():
    import os
    if not os.environ.get("TPU_LOG_DIR"):     # else the compiler logs to /tmp
        os.environ["TPU_LOG_DIR"] = "disabled"
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_kernels(monkeypatch):
    """The ops pick interpret mode from the backend, which is the CPU
    here; compile the native kernels as the chip would run them."""
    for mod in (fga_ops, seg_ops, fesa_ops):
        monkeypatch.setattr(mod, "on_tpu", lambda: True)
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without the chip; keep these runs silent
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _edges(one_chip, e):
    return (_shape(one_chip, (T, e), jnp.int32),
            _shape(one_chip, (T, e), jnp.int32),
            _shape(one_chip, (T, e), jnp.bool_))


def _compiled_text(fn, *args) -> str:
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text()


def _assert_kernels(text: str, at_least: int):
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) >= at_least, f"{len(calls)} Mosaic kernels"


def _grad_of_sum(op, n_diff):
    """value_and_grad of sum(vmap(op)) w.r.t. the first n_diff inputs."""
    def step(*args):
        def loss(*diff):
            out = jax.vmap(op)(*diff, *args[n_diff:])
            return (out * out).sum()
        return jax.value_and_grad(loss, argnums=tuple(range(n_diff)))(
            *args[:n_diff])
    return step


def _gather_aggregate_step(num_dst):
    return _grad_of_sum(lambda h, s, d, m: fused_gather_aggregate(
        h, s, d, m, num_dst, impl="pallas"), 1)


@pytest.mark.parametrize("e,v,f,n", [
    # GraphSAGE layer 0 (batch 1000, fanouts 15/10/5, in_dim 100)
    (990_000, 1_056_000, 100, 66_000),
    # RGCN layer 1 (batch 1000, fanouts 25/15, hidden 1024), one relation
    (15_000, 16_000, 1024, 1_000),
], ids=["graphsage_layer0", "rgcn_hidden1024"])
def test_fused_gather_aggregate_compiles(one_chip, tpu_kernels, e, v, f, n):
    text = _compiled_text(_gather_aggregate_step(n),
                          _shape(one_chip, (T, v, f)), *_edges(one_chip, e))
    _assert_kernels(text, 2)           # forward + the swapped-edge VJP


def test_fused_edge_softmax_aggregate_compiles(one_chip, tpu_kernels):
    # GAT layer 1 (batch 1000, fanouts 15/10/5): 2 heads x 128
    e, v, n, heads, dh = 60_000, 66_000, 6_000, 2, 128
    step = _grad_of_sum(lambda hp, sc, s, d, m: fused_edge_softmax_aggregate(
        hp, sc, s, d, m, n, impl="pallas"), 2)
    text = _compiled_text(step, _shape(one_chip, (T, v, heads, dh)),
                          _shape(one_chip, (T, e, heads)),
                          *_edges(one_chip, e))
    _assert_kernels(text, 1)           # forward; the VJP is the ref's


def test_degree_segment_sum_compiles(one_chip, tpu_kernels):
    # the (E, 1) degree count of GraphSAGE layer 0
    e, n = 990_000, 66_000
    step = _grad_of_sum(lambda msg, d, m: segment_sum(
        msg, d, m, n, impl="pallas"), 1)
    text = _compiled_text(step, _shape(one_chip, (T, e, 1)),
                          _shape(one_chip, (T, e), jnp.int32),
                          _shape(one_chip, (T, e), jnp.bool_))
    _assert_kernels(text, 1)


def test_sparse_adam_compiles(one_chip, tpu_kernels):
    # a 100-wide embedding table over ogbn-products' 2,449,029 nodes,
    # updated at one batch's worth of unique rows
    n, d, r = 2_449_029, 100, 66_000
    kw = dict(beta1=0.9, beta2=0.999, lr=1e-2, eps=1e-8, interpret=False)
    table = _shape(one_chip, (n, d))
    per_row = _shape(one_chip, (r, d))
    text = _compiled_text(
        lambda w, m, v, rows, cm, cv, b1, b2: sparse_adam_pallas(
            w, m, v, rows, cm, cv, b1, b2, **kw),
        table, table, table, _shape(one_chip, (r,), jnp.int32),
        per_row, per_row, per_row, per_row)
    _assert_kernels(text, 2)           # products, then update + scatter
