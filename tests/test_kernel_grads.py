"""Gradients through the Pallas kernels (interpret mode on the CPU).

Each op's ``custom_vjp`` under ``impl="pallas"`` against ``jax.vjp`` of
its jnp reference, alone and under ``jax.vmap`` (the trainer's stacked
trainers); then whole ``DistGNNTrainer`` steps with ``impl="pallas"``
against ``impl="ref"``.

Tolerances. The kernels sum the same f32 values as the references in
another order (sorted by destination, in 1024-edge chunks), so a single
op agrees to ~1e-6 relative; 1e-5 leaves room. Two Adam steps then
amplify that noise: Adam divides each gradient component by its own
running RMS, so a component that is near zero in both runs can move its
parameter by a different fraction of ``lr`` (3e-3). Measured on these
configurations: at most 4.3e-5 (GAT); parameters are held to 1e-4, and
losses, which average over every seed, to 1e-5 relative.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import DistGNNTrainer, TrainJobConfig
from repro.graph import get_dataset
from repro.kernels import (fused_edge_softmax_aggregate,
                           fused_gather_aggregate, segment_sum)
from repro.models.gnn import GNNConfig

OP_TOL = dict(rtol=1e-5, atol=1e-5)


def _graph(rng, batch, e, v, n):
    lead = (batch,) if batch else ()
    src = jnp.asarray(rng.integers(0, v, lead + (e,)), jnp.int32)
    dst = jnp.asarray(rng.integers(0, n, lead + (e,)), jnp.int32)
    mask = jnp.asarray(rng.random(lead + (e,)) > 0.3)
    return src, dst, mask


def _check_vjp(op, diff, rest, batch):
    """value and VJP of op(*diff, *rest) under pallas vs ref, with a
    nonuniform cotangent."""
    results = {}
    for impl in ("ref", "pallas"):
        def f(*d):
            call = lambda *a: op(*a, impl=impl)      # noqa: E731
            return (jax.vmap(call) if batch else call)(*d, *rest)
        out, vjp = jax.vjp(f, *diff)
        cot = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                      ).reshape(out.shape)
        results[impl] = (out, vjp(cot))
    (o_ref, g_ref), (o_pal, g_pal) = results["ref"], results["pallas"]
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               **OP_TOL)
    for a, b in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **OP_TOL)


@pytest.mark.parametrize("batch", [0, 3], ids=["single", "vmap3"])
@pytest.mark.parametrize("e,v,f,n", [
    (300, 90, 20, 40), (1500, 200, 130, 150), (64, 10, 1, 300),
])
def test_fused_gather_aggregate_vjp(batch, e, v, f, n):
    rng = np.random.default_rng(e + f)
    lead = (batch,) if batch else ()
    h = jnp.asarray(rng.standard_normal(lead + (v, f)), jnp.float32)
    src, dst, mask = _graph(rng, batch, e, v, n)
    _check_vjp(lambda h, s, d, m, impl: fused_gather_aggregate(
        h, s, d, m, n, impl=impl), (h,), (src, dst, mask), batch)


@pytest.mark.parametrize("batch", [0, 3], ids=["single", "vmap3"])
@pytest.mark.parametrize("e,f,n", [(300, 1, 40), (1200, 33, 130)])
def test_segment_sum_vjp(batch, e, f, n):
    rng = np.random.default_rng(e + f)
    lead = (batch,) if batch else ()
    msg = jnp.asarray(rng.standard_normal(lead + (e, f)), jnp.float32)
    _, dst, mask = _graph(rng, batch, e, 1, n)
    _check_vjp(lambda m, d, k, impl: segment_sum(m, d, k, n, impl=impl),
               (msg,), (dst, mask), batch)


@pytest.mark.parametrize("batch", [0, 2], ids=["single", "vmap2"])
@pytest.mark.parametrize("e,v,heads,dh,n", [
    (300, 90, 2, 8, 40), (700, 120, 2, 128, 90), (200, 50, 3, 5, 20),
])
def test_fused_edge_softmax_aggregate_vjp(batch, e, v, heads, dh, n):
    rng = np.random.default_rng(e + heads)
    lead = (batch,) if batch else ()
    hp = jnp.asarray(rng.standard_normal(lead + (v, heads, dh)),
                     jnp.float32)
    sc = jnp.asarray(rng.standard_normal(lead + (e, heads)) * 2,
                     jnp.float32)
    src, dst, mask = _graph(rng, batch, e, v, n)
    _check_vjp(lambda hp, sc, s, d, m, impl: fused_edge_softmax_aggregate(
        hp, sc, s, d, m, n, impl=impl), (hp, sc), (src, dst, mask), batch)


@pytest.fixture(scope="module")
def ds():
    return get_dataset("product-sim", scale=11)


@pytest.mark.parametrize("arch,extra", [
    ("graphsage", {}), ("gat", {"num_heads": 2}), ("rgcn", {"num_rels": 1}),
])
def test_two_steps_pallas_match_ref(ds, arch, extra):
    """Two synchronous steps of 2 trainers through the kernels' VJPs
    (the path a TPU takes by default) against the jnp path."""
    cfg = GNNConfig(arch=arch, in_dim=ds.feats.shape[1], hidden_dim=32,
                    num_classes=ds.num_classes, fanouts=[5, 5],
                    batch_size=48, **extra)
    runs = {}
    for impl in ("ref", "pallas"):
        tr = DistGNNTrainer(ds, cfg, TrainJobConfig(
            num_machines=2, trainers_per_machine=1, impl=impl))
        assert tr.batches_per_epoch == 2
        loss = tr.train_epoch(0)["loss"]
        runs[impl] = (loss, jax.tree.leaves(tr.params))
        tr.stop()
    (l_ref, p_ref), (l_pal, p_pal) = runs["ref"], runs["pallas"]
    assert np.isfinite(l_pal)
    np.testing.assert_allclose(l_pal, l_ref, rtol=1e-5)
    for a, b in zip(p_pal, p_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
