"""Public op: fused gather->aggregate with implementation dispatch.

``impl="auto"`` picks the jnp reference on CPU (where XLA fuses the gather
and scatter-add fine and Pallas interpret mode is an emulator) and the
fused Pallas kernel on TPU.  The ref path composes EXACTLY the expressions
the layers used to inline, so the CPU default stays byte-identical.

The Pallas path carries a ``custom_vjp``: the cotangent with respect to
``h_src`` is the same op with ``edge_src`` and ``edge_dst`` swapped, over
``num_src`` output rows — each source row sums the cotangents of the
destinations it feeds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..platform import on_tpu, resolve_impl
from .kernel import fused_gather_aggregate_pallas
from .ref import fused_gather_aggregate_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pallas(h_src, edge_src, edge_dst, edge_mask, num_dst, num_src):
    return fused_gather_aggregate_pallas(h_src, edge_src, edge_dst,
                                         edge_mask, num_dst,
                                         interpret=not on_tpu())


def _pallas_fwd(h_src, edge_src, edge_dst, edge_mask, num_dst, num_src):
    out = _pallas(h_src, edge_src, edge_dst, edge_mask, num_dst, num_src)
    return out, (edge_src, edge_dst, edge_mask)


def _pallas_bwd(num_dst, num_src, res, cot):
    edge_src, edge_dst, edge_mask = res
    live = edge_mask & (edge_dst >= 0) & (edge_dst < num_dst)
    d_h = fused_gather_aggregate_pallas(cot, edge_dst, edge_src, live,
                                        num_src, interpret=not on_tpu())
    return d_h, None, None, None


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def fused_gather_aggregate(h_src: jnp.ndarray, edge_src: jnp.ndarray,
                           edge_dst: jnp.ndarray, edge_mask: jnp.ndarray,
                           num_dst: int, impl: str = "auto") -> jnp.ndarray:
    if resolve_impl(impl) == "ref":
        return fused_gather_aggregate_ref(h_src, edge_src, edge_dst,
                                          edge_mask, num_dst)
    return _pallas(h_src, edge_src, edge_dst, edge_mask.astype(jnp.bool_),
                   num_dst, h_src.shape[0])
