"""Public op: fused attention tail with implementation dispatch.

The Pallas path carries a ``custom_vjp`` whose backward is the
``jax.vjp`` of the reference composition
(:func:`.ref.fused_edge_softmax_aggregate_ref`): the gradient recomputes
the softmax and materializes the (E, H*Dh) messages, as the unfused
layer did; only the forward is fused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..platform import on_tpu, resolve_impl
from .kernel import fused_edge_softmax_aggregate_pallas
from .ref import fused_edge_softmax_aggregate_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _pallas(h_proj, scores, edge_src, edge_dst, edge_mask, num_dst):
    return fused_edge_softmax_aggregate_pallas(
        h_proj, scores, edge_src, edge_dst, edge_mask, num_dst,
        interpret=not on_tpu())


def _pallas_fwd(h_proj, scores, edge_src, edge_dst, edge_mask, num_dst):
    out = _pallas(h_proj, scores, edge_src, edge_dst, edge_mask, num_dst)
    return out, (h_proj, scores, edge_src, edge_dst, edge_mask)


def _pallas_bwd(num_dst, res, cot):
    h_proj, scores, edge_src, edge_dst, edge_mask = res
    _, vjp = jax.vjp(
        lambda hp, sc: fused_edge_softmax_aggregate_ref(
            hp, sc, edge_src, edge_dst, edge_mask, num_dst), h_proj, scores)
    return (*vjp(cot), None, None, None)


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def fused_edge_softmax_aggregate(h_proj: jnp.ndarray, scores: jnp.ndarray,
                                 edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                                 edge_mask: jnp.ndarray, num_dst: int,
                                 impl: str = "auto") -> jnp.ndarray:
    if resolve_impl(impl) == "ref":
        return fused_edge_softmax_aggregate_ref(h_proj, scores, edge_src,
                                                edge_dst, edge_mask, num_dst)
    return _pallas(h_proj, scores, edge_src, edge_dst,
                   edge_mask.astype(jnp.bool_), num_dst)
