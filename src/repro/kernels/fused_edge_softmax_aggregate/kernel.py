"""Pallas TPU path of the fused attention tail: edge softmax -> weighted
gather -> segment-sum, never materializing the (E, H*Dh) message array.

The softmax weights are (E, H) — H is the head count, a handful — so they
are computed in XLA by the reference edge softmax (segment max and sum
over the destinations).  The expensive part, the weighted gather of the
projected source rows and their per-destination sum, is the fused
gather kernel with per-head edge weights
(:mod:`..fused_gather_aggregate.kernel`): the source table stays in HBM
and the (EB, H*Dh) message tile only ever lives in VMEM.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..edge_softmax.ref import edge_softmax_ref
from ..fused_gather_aggregate.kernel import fused_gather_aggregate_pallas


def fused_edge_softmax_aggregate_pallas(h_proj: jnp.ndarray,
                                        scores: jnp.ndarray,
                                        edge_src: jnp.ndarray,
                                        edge_dst: jnp.ndarray,
                                        edge_mask: jnp.ndarray,
                                        num_dst: int, *,
                                        interpret: bool = True
                                        ) -> jnp.ndarray:
    """h_proj: (V, H, Dh); scores: (E, H) -> (num_dst, H*Dh)."""
    v, h, dh = h_proj.shape
    alpha = edge_softmax_ref(scores, edge_dst, edge_mask, num_dst)
    return fused_gather_aggregate_pallas(
        h_proj.reshape(v, h * dh), edge_src, edge_dst, edge_mask, num_dst,
        weights=alpha, interpret=interpret)
