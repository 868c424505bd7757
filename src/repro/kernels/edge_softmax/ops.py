"""Public op: per-destination edge softmax with implementation dispatch.

``impl="auto"`` picks the jnp reference on every platform, the TPU
included: the normalize phase gathers stats rows with integer indexing,
which the TPU compiler refuses, and no model path calls this op (the
attention tail runs through :mod:`..fused_edge_softmax_aggregate`).
``impl="pallas"`` still runs the kernel, in interpret mode off the TPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..platform import on_tpu, resolve_impl
from .kernel import edge_softmax_pallas
from .ref import edge_softmax_ref


def edge_softmax(scores: jnp.ndarray, edge_dst: jnp.ndarray,
                 edge_mask: jnp.ndarray, num_dst: int,
                 impl: str = "auto") -> jnp.ndarray:
    if resolve_impl(impl, tpu_kernel=False) == "ref":
        return edge_softmax_ref(scores, edge_dst, edge_mask, num_dst)
    return edge_softmax_pallas(scores, edge_dst, edge_mask, num_dst,
                               interpret=not on_tpu())
