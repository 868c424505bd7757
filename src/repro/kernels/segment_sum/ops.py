"""Public op: masked segment-sum with implementation dispatch.

``impl="auto"`` picks the pure-jnp reference on CPU and the Pallas kernel
on TPU (:mod:`..platform`). Tests sweep both and assert allclose.

The Pallas path carries a ``custom_vjp``: the cotangent of ``msg`` is
``cot[edge_dst] * mask``, zero for edges the forward dropped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..platform import on_tpu, resolve_impl
from .kernel import segment_sum_pallas
from .ref import segment_sum_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pallas(msg, edge_dst, edge_mask, num_dst):
    return segment_sum_pallas(msg, edge_dst, edge_mask, num_dst,
                              interpret=not on_tpu())


def _pallas_fwd(msg, edge_dst, edge_mask, num_dst):
    return _pallas(msg, edge_dst, edge_mask, num_dst), (edge_dst, edge_mask)


def _pallas_bwd(num_dst, res, cot):
    edge_dst, edge_mask = res
    live = edge_mask & (edge_dst >= 0) & (edge_dst < num_dst)
    d_msg = cot[jnp.clip(edge_dst, 0, num_dst - 1)]
    return jnp.where(live[:, None], d_msg, 0), None, None


_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def segment_sum(msg: jnp.ndarray, edge_dst: jnp.ndarray,
                edge_mask: jnp.ndarray, num_dst: int,
                impl: str = "auto") -> jnp.ndarray:
    if resolve_impl(impl) == "ref":
        return segment_sum_ref(msg, edge_dst, edge_mask, num_dst)
    return _pallas(msg, edge_dst, edge_mask.astype(jnp.bool_), num_dst)
