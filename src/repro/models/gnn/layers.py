"""GNN layers over padded MFG blocks (message passing per Eq. (1)).

Each layer consumes ``h_src`` (cap_src, d_in) — features of the block's
input nodes, dst nodes in the prefix — and produces ``h_dst``
(cap_dst, d_out). Aggregations run through the kernels package (Pallas on
TPU, jnp oracle elsewhere); padded edges are masked out of every reduction.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...kernels import (fused_edge_softmax_aggregate, fused_gather_aggregate,
                        segment_sum)


def _degrees(edge_dst, edge_mask, num_dst, impl):
    ones = edge_mask.astype(jnp.float32)[:, None]
    deg = segment_sum(ones, edge_dst, edge_mask, num_dst, impl=impl)[:, 0]
    return jnp.maximum(deg, 1.0)


def sage_layer(params, h_src: jnp.ndarray, block: dict, num_dst: int,
               activation=jax.nn.relu, impl: str = "auto") -> jnp.ndarray:
    """GraphSAGE mean aggregator: act(W_self h_v + W_neigh mean_u h_u)."""
    edge_src, edge_dst = block["edge_src"], block["edge_dst"]
    edge_mask = block["edge_mask"]
    # fused gather->aggregate: the (E, d_in) message array never
    # materializes on the pallas path (ref path = the old two-step jaxpr)
    agg = fused_gather_aggregate(h_src, edge_src, edge_dst, edge_mask,
                                 num_dst, impl=impl)
    agg = agg / _degrees(edge_dst, edge_mask, num_dst, impl)[:, None]
    h_self = h_src[:num_dst]
    out = h_self @ params["w_self"] + agg @ params["w_neigh"] + params["b"]
    return activation(out) if activation is not None else out


def gat_layer(params, h_src: jnp.ndarray, block: dict, num_dst: int,
              activation=jax.nn.elu, impl: str = "auto",
              negative_slope: float = 0.2) -> jnp.ndarray:
    """GAT layer, multi-head concat. params: w (d_in, H, d_h), a_l/a_r (H, d_h)."""
    edge_src, edge_dst = block["edge_src"], block["edge_dst"]
    edge_mask = block["edge_mask"]
    w, a_l, a_r = params["w"], params["a_l"], params["a_r"]
    h_proj = jnp.einsum("nd,dhf->nhf", h_src, w)            # (cap_src, H, d_h)
    el = jnp.einsum("nhf,hf->nh", h_proj, a_l)              # (cap_src, H)
    er = jnp.einsum("nhf,hf->nh", h_proj[:num_dst], a_r)    # (cap_dst, H)
    scores = el[edge_src] + er[edge_dst]                    # (E, H)
    scores = jax.nn.leaky_relu(scores, negative_slope)
    # fused softmax -> weighted gather -> aggregate (attention tail)
    out = fused_edge_softmax_aggregate(h_proj, scores, edge_src, edge_dst,
                                       edge_mask, num_dst, impl=impl)
    out = out + params["b"]
    return activation(out) if activation is not None else out


def rgcn_layer(params, h_src: jnp.ndarray, block: dict, num_dst: int,
               num_rels: int, activation=jax.nn.relu,
               impl: str = "auto", rel_offsets=None) -> jnp.ndarray:
    """RGCN: h_v = act(W_0 h_v + sum_r (1/c_{v,r}) sum_{u in N_r(v)} W_r h_u).

    params: w_rel (R, d_in, d_out), w_self (d_in, d_out), b (d_out,).
    Relations are looped (R is small and static). Two block layouts:

    * typed (relation-major, ``rel_offsets`` a static (R+1,) tuple from the
      sampler's per-relation capacities): relation r's edges occupy the
      static slot range ``[rel_offsets[r], rel_offsets[r+1])``, so each
      relation's masked segment-sum runs over only its own slots — the
      edge axis per relation shrinks from sum(f_r) to f_r per dst;
    * untyped (legacy): one fused edge axis, each relation re-scans it with
      its own ``edge_types == r`` mask.
    """
    edge_src, edge_dst = block["edge_src"], block["edge_dst"]
    edge_mask, edge_types = block["edge_mask"], block["edge_types"]
    out = h_src[:num_dst] @ params["w_self"] + params["b"]
    for r in range(num_rels):
        if rel_offsets is not None:
            lo, hi = int(rel_offsets[r]), int(rel_offsets[r + 1])
            if hi == lo:          # relation not sampled at this layer
                continue
            es, ed, em = edge_src[lo:hi], edge_dst[lo:hi], edge_mask[lo:hi]
        else:
            es, ed = edge_src, edge_dst
            em = edge_mask & (edge_types == r)
        proj = h_src @ params["w_rel"][r]                   # (cap_src, d_out)
        agg = fused_gather_aggregate(proj, es, ed, em, num_dst, impl=impl)
        agg = agg / _degrees(ed, em, num_dst, impl)[:, None]
        out = out + agg
    return activation(out) if activation is not None else out
