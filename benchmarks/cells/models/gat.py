"""GAT (Velickovic et al., 2018), as the plain reference of the ``gat``
configuration, and the counts of its work.

One layer with H heads of width ``d_h`` over a padded block:

    z_u      = h_u W                          (H, d_h) per source row
    e_uv     = LeakyReLU_0.2(a_l . z_u + a_r . z_v)     per head
    alpha_uv = softmax of e_uv over v's live in-edges
    h'_v     = act(concat_heads(sum_u alpha_uv z_u) + b)

``act`` is ELU on every layer but the last.  The last layer has
``num_classes // H`` lanes a head; where the heads do not add up to the
class count, a linear ``head`` maps their concatenation to the logits.
Parameters are laid out as the program lays out its own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import refparts as common


def _head_dims(cfg, in_dim: int, num_classes: int) -> list:
    heads = int(cfg["num_heads"])
    return [(d_in, heads, max(d_out // heads, 1))
            for d_in, d_out in common.layer_dims(cfg, in_dim, num_classes)]


def _last_width(cfg, in_dim: int, num_classes: int) -> int:
    _, heads, d_h = _head_dims(cfg, in_dim, num_classes)[-1]
    return heads * d_h


def init(cfg, in_dim: int, num_classes: int, key) -> dict:
    dims = _head_dims(cfg, in_dim, num_classes)
    keys = jax.random.split(key, 3 * len(dims) + 1)
    params = {"layers": []}
    d_prev = in_dim
    for l, (_, heads, d_h) in enumerate(dims):
        params["layers"].append({
            "w": common.glorot(keys[3 * l], (d_prev, heads, d_h)),
            "a_l": common.glorot(keys[3 * l + 1], (heads, d_h)),
            "a_r": common.glorot(keys[3 * l + 2], (heads, d_h)),
            "b": jnp.zeros((heads * d_h,), jnp.float32)})
        d_prev = heads * d_h
    if d_prev != num_classes:
        params["head"] = common.glorot(keys[-1], (d_prev, num_classes))
    return params


def forward(cfg, params: dict, batch: dict, caps: list, dtype) -> jnp.ndarray:
    h = batch["input_feats"].astype(dtype)
    last = len(params["layers"]) - 1
    for l, (p, block) in enumerate(zip(params["layers"], batch["blocks"])):
        num_dst = caps[l][0]
        src, dst, live = (block["edge_src"], block["edge_dst"],
                          block["edge_mask"])
        z = jnp.einsum("nd,dhf->nhf", h, p["w"].astype(dtype))
        el = jnp.einsum("nhf,hf->nh", z, p["a_l"].astype(dtype))
        er = jnp.einsum("nhf,hf->nh", z[:num_dst], p["a_r"].astype(dtype))
        e = jax.nn.leaky_relu(el[src] + er[dst], 0.2)
        alpha = common.edge_softmax(e, dst, live, num_dst)
        msg = (z[src] * alpha[:, :, None]).reshape(src.shape[0], -1)
        h = common.masked_sum(msg, dst, live, num_dst) + p["b"].astype(dtype)
        if l != last:
            h = jax.nn.elu(h)
    if "head" in params:
        h = h @ params["head"].astype(dtype)
    return h


def flops(cfg, in_dim: int, num_classes: int, caps: list) -> float:
    """Model FLOPs of one trainer's step at the padded capacities: the
    projection of every source row, the attention logits, softmax and
    weighted sums over every edge slot, the head, forward and backward
    (no input gradient for the first layer's data)."""
    total = 0.0
    for l, (d_in, heads, d_h) in enumerate(_head_dims(cfg, in_dim,
                                                      num_classes)):
        cap_dst, cap_edge, cap_src = caps[l]
        width = heads * d_h
        proj = 2.0 * cap_src * d_in * width
        logits = 2.0 * (cap_src + cap_dst) * width
        edges = float(cap_edge) * (2 * width + 6 * heads)
        fwd = proj + logits + edges
        total += fwd + proj                 # forward, weight gradient
        if l > 0:
            total += fwd                    # input gradients
    width = _last_width(cfg, in_dim, num_classes)
    if width != num_classes:
        total += 3 * 2.0 * caps[-1][0] * width * num_classes
    return total


def gather_calls(cfg, in_dim: int, num_classes: int, caps: list,
                 live_edges: list) -> list:
    """The ``fused_gather_aggregate`` kernel calls of one trainer's step:
    the attention-weighted forward sum of every layer.  The backward of
    the attention tail is XLA's, not the kernel's."""
    return [dict(width=heads * d_h, edges=live_edges[l], out_rows=caps[l][0],
                 heads=heads)
            for l, (_, heads, d_h) in enumerate(_head_dims(cfg, in_dim,
                                                           num_classes))]
