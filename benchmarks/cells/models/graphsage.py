"""GraphSAGE with the mean aggregator (Hamilton et al., 2017), as the
plain reference of the ``graphsage`` configuration, and the counts of its
work.

One layer over a padded block whose destinations are the first
``cap_dst`` rows of its sources:

    agg_v = sum over live edges (u -> v) of h_u / max(deg_v, 1)
    h'_v  = act(h_v W_self + agg_v W_neigh + b)

``act`` is ReLU on every layer but the last, which gives the logits.  The
parameters are laid out as the program lays out its own (``layers``:
``w_self``, ``w_neigh``, ``b``), so that the benchmark can hand the
program the weights it made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import refparts as common


def init(cfg, in_dim: int, num_classes: int, key) -> dict:
    dims = common.layer_dims(cfg, in_dim, num_classes)
    keys = jax.random.split(key, 2 * len(dims))
    return {"layers": [
        {"w_self": common.glorot(keys[2 * l], (d_in, d_out)),
         "w_neigh": common.glorot(keys[2 * l + 1], (d_in, d_out)),
         "b": jnp.zeros((d_out,), jnp.float32)}
        for l, (d_in, d_out) in enumerate(dims)]}


def forward(cfg, params: dict, batch: dict, caps: list, dtype) -> jnp.ndarray:
    h = batch["input_feats"].astype(dtype)
    last = len(params["layers"]) - 1
    for l, (p, block) in enumerate(zip(params["layers"], batch["blocks"])):
        num_dst = caps[l][0]
        agg, deg = common.mean_parts(h, block, num_dst)
        agg = agg / deg[:, None].astype(dtype)
        h = (h[:num_dst] @ p["w_self"].astype(dtype)
             + agg @ p["w_neigh"].astype(dtype) + p["b"].astype(dtype))
        if l != last:
            h = jax.nn.relu(h)
    return h


def flops(cfg, in_dim: int, num_classes: int, caps: list) -> float:
    """Model FLOPs of one trainer's step at the padded capacities:
    forward, the weight gradients of every layer, and the input gradients
    of every layer but the first (its input is data).  The sums over
    edges count one addition per element."""
    total = 0.0
    for l, (d_in, d_out) in enumerate(common.layer_dims(cfg, in_dim,
                                                        num_classes)):
        cap_dst, cap_edge, _ = caps[l]
        mm = 2.0 * 2 * cap_dst * d_in * d_out          # two matmuls
        agg = float(cap_edge) * d_in
        total += mm + agg                               # forward
        total += mm                                     # weight gradients
        if l > 0:
            total += mm + agg                           # input gradients
    return total


def gather_calls(cfg, in_dim: int, num_classes: int, caps: list,
                 live_edges: list) -> list:
    """The ``fused_gather_aggregate`` kernel calls of one trainer's step:
    each layer's forward sum over its live edges, and for every layer but
    the first the backward sum, the same op with the edges reversed into
    the layer's ``cap_src`` rows."""
    calls = []
    for l, (d_in, _) in enumerate(common.layer_dims(cfg, in_dim,
                                                    num_classes)):
        cap_dst, _, cap_src = caps[l]
        calls.append(dict(width=d_in, edges=live_edges[l], out_rows=cap_dst,
                          heads=0))
        if l > 0:
            calls.append(dict(width=d_in, edges=live_edges[l],
                              out_rows=cap_src, heads=0))
    return calls
