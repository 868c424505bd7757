"""R-GCN (Schlichtkrull et al., arXiv:1703.06103, eq. 2), as the plain
reference of an ``rgcn`` configuration, and the counts of its work.

One layer over a padded typed block, whose edge axis is laid out
relation by relation (``edge_rel``: the relation of every slot, by the
harness's static slot offsets, never the program's own edge types):

    h'_v = act(h_v W_0 + b
               + sum_r 1 / max(c_{v,r}, 1) * sum_{u in N_r(v)} h_u W_r)

with ``c_{v,r}`` the live in-edges of ``v`` in relation ``r``.  ``act`` is
ReLU on every layer but the last, which gives the logits.  The forward
projects, then sums, as the formula is written.  Parameters are laid out
as the program lays out its own (``layers``: ``w_rel (R, d_in, d_out)``,
``w_self``, ``b``); ``cfg["num_rels"]`` is the traffic's relation count.

:func:`flops` and :func:`gather_calls` count, per layer and relation with
a budget at that layer, the cheaper of the two orders a program can run:

* sum first: the live edges' rows at ``d_in`` lanes into ``cap_dst``
  rows, then the matmul on the ``cap_dst`` sums;
* project first: the matmul on all ``cap_src`` rows, then the edges at
  ``d_out`` lanes.

For FLOPs that is always sum first (:func:`flops`); for the bytes of the
``fused_gather_aggregate`` calls it is the order that moves fewer
(:func:`gather_calls`): at the paper's widths, sum first on layer 0
(768 -> 1024) and project first on layer 1 (1024 -> 153).  A
program runs one of the two orders on each relation, so it does at least
this much of each: a change of order (ROADMAP A4, sum first in place of
project first) leaves the yardstick valid, and neither ``step_mfu`` nor
the kernel's roofline share read from it can pass 100%.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import counts
import refparts as common


def init(cfg, in_dim: int, num_classes: int, key) -> dict:
    dims = common.layer_dims(cfg, in_dim, num_classes)
    rels = int(cfg["num_rels"])
    keys = jax.random.split(key, 2 * len(dims))
    return {"layers": [
        {"w_rel": common.glorot(keys[2 * l], (rels, d_in, d_out)),
         "w_self": common.glorot(keys[2 * l + 1], (d_in, d_out)),
         "b": jnp.zeros((d_out,), jnp.float32)}
        for l, (d_in, d_out) in enumerate(dims)]}


def forward(cfg, params: dict, batch: dict, caps: list, dtype) -> jnp.ndarray:
    h = batch["input_feats"].astype(dtype)
    last = len(params["layers"]) - 1
    for l, (p, block) in enumerate(zip(params["layers"], batch["blocks"])):
        num_dst = caps[l][0]
        src, dst = block["edge_src"], block["edge_dst"]
        out = h[:num_dst] @ p["w_self"].astype(dtype) + p["b"].astype(dtype)
        for r in range(p["w_rel"].shape[0]):
            live = block["edge_mask"] & (block["edge_rel"] == r)
            z = h @ p["w_rel"][r].astype(dtype)
            agg = common.masked_sum(z[src], dst, live, num_dst)
            deg = jax.ops.segment_sum(live.astype(jnp.float32), dst,
                                      num_segments=num_dst)
            out = out + agg / jnp.maximum(deg, 1.0)[:, None].astype(dtype)
        h = jax.nn.relu(out) if l != last else out
    return h


def _budgets(cfg, l: int) -> list:
    """The relations with slots at layer ``l``."""
    return [r for r, f in cfg["fanouts"][l].items() if int(f) > 0]


def flops(cfg, in_dim: int, num_classes: int, caps: list) -> float:
    """Model FLOPs of one trainer's step at the padded capacities: the
    self matmul on ``cap_dst`` rows, and per relation with a budget its
    ``cap_dst * f_r`` edge slots summed first (one addition a lane of
    ``d_in``) and its matmul on the ``cap_dst`` sums; forward, weight
    gradients (from the forward's sums), and the input gradients of every
    layer but the first (its input is data: the matmul's gradient, sent
    back over the edges).  Summing first is always the cheaper order in
    FLOPs: projecting first runs the matmul on ``cap_src`` rows, at least
    ``cap_dst + cap_dst * f_r``, which costs ``2 * d_in * d_out`` a row
    against the ``d_in`` additions an edge that it saves."""
    total = 0.0
    for l, (d_in, d_out) in enumerate(common.layer_dims(cfg, in_dim,
                                                        num_classes)):
        cap_dst = caps[l][0]
        passes = 3 if l > 0 else 2
        mm = 2.0 * cap_dst * d_in * d_out
        total += mm * passes                            # the self matmul
        for r in _budgets(cfg, l):
            slots = float(cap_dst * int(cfg["fanouts"][l][r]))
            total += mm * passes + slots * d_in * (passes - 1)
    return total


def gather_calls(cfg, in_dim: int, num_classes: int, caps: list,
                 live_edges: list) -> list:
    """The ``fused_gather_aggregate`` calls of one trainer's step,
    ``live_edges[l]`` being ``{relation: live edges}`` of layer ``l``: per
    relation with a budget, those of the order that moves fewer bytes.
    Summing first: the forward sum at ``d_in`` lanes into ``cap_dst``
    rows, and for every layer but the first the backward sum, the same
    edges reversed into the layer's ``cap_src`` rows.  Projecting first:
    the forward and the backward sum (the weight gradient needs it on the
    first layer too) at ``d_out`` lanes."""
    calls = []
    for l, (d_in, d_out) in enumerate(common.layer_dims(cfg, in_dim,
                                                        num_classes)):
        cap_dst, _, cap_src = caps[l]
        for r in _budgets(cfg, l):
            edges = live_edges[l].get(r, 0)
            sum_first = [dict(width=d_in, edges=edges, out_rows=rows,
                              heads=0)
                         for rows in ((cap_dst, cap_src) if l else
                                      (cap_dst,))]
            project_first = [dict(width=d_out, edges=edges, out_rows=rows,
                                  heads=0) for rows in (cap_dst, cap_src)]
            calls += min(sum_first, project_first,
                         key=lambda cs: sum(map(counts.gather_bytes, cs)))
    return calls
