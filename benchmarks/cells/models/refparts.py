"""Pieces the reference models share: widths, initialisation and the
masked sums over a padded block's edges, in plain ``jax.numpy``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def layer_dims(cfg, in_dim: int, num_classes: int) -> list:
    """``(d_in, d_out)`` per layer, input layer first."""
    n = len(cfg["fanouts"])
    hidden = int(cfg["hidden_dim"])
    return [(in_dim if l == 0 else hidden,
             num_classes if l == n - 1 else hidden) for l in range(n)]


def glorot(key, shape) -> jnp.ndarray:
    lim = float(np.sqrt(6.0 / (shape[0] + shape[-1])))
    return jax.random.uniform(key, shape, jnp.float32, -lim, lim)


def masked_sum(msg, dst, live, num_dst):
    """Sum of the live edges' rows into their destinations."""
    msg = jnp.where(live.reshape(live.shape + (1,) * (msg.ndim - 1)), msg, 0)
    return jax.ops.segment_sum(msg, dst, num_segments=num_dst)


def mean_parts(h, block, num_dst):
    """Sum of the live in-neighbours' rows and the in-degree (at least
    1) of each destination."""
    src, dst, live = block["edge_src"], block["edge_dst"], block["edge_mask"]
    agg = masked_sum(h[src], dst, live, num_dst)
    deg = jax.ops.segment_sum(live.astype(jnp.float32), dst,
                              num_segments=num_dst)
    return agg, jnp.maximum(deg, 1.0)


def edge_softmax(e, dst, live, num_dst):
    """Softmax of each live edge's logits over its destination's live
    in-edges; 0 on dead edges."""
    neg = jnp.asarray(-jnp.inf, e.dtype)
    masked = jnp.where(live[:, None], e, neg)
    top = jax.ops.segment_max(masked, dst, num_segments=num_dst)
    top = jnp.where(jnp.isfinite(top), top, 0)
    ex = jnp.where(live[:, None], jnp.exp(masked - top[dst]), 0)
    den = jax.ops.segment_sum(ex, dst, num_segments=num_dst)
    return ex / jnp.where(den > 0, den, 1)[dst]
