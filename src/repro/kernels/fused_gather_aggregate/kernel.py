"""Pallas TPU kernel: fused gather -> masked segment-sum.

The unfused GNN aggregation materializes the (E, F) message array twice
over HBM: the gather writes it, the segment-sum reads it back.  E is the
largest axis of a padded MFG block (cap_edge = cap_dst * fanout_total), so
for wide features that round trip dominates the layer.  This kernel never
materializes it.  The source table stays in HBM (``memory_space=pl.ANY``):
it is a mini-batch's ``cap_src`` rows, which at the paper's GraphSAGE
widths (batch 1000, fanouts 15/10/5) is 1,056,000 rows at layer 0 — 541 MB
per 128-wide feature block, far beyond VMEM.  Each grid step takes one
chunk of EB edges, laid out destination-block-major by
:func:`..segment_sum.kernel.chunk_layout`; it DMAs the chunk's source rows
from HBM into a VMEM tile, one row copy per live edge (the row ids ride in
SMEM), and folds the tile into the destination block's accumulator with
the segment-sum kernel's one-hot matmul:

    out[NB, F] += onehot(edge_dst)[NB, EB] @ h[edge_src][EB, F]

Optional per-edge, per-head ``weights`` (E, H) scale the one-hot instead
of the messages, head by head over that head's F/H lanes: the attention
tail of :mod:`..fused_edge_softmax_aggregate` is this kernel with the
softmax weights.

Row DMAs need rows that are contiguous in HBM, which a (V, F) table is
only when F is one 128-lane tile: the feature axis is padded to a
multiple of 128 and the table viewed as (V * F/128, 128), so one source
row is F/128 consecutive table rows and still one DMA.  Source ids must
lie in ``[0, V)``; edges whose mask is off or whose destination lies
outside ``[0, num_dst)`` are dropped.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..segment_sum.kernel import (EB, EXACT, NB, chunk_layout, chunk_onehot,
                                  dst_spec, flatten_trainers, is_first_chunk,
                                  offset_dst)

LANES = 128


def _kernel(cblk_ref, ccnt_ref, src_ref, dst_ref, *refs, lane_blocks: int,
            heads: int, head_dim: int):
    if heads:
        w_ref, h_hbm, out_ref, buf, sem = refs
    else:
        h_hbm, out_ref, buf, sem = refs
    k = lane_blocks
    c = pl.program_id(0)

    @pl.when(is_first_chunk(cblk_ref, c))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    n = ccnt_ref[c]

    @pl.when(n > 0)
    def _accumulate():
        # source row s is table rows [s*k, s*k + k): one DMA per edge
        def row_copy(r, row):
            return pltpu.make_async_copy(h_hbm.at[pl.ds(row * k, k)],
                                         buf.at[pl.ds(r * k, k)], sem.at[0])

        def start(r, carry):
            row_copy(r, src_ref[r]).start()
            return carry

        def wait(r, carry):
            row_copy(0, 0).wait()
            return carry

        lax.fori_loop(0, n, start, 0)
        lax.fori_loop(0, n, wait, 0)
        eb = buf.shape[0] // k
        live = lax.broadcasted_iota(jnp.int32, (eb, 1), 0) < n
        onehot = chunk_onehot(dst_ref, c, out_ref.shape[0], buf.dtype)
        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        for j in range(k):
            tile = buf[pl.ds(j, eb, stride=k), :] if k > 1 else buf[...]
            msg = jnp.where(live, tile, 0.0)     # stale rows past n -> 0
            lanes = slice(j * LANES, (j + 1) * LANES)
            if not heads:
                out_ref[:, lanes] += jnp.dot(
                    onehot, msg, precision=EXACT,
                    preferred_element_type=out_ref.dtype)
                continue
            acc = jnp.zeros((out_ref.shape[0], LANES), out_ref.dtype)
            for hd in range(heads):
                lo = hd * head_dim - j * LANES
                hi = lo + head_dim
                if hi <= 0 or lo >= LANES:       # head not in these lanes
                    continue
                acc += jnp.dot(onehot * w_ref[hd:hd + 1, :],
                               jnp.where((lane >= lo) & (lane < hi), msg, 0.0),
                               precision=EXACT,
                               preferred_element_type=out_ref.dtype)
            out_ref[:, lanes] += acc


@functools.partial(jax.jit, static_argnames=("num_dst", "interpret"))
def _gather_aggregate_flat(h, edge_src, edge_dst, valid, weights, *,
                           num_dst: int, interpret: bool):
    v, f = h.shape
    e = edge_src.shape[0]
    if e == 0 or num_dst == 0:
        return jnp.zeros((num_dst, f), h.dtype)
    fp = -(-f // LANES) * LANES
    valid = valid & (edge_dst >= 0) & (edge_dst < num_dst)
    lay = chunk_layout(edge_dst, valid, num_dst)
    c_max = lay.chunk_block.shape[0]
    src = jnp.where(lay.slot_valid, jnp.clip(edge_src, 0, v - 1)[lay.edge],
                    0).reshape(-1)
    operands = [lay.chunk_block, lay.chunk_count, src, lay.dst_local]
    in_specs = [pl.BlockSpec((EB,), lambda c, *_: (c,),
                             memory_space=pltpu.SMEM),
                dst_spec()]
    heads = head_dim = 0
    if weights is not None:
        heads = weights.shape[1]
        head_dim = f // heads
        w = jnp.where(lay.slot_valid[..., None], weights[lay.edge], 0)
        operands.append(jnp.swapaxes(w, 1, 2).astype(jnp.float32))
        in_specs.append(pl.BlockSpec((None, heads, EB),
                                     lambda c, *_: (c, 0, 0)))
    # (V, Fp) -> (V * Fp/128, 128): 128-lane rows are contiguous in HBM,
    # so one source row is Fp/128 consecutive table rows
    k = fp // LANES
    operands.append(jnp.pad(h, ((0, 0), (0, fp - f))).reshape(v * k, LANES))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    out = pl.pallas_call(
        functools.partial(_kernel, lane_blocks=k, heads=heads,
                          head_dim=head_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(c_max,), in_specs=in_specs,
            out_specs=pl.BlockSpec((NB, fp), lambda c, cblk, _: (cblk[c], 0)),
            scratch_shapes=[pltpu.VMEM((EB * k, LANES), h.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((lay.num_blocks * NB, fp),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_gather_aggregate",
    )(*operands)
    return out[:num_dst, :f].astype(h.dtype)


def _vmappable(num_dst: int, interpret: bool, weighted: bool):
    def call(h, edge_src, edge_dst, valid, *weights):
        return _gather_aggregate_flat(
            h, edge_src, edge_dst, valid, weights[0] if weighted else None,
            num_dst=num_dst, interpret=interpret)

    op = custom_vmap(call)

    @op.def_vmap
    def _flat(axis_size, in_batched, h, edge_src, edge_dst, valid,
              *weights):
        h, edge_src, edge_dst, valid, *weights = flatten_trainers(
            axis_size, in_batched, (h, edge_src, edge_dst, valid, *weights))
        v = h.shape[1]
        t = jnp.arange(axis_size, dtype=jnp.int32)[:, None]
        out = _vmappable(axis_size * num_dst, interpret, weighted)(
            h.reshape(-1, h.shape[-1]), (edge_src + t * v).reshape(-1),
            offset_dst(edge_dst, valid, num_dst), valid.reshape(-1),
            *[w.reshape(-1, w.shape[-1]) for w in weights])
        return out.reshape(axis_size, num_dst, -1), True
    return op


def fused_gather_aggregate_pallas(h_src: jnp.ndarray, edge_src: jnp.ndarray,
                                  edge_dst: jnp.ndarray,
                                  edge_mask: jnp.ndarray, num_dst: int, *,
                                  weights: Optional[jnp.ndarray] = None,
                                  interpret: bool = True) -> jnp.ndarray:
    """h_src: (V, F); edge_src/edge_dst/edge_mask: (E,); weights: optional
    (E, H) per-head edge weights over F/H-lane heads -> (num_dst, F)."""
    args = (h_src, edge_src.astype(jnp.int32), edge_dst.astype(jnp.int32),
            edge_mask.astype(jnp.bool_))
    if weights is not None:
        args += (weights,)
    return _vmappable(num_dst, interpret, weights is not None)(*args)
