"""Pallas TPU kernel: masked segment-sum (padded-edge GNN aggregation).

TPU adaptation of the scatter-add the paper's GPU backend (cuSPARSE /
segment reduce) performs: TPUs have no fast scatter, but they have an MXU.
The per-destination reduction is a *one-hot matmul* over blocks of NB
destination rows and EB edges,

    out[NB, F] += onehot(edge_dst)[NB, EB] @ msg[EB, F]

A dense sweep of every edge block for every destination block costs
E/EB * N/NB grid steps, which at the paper's widths (E ~ 10^6 per layer)
is 10^6 steps. So the edges are first laid out by destination block, in
XLA outside the kernel (:func:`chunk_layout`): a stable sort by
destination, then each block's edges padded to whole chunks of EB slots.
The grid walks the chunks in order; a scalar-prefetched table maps each
chunk to its destination block, so consecutive chunks of one block
accumulate into the same VMEM-resident output tile and every block is
visited exactly once. That is E/EB + N/NB grid steps.

This kernel keeps the message tile lane-dense, transposed to (F, EB), so
the degree count (F = 1) costs one sublane row per chunk rather than a
128-lane-padded column. Edges whose mask is off or whose destination lies
outside ``[0, num_dst)`` are dropped, as ``jax.ops.segment_sum`` drops
them. The same layout drives the fused gather kernel
(:mod:`..fused_gather_aggregate.kernel`).

Under ``jax.vmap`` (the trainer's T stacked trainers) the op flattens the
trainer axis into the row axis and launches one kernel over T * num_dst
rows, instead of batching the grid.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# EB is also the SMEM block of per-slot row ids in the gather kernel, and
# XLA tiles 1-D int32 arrays by 1024
EB = 1024
NB = 128
# dst ids ride in (8, EB) VMEM tiles, eight chunks to a tile (an int32
# sublane tile), so a chunk's row of EB ids wastes no padding
DST_ROWS = 8
# full-f32 MXU passes: the one-hot is exact in any precision, but the
# messages would otherwise be rounded to bf16 on their way into the MXU,
# while the jnp oracle adds them in f32
EXACT = lax.Precision.HIGHEST


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


class ChunkLayout(NamedTuple):
    edge: jnp.ndarray         # (C, EB) int32: edge index held by each slot
    slot_valid: jnp.ndarray   # (C, EB) bool: slot holds a live edge
    dst_local: jnp.ndarray    # (Cp, EB) int32: dst row within its block, -1 empty
    chunk_block: jnp.ndarray  # (C,) int32: destination block of each chunk
    chunk_count: jnp.ndarray  # (C,) int32: live slots (a prefix) per chunk
    num_blocks: int


def chunk_layout(edge_dst: jnp.ndarray, valid: jnp.ndarray,
                 num_dst: int) -> ChunkLayout:
    """Lay the live edges out destination-block-major in chunks of EB.

    Every block gets at least one chunk (an empty block still has its
    output tile zeroed), so there are at most N/NB + E/EB chunks; the
    static count ``C`` is that bound, and surplus chunks continue the last
    block with no live slots.
    """
    e = edge_dst.shape[0]
    nblk = _cdiv(num_dst, NB)
    c_max = nblk + _cdiv(e, EB)
    key = jnp.where(valid, edge_dst, nblk * NB).astype(jnp.int32)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    skey = key[order]
    block_start = jnp.searchsorted(
        skey, jnp.arange(nblk + 1, dtype=jnp.int32) * NB,
        side="left").astype(jnp.int32)
    count = block_start[1:] - block_start[:-1]
    chunks = jnp.maximum(1, _cdiv(count, EB))
    chunk_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(chunks, dtype=jnp.int32)])
    c = jnp.arange(c_max, dtype=jnp.int32)
    blk = jnp.clip(jnp.searchsorted(chunk_start, c, side="right") - 1,
                   0, nblk - 1).astype(jnp.int32)
    k = c - chunk_start[blk]
    ccount = jnp.clip(count[blk] - k * EB, 0, EB).astype(jnp.int32)
    lane = jnp.arange(EB, dtype=jnp.int32)
    slot_valid = lane[None, :] < ccount[:, None]
    pos = jnp.where(slot_valid,
                    block_start[blk][:, None] + k[:, None] * EB + lane, 0)
    dst_local = jnp.where(slot_valid, skey[pos] - blk[:, None] * NB, -1)
    cp = _cdiv(c_max, DST_ROWS) * DST_ROWS
    dst_local = jnp.pad(dst_local, ((0, cp - c_max), (0, 0)),
                        constant_values=-1)
    return ChunkLayout(order[pos], slot_valid, dst_local, blk, ccount, nblk)


def is_first_chunk(cblk_ref, c):
    """True on the first chunk of a destination block (zero its tile)."""
    return jnp.logical_or(c == 0,
                          cblk_ref[c] != cblk_ref[jnp.maximum(c - 1, 0)])


def chunk_onehot(dst_ref, c, nb: int, dtype):
    """(NB, EB) one-hot of the chunk's local dst ids (-1 matches nothing)."""
    dst = dst_ref[pl.ds(c % DST_ROWS, 1), :]                  # (1, EB)
    rows = lax.broadcasted_iota(jnp.int32, (nb, dst.shape[1]), 0)
    return (rows == dst).astype(dtype)


def dst_spec():
    return pl.BlockSpec((DST_ROWS, EB), lambda c, *_: (c // DST_ROWS, 0))


def flatten_trainers(axis_size: int, in_batched, args):
    """Broadcast the unbatched operands of a vmapped op to the batch."""
    return [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip(args, in_batched)]


def offset_dst(edge_dst, valid, num_dst):
    """(T, E) per-trainer dst ids -> ids into the (T * num_dst) flat rows;
    dropped edges (mask off, dst out of range) get -1."""
    t = jnp.arange(edge_dst.shape[0], dtype=jnp.int32)[:, None]
    live = valid & (edge_dst >= 0) & (edge_dst < num_dst)
    return jnp.where(live, edge_dst + t * num_dst, -1).reshape(-1)


def _kernel(cblk_ref, ccnt_ref, dst_ref, msg_ref, out_ref):
    c = pl.program_id(0)

    @pl.when(is_first_chunk(cblk_ref, c))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ccnt_ref[c] > 0)
    def _accumulate():
        msg = msg_ref[...]                                      # (F, EB)
        onehot = chunk_onehot(dst_ref, c, out_ref.shape[1], msg.dtype)
        out_ref[...] += lax.dot_general(
            msg, onehot, (((1,), (1,)), ((), ())), precision=EXACT,
            preferred_element_type=out_ref.dtype)               # (F, NB)


@functools.partial(jax.jit, static_argnames=("num_dst", "interpret"))
def _segment_sum_flat(msg, edge_dst, valid, *, num_dst: int,
                      interpret: bool):
    e, f = msg.shape
    if e == 0 or num_dst == 0:
        return jnp.zeros((num_dst, f), msg.dtype)
    valid = valid & (edge_dst >= 0) & (edge_dst < num_dst)
    lay = chunk_layout(edge_dst, valid, num_dst)
    c_max = lay.chunk_block.shape[0]
    slots = lay.edge.reshape(-1)
    msg_t = jnp.where(lay.slot_valid.reshape(-1)[:, None], msg[slots], 0).T
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(c_max,),
            in_specs=[dst_spec(),
                      pl.BlockSpec((f, EB), lambda c, *_: (0, c))],
            out_specs=pl.BlockSpec((f, NB), lambda c, cblk, _: (0, cblk[c]))),
        out_shape=jax.ShapeDtypeStruct((f, lay.num_blocks * NB),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="segment_sum",
    )(lay.chunk_block, lay.chunk_count, lay.dst_local, msg_t)
    return out[:, :num_dst].T.astype(msg.dtype)


def _vmappable(num_dst: int, interpret: bool):
    @custom_vmap
    def op(msg, edge_dst, valid):
        return _segment_sum_flat(msg, edge_dst, valid, num_dst=num_dst,
                                 interpret=interpret)

    @op.def_vmap
    def _flat(axis_size, in_batched, msg, edge_dst, valid):
        msg, edge_dst, valid = flatten_trainers(axis_size, in_batched,
                                                (msg, edge_dst, valid))
        out = _vmappable(axis_size * num_dst, interpret)(
            msg.reshape(-1, msg.shape[-1]),
            offset_dst(edge_dst, valid, num_dst), valid.reshape(-1))
        return out.reshape(axis_size, num_dst, -1), True
    return op


def segment_sum_pallas(msg: jnp.ndarray, edge_dst: jnp.ndarray,
                       edge_mask: jnp.ndarray, num_dst: int, *,
                       interpret: bool = True) -> jnp.ndarray:
    """msg: (E, F); edge_dst: (E,) int; edge_mask: (E,) -> (num_dst, F)."""
    return _vmappable(num_dst, interpret)(
        msg, edge_dst.astype(jnp.int32), edge_mask.astype(jnp.bool_))
