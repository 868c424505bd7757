"""Pallas TPU kernels for the GNN hot spots (+ jnp oracles).

Each kernel package has kernel.py (pl.pallas_call, compiled for the TPU
and validated under interpret=True on CPU), ops.py (the wrapper that
dispatches on ``impl`` through :mod:`.platform` and gives the Pallas path
its ``custom_vjp``) and ref.py (pure-jnp oracle).  The ``fused_*``
packages fuse whole layer tails (gather -> aggregate, softmax ->
weighted gather -> aggregate) so the (E, F) message array never touches
HBM; ``sparse_adam`` fuses the
DistEmbedding optimizer's gather -> update -> scatter; ``pack`` is the
packed one-shot device staging used by every device-prefetch stage
(DESIGN.md §9).
"""
from .segment_sum.ops import segment_sum
from .segment_sum.ref import segment_max_ref, segment_sum_ref
from .gather.ops import gather_rows
from .edge_softmax.ops import edge_softmax
from .fused_gather_aggregate.ops import fused_gather_aggregate
from .fused_gather_aggregate.ref import fused_gather_aggregate_ref
from .fused_edge_softmax_aggregate.ops import fused_edge_softmax_aggregate
from .fused_edge_softmax_aggregate.ref import fused_edge_softmax_aggregate_ref
from .sparse_adam.ops import sparse_adam_apply
from .pack.ops import (PackSpec, PackedBatch, device_stage, pack, unpack,
                       unpack_flat)

__all__ = ["segment_sum", "segment_sum_ref", "segment_max_ref",
           "gather_rows", "edge_softmax",
           "fused_gather_aggregate", "fused_gather_aggregate_ref",
           "fused_edge_softmax_aggregate", "fused_edge_softmax_aggregate_ref",
           "sparse_adam_apply",
           "PackSpec", "PackedBatch", "device_stage", "pack", "unpack",
           "unpack_flat"]
