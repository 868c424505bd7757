from .ops import (PackSpec, PackedBatch, StackedArena, device_stage,
                  flatten_tree, pack, pack_into, stacked_spec, stage_arena,
                  unflatten_tree, unpack, unpack_flat)

__all__ = ["PackSpec", "PackedBatch", "StackedArena", "device_stage",
           "flatten_tree", "pack", "pack_into", "stacked_spec",
           "stage_arena", "unflatten_tree", "unpack", "unpack_flat"]
