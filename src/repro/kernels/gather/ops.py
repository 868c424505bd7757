"""Public op: feature-row gather with implementation dispatch.

``impl="auto"`` picks the jnp reference on every platform, the TPU
included: the kernel's (1, fb) row blocks are refused by the TPU compiler
(a block's last two dimensions must be divisible by 8 and 128), and no
model path calls this op (the fused gather kernel replaced it).
``impl="pallas"`` still runs the kernel, in interpret mode off the TPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..platform import on_tpu, resolve_impl
from .kernel import gather_rows_pallas
from .ref import gather_rows_ref


def gather_rows(table: jnp.ndarray, idx: jnp.ndarray,
                impl: str = "auto") -> jnp.ndarray:
    if resolve_impl(impl, tpu_kernel=False) == "ref":
        return gather_rows_ref(table, idx)
    return gather_rows_pallas(table, idx, interpret=not on_tpu())
