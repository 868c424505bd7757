"""The one platform check behind every kernel op's ``impl`` dispatch.

``impl="auto"`` picks the Pallas kernel on a TPU and the jnp/NumPy
oracle elsewhere (XLA's CPU scatter is fine there and Pallas interpret
mode is an emulator, not a performance path). An explicit
``impl="pallas"`` off the TPU runs the kernel in interpret mode; on the
TPU it always compiles natively.
"""
from __future__ import annotations

import jax

IMPLS = ("auto", "ref", "pallas")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str, *, tpu_kernel: bool = True) -> str:
    """``"auto"`` -> ``"pallas"`` on a TPU (when the op has a kernel that
    compiles there, ``tpu_kernel``), else ``"ref"``; validates the rest."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; have {IMPLS}")
    if impl == "auto":
        return "pallas" if tpu_kernel and on_tpu() else "ref"
    return impl
