"""Public op: fused row-sparse Adam with implementation dispatch.

The caller (``DistEmbedding.push_grad``) owns everything stateful: the
int64 step counters ``t`` (incremented host-side — they must never pass
through a device transfer, which would downcast them), the duplicate-id
coalescing, and the transport accounting.  This op only applies one
already-coalesced update to one shard's tables.

Bitwise contract (both impls): identical bytes to the NumPy expressions
in :func:`..sparse_adam.ref.sparse_adam_ref` — which is what the dense
oracle in tests/test_embedding_oracle.py computes.  The ``(1-beta)*g``
terms and bias corrections are computed here in NumPy for BOTH impls (the
transcendental ``beta**t`` and the mul->add-contraction-prone products
must not be recomputed on device; see kernel.py).
"""
from __future__ import annotations

import numpy as np

from ..platform import on_tpu, resolve_impl
from .kernel import sparse_adam_pallas
from .ref import sparse_adam_ref


def sparse_adam_apply(w: np.ndarray, m: np.ndarray, v: np.ndarray,
                      rows: np.ndarray, grad: np.ndarray, t: np.ndarray, *,
                      beta1: float, beta2: float, lr: float, eps: float,
                      impl: str = "auto") -> None:
    """One shard's row-sparse Adam step, in place.

    w/m/v: (N, D) tables (mutated in place); t: (N,) int64 step counters
    (mutated in place — incremented BEFORE the bias correction, exactly
    like the oracle); rows: (R,) unique local row ids; grad: (R, D) f32
    coalesced gradients.
    """
    impl = resolve_impl(impl)
    t[rows] += 1
    tr = t[rows].astype(np.float32)[:, None]
    bc1 = 1 - beta1 ** tr
    bc2 = 1 - beta2 ** tr
    if impl == "ref":
        sparse_adam_ref(w, m, v, rows, grad, bc1, bc2, beta1=beta1,
                        beta2=beta2, lr=lr, eps=eps)
        return
    if w.dtype != np.float32:
        # non-f32 tables keep the NumPy path: the bitwise contract is
        # only defined for f32 (and the kernel assumes one dtype)
        sparse_adam_ref(w, m, v, rows, grad, bc1, bc2, beta1=beta1,
                        beta2=beta2, lr=lr, eps=eps)
        return
    g = grad.astype(np.float32)
    cm = (1 - beta1) * g                    # the oracle's exact products
    cv = (1 - beta2) * g * g
    d = w.shape[1]
    w2, m2, v2 = sparse_adam_pallas(
        w, m, v, rows.astype(np.int32), cm, cv,
        np.broadcast_to(bc1, (len(rows), d)).astype(np.float32),
        np.broadcast_to(bc2, (len(rows), d)).astype(np.float32),
        beta1=beta1, beta2=beta2, lr=lr, eps=eps,
        interpret=not on_tpu())
    # scatter back into the server's storage (the kernel already scattered
    # device-side via aliasing; these copies land the bytes in host numpy)
    np.copyto(w, np.asarray(w2))
    np.copyto(m, np.asarray(m2))
    np.copyto(v, np.asarray(v2))
