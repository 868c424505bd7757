"""Pallas TPU kernel: fused row-sparse Adam (gather -> update -> scatter).

The KVStore servers apply Adam to exactly the rows a mini-batch touched.
Expressed naively on an accelerator that is gather / three elementwise
updates / scatter — five HBM round trips over the full tables.  Here the
whole update runs as two Pallas programs over tiles of RB touched rows:
the tables stay in HBM (``memory_space=pl.ANY``), the tile's row ids ride
in SMEM, and each grid step DMAs its RB rows of the tables into VMEM one
row copy at a time, updates them, and DMAs them back into the outputs,
which alias the inputs through ``input_output_aliases`` — rows never
touched keep their exact bytes because the output IS the input buffer.
(One-row BlockSpecs would be simpler, but the TPU compiler refuses a
block whose second-to-last dimension is not a multiple of 8.)

Why TWO pallas_calls (products, then update+scatter) and not one: the
bitwise contract.  The server-side NumPy update is the repo's oracle, and
XLA (CPU *and* TPU) contracts ``a*b + c`` into a fused multiply-add,
which rounds once where NumPy rounds twice — a 1-ulp divergence the
byte-identity tests would catch (``optimization_barrier`` does not
survive XLA:CPU's fusion pass; measured).  The split puts every fmul in
one program and every fadd in the other, so no program contains a
contractible mul->add pair:

  * program 1 (gather + products):  p_m = beta1 * m[row],
    p_v = beta2 * v[row] — multiplies only;
  * host (NumPy, shared with the oracle): c_m = (1-beta1)*g,
    c_v = (1-beta2)*g*g, bias corrections 1 - beta**t;
  * program 2 (update + scatter):  m' = p_m + c_m, v' = p_v + c_v,
    w' = w[row] - lr*(m'/bc1) / (sqrt(v'/bc2) + eps) — the only multiply
    (``lr * mhat``) feeds a divide, which never contracts.

Both calls dispatch eagerly (no enclosing jit), so XLA cannot fuse across
them.  Remaining ops are single correctly-rounded IEEE f32 ops on both
NumPy and XLA: the result is bit-identical to the NumPy reference
(pinned against the dense oracle in tests/test_embedding_oracle.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# row ids ride in SMEM blocks of 1024 (XLA tiles 1-D int32 arrays by
# 1024); each grid step (i, j) handles RB of block i's rows
ID_BLOCK = 1024
RB = 256
LANES = 128


def _copy_rows(n, rows_ref, base, src, dst, sem, *, gather: bool):
    """Start then wait n row DMAs between table ``src``/``dst`` rows
    ``rows_ref[base + r]`` and tile row r (gather: table -> tile)."""
    def copy(r, row):
        if gather:
            return pltpu.make_async_copy(src.at[pl.ds(row, 1)],
                                         dst.at[pl.ds(r, 1)], sem)
        return pltpu.make_async_copy(src.at[pl.ds(r, 1)],
                                     dst.at[pl.ds(row, 1)], sem)

    def start(r, carry):
        copy(r, rows_ref[base + r]).start()
        return carry

    def wait(r, carry):
        copy(0, 0).wait()
        return carry

    lax.fori_loop(0, n, start, 0)
    lax.fori_loop(0, n, wait, 0)


def _tile_rows(num_rows: int):
    """(offset of this tile in its SMEM id block, live rows in the tile)."""
    i, j = pl.program_id(0), pl.program_id(1)
    base = j * RB
    n = jnp.clip(num_rows - i * ID_BLOCK - base, 0, RB)
    return base, n


def _products_kernel(rows_ref, m_hbm, v_hbm, pm_ref, pv_ref, m_buf, v_buf,
                     sem, *, beta1: float, beta2: float, num_rows: int):
    base, n = _tile_rows(num_rows)
    _copy_rows(n, rows_ref, base, m_hbm, m_buf, sem.at[0], gather=True)
    _copy_rows(n, rows_ref, base, v_hbm, v_buf, sem.at[1], gather=True)
    pm_ref[...] = beta1 * m_buf[...]
    pv_ref[...] = beta2 * v_buf[...]


def _update_kernel(rows_ref, w_hbm, m_hbm, v_hbm, pm_ref, pv_ref, cm_ref,
                   cv_ref, bc1_ref, bc2_ref, w_out, m_out, v_out,
                   w_buf, m_buf, v_buf, sem, *, lr: float, eps: float,
                   num_rows: int):
    del m_hbm, v_hbm                      # aliased by m_out / v_out
    base, n = _tile_rows(num_rows)
    _copy_rows(n, rows_ref, base, w_hbm, w_buf, sem.at[0], gather=True)
    mm = pm_ref[...] + cm_ref[...]
    vv = pv_ref[...] + cv_ref[...]
    mhat = mm / bc1_ref[...]
    vhat = vv / bc2_ref[...]
    w_buf[...] = w_buf[...] - lr * mhat / (jnp.sqrt(vhat) + eps)
    m_buf[...] = mm
    v_buf[...] = vv
    for k, (buf, out) in enumerate(((w_buf, w_out), (m_buf, m_out),
                                    (v_buf, v_out))):
        _copy_rows(n, rows_ref, base, buf, out, sem.at[k], gather=False)


def sparse_adam_pallas(w: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray,
                       rows: jnp.ndarray, cm: jnp.ndarray, cv: jnp.ndarray,
                       bc1: jnp.ndarray, bc2: jnp.ndarray, *,
                       beta1: float, beta2: float, lr: float, eps: float,
                       interpret: bool = True):
    """Full tables in, full tables out; only ``rows`` change.

    w/m/v: (N, D) f32; rows: (R,) unique int32; cm/cv: (R, D) f32 host-
    precomputed ``(1-beta)*g`` terms; bc1/bc2: (R, D) f32 bias corrections
    (row-broadcast by the caller).  Returns (w', m', v').
    """
    n, d = w.shape
    r = rows.shape[0]
    rp = -(-r // ID_BLOCK) * ID_BLOCK
    dp = -(-d // LANES) * LANES
    rows = jnp.pad(jnp.asarray(rows, jnp.int32), (0, rp - r))
    w, m, v = (jnp.pad(x, ((0, 0), (0, dp - d))) for x in (w, m, v))
    cm, cv, bc1, bc2 = (jnp.pad(x, ((0, rp - r), (0, dp - d)),
                                constant_values=1)
                        for x in (cm, cv, bc1, bc2))
    grid = (rp // ID_BLOCK, ID_BLOCK // RB)
    ids = pl.BlockSpec((ID_BLOCK,), lambda i, j: (i,),
                       memory_space=pltpu.SMEM)
    table = pl.BlockSpec(memory_space=pl.ANY)
    tile = pl.BlockSpec((RB, dp),
                        lambda i, j: (i * (ID_BLOCK // RB) + j, 0))
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"))

    pm, pv = pl.pallas_call(
        functools.partial(_products_kernel, beta1=beta1, beta2=beta2,
                          num_rows=r),
        grid=grid, in_specs=[ids, table, table], out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((rp, dp), w.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((RB, dp), w.dtype)] * 2
        + [pltpu.SemaphoreType.DMA((2,))],
        compiler_params=params, interpret=interpret,
        name="sparse_adam_products",
    )(rows, m, v)

    # aliased scatter: inputs w/m/v (operand indices 1..3 — the row ids
    # are operand 0) become the outputs, so untouched rows pass through
    # bit-exactly without ever being read
    w2, m2, v2 = pl.pallas_call(
        functools.partial(_update_kernel, lr=lr, eps=eps, num_rows=r),
        grid=grid, in_specs=[ids] + [table] * 3 + [tile] * 6,
        out_specs=[table] * 3,
        out_shape=[jax.ShapeDtypeStruct((n, dp), w.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((RB, dp), w.dtype)] * 3
        + [pltpu.SemaphoreType.DMA((3,))],
        input_output_aliases={1: 0, 2: 1, 3: 2},
        compiler_params=params, interpret=interpret,
        name="sparse_adam_update",
    )(rows, w, m, v, pm, pv, cm, cv, bc1, bc2)
    return w2[:, :d], m2[:, :d], v2[:, :d]
