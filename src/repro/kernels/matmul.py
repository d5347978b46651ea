"""Tiled matmul Pallas kernel — the TRA kernel function K for contraction
nodes (the paper's MKL batch-GEMM, re-tiled for MXU/VMEM; DESIGN.md §2,
adaptation 5).

grid = (m_blocks, n_blocks, k_blocks) with the contraction (k) innermost and
sequential; the (blk_m, blk_n) f32 accumulator lives in VMEM scratch and the
output block is written once on the final k step.  Default tiles 128x128x128:
every matmul dim is MXU-aligned and the working set
(blk_m*blk_k + blk_k*blk_n + blk_m*blk_n floats) is ~192 KiB << VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import fit, mxu_precision, pad_to


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32),
        w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        precision=mxu_precision(x_ref.dtype, w_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(
    x: jnp.ndarray,  # (m, k)
    w: jnp.ndarray,  # (k, n)
    *,
    blk_m: int = 128,
    blk_n: int = 128,
    blk_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    m, k = x.shape
    k2, n = w.shape
    if k != k2:
        raise ValueError(f"matmul: contraction dims differ, {k} vs {k2}")
    blk_m, mp = fit(m, blk_m)
    blk_n, np_ = fit(n, blk_n)
    blk_k, kp = fit(k, blk_k)
    x = pad_to(pad_to(x, 0, mp), 1, kp)
    w = pad_to(pad_to(w, 0, kp), 1, np_)

    out = pl.pallas_call(
        _mm_kernel,
        grid=(mp // blk_m, np_ // blk_n, kp // blk_k),
        in_specs=[
            pl.BlockSpec((blk_m, blk_k), lambda im, jn, ik: (im, ik)),
            pl.BlockSpec((blk_k, blk_n), lambda im, jn, ik: (ik, jn)),
        ],
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda im, jn, ik: (im, jn)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((blk_m, blk_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:m, :n]
