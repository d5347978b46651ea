"""Grouped (expert) matmul Pallas kernel for MoE FFNs.

Operates on capacity-padded dispatch buffers (GShard layout):
    x (e, c, k) @ w (e, k, n) -> (e, c, n)
grid = (experts, c_blocks, n_blocks, k_blocks), contraction innermost with a
VMEM f32 accumulator.  The expert dim is fully parallel — exactly the label
the EinDecomp plan assigns a mesh axis to for expert parallelism (the
per-device call then sees its local expert slice).

Block sizes (128, 128, 128) keep all tiles MXU-aligned (capacity, k and n
are zero-padded to a block multiple and the output sliced back); the expert index
only selects blocks, so one expert's weight tile is fetched HBM->VMEM per
(c_block, n_block, k_block) visit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import fit, mxu_precision, pad_to


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        precision=mxu_precision(x_ref.dtype, w_ref.dtype),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _fin():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def gmm(
    x: jnp.ndarray,  # (e, c, k)
    w: jnp.ndarray,  # (e, k, n)
    *,
    blk_c: int = 128,
    blk_n: int = 128,
    blk_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    e, c, k = x.shape
    e2, k2, n = w.shape
    if (e, k) != (e2, k2):
        raise ValueError(f"gmm: x {x.shape} and w {w.shape} disagree on "
                         "experts or contraction dim")
    blk_c, cp = fit(c, blk_c)
    blk_n, np_ = fit(n, blk_n)
    blk_k, kp = fit(k, blk_k)
    x = pad_to(pad_to(x, 1, cp), 2, kp)
    w = pad_to(pad_to(w, 1, kp), 2, np_)

    out = pl.pallas_call(
        _gmm_kernel,
        grid=(e, cp // blk_c, np_ // blk_n, kp // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_c, blk_k), lambda ie, ic, jn, ik: (ie, ic, ik)),
            pl.BlockSpec((1, blk_k, blk_n), lambda ie, ic, jn, ik: (ie, ik, jn)),
        ],
        out_specs=pl.BlockSpec((1, blk_c, blk_n),
                               lambda ie, ic, jn, ik: (ie, ic, jn)),
        out_shape=jax.ShapeDtypeStruct((e, cp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((blk_c, blk_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
    return out[:, :c, :n]
