"""Block sizing, exact zero-padding and dot precision shared by the
Pallas kernels.

On the TPU the last two dims of every block must be multiples of (8, 128)
or span the whole array dim.  Each kernel entry therefore takes
``blk = min(blk, dim)`` — a whole-dim block when the dim is small — and
otherwise pads the dim up to a multiple of ``blk``.  Padding is exact: a
zero-padded contraction dim adds zero products, padded output rows and
columns are sliced off, and the attention kernels mask padded keys.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fit(dim: int, blk: int) -> tuple[int, int]:
    """``(block, padded dim)`` for one array dim and a requested block."""
    blk = min(blk, dim)
    return blk, -(-dim // blk) * blk


def pad_to(x: jnp.ndarray, axis: int, size: int, value=0) -> jnp.ndarray:
    """``x`` padded at the end of ``axis`` up to ``size`` with ``value``."""
    extra = size - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths, constant_values=value)


def mxu_precision(*dtypes):
    """Dot precision that computes float32 operands in float32.

    A float32 dot at default precision takes one bfloat16 pass on the TPU's
    MXU (about 2**-9 relative error per operand), which silently lowers a
    program declared in float32.  Float32 operands therefore get
    ``HIGHEST``; other dtypes keep the default (exact bfloat16 products
    with float32 accumulation).
    """
    if dtypes and all(jnp.dtype(d) == jnp.float32 for d in dtypes):
        return jax.lax.Precision.HIGHEST
    return None
