"""Matrix products for the references and their lower-precision control.

``dot("float32")`` is the reference's own product: float32 operands at
``HIGHEST`` precision, so a TPU does not drop to one bfloat16 pass.

``dot("fp8")`` is the control for a bfloat16 configuration: the reference
one precision below the configuration's, standing in the program's place
to show that the comparison which decides ``correct`` would catch that
step.  Both operands of every product are rounded to float8 e4m3 with one
scale per tensor (its largest magnitude maps to 448), as a later change
that computed in fp8 would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

E4M3_MAX = 448.0
HIGHEST = lax.Precision.HIGHEST


def fp8_round(x: jax.Array) -> jax.Array:
    """``x`` rounded through float8 e4m3 with a per-tensor scale."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def dot(precision: str = "float32"):
    """``f(spec, a, b)``: ``einsum`` in float32 under the named precision."""
    if precision not in ("float32", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")

    def f(spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if precision == "fp8":
            a, b = fp8_round(a), fp8_round(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    return f
