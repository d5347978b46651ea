"""The plain reference computes what the program computes (float32, tiny
sizes, on the CPU), and the lower-precision control moves its logits far
past where the program's lie."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights
from chipbench.reference import hymba
from chipbench.tests.tiny import HYMBA


def _cfg(m):
    from repro.configs.base import ModelConfig

    return ModelConfig(**{**m, "block_pattern": tuple(m["block_pattern"])})


def test_hymba_logits_match_the_program_forward():
    from repro.models import transformer as tf

    params = weights.make(hymba.param_layout(HYMBA), 123, jnp.float32)
    toks = np.random.default_rng(0).integers(0, 500, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = tf.forward(params, toks, _cfg(HYMBA), remat=False)[0]
    want = hymba.logits(params, toks, HYMBA)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got[..., :500] - want))) < 1e-4 * scale
    control = hymba.logits(params, toks, HYMBA, "fp8")
    assert float(jnp.max(jnp.abs(control - want))) > 1e-2 * scale


@pytest.mark.parametrize("precision", ["float32", "fp8"])
def test_precision_products_keep_float32_shapes(precision):
    from chipbench.reference.precision import dot

    a = jnp.ones((3, 4), jnp.bfloat16)
    b = jnp.ones((4, 5), jnp.float32)
    out = dot(precision)("ij,jk->ik", a, b)
    assert out.dtype == jnp.float32 and out.shape == (3, 5)
    assert np.allclose(np.asarray(out), 4.0)
