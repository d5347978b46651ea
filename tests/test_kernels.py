"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul import matmul
from repro.kernels.moe_gmm import gmm

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


ATT_CASES = [
    # (b, hq, hkv, sq, sk, d, causal, window, dtype)
    (1, 4, 2, 128, 128, 64, True, 0, jnp.float32),
    (2, 2, 1, 256, 256, 32, True, 64, jnp.float32),
    (1, 2, 2, 128, 256, 64, False, 0, jnp.float32),
    (1, 8, 1, 128, 128, 128, True, 0, jnp.float32),
    (1, 4, 4, 128, 128, 64, True, 0, jnp.bfloat16),
    (2, 4, 2, 64, 64, 16, True, 32, jnp.float32),
    # lengths that are not a block multiple: padded inside the kernel entry
    (1, 5, 1, 200, 200, 64, True, 0, jnp.float32),
    (1, 5, 1, 200, 200, 64, True, 64, jnp.float32),
    (1, 5, 1, 72, 200, 64, False, 0, jnp.float32),
    (1, 5, 1, 100, 100, 64, True, 0, jnp.float32),
]


@pytest.mark.parametrize("case", ATT_CASES)
def test_flash_attention_vs_oracle(case):
    b, hq, hkv, sq, sk, d, causal, win, dt = case
    q = _rand((b, hq, sq, d), dt)
    k = _rand((b, hkv, sk, d), dt)
    v = _rand((b, hkv, sk, d), dt)
    qoff = sk - sq if causal else 0
    out = flash_attention(q, k, v, causal=causal, window=win, q_offset=qoff,
                          blk_q=64, blk_k=64, interpret=True)
    want = ref.attention(q, k, v, causal=causal, window=win, q_offset=qoff)
    tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
    assert out.dtype == dt
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_shape_invariance():
    q = _rand((1, 2, 256, 64), jnp.float32)
    k = _rand((1, 2, 256, 64), jnp.float32)
    v = _rand((1, 2, 256, 64), jnp.float32)
    outs = [flash_attention(q, k, v, blk_q=bq, blk_k=bk, interpret=True)
            for bq, bk in ((64, 64), (128, 128), (256, 64), (64, 256))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,k,n,dt", [
    (128, 128, 128, jnp.float32),
    (256, 384, 128, jnp.float32),
    (128, 256, 512, jnp.bfloat16),
    (64, 64, 64, jnp.float32),
    (200, 1600, 200, jnp.float32),   # ragged: every dim padded to 128s
])
def test_matmul_vs_oracle(m, k, n, dt):
    x = _rand((m, k), dt)
    w = _rand((k, n), dt)
    out = matmul(x, w, interpret=True)
    want = ref.matmul(x, w)
    tol = 3e-2 if dt == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol * 8)


@pytest.mark.parametrize("e,c,k,n,dt", [
    (4, 128, 256, 128, jnp.float32),
    (8, 128, 128, 384, jnp.float32),
    (2, 256, 128, 128, jnp.bfloat16),
    (2, 200, 160, 300, jnp.float32),  # ragged capacity, k and n
])
def test_gmm_vs_oracle(e, c, k, n, dt):
    x = _rand((e, c, k), dt)
    w = _rand((e, k, n), dt)
    out = gmm(x, w, interpret=True)
    want = ref.gmm(x, w)
    tol = 3e-2 if dt == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol * 8)


def test_attention_oracle_decode_consistency():
    """Prefill oracle vs step-by-step decode with q_offset must agree."""
    b, h, s, d = 1, 2, 16, 32
    q = _rand((b, h, s, d), jnp.float32)
    k = _rand((b, h, s, d), jnp.float32)
    v = _rand((b, h, s, d), jnp.float32)
    full = ref.attention(q, k, v, causal=True)
    for t in (0, 5, 15):
        one = ref.attention(q[:, :, t:t + 1], k[:, :, :s], v[:, :, :s],
                            causal=True, q_offset=t)
        np.testing.assert_allclose(np.asarray(one[:, :, 0]),
                                   np.asarray(full[:, :, t]),
                                   rtol=1e-5, atol=1e-5)
