"""The Pallas kernels compile for a TPU v5e at the widths the main path uses.

Nothing runs here: each kernel is lowered and compiled for a described
(not attached) v5e chip, with ``interpret=False``, and the compiled program
must hold the Mosaic kernel (``tpu_custom_call``).  This catches what
interpret mode cannot — block shapes the TPU refuses, unaligned slices,
too much VMEM — without a chip.

The topology is described inside a module-scoped fixture (never at import,
in ``parametrize`` or in ``skipif``), so every xdist worker collects the
same tests and only the worker that runs this file loads the TPU compiler.
Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention, flash_attention_step
from repro.kernels.matmul import matmul
from repro.kernels.moe_gmm import gmm


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read the persistent compile cache back, so
    # keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.mark.parametrize("hq,hkv,d,sq,window", [
    (25, 5, 64, 2048, 1024),     # hymba-1.5b: GQA 5:1, head_dim 64, SWA
    (32, 32, 128, 2048, 0),      # llama-7b
    (25, 5, 64, 200, 1024),      # hymba prompt of 200 tokens (padded)
    (25, 5, 64, 100, 1024),      # shorter than one block (whole-dim block)
], ids=["hymba", "llama", "hymba-sq200", "hymba-sq100"])
def test_flash_attention_compiles(one_chip, hq, hkv, d, sq, window):
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=False)

    txt = _compile_text(fn, [((1, hq, sq, d), BF16), ((1, hkv, sq, d), BF16),
                             ((1, hkv, sq, d), BF16)], one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("hq,hkv,sq,sk", [
    (32, 8, 1024, 1024),         # llama-width ring step, 4-way ring of 4096
    (32, 32, 2048, 2048),        # 2-way ring of 4096, MHA
    (32, 8, 200, 200),           # ragged shard (padded)
])
def test_flash_attention_step_compiles(one_chip, hq, hkv, sq, sk):
    def fn(q, k, v, q_off, kv_off):
        carry = flash_attention_step(q, k, v, None, q_offset=q_off,
                                     kv_offset=kv_off, interpret=False)
        return flash_attention_step(q, k, v, carry, q_offset=q_off,
                                    kv_offset=kv_off + sk, interpret=False)

    txt = _compile_text(fn, [((1, hq, sq, 128), F32), ((1, hkv, sk, 128), F32),
                             ((1, hkv, sk, 128), F32), ((), jnp.int32),
                             ((), jnp.int32)], one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("m,k,n", [
    (2048, 1600, 5504),          # hymba d_model 1600 -> d_ff
    (2048, 1376, 1600),          # d_ff shard 5504 / 4 back to d_model
    (2048, 4096, 11008),         # llama-7b up-projection
    (1, 1600, 4096),             # a single-token row
])
def test_matmul_compiles(one_chip, m, k, n):
    txt = _compile_text(lambda x, w: matmul(x, w, interpret=False),
                        [((m, k), F32), ((k, n), F32)], one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("e,c,k,n", [
    (8, 640, 4096, 14336),       # mixtral: capacity 640 (5 blocks)
    (2, 200, 4096, 1792),        # local experts, ragged capacity and n
])
def test_gmm_compiles(one_chip, e, c, k, n):
    txt = _compile_text(lambda x, w: gmm(x, w, interpret=False),
                        [((e, c, k), BF16), ((e, k, n), BF16)], one_chip)
    assert "tpu_custom_call" in txt
