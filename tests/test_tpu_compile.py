"""The Pallas kernels compile for a TPU v5e at the widths the main path uses,
and the paged decode step compiles there without copying its KV pool.

Nothing runs here: each kernel is lowered and compiled for a described
(not attached) v5e chip, with ``interpret=False``, and the compiled program
must hold the Mosaic kernel (``tpu_custom_call``).  This catches what
interpret mode cannot — block shapes the TPU refuses, unaligned slices,
too much VMEM — without a chip.  The decode step's compiled text is read
for the copies the TPU compiler puts around the pool.

The topology is described inside a module-scoped fixture (never at import,
in ``parametrize`` or in ``skipif``), so every xdist worker collects the
same tests and only the worker that runs this file loads the TPU compiler.
Keep every such compile in this one file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

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


_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                   r"([a-z][a-z\-]*)\(([^)]*)\)")


def _paged_decode_compiled(one_chip, n_layers):
    """The jitted paged decode step of hymba-1.5b at its widths, 64 slots,
    ``max_seq`` 1536, block 16, caches donated, compiled for the chip."""
    from repro.configs import get_config
    from repro.launch import steps
    from repro.models import transformer as tf

    cfg = dataclasses.replace(get_config("hymba-1.5b"), n_layers=n_layers)
    b, block, W = 64, 16, 1536 // 16
    n_blocks = 1 + b * W

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    mesh = Mesh(np.array(list(one_chip.device_set)), ("x",))
    step = jax.jit(steps.make_paged_serve_step(cfg, mesh=mesh),
                   donate_argnums=(2,))
    params = sds(tf.init_params(cfg, abstract=True))
    caches = sds(tf.init_paged_caches(cfg, b, n_blocks, block, abstract=True))
    compiled = step.lower(
        params, sds(jax.ShapeDtypeStruct((b, 1), jnp.int32)), caches,
        sds(jax.ShapeDtypeStruct((b, W), jnp.int32)),
        sds(jax.ShapeDtypeStruct((b,), jnp.int32))).compile()
    n_params = len(jax.tree.leaves(params))
    cache_args = set(range(n_params + 1,
                           n_params + 1 + len(jax.tree.leaves(caches))))
    return compiled, (n_blocks, block, 5, 64), cache_args


def test_paged_decode_step_updates_the_pool_in_place(one_chip):
    """The stacked pool is the layer scan's carry and is written in place:
    no instruction copies or slices the stack, each write into it is one
    slot's row, the only layer-sized reads are the gather's (the TPU keeps
    the pool blocks-minor, its gather wants blocks-major), the donated
    caches alias the outputs, and the temporaries hold less than the
    stacked pools and do not grow with the layers."""
    temps = {}
    for n_layers in (2, 4):
        compiled, pool, cache_args = _paged_decode_compiled(one_chip,
                                                            n_layers)
        txt = compiled.as_text()
        insts = {m.group(1): m for m in map(_INST.match, txt.splitlines())
                 if m}
        stack = (n_layers, *pool)
        for name, m in insts.items():
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            op = m.group(4)
            line = m.string
            if dims == stack and op in ("copy", "dynamic-slice", "fusion",
                                        "dynamic-update-slice"):
                assert op == "dynamic-update-slice", line[:200]
                update = insts[m.group(5).split(",")[1].strip().lstrip("%")]
                assert update.group(3) == "1,1,1,5,64", line[:200]
            if dims in (pool, (1, *pool)) and op not in (
                    "parameter", "bitcast", "get-tuple-element"):
                assert "/kv_gather/" in line, line[:200]
        aliased = {int(a) for a in re.findall(
            r"\{\d+\}: \((\d+), \{\}, may-alias\)", txt.splitlines()[0])}
        assert cache_args <= aliased
        temps[n_layers] = compiled.memory_analysis().temp_size_in_bytes
    layer_pool = int(np.prod(pool)) * 2                   # bf16, 62.9 MB
    assert temps[4] < 2 * 4 * layer_pool                  # both stacks
    assert temps[4] - temps[2] < layer_pool
