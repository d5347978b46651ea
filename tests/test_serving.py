"""Serving tier: paged KV OpDef, block allocator, shape buckets, the
continuous-batching engine, and the serve.py cache-preparation edge cases.

The engine's headline contract — continuous batching produces generations
bit-for-bit identical to sequential per-request ``serve()`` — is asserted
here on a small mixed-length workload; benchmarks/bench_serve.py runs the
full three-family version under 8 forced host devices.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels import ops
from repro.launch.serve import _ring_pack, prepare_decode_caches, serve
from repro.models import transformer as tf
from repro.models.attention import KVCache
from repro.serving import (BlockAllocator, BucketRegistry, ServingEngine,
                           bucket_len, pad_free)

# ---------------------------------------------------------------------------
# kv_block_gather: the paged-KV OpDef's dense semantics
# ---------------------------------------------------------------------------


def test_kv_block_gather_matches_manual_lookup():
    rng = np.random.default_rng(0)
    n, p, k, d = 7, 4, 2, 3
    pool = rng.normal(size=(n, p, k, d)).astype(np.float32)
    tables = np.array([[1, 3, 0], [6, 2, 5]], np.int32)   # (b=2, w=3)
    kv_len = 10                                           # truncates w*p=12
    out = np.asarray(ops.kv_block_gather(pool, tables, kv_len))
    assert out.shape == (2, k, kv_len, d)
    for b in range(2):
        rows = np.concatenate([pool[tables[b, j]] for j in range(3)], axis=0)
        want = rows[:kv_len].transpose(1, 0, 2)           # (k, t, d)
        np.testing.assert_array_equal(out[b], want)


def test_kv_block_gather_rejects_overlong_kv_len():
    pool = np.zeros((3, 2, 1, 1), np.float32)
    tables = np.zeros((1, 2), np.int32)
    with pytest.raises(ValueError):
        ops.kv_block_gather(pool, tables, kv_len=5)       # > w*p = 4


def test_kv_block_gather_opdef_checks():
    from repro.core import opdef

    opdef.check_impl("kv_block_gather")
    od = opdef.get("kv_block_gather")
    assert od is not None and od.shard_rule == "paged"


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------


def test_block_allocator_reserves_scratch_and_recycles():
    al = BlockAllocator(n_blocks=5, block=8)              # blocks 1..4 free
    assert al.n_free == 4
    a = al.alloc(3)
    assert a == [1, 2, 3] and 0 not in a
    assert al.alloc(2) is None                            # all-or-nothing
    assert al.n_free == 1                                 # failed alloc kept
    al.release(a)
    assert al.n_free == 4
    with pytest.raises(ValueError):
        al.release([1])                                   # double free
    with pytest.raises(ValueError):
        al.release([0])                                   # scratch is not
    assert al.blocks_for(17) == 3                         #   allocatable


# ---------------------------------------------------------------------------
# bucket policy
# ---------------------------------------------------------------------------


def test_bucket_policy_pow2_only_when_pad_free():
    llama = reduced(get_config("llama-7b"))
    xlstm = reduced(get_config("xlstm-125m"))
    moe = reduced(get_config("mixtral-8x7b"))
    assert pad_free(llama) and not pad_free(xlstm) and not pad_free(moe)
    assert bucket_len(llama, 13) == 16                    # pow2 rounding
    assert bucket_len(llama, 16) == 16
    assert bucket_len(llama, 3) == 8                      # min bucket
    assert bucket_len(xlstm, 13) == 13                    # recurrent: exact
    assert bucket_len(moe, 13) == 13                      # capacity: exact
    assert bucket_len(llama, 13, mode="exact") == 13
    assert bucket_len(xlstm, 13, mode="pow2") == 16       # explicit override


def test_bucket_registry_warm_after_first_touch():
    from repro.core.plancache import PlanCache

    cfg = reduced(get_config("llama-7b"))
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    pc = PlanCache()
    reg = BucketRegistry(cfg, mesh, plan_cache=pc)
    e1 = reg.prefill(13)
    e2 = reg.prefill(14)                                  # same pow2 bucket
    assert e1 is e2 and e1.hits == 1
    assert reg.stats.compiles == 1 and reg.stats.lookups == 2
    assert e1.key[2] == 16 and e1.canonical_key
    # a second registry on the same plan cache skips the DP (warm hit)
    reg2 = BucketRegistry(cfg, mesh, plan_cache=pc)
    e3 = reg2.prefill(13)
    assert reg2.stats.plan_cache_hits == 1
    assert e3.canonical_key == e1.canonical_key


# ---------------------------------------------------------------------------
# serve.py cache preparation edge cases (_ring_pack / prepare_decode_caches)
# ---------------------------------------------------------------------------


def _fake_kv(L, b, s, kh, hd):
    k = np.arange(L * b * s * kh * hd, dtype=np.float32).reshape(
        L, b, s, kh, hd)
    return KVCache(jnp.asarray(k), jnp.asarray(k + 0.5))


def test_ring_pack_prompt_shorter_than_window():
    kv = _fake_kv(1, 1, 3, 1, 1)                          # prompt_len 3
    out = _ring_pack(kv, prompt_len=3, window=5)
    k = np.asarray(out.k)
    assert k.shape == (1, 1, 5, 1, 1)
    # slots 0..2 hold the prompt rows in order, the rest stay zero
    np.testing.assert_array_equal(k[0, 0, :3, 0, 0], [0, 1, 2])
    np.testing.assert_array_equal(k[0, 0, 3:, 0, 0], [0, 0])


def test_ring_pack_prompt_exactly_window():
    kv = _fake_kv(1, 1, 4, 1, 1)
    out = _ring_pack(kv, prompt_len=4, window=4)
    # (prompt_len - take + arange) % window == arange: identity layout
    np.testing.assert_array_equal(np.asarray(out.k)[0, 0, :, 0, 0],
                                  [0, 1, 2, 3])


def test_ring_pack_prompt_longer_than_window_wraps():
    kv = _fake_kv(1, 1, 6, 1, 1)                          # rows 0..5
    out = _ring_pack(kv, prompt_len=6, window=4)
    # last 4 rows (2,3,4,5) at slots (6-4+i) % 4 = (2,3,0,1)
    np.testing.assert_array_equal(np.asarray(out.k)[0, 0, :, 0, 0],
                                  [4, 5, 2, 3])


def test_prepare_decode_caches_pads_dense_path():
    cfg = reduced(get_config("llama-7b"))                 # no window
    kv = _fake_kv(1, 2, 3, 1, 1)
    out = prepare_decode_caches(cfg, [kv], prompt_len=3, kv_len=7)
    k = np.asarray(out[0].k)
    assert k.shape == (1, 2, 7, 1, 1)
    np.testing.assert_array_equal(k[:, :, :3], np.asarray(kv.k))
    assert (k[:, :, 3:] == 0).all()                       # zero tail


def test_prepare_decode_caches_hymba_tuple_keeps_state():
    cfg = reduced(get_config("hymba-1.5b"))               # windowed hybrid
    kv = _fake_kv(1, 1, 3, 1, 1)
    st = {"s": jnp.ones((1, 1, 4))}                       # opaque state tree
    out = prepare_decode_caches(cfg, [(kv, st)], prompt_len=3,
                                kv_len=cfg.window)
    kv2, st2 = out[0]
    assert np.asarray(kv2.k).shape[2] == cfg.window       # ring-packed
    assert st2 is st                                      # state untouched


# ---------------------------------------------------------------------------
# bucketed prefill: logit_index == last_logit_only on the real token
# ---------------------------------------------------------------------------


def test_forward_logit_index_matches_exact_prefill_bitwise():
    cfg = reduced(get_config("llama-7b"))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    plen, bucket = 13, 16
    toks = rng.integers(0, cfg.vocab, size=(2, plen)).astype(np.int32)
    padded = np.zeros((2, bucket), np.int32)
    padded[:, :plen] = toks

    exact, caches_e, _ = tf.forward(params, jnp.asarray(toks), cfg,
                                    collect_cache=True, remat=False,
                                    last_logit_only=True)
    buck, caches_b, _ = tf.forward(params, jnp.asarray(padded), cfg,
                                   collect_cache=True, remat=False,
                                   logit_index=jnp.int32(plen - 1))
    np.testing.assert_array_equal(np.asarray(exact), np.asarray(buck))
    # the real-token cache rows are bitwise too (pad rows are masked junk)
    k_e = np.asarray(caches_e[0][0])
    k_b = np.asarray(caches_b[0][0])
    np.testing.assert_array_equal(k_e, k_b[:, :, :plen])


# ---------------------------------------------------------------------------
# paged decode step: the stacked caches updated in place == layer by layer
# ---------------------------------------------------------------------------


def _paged_attention_one_layer(p, x, pool, tables, pos, cfg):
    """One layer's paged attention on its own (n, block, k, d) pool."""
    from repro.models.attention import _decode_attend, _project_qkv

    blk, W = pool.k.shape[1], tables.shape[1]
    q, k_new, v_new = _project_qkv(p, x, cfg, pos[:, None])
    blk_ids = jnp.take_along_axis(tables, (pos // blk)[:, None], axis=1)[:, 0]
    off = pos % blk
    k_pool = pool.k.at[blk_ids, off].set(k_new[:, 0])
    v_pool = pool.v.at[blk_ids, off].set(v_new[:, 0])
    kh = ops.kv_block_gather(k_pool, tables, W * blk)
    vh = ops.kv_block_gather(v_pool, tables, W * blk)
    idx = jnp.arange(W * blk)
    valid = idx[None, :] <= pos[:, None]
    if cfg.window:
        valid &= idx[None, :] > (pos[:, None] - cfg.window)
    o = _decode_attend(q.transpose(0, 2, 1, 3), kh, vh, valid, cfg)
    out = jnp.einsum("bshd,hda->bsa", o.transpose(0, 2, 1, 3), p["wo"])
    return out, type(pool)(k_pool, v_pool)


def _decode_step_paged_layer_by_layer(params, tokens, caches, tables, pos,
                                      cfg):
    """The paged decode step as a plain loop over layers: each layer's
    cache is sliced out of the stack, updated, and the updated layers are
    stacked again."""
    from repro.models import ffn as ffn_mod
    from repro.models import moe as moe_mod
    from repro.models import ssm as ssm_mod
    from repro.models import xlstm as xlstm_mod
    from repro.models.common import dtype_of, embed, lm_logits, rmsnorm

    def layer(tree, l):
        return jax.tree.map(lambda a: a[l], tree)

    x = embed(params["embed"], tokens).astype(dtype_of(cfg))
    units = cfg.n_layers // len(cfg.block_pattern)
    new = [[] for _ in cfg.block_pattern]
    for l in range(units):
        for i, blk in enumerate(cfg.block_pattern):
            p, c = layer(params["layers"][i], l), layer(caches[i], l)
            h = rmsnorm(x, p["norm1"], cfg.norm_eps)
            if blk in ("attn", "hymba"):
                pool = c if blk == "attn" else c[0]
                a_out, pool2 = _paged_attention_one_layer(
                    p["attn"], h, pool, tables, pos, cfg)
            if blk == "attn":
                x = x + a_out
                h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
                x = x + (moe_mod.moe_ffn(p["moe"], h2, cfg)[0] if cfg.moe
                         else ffn_mod.ffn(p["ffn"], h2, cfg))
                c2 = pool2
            elif blk == "hymba":
                s_out, st2 = ssm_mod.ssm_decode(p["ssm"], h, c[1], cfg)
                x = x + 0.5 * (rmsnorm(a_out, p["norm_a"], cfg.norm_eps)
                               + rmsnorm(s_out, p["norm_s"], cfg.norm_eps))
                h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
                x = x + ffn_mod.ffn(p["ffn"], h2, cfg)
                c2 = (pool2, st2)
            else:
                decode = (xlstm_mod.mlstm_decode if blk == "mlstm"
                          else xlstm_mod.slstm_decode)
                out, c2 = decode(p[blk], h, c, cfg)
                x = x + out
            new[i].append(c2)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    logits = lm_logits(x, params["embed"].T if head is None else head)
    return logits, [jax.tree.map(lambda *ls: jnp.stack(ls), *n) for n in new]


@pytest.mark.parametrize("arch", ["llama-7b", "hymba-1.5b", "xlstm-125m",
                                  "mixtral-8x7b"],
                         ids=["attn", "hymba", "mlstm+slstm", "attn-moe"])
def test_paged_decode_step_matches_layer_by_layer_bitwise(arch):
    """Several decode steps with the stacked caches carried through the
    layer scan and updated in place give the logits and every cache leaf,
    bit for bit, of the layer-by-layer step.  Slots sit at distinct
    positions, slot 3 idles on scratch block 0, and slot 0 crosses from
    its first block into its second."""
    cfg = reduced(get_config(arch))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    b, blk, W = 4, 4, 3
    caches = tf.init_paged_caches(cfg, b, 1 + b * W, blk)
    leaves, tree = jax.tree.flatten(caches)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    caches = jax.tree.unflatten(tree, [            # every row holds data
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    tables = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [0, 0, 0]],
                       jnp.int32)
    pos = jnp.array([2, 6, 9, 0], jnp.int32)
    advance = jnp.array([1, 1, 1, 0], jnp.int32)
    tokens = jnp.array([[5], [7], [11], [0]], jnp.int32)

    step = jax.jit(lambda *a: tf.decode_step_paged(*a, cfg))
    ref_step = jax.jit(lambda *a: _decode_step_paged_layer_by_layer(*a, cfg))
    ref_caches = caches
    for _ in range(3):
        logits, caches = step(params, tokens, caches, tables, pos)
        ref_logits, ref_caches = ref_step(params, tokens, ref_caches, tables,
                                          pos)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        for got, want in zip(jax.tree.leaves(caches),
                             jax.tree.leaves(ref_caches)):
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        tokens = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        pos = pos + advance
    assert int(pos[0]) == 5                       # 2, 3 | 4, 5: a new block


# ---------------------------------------------------------------------------
# the engine: continuous batching == sequential serve(), bit for bit
# ---------------------------------------------------------------------------


def test_engine_matches_sequential_serve_bitwise():
    cfg = reduced(get_config("llama-7b"))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=(L,)).astype(np.int32)
               for L in (5, 9, 12)]
    max_new = 4

    eng = ServingEngine(cfg, batch=2, max_seq=24, block=8, params=params)
    rids = [eng.submit(p, max_new) for p in prompts]
    results, metrics = eng.run()
    assert metrics.prefills == 3
    assert metrics.tokens_generated == 3 * max_new
    assert len(metrics.ttft_s) == 3
    assert metrics.logits_finite and metrics.summary()["logits_finite"]

    for rid, p in zip(rids, prompts):
        gen, _ = serve(cfg, p[None, :], max_new=max_new, params=params,
                       kv_len=eng.seq, mesh=eng.mesh)
        np.testing.assert_array_equal(results[rid], gen[0])


def test_engine_stamps_queue_wait_before_first_token():
    """One slot, three requests: each waits in the queue for the one before
    it, and its queue wait (submit -> admission started) lies inside its
    time to first token."""
    cfg = reduced(get_config("hymba-1.5b"))
    eng = ServingEngine(cfg, batch=1, max_seq=24, block=8,
                        params=tf.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab, size=(L,)), 3)
            for L in (5, 9, 7)]
    _, metrics = eng.run()
    assert sorted(metrics.queue_s) == sorted(metrics.ttft_s) == rids
    for rid in rids:
        assert 0.0 <= metrics.queue_s[rid] <= metrics.ttft_s[rid]
    waits = [metrics.queue_s[rid] for rid in rids]
    assert waits == sorted(waits) and waits[0] < waits[1]
    assert metrics.summary()["logits_finite"]


def test_engine_rejects_oversized_request_and_detects_deadlock():
    cfg = reduced(get_config("llama-7b"))
    eng = ServingEngine(cfg, batch=2, max_seq=16, block=8,
                        params=tf.init_params(cfg, jax.random.PRNGKey(0)))
    with pytest.raises(ValueError):
        eng.submit(np.zeros(20, np.int32), 8)             # > max_seq

    tiny = ServingEngine(cfg, batch=1, max_seq=24, block=8, n_blocks=2,
                         params=tf.init_params(cfg, jax.random.PRNGKey(0)))
    tiny.submit(np.zeros(12, np.int32), 8)                # needs 3 blocks,
    with pytest.raises(RuntimeError):                     # pool has 1
        tiny.run()
