"""GQA / MQA / sliding-window attention with KV caching.

Three call modes:
  * full-sequence (train / prefill): fused flash attention (Pallas on TPU,
    jnp oracle elsewhere) over the whole (possibly windowed, causal) span.
  * decode: one query token against a KV cache buffer; sliding-window archs
    keep a ring buffer of size `window` so 500k-token decode is O(window).

Parameter layout keeps heads (h) and head_dim (d) as separate tensor dims —
these are exactly the EinSum labels EinDecomp assigns mesh axes to (the
multi-head-attention EinGraph of paper §3).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ops
from repro.models.common import ParamFactory, apply_rope


def init_attention(pf: ParamFactory, cfg) -> dict:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": pf.dense(D, H, hd),
        "wk": pf.dense(D, K, hd),
        "wv": pf.dense(D, K, hd),
        "wo": pf.dense(H, hd, D, scale=(H * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = pf.zeros(H, hd)
        p["bk"] = pf.zeros(K, hd)
        p["bv"] = pf.zeros(K, hd)
    return p


def _project_qkv(p: dict, x: jnp.ndarray, cfg, positions: jnp.ndarray):
    q = jnp.einsum("bsa,ahd->bshd", x, p["wq"])
    k = jnp.einsum("bsa,akd->bskd", x, p["wk"])
    v = jnp.einsum("bsa,akd->bskd", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_full(p: dict, x: jnp.ndarray, cfg, *,
                   prefix_len: int = 0) -> tuple[jnp.ndarray, tuple]:
    """Train / prefill path.  Returns (out, (k_cache, v_cache)).

    ``prefix_len`` > 0 marks a non-causal prefix (PaliGemma patch tokens):
    implemented as full attention within the prefix via window exemption —
    we keep plain causal for the whole span and note the simplification in
    DESIGN.md (the decomposition structure is identical).
    """
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, positions)
    # (b, s, h, d) -> (b, h, s, d) for the kernel
    o = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=cfg.window)
    o = o.transpose(0, 2, 1, 3)  # (b, s, h, d)
    out = jnp.einsum("bshd,hda->bsa", o, p["wo"])
    return out, (k, v)


class KVCache(NamedTuple):
    k: jnp.ndarray  # (b, S, kv_heads, hd)
    v: jnp.ndarray


def init_kv_cache(cfg, batch: int, length: int, dtype) -> KVCache:
    K, hd = cfg.n_kv_heads, cfg.hd
    shape = (batch, length, K, hd)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def attention_decode(p: dict, x: jnp.ndarray, cache: KVCache, pos: jnp.ndarray,
                     cfg) -> tuple[jnp.ndarray, KVCache]:
    """One decode step.  x: (b, 1, d_model); pos: scalar absolute position.

    Sliding-window archs use the cache as a ring buffer (slot = pos % W) and
    attend with window masking on absolute positions reconstructed from the
    ring; full-attention archs write at slot = pos.
    """
    b = x.shape[0]
    S = cache.k.shape[1]
    positions = jnp.full((1,), pos)
    q, k_new, v_new = _project_qkv(p, x, cfg, positions)

    slot = (pos % S) if cfg.window else pos
    k = jax.lax.dynamic_update_slice(cache.k, k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache.v, v_new, (0, slot, 0, 0))

    qh = q.transpose(0, 2, 1, 3)          # (b, h, 1, hd)
    kh = k.transpose(0, 2, 1, 3)          # (b, kv, S, hd)
    vh = v.transpose(0, 2, 1, 3)

    if cfg.window:
        # ring buffer: absolute position of slot i given current pos
        idx = jnp.arange(S)
        abs_pos = pos - ((pos % S) - idx) % S   # in (pos-S, pos]
        valid = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - cfg.window)
    else:
        idx = jnp.arange(S)
        valid = idx <= pos

    o = _decode_attend(qh, kh, vh, valid, cfg)
    o = o.transpose(0, 2, 1, 3)
    out = jnp.einsum("bshd,hda->bsa", o, p["wo"])
    return out, KVCache(k, v)


class PagedKVCache(NamedTuple):
    """Block-pool KV cache (the serving tier): ``n_blocks`` blocks of
    ``block`` cache rows each; sequences own disjoint block sets through
    per-slot block tables.  Block 0 is reserved as scratch (inactive slots
    write there; nothing valid ever reads it)."""

    k: jnp.ndarray  # (n_blocks, block, kv_heads, hd)
    v: jnp.ndarray


def init_paged_kv_cache(cfg, n_blocks: int, block: int, dtype) -> PagedKVCache:
    K, hd = cfg.n_kv_heads, cfg.hd
    shape = (n_blocks, block, K, hd)
    return PagedKVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def attention_decode_paged(p: dict, x: jnp.ndarray, pools: PagedKVCache,
                           layer, tables: jnp.ndarray, pos: jnp.ndarray,
                           cfg) -> tuple[jnp.ndarray, PagedKVCache]:
    """One decode step of layer ``layer`` against its paged block pool.

    ``pools`` holds every layer's pool stacked, (L, n_blocks, block, k, d)
    leaves, and is updated in place.  x: (b, 1, d_model); tables: (b, W)
    int32 block tables; pos: (b,) int32 per-slot absolute positions —
    unlike ``attention_decode``, every batch slot sits at its *own*
    position (continuous batching).  This step's K/V row of slot ``s`` is
    written into ``pools[layer]`` at block ``tables[s, pos//block]``, row
    offset ``pos % block`` (block ids come from the allocator, so they are
    in range); the time-ordered cache view is gathered from the layer's
    pool through the same block-table lookup the planner prices
    (``ops.kv_block_gather``) and attended with per-row validity masks
    (``idx <= pos``, plus the sliding window on absolute positions for
    windowed archs — the pool is time-ordered, so no ring reconstruction
    is needed).
    """
    b = x.shape[0]
    blk = pools.k.shape[2]
    W = tables.shape[1]
    # obs.part names the step's parts (repro.obs.PARTS) in the compiled
    # program's metadata, so a device trace can be split by part
    with obs.part("qkv"):
        q, k_new, v_new = _project_qkv(p, x, cfg, pos[:, None])
    with obs.part("kv_write"):
        blk_ids = jnp.take_along_axis(tables, (pos // blk)[:, None],
                                      axis=1)[:, 0]
        off = pos % blk
        # one row per slot (block 0 = shared scratch for idle slots).  An
        # update-slice writes in place whatever layout the pool is stored
        # in; a scatter makes the TPU compiler copy the stack into the
        # layout its scatter wants
        k_pools, v_pools = pools
        for s in range(b):
            at = (layer, blk_ids[s], off[s], 0, 0)
            k_pools = jax.lax.dynamic_update_slice(
                k_pools, k_new[s][None, None].astype(k_pools.dtype), at)
            v_pools = jax.lax.dynamic_update_slice(
                v_pools, v_new[s][None, None].astype(v_pools.dtype), at)

    with obs.part("kv_gather"):
        kh = ops.kv_block_gather(
            jax.lax.dynamic_index_in_dim(k_pools, layer, keepdims=False),
            tables, W * blk)                                # (b, kv, t, d)
        vh = ops.kv_block_gather(
            jax.lax.dynamic_index_in_dim(v_pools, layer, keepdims=False),
            tables, W * blk)
    qh = q.transpose(0, 2, 1, 3)                        # (b, h, 1, hd)

    with obs.part("attend"):
        idx = jnp.arange(W * blk)
        valid = idx[None, :] <= pos[:, None]
        if cfg.window:
            valid &= idx[None, :] > (pos[:, None] - cfg.window)
        o = _decode_attend(qh, kh, vh, valid, cfg)
    with obs.part("out_proj"):
        o = o.transpose(0, 2, 1, 3)
        out = jnp.einsum("bshd,hda->bsa", o, p["wo"])
    return out, PagedKVCache(k_pools, v_pools)


def _decode_attend(q, k, v, valid, cfg):
    """Masked attention for a single query against the whole cache buffer.
    ``valid`` is (S,) shared across the batch, or (b, S) per-row (the paged
    decode path, where every slot sits at its own position)."""
    hq, hkv = q.shape[1], k.shape[1]
    g = hq // hkv
    b, _, S, d = k.shape
    qs = q.reshape(b, hkv, g, 1, d).astype(jnp.float32) * (d ** -0.5)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qs, k.astype(jnp.float32))
    mask = (valid[:, None, None, None, :] if valid.ndim == 2
            else valid[None, None, None, None, :])
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p / l, v.astype(jnp.float32))
    return o.reshape(b, hq, 1, d).astype(q.dtype)
