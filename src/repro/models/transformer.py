"""The model stack: embedding -> scanned blocks -> norm -> LM head.

One implementation serves all ten assigned architectures; ``cfg.blocks()``
cycles the block pattern (attn | hymba | mlstm | slstm) over layers.  Layers
are grouped into *units* of one pattern period and scanned with
``jax.lax.scan`` (stacked params, leading L axis), with optional remat.

Sharding: a ``ShardingPolicy`` (usually derived from an EinDecomp plan)
supplies PartitionSpecs; activations get ``with_sharding_constraint`` at the
canonical cut points (embed out, block out, ffn hidden, logits), parameters
get in_shardings via ``param_shardings``.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro import obs
from repro.models import attention as attn_mod
from repro.models import ffn as ffn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import (ParamFactory, dtype_of, embed, lm_logits,
                                 rmsnorm, softmax_xent)
from repro.models.policy import ShardingPolicy

# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_block(pf: ParamFactory, cfg, blk: str) -> dict:
    p: dict[str, Any] = {"norm1": pf.ones(cfg.d_model)}
    if blk == "attn":
        p["attn"] = attn_mod.init_attention(pf, cfg)
        p["norm2"] = pf.ones(cfg.d_model)
        if cfg.moe:
            p["moe"] = moe_mod.init_moe(pf, cfg)
        else:
            p["ffn"] = ffn_mod.init_ffn(pf, cfg)
    elif blk == "hymba":
        p["attn"] = attn_mod.init_attention(pf, cfg)
        p["ssm"] = ssm_mod.init_ssm(pf, cfg)
        p["norm_a"] = pf.ones(cfg.d_model)
        p["norm_s"] = pf.ones(cfg.d_model)
        p["norm2"] = pf.ones(cfg.d_model)
        p["ffn"] = ffn_mod.init_ffn(pf, cfg)
    elif blk == "mlstm":
        p["mlstm"] = xlstm_mod.init_mlstm(pf, cfg)
    elif blk == "slstm":
        p["slstm"] = xlstm_mod.init_slstm(pf, cfg)
    else:
        raise ValueError(blk)
    return p


def _stack(trees: list):
    def leaf(*xs):
        if isinstance(xs[0], jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct((len(xs),) + xs[0].shape, xs[0].dtype)
        return jnp.stack(xs)

    return jax.tree.map(leaf, *trees)


def init_params(cfg, key: jax.Array | None = None, *, abstract: bool = False) -> dict:
    dt = dtype_of(cfg)
    pf = ParamFactory(key, dt, abstract)
    pattern = cfg.block_pattern
    units = cfg.n_layers // len(pattern)
    assert units * len(pattern) == cfg.n_layers

    layers = []
    for pos, blk in enumerate(pattern):
        layers.append(_stack([_init_block(pf, cfg, blk) for _ in range(units)]))

    params = {
        # d**-0.5 keeps tied-head logits unit-variance (x RMS=1 post-norm)
        "embed": pf.dense(cfg.vocab_padded, cfg.d_model,
                          scale=cfg.d_model ** -0.5),
        "layers": layers,
        "final_norm": pf.ones(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = pf.dense(cfg.d_model, cfg.vocab_padded)
    return params


# label strings mirroring init_params structure (for param_shardings)


def _block_labels(cfg, blk: str) -> dict:
    p: dict[str, Any] = {"norm1": "L a"}
    if blk in ("attn", "hymba"):
        at = {"wq": "L a h d", "wk": "L a k d", "wv": "L a k d", "wo": "L h d a"}
        if cfg.qkv_bias:
            at.update({"bq": "L h d", "bk": "L k d", "bv": "L k d"})
        p["attn"] = at
        p["norm2"] = "L a"
        ffl = {"w1": "L a f", "w2": "L f a"}
        if cfg.gated_ffn:
            ffl["w3"] = "L a f"
        if blk == "attn" and cfg.moe:
            ml = {"router": "L a e", "w1": "L e a f", "w2": "L e f a"}
            if cfg.gated_ffn:
                ml["w3"] = "L e a f"
            if cfg.shared_expert_ff:
                ml["shared"] = dict(ffl)
            p["moe"] = ml
        else:
            p["ffn"] = dict(ffl)
    if blk == "hymba":
        p["ssm"] = {"in_proj": "L a f", "conv_w": "L z a", "x_proj": "L a z",
                    "a_log": "L a n", "d_skip": "L a", "out_proj": "L f a"}
        p["norm_a"] = "L a"
        p["norm_s"] = "L a"
    if blk == "mlstm":
        p["mlstm"] = {"w_up": "L a f", "wq": "L a f", "wk": "L a f",
                      "wv": "L a f", "w_if": "L a z", "w_down": "L f a",
                      "norm": "L a"}
    if blk == "slstm":
        p["slstm"] = {"w_in": "L a f", "r": "L a f", "w_down": "L f a",
                      "norm": "L a"}
    return p


def param_labels(cfg) -> dict:
    labels = {
        "embed": "v a",
        "layers": [_block_labels(cfg, blk) for blk in cfg.block_pattern],
        "final_norm": "a",
    }
    if not cfg.tie_embeddings:
        labels["head"] = "a v"
    return labels


def param_shardings(cfg, policy: ShardingPolicy, mesh) -> dict:
    """Pytree of NamedShardings matching init_params(abstract=True)."""
    abstract = init_params(cfg, abstract=True)
    labels = param_labels(cfg)

    def make(sds, lab):
        return policy.sharding(mesh, lab, sds.shape, param=True)

    return jax.tree.map(make, abstract, labels)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _cst(x, labels: str, policy, mesh):
    if policy is None or mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, policy.sharding(mesh, labels, x.shape))


def _block_forward(blk: str, p: dict, x, cfg, policy, mesh):
    """Full-sequence block.  Returns (x, cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if blk == "attn":
        a_out, kv = attn_mod.attention_full(p["attn"], h, cfg)
        kv = (_cst(kv[0], "b s k d", policy, mesh),
              _cst(kv[1], "b s k d", policy, mesh))
        x = x + _cst(a_out, "b s a", policy, mesh)
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if cfg.moe:
            m_out, aux = moe_mod.moe_ffn(p["moe"], h2, cfg, policy=policy,
                                         mesh=mesh)
        else:
            m_out = ffn_mod.ffn(p["ffn"], h2, cfg)
        x = x + _cst(m_out, "b s a", policy, mesh)
        cache = kv
    elif blk == "hymba":
        a_out, kv = attn_mod.attention_full(p["attn"], h, cfg)
        kv = (_cst(kv[0], "b s k d", policy, mesh),
              _cst(kv[1], "b s k d", policy, mesh))
        s_out, st = ssm_mod.ssm_forward(p["ssm"], h, cfg)
        mixed = 0.5 * (rmsnorm(a_out, p["norm_a"], cfg.norm_eps)
                       + rmsnorm(s_out, p["norm_s"], cfg.norm_eps))
        x = x + _cst(mixed, "b s a", policy, mesh)
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + _cst(ffn_mod.ffn(p["ffn"], h2, cfg), "b s a", policy, mesh)
        cache = (kv, st)
    elif blk == "mlstm":
        out, st = xlstm_mod.mlstm_forward(p["mlstm"], h, cfg)
        x = x + _cst(out, "b s a", policy, mesh)
        cache = st
    elif blk == "slstm":
        out, st = xlstm_mod.slstm_forward(p["slstm"], h, cfg)
        x = x + _cst(out, "b s a", policy, mesh)
        cache = st
    else:
        raise ValueError(blk)
    return x, cache, aux


def _embed_tokens(params, tokens, prefix_embeds, cfg, policy, mesh):
    x = embed(params["embed"], tokens).astype(dtype_of(cfg))
    if cfg.prefix_len and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return _cst(x, "b s a", policy, mesh)


def forward(params, tokens, cfg, *, prefix_embeds=None, policy=None,
            mesh=None, collect_cache: bool = False, remat: bool | None = None,
            unroll: bool = False, last_logit_only: bool = False,
            logit_index=None):
    """Full-sequence forward.  Returns (logits, caches, aux_loss).
    ``last_logit_only`` computes the LM head for the final position only
    (prefill serving: (b,s,v) logits are never needed — §Perf).
    ``logit_index`` (a scalar, may be traced) generalizes it to *any*
    single position — bucketed serving prefill pads the prompt to the
    bucket length and takes the logit at the last real token."""
    x = _embed_tokens(params, tokens, prefix_embeds, cfg, policy, mesh)
    pattern = cfg.block_pattern
    remat = (policy.remat if policy is not None else True) if remat is None else remat

    def unit(carry, unit_params):
        x, aux = carry
        caches = []
        for pos, blk in enumerate(pattern):
            x, cache, a = _block_forward(blk, unit_params[pos], x, cfg,
                                         policy, mesh)
            caches.append(cache)
            aux = aux + a
        return (x, aux), (tuple(caches) if collect_cache else 0)

    if remat == "dots":
        # selective remat: keep matmul outputs, recompute elementwise only
        body = jax.checkpoint(
            unit, policy=jax.checkpoint_policies.dots_saveable)
    elif remat:
        body = jax.checkpoint(unit)
    else:
        body = unit
    (x, aux), caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        tuple(params["layers"]), unroll=True if unroll else 1)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if last_logit_only:
        x = x[:, -1:]
    elif logit_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, logit_index, 1, axis=1)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    logits = lm_logits(x, head)
    logits = _cst(logits, "b s v", policy, mesh)
    return logits, caches, aux


def loss_fn(params, batch, cfg, *, policy=None, mesh=None, unroll: bool = False):
    logits, _, aux = forward(
        params, batch["tokens"], cfg,
        prefix_embeds=batch.get("prefix_embeds"), policy=policy, mesh=mesh,
        unroll=unroll)
    # loss over token positions only (prefix positions predict nothing)
    if cfg.prefix_len:
        logits = logits[:, cfg.prefix_len:]
    ce = softmax_xent(logits[:, :-1], batch["labels"][:, 1:], cfg.vocab)
    return ce + 0.01 * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def init_caches(cfg, batch: int, kv_len: int, *, abstract: bool = False):
    """Per-pattern-position stacked (units, ...) decode caches."""
    dt = dtype_of(cfg)
    units = cfg.n_layers // len(cfg.block_pattern)

    def one(blk):
        if blk == "attn":
            return attn_mod.init_kv_cache(cfg, batch, kv_len, dt)
        if blk == "hymba":
            return (attn_mod.init_kv_cache(cfg, batch, kv_len, dt),
                    ssm_mod.init_ssm_state(cfg, batch, dt))
        if blk == "mlstm":
            return xlstm_mod.init_mlstm_state(cfg, batch)
        if blk == "slstm":
            return xlstm_mod.init_slstm_state(cfg, batch)
        raise ValueError(blk)

    def build():
        return [_stack([one(blk) for _ in range(units)])
                for blk in cfg.block_pattern]

    if abstract:
        return jax.eval_shape(build)  # no allocation (77GB+ at 32k decode)
    return build()


def _block_decode(blk: str, p: dict, x, cache, pos, cfg, policy, mesh):
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if blk == "attn":
        a_out, cache2 = attn_mod.attention_decode(p["attn"], h, cache, pos, cfg)
        cache2 = attn_mod.KVCache(_cst(cache2.k, "b t k d", policy, mesh),
                                  _cst(cache2.v, "b t k d", policy, mesh))
        x = x + a_out
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        if cfg.moe:
            m_out, _ = moe_mod.moe_ffn(p["moe"], h2, cfg, policy=policy,
                                       mesh=mesh)
        else:
            m_out = ffn_mod.ffn(p["ffn"], h2, cfg)
        x = x + m_out
    elif blk == "hymba":
        kv, st = cache
        a_out, kv2 = attn_mod.attention_decode(p["attn"], h, kv, pos, cfg)
        kv2 = attn_mod.KVCache(_cst(kv2.k, "b t k d", policy, mesh),
                               _cst(kv2.v, "b t k d", policy, mesh))
        s_out, st2 = ssm_mod.ssm_decode(p["ssm"], h, st, cfg)
        mixed = 0.5 * (rmsnorm(a_out, p["norm_a"], cfg.norm_eps)
                       + rmsnorm(s_out, p["norm_s"], cfg.norm_eps))
        x = x + mixed
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        x = x + ffn_mod.ffn(p["ffn"], h2, cfg)
        cache2 = (kv2, st2)
    elif blk == "mlstm":
        out, cache2 = xlstm_mod.mlstm_decode(p["mlstm"], h, cache, cfg)
        x = x + out
    elif blk == "slstm":
        out, cache2 = xlstm_mod.slstm_decode(p["slstm"], h, cache, cfg)
        x = x + out
    else:
        raise ValueError(blk)
    return x, cache2


def decode_step(params, tokens, caches, pos, cfg, *, policy=None, mesh=None,
                unroll: bool = False):
    """One token for the whole batch.  tokens (b, 1); pos scalar int32.
    Returns (logits (b, 1, v), new caches)."""
    x = embed(params["embed"], tokens).astype(dtype_of(cfg))
    x = _cst(x, "b s a", policy, mesh)
    pattern = cfg.block_pattern

    def unit(x, scanned):
        unit_params, unit_caches = scanned
        new_caches = []
        for ppos, blk in enumerate(pattern):
            x, c2 = _block_decode(blk, unit_params[ppos], x, unit_caches[ppos],
                                  pos, cfg, policy, mesh)
            new_caches.append(c2)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(
        unit, x, (tuple(params["layers"]), tuple(caches)),
        unroll=True if unroll else 1)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    logits = lm_logits(x, head)
    logits = _cst(logits, "b s v", policy, mesh)
    return logits, list(new_caches)


# ---------------------------------------------------------------------------
# Paged decode (the serving tier): block-pool KV caches + per-slot positions
# ---------------------------------------------------------------------------


def init_paged_caches(cfg, batch: int, n_blocks: int, block: int, *,
                      abstract: bool = False):
    """Per-pattern-position stacked (units, ...) paged decode caches.

    Attention blocks hold a ``PagedKVCache`` pool of ``n_blocks`` blocks x
    ``block`` rows (shared by all batch slots through block tables) instead
    of the dense per-slot (b, S, k, d) buffer; recurrent states are
    unchanged (per-slot already, so ``batch`` sizes only those)."""
    dt = dtype_of(cfg)
    units = cfg.n_layers // len(cfg.block_pattern)

    def one(blk):
        if blk == "attn":
            return attn_mod.init_paged_kv_cache(cfg, n_blocks, block, dt)
        if blk == "hymba":
            return (attn_mod.init_paged_kv_cache(cfg, n_blocks, block, dt),
                    ssm_mod.init_ssm_state(cfg, batch, dt))
        if blk == "mlstm":
            return xlstm_mod.init_mlstm_state(cfg, batch)
        if blk == "slstm":
            return xlstm_mod.init_slstm_state(cfg, batch)
        raise ValueError(blk)

    def build():
        return [_stack([one(blk) for _ in range(units)])
                for blk in cfg.block_pattern]

    if abstract:
        return jax.eval_shape(build)
    return build()


def _stored_layout(caches, device):
    """Pin each carried cache leaf to the layout ``device`` stores it in.

    Left free, the compiler gives the loop carry the layout its readers
    prefer and copies the whole stack into it and back around the loop:
    the TPU stores a bf16 (L, n, 16, 5, 64) pool blocks-minor, while its
    gather wants blocks-major.  Pinned, only the layer that is read is
    converted."""
    def pin(x):
        layout = device.client.get_default_layout(x.dtype, x.shape, device)
        return with_layout_constraint(x, Layout.from_pjrt_layout(layout))

    return jax.tree.map(pin, caches)


def _layer_of(states, l):
    return jax.tree.map(
        lambda s: jax.lax.dynamic_index_in_dim(s, l, keepdims=False), states)


def _set_layer(states, l, new):
    return jax.tree.map(
        lambda s, n: jax.lax.dynamic_update_index_in_dim(s, n, l, 0),
        states, new)


def _block_decode_paged(blk: str, p: dict, x, caches, l, tables, pos, cfg,
                        policy, mesh):
    """Layer ``l`` of one pattern position.  ``caches`` is the position's
    stacked (units, ...) cache; the block reads its own layer of it and
    writes that layer back in place, so the stack can be the loop's carry:
    the KV pool through ``attention_decode_paged``, a recurrent state by
    slicing layer ``l`` out and updating it there."""
    h = rmsnorm(x, p["norm1"], cfg.norm_eps)
    if blk == "attn":
        a_out, cache2 = attn_mod.attention_decode_paged(
            p["attn"], h, caches, l, tables, pos, cfg)
        x = x + a_out
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        with obs.part("ffn"):
            if cfg.moe:
                m_out, _ = moe_mod.moe_ffn(p["moe"], h2, cfg, policy=policy,
                                           mesh=mesh)
            else:
                m_out = ffn_mod.ffn(p["ffn"], h2, cfg)
        x = x + m_out
    elif blk == "hymba":
        kv, st = caches
        a_out, kv2 = attn_mod.attention_decode_paged(
            p["attn"], h, kv, l, tables, pos, cfg)
        with obs.part("ssm"):
            s_out, st2 = ssm_mod.ssm_decode(p["ssm"], h, _layer_of(st, l),
                                            cfg)
        mixed = 0.5 * (rmsnorm(a_out, p["norm_a"], cfg.norm_eps)
                       + rmsnorm(s_out, p["norm_s"], cfg.norm_eps))
        x = x + mixed
        h2 = rmsnorm(x, p["norm2"], cfg.norm_eps)
        with obs.part("ffn"):
            f_out = ffn_mod.ffn(p["ffn"], h2, cfg)
        x = x + f_out
        cache2 = (kv2, _set_layer(st, l, st2))
    elif blk == "mlstm":
        out, st2 = xlstm_mod.mlstm_decode(p["mlstm"], h, _layer_of(caches, l),
                                          cfg)
        x = x + out
        cache2 = _set_layer(caches, l, st2)
    elif blk == "slstm":
        out, st2 = xlstm_mod.slstm_decode(p["slstm"], h, _layer_of(caches, l),
                                          cfg)
        x = x + out
        cache2 = _set_layer(caches, l, st2)
    else:
        raise ValueError(blk)
    return x, cache2


def decode_step_paged(params, tokens, caches, tables, pos, cfg, *,
                      policy=None, mesh=None, unroll: bool = False):
    """One continuous-batching decode step.  tokens (b, 1); tables (b, W)
    int32 block tables; pos (b,) int32 per-slot positions.  Returns
    (logits (b, 1, v), new caches).  Idle slots point their table rows at
    the scratch block 0 and carry pos such that their writes land there.

    The stacked caches are the layer scan's carry, not its scanned input
    and output: each unit updates its own layer of them in place, so a
    step moves this step's rows and never a whole pool (with the caches
    donated, the output aliases them)."""
    x = embed(params["embed"], tokens).astype(dtype_of(cfg))
    x = _cst(x, "b s a", policy, mesh)
    pattern = cfg.block_pattern
    units = cfg.n_layers // len(pattern)
    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]

    def unit(carry, scanned):
        x, caches = carry
        unit_params, l = scanned
        new_caches = []
        for ppos, blk in enumerate(pattern):
            x, c2 = _block_decode_paged(
                blk, unit_params[ppos], x, caches[ppos], l, tables, pos,
                cfg, policy, mesh)
            new_caches.append(c2)
        return (x, _stored_layout(tuple(new_caches), device)), None

    (x, new_caches), _ = jax.lax.scan(
        unit, (x, tuple(caches)),
        (tuple(params["layers"]), jnp.arange(units, dtype=jnp.int32)),
        unroll=True if unroll else 1)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("head")
    if head is None:
        head = params["embed"].T
    with obs.part("lm_head"):
        logits = lm_logits(x, head)
    logits = _cst(logits, "b s v", policy, mesh)
    return logits, list(new_caches)


def cache_labels(cfg):
    """Label strings mirroring init_caches structure (for shardings)."""
    from repro.models.attention import KVCache
    from repro.models.ssm import SSMState
    from repro.models.xlstm import MLSTMState, SLSTMState

    def one(blk):
        kv = KVCache("L b t k d", "L b t k d")
        if blk == "attn":
            return kv
        if blk == "hymba":
            return (kv, SSMState("L b a n", "L b z a"))
        if blk == "mlstm":
            return MLSTMState("L b h d d", "L b h d", "L b h")
        if blk == "slstm":
            return SLSTMState("L b a", "L b a", "L b a", "L b a")
        raise ValueError(blk)

    return [one(blk) for blk in cfg.block_pattern]


def cache_shardings(cfg, batch: int, kv_len: int, policy, mesh):
    abstract = init_caches(cfg, batch, kv_len, abstract=True)
    labels = cache_labels(cfg)

    def make(sds, lab):
        return policy.sharding(mesh, lab, sds.shape)

    return jax.tree.map(make, abstract, labels)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs for the dry-run; real arrays for smoke)
# ---------------------------------------------------------------------------


def input_specs(cfg, shape, *, policy=None, mesh=None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""

    def sds(shp, dtype, labels):
        if policy is not None and mesh is not None:
            return jax.ShapeDtypeStruct(
                shp, dtype, sharding=policy.sharding(mesh, labels, shp))
        return jax.ShapeDtypeStruct(shp, dtype)

    B, S = shape.batch, shape.seq
    dt = dtype_of(cfg)
    if shape.kind in ("train", "prefill"):
        toks = S - (cfg.prefix_len or 0)
        out = {"tokens": sds((B, toks), jnp.int32, "b s"),
               "labels": sds((B, toks), jnp.int32, "b s")}
        if cfg.prefix_len:
            out["prefix_embeds"] = sds((B, cfg.prefix_len, cfg.d_model), dt,
                                       "b s a")
        if shape.kind == "prefill":
            out.pop("labels")
        return out
    # decode: one token + caches + position
    out = {"tokens": sds((B, 1), jnp.int32, "b s"),
           "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    return out
