"""Plain float32 reference of the repo's hymba block stack.

It computes what ``models/transformer.py`` computes for
``block_pattern == ("hymba",)`` — the function the program serves, with the
departures from the published Hymba listed in the configuration file — in
straightforward ``jax.numpy``: no Pallas kernel, no paged cache, no
chunked scan, no planner.  Attention is a dense masked softmax, the SSM a
sequential ``lax.scan`` over time.  It runs layer by layer, one compiled
layer reused for all of them, so it fits beside nothing else on one chip.

``precision`` picks the products (``reference/precision.py``): ``float32``
is the reference, ``fp8`` the control one precision below bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.precision import dot
from chipbench.weights import Leaf


def vocab_padded(m: dict) -> int:
    return -(-m["vocab"] // 256) * 256


def param_layout(m: dict) -> dict:
    """The parameters in the layout ``ServingEngine(params=...)`` takes."""
    D, H, K, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    F, L, n, kc = m["d_ff"], m["n_layers"], m["ssm_state"], m["ssm_conv"]
    V = vocab_padded(m)

    def w(*shape, fan_in=None):
        fan = fan_in if fan_in is not None else shape[1]
        return Leaf((L,) + shape, std=fan ** -0.5)

    def gain(*shape, scale=1.0):
        return Leaf((L,) + shape, kind="gain", std=scale)

    # The SSM head's output is cubic in its input (B and C are both
    # projections of it); entering the residual at unit gain in every layer
    # it makes a random 32-layer stack chaotic, so that bfloat16 and fp8
    # serve equally unrelated tokens.  Its gain starts at 1/sqrt(2 layers),
    # as residual branches are scaled at initialization (GPT-2).
    layer = {
        "norm1": gain(D), "norm_a": gain(D),
        "norm_s": gain(D, scale=(2 * L) ** -0.5),
        "norm2": gain(D),
        "attn": {"wq": w(D, H, hd, fan_in=D), "wk": w(D, K, hd, fan_in=D),
                 "wv": w(D, K, hd, fan_in=D),
                 "wo": w(H, hd, D, fan_in=H * hd)},
        "ssm": {"in_proj": w(D, 2 * D), "conv_w": w(kc, D, fan_in=kc),
                "x_proj": w(D, 2 * n + 1), "a_log": Leaf((L, D, n), "alog"),
                "d_skip": gain(D), "out_proj": w(D, D)},
        "ffn": {"w1": w(D, F), "w3": w(D, F), "w2": w(F, D)},
    }
    return {"embed": Leaf((V, D), std=D ** -0.5), "layers": [layer],
            "final_norm": Leaf((D,), "gain"),
            "head": Leaf((D, V), "head", std=D ** -0.5, real=m["vocab"])}


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (b, s, h, d), positions 0..s-1; rotate the two halves."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, h, m, mm):
    b, s, _ = h.shape
    K, hd = m["n_kv_heads"], m["head_dim"]
    g = m["n_heads"] // K
    q = _rope(mm("bsa,ahd->bshd", h, p["wq"]), m["rope_theta"])
    k = _rope(mm("bsa,akd->bskd", h, p["wk"]), m["rope_theta"])
    v = mm("bsa,akd->bskd", h, p["wv"])
    q = q.reshape(b, s, K, g, hd) * hd ** -0.5      # head i reads kv i // g
    sc = mm("bskgd,btkd->bkgst", q, k)
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    keep = ki <= qi
    if m["window"]:
        keep &= ki > qi - m["window"]
    sc = jnp.where(keep, sc, -jnp.inf)
    o = mm("bkgst,btkd->bskgd", jax.nn.softmax(sc, axis=-1), v)
    return mm("bshd,hda->bsa", o.reshape(b, s, K * g, hd), p["wo"])


def _ssm(p, h, m, mm):
    n, kc = m["ssm_state"], m["ssm_conv"]
    xin, z = jnp.split(mm("bsd,de->bse", h, p["in_proj"]), 2, axis=-1)
    s = xin.shape[1]
    xp = jnp.pad(xin, ((0, 0), (kc - 1, 0), (0, 0)))
    conv_w = p["conv_w"].astype(jnp.float32)
    xin = jax.nn.silu(sum(xp[:, i:i + s] * conv_w[i] for i in range(kc)))
    f = mm("bsd,df->bsf", xin, p["x_proj"])
    B, C, dt = f[..., :n], f[..., n:2 * n], jax.nn.softplus(f[..., 2 * n])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))              # (D, n)

    def step(state, inp):
        dt_t, b_t, c_t, x_t = inp                             # (b,), (b,n), (b,n), (b,D)
        state = (jnp.exp(dt_t[:, None, None] * a) * state
                 + (dt_t[:, None] * b_t)[:, None, :] * x_t[:, :, None])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    state0 = jnp.zeros((xin.shape[0], xin.shape[2], n), jnp.float32)
    _, y = jax.lax.scan(step, state0, (dt.swapaxes(0, 1), B.swapaxes(0, 1),
                                       C.swapaxes(0, 1), xin.swapaxes(0, 1)))
    y = y.swapaxes(0, 1) + xin * p["d_skip"].astype(jnp.float32)
    return mm("bsd,de->bse", y * jax.nn.silu(z), p["out_proj"])


def _layer(x, p, m, mm):
    eps = m["norm_eps"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = _rms(x, p["norm1"], eps)
    a = _attention(p["attn"], h, m, mm)
    s = _ssm(p["ssm"], h, m, mm)
    x = x + 0.5 * (_rms(a, p["norm_a"], eps) + _rms(s, p["norm_s"], eps))
    h2 = _rms(x, p["norm2"], eps)
    f = p["ffn"]
    u = jax.nn.silu(mm("bsa,af->bsf", h2, f["w1"])) * mm("bsa,af->bsf", h2,
                                                         f["w3"])
    return x + mm("bsf,fa->bsa", u, f["w2"])


def logits(params, tokens, m: dict, precision: str = "float32"):
    """(b, s, vocab) float32 logits of every position of ``tokens``."""
    mm = dot(precision)
    layer = jax.jit(lambda x, p: _layer(x, p, m, mm))
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    stack = params["layers"][0]
    for i in range(m["n_layers"]):
        x = layer(x, jax.tree.map(lambda a: a[i], stack))
    head = jax.jit(lambda x, g, w: mm(
        "bsd,dv->bsv", _rms(x, g.astype(jnp.float32), m["norm_eps"]),
        w)[..., :m["vocab"]])
    return head(x, params["final_norm"], params["head"])
