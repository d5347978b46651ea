"""Selective SSM (Mamba-style) used by the Hymba hybrid blocks.

TPU adaptation (DESIGN.md §2): the recurrence h_t = a_t ⊙ h_{t-1} + b_t is
computed *chunkwise* — ``lax.scan`` over chunks (sequential, carries the
(b, di, n) state) with ``lax.associative_scan`` inside each chunk (parallel
on the VPU).  This bounds live memory to one chunk's expanded state instead
of the full (b, s, di, n) tensor, and gives O(state) 500k-token decode.

Simplifications vs. Mamba (noted per DESIGN.md §4): dt is a scalar per
position (x_proj emits 2n+1 features: B, C, dt) and the inner width equals
d_model.  The decomposition-relevant structure — a recurrent scan whose
sequence label cannot be partitioned, with batch/state labels free — is
exactly preserved, which is what EinDecomp reasons about.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.tiling import pad_to
from repro.models.common import ParamFactory


class SSMState(NamedTuple):
    h: jnp.ndarray      # (b, di, n)
    conv: jnp.ndarray   # (b, k-1, di) — causal-conv tail


def init_ssm(pf: ParamFactory, cfg) -> dict:
    D = cfg.d_model
    di = D
    n = cfg.ssm_state
    kc = cfg.ssm_conv
    return {
        "in_proj": pf.dense(D, 2 * di),
        "conv_w": pf.dense(kc, di, scale=kc ** -0.5),
        "x_proj": pf.dense(di, 2 * n + 1),
        "a_log": pf.ones(di, n),
        "d_skip": pf.ones(di),
        "out_proj": pf.dense(di, D),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, tail: jnp.ndarray):
    """Depthwise causal conv along s.  x (b, s, di); w (k, di); tail
    (b, k-1, di) = the last k-1 inputs from the previous call."""
    k = w.shape[0]
    xp = jnp.concatenate([tail, x], axis=1)
    out = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(k))
    return out, xp[:, -(k - 1):]


def _ssm_features(p: dict, xin: jnp.ndarray, n: int):
    feats = jnp.einsum("bsd,df->bsf", xin, p["x_proj"]).astype(jnp.float32)
    B, C, dt = feats[..., :n], feats[..., n : 2 * n], feats[..., 2 * n]
    dt = jax.nn.softplus(dt)[..., None]                     # (b, s, 1)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))            # (di, n)
    decay = jnp.exp(dt[..., None] * a)                      # (b, s, di, n)
    drive = (dt * B)[..., None, :] * xin.astype(jnp.float32)[..., None]
    return decay, drive, C


def ssm_forward(p: dict, x: jnp.ndarray, cfg, *, chunk: int = 256
                ) -> tuple[jnp.ndarray, SSMState]:
    """Full-sequence path.  x: (b, s, D) -> (y, final state)."""
    b, s, D = x.shape
    n = cfg.ssm_state
    di = D
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xin, z = jnp.split(xz, 2, axis=-1)
    tail0 = jnp.zeros((b, cfg.ssm_conv - 1, di), x.dtype)
    xin, _tail = _causal_conv(xin, p["conv_w"], tail0)
    xin = jax.nn.silu(xin)

    chunk = min(chunk, s)
    nchunks = -(-s // chunk)
    sp = nchunks * chunk
    decay, drive, C = _ssm_features(p, xin, n)
    # a length that is not a multiple of the chunk runs identity steps
    # (decay 1, drive 0) after its last position: every real output and
    # the final state are exactly those of the unpadded recurrence
    decay = pad_to(decay, 1, sp, 1.0)
    drive, C = pad_to(drive, 1, sp), pad_to(C, 1, sp)
    # reshape to (nchunks, b, chunk, ...)
    def split(t):
        return t.reshape(b, nchunks, chunk, *t.shape[2:]).swapaxes(0, 1)

    decay_c, drive_c, C_c = split(decay), split(drive), split(C)

    def chunk_step(h, inputs):
        dc, dr, cc = inputs                                  # (b, chunk, di, n)…
        # intra-chunk parallel scan of h_t = dc_t*h_{t-1} + dr_t
        def comb(e1, e2):
            a1, b1 = e1
            a2, b2 = e2
            return a1 * a2, a2 * b1 + b2
        A, Bd = jax.lax.associative_scan(comb, (dc, dr), axis=1)
        hs = A * h[:, None] + Bd                              # (b, chunk, di, n)
        y = jnp.einsum("bcdn,bcn->bcd", hs, cc)              # contract state
        return hs[:, -1], y

    h0 = jnp.zeros((b, di, n), jnp.float32)
    h_last, ys = jax.lax.scan(chunk_step, h0, (decay_c, drive_c, C_c))
    y = ys.swapaxes(0, 1).reshape(b, sp, di)[:, :s]
    y = y + xin.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"])
    return out, SSMState(h_last, _tail)


def init_ssm_state(cfg, batch: int, dtype) -> SSMState:
    di = cfg.d_model
    return SSMState(
        jnp.zeros((batch, di, cfg.ssm_state), jnp.float32),
        jnp.zeros((batch, cfg.ssm_conv - 1, di), dtype))


def ssm_decode(p: dict, x: jnp.ndarray, state: SSMState, cfg
               ) -> tuple[jnp.ndarray, SSMState]:
    """One-token step.  x: (b, 1, D)."""
    b, _, D = x.shape
    n = cfg.ssm_state
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xin, z = jnp.split(xz, 2, axis=-1)
    xin, tail = _causal_conv(xin, p["conv_w"], state.conv)
    xin = jax.nn.silu(xin)
    decay, drive, C = _ssm_features(p, xin, n)
    h = decay[:, 0] * state.h + drive[:, 0]                  # (b, di, n)
    y = jnp.einsum("bdn,bn->bd", h, C[:, 0])[:, None]
    y = y + xin.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"])
    return out, SSMState(h, tail)
