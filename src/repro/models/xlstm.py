"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, chunkwise-
parallel) and sLSTM (scalar memory, inherently sequential scan).

TPU adaptation (DESIGN.md §2): the mLSTM runs in *chunkwise* form — the
inter-chunk recurrence over the (b, h, d, d) matrix memory is a short
``lax.scan``; within a chunk the quadratic (L x L) gate-decay matrix is
formed in VMEM-sized tiles (L=256 default), giving O(s·d²) total work
instead of the O(s²) fully-parallel form.  The sLSTM keeps its sequential
``lax.scan`` over time — its sequence label is non-partitionable and its
EinGraph node says so (shardable excludes s), which is precisely what
EinDecomp needs to know (DESIGN.md §4 Arch-applicability).

Gating follows the paper's stabilized exponential form: i and f are kept in
log space, a per-step running max m_t is subtracted before exponentiation.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.tiling import pad_to
from repro.models.common import ParamFactory, rmsnorm


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------


class MLSTMState(NamedTuple):
    c: jnp.ndarray   # (b, h, d, d) matrix memory
    n: jnp.ndarray   # (b, h, d)    normalizer
    m: jnp.ndarray   # (b, h)       running log-max (stabilizer)


def init_mlstm(pf: ParamFactory, cfg) -> dict:
    D = cfg.d_model
    H = cfg.n_heads
    return {
        "w_up": pf.dense(D, 2 * D),      # -> (mlstm input, output gate z)
        "wq": pf.dense(D, D),
        "wk": pf.dense(D, D),
        "wv": pf.dense(D, D),
        "w_if": pf.dense(D, 2 * H),      # input & forget gate preacts per head
        "w_down": pf.dense(D, D),
        "norm": pf.ones(D),
    }


def _heads(x: jnp.ndarray, h: int) -> jnp.ndarray:
    b, s, d = x.shape
    return x.reshape(b, s, h, d // h).transpose(0, 2, 1, 3)  # (b, h, s, dh)


def mlstm_forward(p: dict, x: jnp.ndarray, cfg, *, chunk: int = 256
                  ) -> tuple[jnp.ndarray, MLSTMState]:
    b, s, D = x.shape
    H = cfg.n_heads
    dh = D // H
    up = jnp.einsum("bsd,de->bse", x, p["w_up"])
    xm, z = jnp.split(up, 2, axis=-1)
    q = _heads(jnp.einsum("bsd,de->bse", xm, p["wq"]), H).astype(jnp.float32)
    k = _heads(jnp.einsum("bsd,de->bse", xm, p["wk"]), H).astype(jnp.float32) * dh ** -0.5
    v = _heads(jnp.einsum("bsd,de->bse", xm, p["wv"]), H).astype(jnp.float32)
    gates = jnp.einsum("bsd,dg->bsg", xm, p["w_if"]).astype(jnp.float32)
    i_pre = gates[..., :H].transpose(0, 2, 1)                 # (b, h, s)
    f_pre = gates[..., H:].transpose(0, 2, 1)
    logf = -jax.nn.softplus(-f_pre)                           # log sigmoid(f)

    chunk = min(chunk, s)
    nc = -(-s // chunk)
    sp = nc * chunk

    def split(t, value=0.0):
        # a length that is not a multiple of the chunk runs identity steps
        # after its last position (forget gate 1, input gate ~0, zero q/k/v):
        # every real output and the final state are those of the unpadded
        # recurrence
        t = pad_to(t, 2, sp, value)
        shp = list(t.shape)
        shp[2:3] = [nc, chunk]
        return jnp.moveaxis(t.reshape(shp), 2, 0)

    qc, kc, vc = split(q), split(k), split(v)
    ic, fc = split(i_pre, -1e30), split(logf)

    def chunk_step(carry, inp):
        C, N, M = carry                                       # (b,h,d,d),(b,h,d),(b,h)
        qq, kk, vv, ii, ff = inp                              # (b,h,L,dh), gates (b,h,L)
        L = qq.shape[2]
        Fc = jnp.cumsum(ff, axis=-1)                          # (b,h,L) cumulative log f
        # stabilizer: m_t = max(Fc_t + M, max_{j<=t}(Fc_t - Fc_j + i_j))
        a = Fc + M[..., None]                                 # inter contribution
        blog = Fc[..., :, None] - Fc[..., None, :] + ii[..., None, :]  # (b,h,L,L)
        tri = jnp.tril(jnp.ones((L, L), bool))
        blog = jnp.where(tri, blog, -jnp.inf)
        m_t = jnp.maximum(a, jnp.max(blog, axis=-1))          # (b,h,L)
        Ddec = jnp.exp(blog - m_t[..., None])                 # intra decay matrix
        inter_w = jnp.exp(a - m_t)                            # (b,h,L)
        s_qk = jnp.einsum("bhld,bhjd->bhlj", qq, kk)
        h_intra = jnp.einsum("bhlj,bhjd->bhld", s_qk * Ddec, vv)
        h_inter = jnp.einsum("bhld,bhde->bhle", qq, C) * inter_w[..., None]
        # normalizer: n_t = sum_j decay * k_j  (intra)  +  inter_w * N
        n_intra = jnp.einsum("bhlj,bhjd->bhld", Ddec, kk)
        n_t = n_intra + inter_w[..., None] * N[:, :, None, :]
        h_num = h_intra + h_inter
        denom = jnp.maximum(jnp.abs(jnp.einsum("bhld,bhld->bhl", qq, n_t)),
                            jnp.exp(-m_t))[..., None]
        h_out = h_num / denom                                 # (b,h,L,dh)
        # carry update to end of chunk
        m_new = jnp.maximum(Fc[..., -1] + M,
                            jnp.max(Fc[..., -1:] - Fc + ii, axis=-1))
        wgt = jnp.exp(Fc[..., -1:] - Fc + ii - m_new[..., None])  # (b,h,L)
        C_new = (jnp.exp(Fc[..., -1] + M - m_new)[..., None, None] * C
                 + jnp.einsum("bhl,bhld,bhle->bhde", wgt, kk, vv))
        N_new = (jnp.exp(Fc[..., -1] + M - m_new)[..., None] * N
                 + jnp.einsum("bhl,bhld->bhd", wgt, kk))
        return (C_new, N_new, m_new), h_out

    C0 = jnp.zeros((b, H, dh, dh), jnp.float32)
    N0 = jnp.zeros((b, H, dh), jnp.float32)
    M0 = jnp.full((b, H), -jnp.inf)
    (C, N, M), hs = jax.lax.scan(chunk_step, (C0, N0, M0), (qc, kc, vc, ic, fc))
    h = jnp.moveaxis(hs, 0, 2).reshape(b, H, sp, dh)[:, :, :s]  # (b,h,s,dh)
    h = h.transpose(0, 2, 1, 3).reshape(b, s, D).astype(x.dtype)
    h = rmsnorm(h, p["norm"])
    out = jnp.einsum("bsd,de->bse", h * jax.nn.silu(z), p["w_down"])
    return out, MLSTMState(C, N, M)


def init_mlstm_state(cfg, batch: int) -> MLSTMState:
    H, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    return MLSTMState(
        jnp.zeros((batch, H, dh, dh), jnp.float32),
        jnp.zeros((batch, H, dh), jnp.float32),
        jnp.full((batch, H), -jnp.inf))


def mlstm_decode(p: dict, x: jnp.ndarray, state: MLSTMState, cfg
                 ) -> tuple[jnp.ndarray, MLSTMState]:
    """One-token recurrent step (exact xLSTM eqs. 19-27)."""
    b, _, D = x.shape
    H = cfg.n_heads
    dh = D // H
    up = jnp.einsum("bsd,de->bse", x, p["w_up"])
    xm, z = jnp.split(up, 2, axis=-1)
    q = jnp.einsum("bsd,de->bse", xm, p["wq"])[:, 0].reshape(b, H, dh).astype(jnp.float32)
    k = jnp.einsum("bsd,de->bse", xm, p["wk"])[:, 0].reshape(b, H, dh).astype(jnp.float32) * dh ** -0.5
    v = jnp.einsum("bsd,de->bse", xm, p["wv"])[:, 0].reshape(b, H, dh).astype(jnp.float32)
    gates = jnp.einsum("bsd,dg->bsg", xm, p["w_if"])[:, 0].astype(jnp.float32)
    i_pre, f_pre = gates[..., :H], gates[..., H:]
    logf = -jax.nn.softplus(-f_pre)
    m_new = jnp.maximum(logf + state.m, i_pre)
    fw = jnp.exp(logf + state.m - m_new)
    iw = jnp.exp(i_pre - m_new)
    C = fw[..., None, None] * state.c + iw[..., None, None] * jnp.einsum(
        "bhd,bhe->bhde", k, v)
    N = fw[..., None] * state.n + iw[..., None] * k
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, N)),
                        jnp.exp(-m_new))[..., None]
    h = jnp.einsum("bhd,bhde->bhe", q, C) / denom
    h = h.reshape(b, 1, D).astype(x.dtype)
    h = rmsnorm(h, p["norm"])
    out = jnp.einsum("bsd,de->bse", h * jax.nn.silu(z), p["w_down"])
    return out, MLSTMState(C, N, m_new)


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------


class SLSTMState(NamedTuple):
    c: jnp.ndarray   # (b, d)
    n: jnp.ndarray   # (b, d)
    h: jnp.ndarray   # (b, d)
    m: jnp.ndarray   # (b, d)


def init_slstm(pf: ParamFactory, cfg) -> dict:
    D = cfg.d_model
    return {
        "w_in": pf.dense(D, 4 * D),     # z, i, f, o preacts from x
        "r": pf.dense(D, 4 * D, scale=D ** -0.5),  # recurrent (block approx)
        "w_down": pf.dense(D, D),
        "norm": pf.ones(D),
    }


def _slstm_cell(p, x_t, st: SLSTMState) -> SLSTMState:
    pre = (x_t @ p["w_in"].astype(jnp.float32)
           + st.h @ p["r"].astype(jnp.float32))
    D = st.c.shape[-1]
    z, i_pre, f_pre, o = jnp.split(pre, 4, axis=-1)
    logf = -jax.nn.softplus(-f_pre)
    m_new = jnp.maximum(logf + st.m, i_pre)
    fw = jnp.exp(logf + st.m - m_new)
    iw = jnp.exp(i_pre - m_new)
    c = fw * st.c + iw * jnp.tanh(z)
    n = fw * st.n + iw
    h = jax.nn.sigmoid(o) * c / jnp.maximum(n, 1.0)
    return SLSTMState(c, n, h, m_new)


def init_slstm_state(cfg, batch: int) -> SLSTMState:
    D = cfg.d_model
    z = jnp.zeros((batch, D), jnp.float32)
    return SLSTMState(z, z, z, jnp.full((batch, D), -jnp.inf))


def slstm_forward(p: dict, x: jnp.ndarray, cfg
                  ) -> tuple[jnp.ndarray, SLSTMState]:
    b, s, D = x.shape

    def step(st, x_t):
        st = _slstm_cell(p, x_t.astype(jnp.float32), st)
        return st, st.h

    st, hs = jax.lax.scan(step, init_slstm_state(cfg, b), x.swapaxes(0, 1))
    h = hs.swapaxes(0, 1).astype(x.dtype)
    h = rmsnorm(h, p["norm"])
    return jnp.einsum("bsd,de->bse", h, p["w_down"]), st


def slstm_decode(p: dict, x: jnp.ndarray, state: SLSTMState, cfg
                 ) -> tuple[jnp.ndarray, SLSTMState]:
    st = _slstm_cell(p, x[:, 0].astype(jnp.float32), state)
    h = st.h[:, None].astype(x.dtype)
    h = rmsnorm(h, p["norm"])
    return jnp.einsum("bsd,de->bse", h, p["w_down"]), st
