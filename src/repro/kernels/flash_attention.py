"""FlashAttention for TPU in Pallas (the paper's attention hot-spot,
re-thought for the TPU memory hierarchy — DESIGN.md §2, adaptation 3).

Online-softmax attention with explicit VMEM tiling:

* grid = (batch, q_heads, q_blocks, kv_blocks); the kv axis is the innermost
  "arbitrary" (sequential) dimension so the output block is revisited and
  the running (m, l, acc) state lives in VMEM scratch.
* Q/K/V/O blocks are (1, 1, blk, d) slices; the kv-head index_map divides by
  the GQA group size so grouped-query attention reads each KV block once
  per query-head group member without materializing repeats in HBM.
* causal / sliding-window blocks that are fully masked are skipped via
  ``pl.when`` (no MXU work, no VMEM traffic for the P·V matmul).
* block sizes default to (128, 128) — MXU-aligned (multiples of 128 in the
  contracting and lane dims) and small enough that the working set
  q(128·d) + k,v(128·d each) + acc(128·d) fits VMEM for d ≤ 256.
* a sequence that is not a multiple of its block is zero-padded up to one:
  padded query rows are sliced off the output and padded keys get zero
  weight, so any prompt length runs the same kernel.

Numerics: scores and the running state are f32 regardless of input dtype
(bf16 in production); the output is cast back.  Float32 inputs take
float32 dots (``tiling.mxu_precision``), not the MXU's one bfloat16 pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import fit, mxu_precision, pad_to

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,          # inputs
    o_ref,                        # output
    m_ref, l_ref, acc_ref,        # VMEM scratch (carried over kv grid dim)
    *,
    scale: float,
    causal: bool,
    window: int,
    blk_q: int,
    blk_k: int,
    q_offset: int,
    kv_offset: int,
    kv_len: int,
    padded: bool,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * blk_q + q_offset
    k_start = ik * blk_k + kv_offset

    # block-level relevance: any (q, k) pair in this tile unmasked?
    relevant = True
    if causal:
        relevant = k_start <= q_start + blk_q - 1
    if window:
        relevant = jnp.logical_and(relevant, k_start + blk_k - 1 > q_start - window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (blk_q, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (blk_k, d)
        s = jax.lax.dot_general(                              # (blk_q, blk_k) on MXU
            q, k, (((1,), (1,)), ((), ())),
            precision=mxu_precision(q_ref.dtype, k_ref.dtype),
            preferred_element_type=jnp.float32)

        s = _mask_scores(s, q_start, k_start, causal, window)
        m_prev = m_ref[:, 0]                                  # (blk_q,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])                       # (blk_q, blk_k)
        if padded:
            p = _zero_padded_keys(p, ik * blk_k, kv_len)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)                   # (blk_k, d)
        pv = jax.lax.dot_general(                              # MXU
            p, v, (((1,), (0,)), ((), ())),
            precision=mxu_precision(v_ref.dtype),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[:, 0] = m_cur

    @pl.when(ik == nk - 1)
    def _fin():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: jnp.ndarray,  # (b, hq, sq, d)
    k: jnp.ndarray,  # (b, hkv, sk, d)
    v: jnp.ndarray,  # (b, hkv, sk, d)
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, "GQA requires hq % hkv == 0"
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else float(scale)
    blk_q, sqp = fit(sq, blk_q)
    blk_k, skp = fit(sk, blk_k)
    q = pad_to(q, 2, sqp)
    k, v = pad_to(k, 2, skp), pad_to(v, 2, skp)
    grid = (b, hq, sqp // blk_q, skp // blk_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, q_offset=q_offset, kv_offset=kv_offset,
        kv_len=sk, padded=skp != sk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # m
            pltpu.VMEM((blk_q, 1), jnp.float32),   # l
            pltpu.VMEM((blk_q, d), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]


def _mask_scores(s, q_start, k_start, causal: bool, window: int):
    """Causal / sliding-window mask of one (blk_q, blk_k) score tile at
    absolute positions ``q_start`` / ``k_start``."""
    if not (causal or window):
        return s
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    return jnp.where(mask, s, NEG_INF)


def _zero_padded_keys(p, k_first: int, kv_len: int):
    """Zero the softmax weights of the padded keys (array index >=
    ``kv_len``) in a tile whose first key has index ``k_first``."""
    kidx = k_first + jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    return jnp.where(kidx < kv_len, p, 0.0)


# ---------------------------------------------------------------------------
# Ring-attention step: one kv block folded into carried (m, l, acc) state
# ---------------------------------------------------------------------------


def _flash_step_kernel(
    offs_ref,                       # (1, 2) int32: [q_offset, kv_offset]
    q_ref, k_ref, v_ref,            # inputs
    m_in_ref, l_in_ref, acc_in_ref,  # carried state in
    m_out_ref, l_out_ref, acc_out_ref,  # carried state out
    m_s, l_s, acc_s,                # VMEM scratch (carried over kv grid dim)
    *,
    scale: float,
    causal: bool,
    window: int,
    blk_q: int,
    blk_k: int,
    kv_len: int,
    padded: bool,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = m_in_ref[0, 0]
        l_s[...] = l_in_ref[0, 0]
        acc_s[...] = acc_in_ref[0, 0]

    q_start = iq * blk_q + offs_ref[0, 0]
    k_start = ik * blk_k + offs_ref[0, 1]

    # No block skipping here: every tile runs with the finite-NEG_INF mask
    # so the state transition matches kernels/ref.py attention_step exactly
    # (a fully-masked tile contributes weight exp(NEG_INF - m) == 0).
    q = q_ref[0, 0].astype(jnp.float32) * scale            # (blk_q, d)
    k = k_ref[0, 0].astype(jnp.float32)                    # (blk_k, d)
    s = jax.lax.dot_general(                               # (blk_q, blk_k)
        q, k, (((1,), (1,)), ((), ())),
        precision=mxu_precision(q_ref.dtype, k_ref.dtype),
        preferred_element_type=jnp.float32)
    s = _mask_scores(s, q_start, k_start, causal, window)

    m_prev = m_s[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    if padded:
        p = _zero_padded_keys(p, ik * blk_k, kv_len)
    l_s[:, 0] = l_s[:, 0] * alpha + jnp.sum(p, axis=1)
    v = v_ref[0, 0].astype(jnp.float32)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        precision=mxu_precision(v_ref.dtype),
        preferred_element_type=jnp.float32)
    acc_s[...] = acc_s[...] * alpha[:, None] + pv
    m_s[:, 0] = m_cur

    @pl.when(ik == nk - 1)
    def _fin():
        m_out_ref[0, 0] = m_s[...]
        l_out_ref[0, 0] = l_s[...]
        acc_out_ref[0, 0] = acc_s[...]


def flash_attention_step(
    q: jnp.ndarray,  # (b, hq, sq, d)
    k: jnp.ndarray,  # (b, hkv, sk_blk, d) — one kv block of the ring
    v: jnp.ndarray,  # (b, hkv, sk_blk, d)
    carry: tuple | None = None,
    *,
    causal: bool = True,
    window: int = 0,
    scale: float | None = None,
    q_offset=0,      # absolute position of q[0]; int or traced scalar
    kv_offset=0,     # absolute position of k[0]; int or traced scalar
    blk_q: int = 128,
    blk_k: int = 128,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ring-attention step entry point: fold one kv block into the carried
    online-softmax state ``(m, l, acc)``.

    The offsets ride in as a (1, 2) int32 array, so they may be traced
    values (``lax.axis_index`` arithmetic inside ``shard_map``) — the causal
    / sliding-window masks compare against the block's *absolute* positions,
    which is what keeps rotated kv blocks correctly masked at every ring
    offset.  Finalize with ``ref.attention_finalize`` (acc / l).

    The carried ``m`` / ``l`` cross the kernel boundary as ``(b, hq, sq,
    1)`` columns: their ``(blk_q, 1)`` blocks meet the TPU's block rule
    (second-minor a multiple of 8, minor the whole dim), which ``(1, 1,
    blk_q)`` blocks over ``(b, hq, sq)`` do not.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, "GQA requires hq % hkv == 0"
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else float(scale)
    blk_q, sqp = fit(sq, blk_q)
    blk_k, skp = fit(sk, blk_k)
    grid = (b, hq, sqp // blk_q, skp // blk_k)

    if carry is None:
        m = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
        l = jnp.zeros((b, hq, sq), jnp.float32)
        acc = jnp.zeros((b, hq, sq, d), jnp.float32)
    else:
        m, l, acc = carry
    q = pad_to(q, 2, sqp)
    k, v = pad_to(k, 2, skp), pad_to(v, 2, skp)
    m = pad_to(m, 2, sqp, NEG_INF)[..., None]
    l = pad_to(l, 2, sqp)[..., None]
    acc = pad_to(acc, 2, sqp)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)]).reshape(1, 2)

    kernel = functools.partial(
        _flash_step_kernel, scale=scale, causal=causal, window=window,
        blk_q=blk_q, blk_k=blk_k, kv_len=sk, padded=skp != sk)

    state_spec = pl.BlockSpec((1, 1, blk_q, 1),
                              lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    m, l, acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda ib, ih, iq, ik: (0, 0)),
            pl.BlockSpec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, blk_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            state_spec, state_spec,
            pl.BlockSpec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_specs=[
            state_spec, state_spec,
            pl.BlockSpec((1, 1, blk_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sqp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sqp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sqp, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # m
            pltpu.VMEM((blk_q, 1), jnp.float32),   # l
            pltpu.VMEM((blk_q, d), jnp.float32),   # acc
        ],
        # the incoming carry is dead after the call: alias each (m, l, acc)
        # input buffer to its output so XLA updates the ring state in place
        # instead of allocating fresh HBM every ring step
        input_output_aliases={4: 0, 5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offs, q, k, v, m, l, acc)
    return m[:, :, :sq, 0], l[:, :, :sq, 0], acc[:, :, :sq]
