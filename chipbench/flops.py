"""Operations and bytes each cell's work needs, worked out from shapes.

Counted as the algorithm needs them, never as a program happens to run
them: causal and windowed attention count only the key positions a query
may see, a decode step counts its own context and not the pool's
capacity, the LM head of a prefill counts the one position it is read at,
and training counts forward plus backward (twice the forward for the
backward) with no recompute.  A multiply-add is two operations.
"""
from __future__ import annotations


def attn_pairs(length: int, window: int) -> int:
    """(query, key) pairs a causal, optionally windowed, attention over
    ``length`` positions computes."""
    if not window or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


# -- hymba (repo block: attention + SSM heads in parallel, gated FFN) --------


def _hymba_token_matmul(m: dict) -> int:
    """Per token per layer: every projection, the SSM's elementwise scan
    (6 operations per state element) and the FFN — attention scores apart."""
    D, H, K, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    F, n, kc = m["d_ff"], m["ssm_state"], m["ssm_conv"]
    attn = 2 * D * (H + 2 * K) * hd + 2 * H * hd * D
    ssm = 2 * D * 2 * D + 2 * D * (2 * n + 1) + 2 * D * D + 2 * kc * D + 6 * D * n
    ffn = 2 * 3 * D * F
    return attn + ssm + ffn


def prefill(m: dict, length: int) -> int:
    """One prefill of ``length`` tokens, LM head at the last one."""
    per_layer = (length * _hymba_token_matmul(m)
                 + 4 * m["head_dim"] * m["n_heads"]
                 * attn_pairs(length, m["window"]))
    return m["n_layers"] * per_layer + 2 * m["d_model"] * m["vocab"]


def decode_token(m: dict, position: int) -> int:
    """The token fed at ``position`` (0-based), attending over its context."""
    ctx = position + 1 if not m["window"] else min(position + 1, m["window"])
    per_layer = _hymba_token_matmul(m) + 4 * m["head_dim"] * m["n_heads"] * ctx
    return m["n_layers"] * per_layer + 2 * m["d_model"] * m["vocab"]


def serve_work(m: dict, requests: list[tuple[int, int]]) -> dict:
    """{"prefill": ..., "decode": ...} operations of finished requests given
    as (prompt length, tokens served); the first token comes from the
    prefill, token j > 0 from the decode step fed at L - 1 + j."""
    pre = sum(prefill(m, L) for L, _ in requests)
    dec = sum(decode_token(m, L - 1 + j) for L, n in requests
              for j in range(1, n))
    return {"prefill": pre, "decode": dec}


def flash_attention_prefill(m: dict, lengths: list[int],
                            itemsize: int = 2) -> dict:
    """The flash-attention kernel's operations and bytes over prefills of
    these lengths: scores and values over the visible pairs; q, k, v read
    once and the output written once."""
    H, K, hd, L = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["n_layers"]
    fl = sum(4 * hd * H * attn_pairs(s, m["window"]) for s in lengths) * L
    by = sum((2 * H + 2 * K) * s * hd * itemsize for s in lengths) * L
    return {"flops": fl, "bytes": by}
