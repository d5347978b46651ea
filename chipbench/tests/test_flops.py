"""Operation counts against hand counts."""
import json

from chipbench import flops
from chipbench.spec import ROOT


def _cfg(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


def test_attention_pairs():
    assert flops.attn_pairs(4, 0) == 10           # 1 + 2 + 3 + 4
    assert flops.attn_pairs(5, 2) == 1 + 2 + 2 + 2 + 2
    assert flops.attn_pairs(3, 8) == 6


def test_one_hymba_layer_prefill_by_hand():
    m = dict(_cfg("hymba-1.5b")["model"], n_layers=1)
    L = 2048
    D, F = 1600, 5504
    qkvo = 2 * L * D * (25 + 5 + 5) * 64 + 2 * L * 25 * 64 * D
    ssm = L * (2 * D * 3200 + 2 * D * 33 + 2 * D * D + 2 * 4 * D + 6 * D * 16)
    ffn = 2 * L * 3 * D * F
    pairs = 1024 * 1025 // 2 + (2048 - 1024) * 1024
    attn = 4 * 64 * 25 * pairs
    head = 2 * D * 32001
    assert flops.prefill(m, L) == qkvo + ssm + ffn + attn + head


def test_decode_token_counts_its_window_only():
    m = _cfg("hymba-1.5b")["model"]
    near = flops.decode_token(m, 10)
    far = flops.decode_token(m, 5000)
    assert far - near == 32 * 4 * 64 * 25 * (1024 - 11)
