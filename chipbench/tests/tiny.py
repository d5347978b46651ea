"""A benchmark tree at tiny sizes, for driving the harness on the CPU."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.spec import ROOT

HYMBA = {
    "name": "tiny-hymba", "family": "hybrid", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
    "vocab": 500, "window": 16, "block_pattern": ["hymba"], "ssm_state": 8,
    "ssm_conv": 4, "rope_theta": 10000.0, "norm_eps": 1e-06, "act": "silu",
    "gated_ffn": True, "tie_embeddings": False, "dtype": "float32"}

# the chat mix at tiny sizes: a window of a few tenths of a second
TINY_CHAT = {
    "slots": 4, "max_seq": 96, "kv_block": 8, "initial": 4,
    "rate_per_s": 20, "lengths": 12,
    "prompt": {"median": 24, "sigma": 0.5, "min": 9, "max": 40,
               "grid": [9, 24, 40]},
    "output": {"median": 8, "sigma": 0.5, "min": 3, "max": 20},
    "trace": {"start_s": 0, "seconds": 0.2}}


def write_tree(root: Path, limit: float | None = None,
               model: dict | None = None, requests: int = 3) -> Path:
    """BENCHMARK.json and the files it names under ``root``, with the
    model (``HYMBA`` by default) and the mix cut to tiny sizes, and the
    limits (where given) and the requests compared replaced;
    the real tree's metrics, readers and runners are reused."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs").mkdir(parents=True, exist_ok=True)
    (root / "chipbench" / "traffic").mkdir(parents=True, exist_ok=True)
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["model"] = dict(model or HYMBA)
        (root / c["file"]).write_text(json.dumps(cfg))
    for w in bench["workloads"]:
        name = f"{w['traffic']}.json"
        mix = json.loads((ROOT / "chipbench" / "traffic" / name).read_text())
        mix.update(TINY_CHAT)
        mix["check"]["requests"] = requests
        if limit is not None:
            mix["check"]["limits"] = dict.fromkeys(mix["check"]["limits"],
                                                   limit)
        (root / "chipbench" / "traffic" / name).write_text(json.dumps(mix))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
