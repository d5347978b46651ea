"""The one traffic generator: it reads a mix file and draws from ``--seed``.

A serving mix is an open loop: ``initial`` requests are due when the
window opens, then one every ``1 / rate_per_s`` seconds until it closes,
whatever the engine does.  Every seed gets the same work: lengths are not
sampled but taken at fixed quantiles of the mix's distributions (median,
spread, range), prompt lengths moved onto the mix's grid, paired and put
in one fixed order that every seed sends; the seed draws every token.  In
a window shorter than a long answer, which requests arrive when decides
how much work the window holds, so a seed that reordered them would change
the work (tokens/s spread by 20% over six seeds that did).
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> list[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with
    this median and log-space ``sigma``, rounded and clipped to [lo, hi]."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def snap(values: list[int], grid: list[int]) -> list[int]:
    """Each value moved to the nearest grid length (in log space)."""
    g = np.asarray(sorted(grid), float)
    return [int(g[np.argmin(np.abs(np.log(g) - math.log(v)))])
            for v in values]


def request_lengths(mix: dict) -> tuple[list[int], list[int]]:
    """The prompt and answer lengths of the mix's ``lengths`` requests, in
    the order they are sent (request ``i`` takes pair ``i % lengths``)."""
    n = int(mix["lengths"])
    p, o = mix["prompt"], mix["output"]
    prompts = snap(lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                       p["max"]), p["grid"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    fixed = np.random.default_rng(0)
    return ([prompts[i] for i in fixed.permutation(n)],
            [outputs[i] for i in fixed.permutation(n)])


def prompt_grid(mix: dict) -> list[int]:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    return sorted(set(request_lengths(mix)[0]))


def due_times(mix: dict, seconds: float) -> list[float]:
    """When each request of a window of ``seconds`` is due, from its
    opening: ``initial`` at 0, then one every ``1 / rate_per_s``."""
    gap = 1.0 / float(mix["rate_per_s"])
    n_rate = max(0, math.ceil(seconds / gap - 1e-9) - 1)
    return [0.0] * int(mix["initial"]) + [gap * (k + 1)
                                          for k in range(n_rate)]


def serve_schedule(mix: dict, seed: int, vocab: int,
                   seconds: float) -> list[tuple[float, np.ndarray, int]]:
    """``(due seconds, prompt int32 array, max_new)`` of every request
    due in a window of ``seconds``."""
    prompts, outputs = request_lengths(mix)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    out = []
    for i, due in enumerate(due_times(mix, seconds)):
        k = i % len(prompts)
        out.append((due, rng.integers(0, vocab, size=prompts[k],
                                      dtype=np.int32), outputs[k]))
    return out
