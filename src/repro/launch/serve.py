"""Serving driver: batched prefill + decode loop with KV/state caches.

``serve`` takes a batch of prompts, prefills them in one fused forward
(returning per-layer caches), then decodes greedily token-by-token with the
jitted serve_step.  Sliding-window archs keep ring-buffer caches, recurrent
archs carry constant-size state — the 500k-token decode shape runs in O(1)
memory per token (docs/architecture.md, "Serving tier").

``--continuous`` switches to the serving tier proper
(``repro.serving.ServingEngine``): slot-based continuous batching over a
paged KV-block pool, with prefill programs resolved through the
shape-bucket registry and the plan cache.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, mesh_axes_dict
from repro.models import transformer as tf
from repro.models.attention import KVCache
from repro.models.eingraphs import program_for


def _ring_pack(cache_kv: KVCache, prompt_len: int, window: int) -> KVCache:
    """Re-pack a prefill cache (time-ordered) into decode ring order.
    Layout is (L, b, S, kh, hd) stacked per unit."""
    take = min(window, prompt_len)
    slots = (prompt_len - take + np.arange(take)) % window

    def pack(x):
        ring = jnp.zeros(x.shape[:2] + (window,) + x.shape[3:], x.dtype)
        src = x[:, :, prompt_len - take:prompt_len]
        return ring.at[:, :, slots].set(src)

    return KVCache(pack(cache_kv.k), pack(cache_kv.v))


def prepare_decode_caches(cfg, prefill_caches, prompt_len: int, kv_len: int):
    """Convert prefill-collected caches into decode-ready buffers."""
    out = []
    for blk, cache in zip(cfg.block_pattern, prefill_caches):
        if blk in ("attn", "hymba"):
            kv = cache[0] if blk == "hymba" else cache
            k, v = kv
            if cfg.window:
                kv2 = _ring_pack(KVCache(k, v), prompt_len, kv_len)
            else:
                pad = kv_len - k.shape[2]
                k2 = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                v2 = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
                kv2 = KVCache(k2, v2)
            out.append((kv2, cache[1]) if blk == "hymba" else kv2)
        else:
            out.append(cache)
    return out


def decode_loop(decode, params, caches, first_tok, prompt_len: int,
                max_new: int):
    """Greedy decode: ``max_new`` tokens total — the prefill's argmax plus
    ``max_new - 1`` decode steps, every step's logits consumed.

    (The historical loop appended the prefill token first but still ran
    ``max_new`` decode steps, so the final call's logits were computed and
    thrown away — one wasted step per request, and a tok/s figure counting
    a token the decode path never produced.)

    Tokens are accumulated **on device** and fetched with a single host
    transfer at the end: the previous ``np.asarray(tok)`` per iteration
    blocked the host on every step, serializing dispatch against compute
    and capping tok/s at the round-trip latency — greedy argmax feeds the
    next step from device memory just fine, so the loop now runs fully
    async under jax's dispatch queue.

    Returns ``(generations (b, max_new) int32, caches, decode_steps)``.
    """
    b = first_tok.shape[0]
    if max_new <= 0:
        return np.zeros((b, 0), np.int32), caches, 0
    outs = [first_tok]
    tok = first_tok
    steps = 0
    for i in range(max_new - 1):
        logits, caches = decode(params, tok, caches, jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        outs.append(tok)
        steps += 1
    return np.asarray(jnp.concatenate(outs, axis=1)), caches, steps


def serve(cfg, prompts: np.ndarray, *, max_new: int = 32, mesh=None,
          kv_len: int | None = None, params=None, greedy: bool = True,
          seed: int = 0, plan_cache=None, executor: str = "gspmd"):
    """prompts: (b, prompt_len) int32.  Returns (b, max_new) generations.

    ``plan_cache`` is a ``core.plancache.PlanCache`` or a path to its JSON
    store: the planner warm-starts from it (a structurally identical graph
    planned by any earlier process is a cache hit, skipping the §8 DP) and
    persists the plan it used for the next restart.

    ``executor`` selects how the cell's Program realizes its plan
    (``engine.EXECUTORS``); with ``"shard_map"`` the compiled program's
    static collective schedule is printed (the serving steps themselves
    still run the production transformer stack under the derived policy).
    """
    mesh = mesh or make_host_mesh()
    b, prompt_len = prompts.shape
    kv_len = kv_len or (cfg.kv_len(ShapeConfig("serve", "decode",
                                               prompt_len + max_new, b)))
    shape = ShapeConfig("serve", "prefill", prompt_len, b)
    # declare -> trace -> decompose (through the plan cache) -> project:
    # the serving path runs entirely on the Program surface.
    compiled = program_for(cfg, shape).compile(
        mesh_axes=mesh_axes_dict(mesh), cache=plan_cache,
        mesh=mesh if executor == "shard_map" else None, executor=executor)
    policy = compiled.policy()
    if compiled.collectives is not None:
        print(f"[serve] shard_map executor schedule for {cfg.name}:")
        print(compiled.collectives.summary())

    if params is None:
        params = tf.init_params(cfg, jax.random.PRNGKey(seed))
    params = jax.device_put(params, tf.param_shardings(cfg, policy, mesh))

    prefill = jax.jit(steps.make_prefill_step(cfg, policy=policy, mesh=mesh))
    decode = jax.jit(steps.make_serve_step(cfg, policy=policy, mesh=mesh),
                     donate_argnums=(2,))

    t0 = time.perf_counter()
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompts)})
    caches = prepare_decode_caches(cfg, caches, prompt_len, kv_len)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    t0 = time.perf_counter()
    gen, caches, decode_steps = decode_loop(decode, params, caches, tok,
                                            prompt_len, max_new)
    t_decode = time.perf_counter() - t0
    return gen, {"t_prefill_s": t_prefill, "t_decode_s": t_decode,
                 "decode_steps": decode_steps,
                 "tok_per_s": b * decode_steps / max(t_decode, 1e-9)}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--plan-cache", default=None,
                    help="path to a persistent plan-cache JSON store; "
                         "warm-starts the planner across restarts")
    ap.add_argument("--executor", default="gspmd",
                    choices=["gspmd", "shard_map"],
                    help="plan realization: GSPMD sharding hints, or the "
                         "explicit-collective shard_map executor "
                         "(prints its static collective schedule)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine (repro.serving): "
                         "slot scheduler + paged KV pool + bucket registry; "
                         "prompts get mixed lengths around --prompt-len")
    ap.add_argument("--requests", type=int, default=8,
                    help="[--continuous] number of requests to submit")
    ap.add_argument("--kv-block", type=int, default=16,
                    help="[--continuous] KV pool block size (cache rows)")
    ap.add_argument("--max-seq", type=int, default=0,
                    help="[--continuous] per-request capacity ceiling "
                         "(prompt+generated); default prompt-len + max-new")
    ap.add_argument("--bucket", default="auto",
                    choices=["auto", "pow2", "exact"],
                    help="[--continuous] prefill bucket policy: pow2 "
                         "rounding for pad-free archs under 'auto'")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    rng = np.random.default_rng(0)

    if args.continuous:
        from repro.serving import ServingEngine

        max_seq = args.max_seq or (args.prompt_len + args.max_new)
        eng = ServingEngine(cfg, batch=args.batch, max_seq=max_seq,
                            block=args.kv_block, plan_cache=args.plan_cache,
                            bucket=args.bucket)
        for _ in range(args.requests):
            plen = int(rng.integers(max(1, args.prompt_len // 2),
                                    args.prompt_len + 1))
            eng.submit(rng.integers(0, cfg.vocab, size=(plen,)), args.max_new)
        results, metrics = eng.run()
        for rid in sorted(results):
            print(f"request {rid}: {results[rid]}")
        print(metrics.summary())
        print(eng.registry.stats)
        return

    prompts = rng.integers(0, cfg.vocab,
                           size=(args.batch, args.prompt_len)).astype(np.int32)
    gen, stats = serve(cfg, prompts, max_new=args.max_new,
                       plan_cache=args.plan_cache, executor=args.executor)
    print("generations:\n", gen)
    print(stats)


if __name__ == "__main__":
    main()
