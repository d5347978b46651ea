#!/usr/bin/env python3
"""Smoke run of EinDecomp's main path on a TPU, at published widths.

    python chip_smoke.py [--seed N]          one chip: executor, serve, train
    python chip_smoke.py --chips 4 [--seed N]
                                             four chips: a ring-attention
                                             llama-7b block and an a2a
                                             mixtral-8x7b block, nothing else

One-chip phases, all in this one process:

* executor — one block period of llama-7b and of hymba-1.5b (prefill, seq
  2048, batch 1) compiled by ``Program.compile`` for the shard_map and the
  GSPMD executor on the one-chip mesh, each compared with a float32 dense
  oracle.  The shard_map program must hold a Pallas kernel
  (``tpu_custom_call``).
* serve — hymba-1.5b through ``repro.serving.ServingEngine`` (4 slots, kv
  block 16, max_seq 1024, 8 requests of 100-700 prompt tokens, 16 new
  tokens each); one request's prefill logits are compared with a float32
  forward of ``models/transformer.py``.
* train — xlstm-125m, three steps of ``repro.launch.train.train`` at seq
  2048 and batch 8; the first loss is compared with a float32 loss.

Weights and inputs are random, made from ``--seed``.  Each phase prints one
``phase ...`` line (model, widths, compile and run seconds, largest error
against its reference and its tolerance, ``tpu_custom_call`` count).  The
last line, printed only when every phase passed, is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or without the repository's ``src/`` beside this file, the
script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Tolerances, as (largest |got - want|) / (largest |want|) over the output.
#
# EXEC_TOL — executors vs the float32 oracle.  Both sides compute in float32
# with float32 matmuls; they differ only in summation order and tiling, so
# a block's logits agree to ~1e-6 of their scale (llama-7b and hymba-1.5b
# blocks on a TPU v5e: under 1e-6).  A matmul that silently ran a single
# bfloat16 pass (unit roundoff 2**-9 per operand) leaves ~5e-3 (same blocks,
# same chip), so 1e-4 sits 50x from each.
EXEC_TOL = 1e-4
# SERVE_TOL — the bfloat16 serving prefill vs a float32 forward over the
# same (bfloat16-valued) weights.  Activations round to bfloat16 at every
# op through 32 layers, which leaves ~5e-2 of the logit scale (hymba-1.5b,
# TPU v5e); logits of a wrong cache row, position or context are unrelated
# to the right ones and leave O(1).
SERVE_TOL = 2e-1
# TRAIN_TOL — the first training loss (bfloat16 forward) vs the float32
# loss on the same weights and batch, relative to the loss (~ln V ~ 11).
# A mean over 16k tokens averages the bfloat16 rounding to ~5e-5 (xlstm-125m,
# TPU v5e).
TRAIN_TOL = 1e-3

EXEC_SEQ = 2048          # prefill length of the executor blocks
RING_SEQ = 4096          # llama-7b block on four chips
A2A_SEQ = 2048           # mixtral-8x7b block on four chips


class SmokeFailure(Exception):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _rel_err(got, want) -> float:
    """Largest |got - want| over largest |want| (on the host, so the two
    may live on different devices)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += secs

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def _report(name: str, fields: dict) -> None:
    print(f"phase {name}: {json.dumps(fields, sort_keys=False)}", flush=True)


# ---------------------------------------------------------------------------
# Declared programs: seeded feeds and the float32 dense oracle
# ---------------------------------------------------------------------------


def seeded_feeds(g, vocab: int, seed: int) -> dict:
    """Device arrays for every graph input, made from ``seed``.  Weights
    are N(0, 1/fan_in), where fan_in is the product of the dims their
    consuming einsum contracts (1 for the embedding table), so activations
    stay O(1) through the block."""
    import jax
    import jax.numpy as jnp

    cons = g.consumers()

    def fan_in(n) -> int:
        for c in cons[n.nid]:
            node = g.nodes[c]
            if node.kind == "einsum":
                agg = set(node.spec.agg_labels)
                return math.prod(d for l, d in zip(n.labels, n.shape)
                                 if l in agg) or 1
        return 1

    @jax.jit
    def make(key):
        out = {}
        for i, n in enumerate(g.nodes[j] for j in g.input_ids()):
            k = jax.random.fold_in(key, i)
            if np.dtype(n.dtype) == np.int32:
                out[n.name] = jax.random.randint(k, n.shape, 0, vocab,
                                                 jnp.int32)
            else:
                out[n.name] = (jax.random.normal(k, n.shape, jnp.float32)
                               * fan_in(n) ** -0.5)
        return out

    return make(jax.random.PRNGKey(seed))


def dense_oracle(g, out_id: int):
    """``feeds -> output`` evaluating the graph node by node in jnp with
    every opaque op's dense reference (never its kernel), traced under
    float32 ("highest") matmul precision."""
    import jax

    from repro.core import engine, opdef

    def f(feeds):
        vals = {}
        for nid in g.topo_order():
            n = g.nodes[nid]
            args = [vals[a] for a in n.inputs]
            if n.kind == "input":
                vals[nid] = feeds[n.name]
            elif n.kind == "einsum":
                vals[nid] = engine.lower_einsum(n.spec, *args)
            else:
                od = opdef.require(n.op)
                fn = od.fn if od.fn is not None else od.executable
                kw = n.params if n.kind == "map" else n.call_params
                vals[nid] = fn(*args, **kw)
        return vals[out_id]

    jf = jax.jit(f)

    def run(feeds):
        with jax.default_matmul_precision("highest"):
            return jf(feeds)

    return run


def _compile_and_run(run, feeds):
    """AOT-compile a CompiledProgram's logits for these feeds; returns
    (logits, compile s, run s, tpu_custom_call count)."""
    import jax

    fn = jax.jit(lambda f: run(f)["logits"])
    t0 = time.perf_counter()
    exe = fn.lower(feeds).compile()
    t_compile = time.perf_counter() - t0
    kernels = exe.as_text().count("tpu_custom_call")
    t0 = time.perf_counter()
    out = exe(feeds).block_until_ready()
    return out, t_compile, time.perf_counter() - t0, kernels


def _block_program(cfg, seq: int):
    from repro.configs.base import ShapeConfig
    from repro.models.eingraphs import program_for
    from repro.models.opaque_stubs import capacity_of, make_stub_opaques

    prog = program_for(cfg, ShapeConfig("smoke", "prefill", seq, 1))
    # the declared MoE / scan opaques run their shared stand-ins
    make_stub_opaques(capacity_of(prog.graph))
    return prog


def _widths(cfg) -> dict:
    return {"d_model": cfg.d_model, "heads": cfg.n_heads,
            "kv_heads": cfg.n_kv_heads, "head_dim": cfg.hd,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab}


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def executor_phase(cfg, *, seed: int, mesh, seq: int = EXEC_SEQ,
                   tol: float = EXEC_TOL) -> None:
    prog = _block_program(cfg, seq)
    g = prog.graph
    feeds = seeded_feeds(g, cfg.vocab, seed)
    t0 = time.perf_counter()
    want = dense_oracle(g, prog._out["logits"])(feeds).block_until_ready()
    t_oracle = time.perf_counter() - t0
    for executor in ("shard_map", "gspmd"):
        run = prog.compile(mesh=mesh, executor=executor)
        got, t_c, t_r, kernels = _compile_and_run(run, feeds)
        err = _rel_err(got, want)
        _report(f"executor/{executor}", {
            "model": cfg.name, **_widths(cfg), "seq": seq, "batch": 1,
            "compile_s": t_c, "run_s": t_r, "oracle_s": t_oracle,
            "max_rel_err": err, "tol": tol, "tpu_custom_call": kernels})
        _check(bool(np.isfinite(err)) and err <= tol,
               f"{cfg.name} {executor}: error {err:.3g} > {tol:g}")
        if executor == "shard_map":
            _check(kernels > 0, f"{cfg.name} shard_map: no Pallas kernel "
                                "in the compiled program")


def _prompt_lengths(rng, n: int, lo: int, hi: int) -> list[int]:
    """``n`` lengths in [lo, hi]; at least two above 128 and not a
    multiple of 128, so the attention kernel pads inside its entry."""
    while True:
        lens = [int(x) for x in rng.integers(lo, hi + 1, size=n)]
        if sum(1 for L in lens if L > 128 and L % 128) >= 2:
            return lens


def serve_phase(cfg, *, seed: int, clock: CompileClock, batch: int = 4,
                block: int = 16, max_seq: int = 1024, n_requests: int = 8,
                max_new: int = 16, lengths: tuple = (100, 700),
                tol: float = SERVE_TOL) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tf
    from repro.serving import ServingEngine

    rng = np.random.default_rng(seed)
    lens = _prompt_lengths(rng, n_requests, *lengths)
    prompts = [rng.integers(0, cfg.vocab, size=L).astype(np.int32)
               for L in lens]
    clock.lap()
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, batch=batch, max_seq=max_seq, block=block,
                        seed=seed)
    for p in prompts:
        eng.submit(p, max_new)
    results, metrics = eng.run()
    wall = time.perf_counter() - t0
    t_c = clock.lap()
    _check(len(results) == n_requests,
           f"serve: {len(results)} of {n_requests} requests returned")
    short = {r: len(t) for r, t in results.items() if len(t) != max_new}
    _check(not short, f"serve: requests with != {max_new} tokens: {short}")
    _check(metrics.logits_finite, "serve: non-finite logits")

    # one request's prefill logits against a float32 forward
    r = next(i for i, L in enumerate(lens) if L > 128 and L % 128)
    L = lens[r]
    ent = eng.registry.prefill(L)
    toks = np.zeros((1, ent.key[2]), np.int32)
    toks[0, :L] = prompts[r]
    got, _ = ent.step(eng.params, {"tokens": toks}, jnp.int32(L - 1))
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), eng.params)
    fwd = jax.jit(lambda p, t: tf.forward(p, t, cfg32, remat=False,
                                          last_logit_only=True)[0])
    with jax.default_matmul_precision("highest"):
        want = fwd(p32, jnp.asarray(prompts[r][None]))
    err = _rel_err(got[0, -1], want[0, -1])
    del p32
    kernels = ent.step.lower(eng.params, {"tokens": toks},
                             jnp.int32(L - 1)).compile().as_text().count(
                                 "tpu_custom_call")
    _report("serve", {
        "model": cfg.name, **_widths(cfg), "layers": cfg.n_layers,
        "batch": batch, "kv_block": block, "max_seq": max_seq,
        "requests": n_requests, "max_new": max_new, "prompt_lens": lens,
        "compile_s": t_c, "run_s": wall - t_c,
        "tokens": metrics.tokens_generated, "decode_steps":
        metrics.decode_steps, "checked_request": r, "checked_len": L,
        "max_rel_err": err, "tol": tol, "tpu_custom_call": kernels})
    _check(bool(np.isfinite(err)) and err <= tol,
           f"serve: prefill error {err:.3g} > {tol:g}")


def train_phase(cfg, *, seed: int, clock: CompileClock, seq: int = 2048,
                batch: int = 8, steps: int = 3, tol: float = TRAIN_TOL,
                ref_chunk: int = 2) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig
    from repro.data.synthetic import SyntheticLM
    from repro.launch.train import train
    from repro.models import transformer as tf

    clock.lap()
    t0 = time.perf_counter()
    out = train(cfg, ShapeConfig("smoke", "train", seq, batch),
                steps_total=steps, log_every=1, seed=seed)
    wall = time.perf_counter() - t0
    t_c = clock.lap()
    losses = [loss for _, loss in out["history"]]
    _check(len(losses) == steps, f"train: {len(losses)} of {steps} losses")
    _check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    leaves = jax.tree.leaves(out["opt_state"])
    off = [leaf for leaf in leaves if not isinstance(leaf, jax.Array)
           or any(d.platform != "tpu" for d in leaf.devices())]
    _check(not off, f"train: {len(off)} optimizer-state leaves off the chip")

    # the first loss is the loss of the initial weights on batch 0: redo it
    # in float32, a few rows at a time (equal row counts, so the mean of
    # the chunk means is the batch mean)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       tf.init_params(cfg, jax.random.PRNGKey(seed)))
    toks = SyntheticLM(cfg.vocab, seq, batch, seed=seed).global_batch_at(0)
    loss_fn = jax.jit(lambda p, b: tf.loss_fn(p, b, cfg32)[0])
    with jax.default_matmul_precision("highest"):
        ref = np.mean([float(loss_fn(p32, {k: v[i:i + ref_chunk]
                                           for k, v in toks.items()}))
                       for i in range(0, batch, ref_chunk)])
    err = abs(losses[0] - ref) / abs(ref)
    _report("train", {
        "model": cfg.name, **_widths(cfg), "layers": cfg.n_layers,
        "seq": seq, "batch": batch, "steps": steps, "losses": losses,
        "compile_s": t_c, "run_s": wall - t_c, "ref_loss": float(ref),
        "max_rel_err": err, "tol": tol, "opt_state_leaves": len(leaves),
        "tpu_custom_call": "not counted (xlstm's path calls no Pallas "
                           "kernel)"})
    _check(err <= tol, f"train: first loss off by {err:.3g} > {tol:g}")


def four_chip_phase(llama, mixtral, *, seed: int, devices,
                    tol: float = EXEC_TOL) -> None:
    """Shard_map executor on a 2x2 mesh: llama-7b with attention on the
    ring rule (flash_attention_step + ppermute) and mixtral-8x7b with the
    MoE on the a2a rule (all_to_all + gmm), each against the same program
    unsharded on one chip and against the float32 oracle."""
    from jax.sharding import Mesh

    mesh4 = Mesh(np.array(devices[:4]).reshape(2, 2), ("data", "model"))
    mesh1 = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"))
    for cfg, seq, rule, node, coll in (
            (llama, RING_SEQ, "ring", "attn", "ppermute"),
            (mixtral, A2A_SEQ, "a2a", "dispatch", "all_to_all")):
        prog = _block_program(cfg, seq)
        g = prog.graph
        feeds = seeded_feeds(g, cfg.vocab, seed)
        want = dense_oracle(g, prog._out["logits"])(feeds)
        run4 = prog.compile(mesh=mesh4, executor="shard_map")
        tr = run4.collectives
        rules = {g.nodes[i].name: r for i, r in tr.rule_by_node.items()}
        got4, t_c4, t_r4, k4 = _compile_and_run(run4, feeds)
        got1, t_c1, t_r1, k1 = _compile_and_run(
            prog.compile(mesh=mesh1, executor="shard_map"), feeds)
        shards = sorted({s.device.id for s in got4.addressable_shards})
        errs = {"four_vs_oracle": _rel_err(got4, want),
                "one_vs_oracle": _rel_err(got1, want),
                "four_vs_one": _rel_err(got4, got1)}
        _report(f"four_chip/{rule}", {
            "model": cfg.name, **_widths(cfg), "seq": seq, "batch": 1,
            "mesh": {"data": 2, "model": 2}, "rules": rules,
            "collectives": dict(tr.counts), "compile_s": t_c4,
            "run_s": t_r4, "compile_s_one_chip": t_c1,
            "run_s_one_chip": t_r1, **errs, "tol": tol,
            "tpu_custom_call": k4, "tpu_custom_call_one_chip": k1,
            "output_shard_devices": shards})
        _check(rules.get(node) == rule,
               f"{cfg.name}: {node} ran the {rules.get(node)} rule")
        _check(tr.counts.get(coll, 0) > 0, f"{cfg.name}: no {coll}")
        _check(k4 > 0, f"{cfg.name}: no Pallas kernel on four chips")
        _check(shards != [0], f"{cfg.name}: every output shard on device 0")
        bad = {k: v for k, v in errs.items()
               if not (np.isfinite(v) and v <= tol)}
        _check(not bad, f"{cfg.name}: errors above {tol:g}: {bad}")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.mesh import make_host_mesh
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 3

    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {enable_compile_cache()}, seed {args.seed}",
          flush=True)
    clock = CompileClock()
    if args.chips == 4:
        phases = [("four_chip", lambda: four_chip_phase(
            get_config("llama-7b"), get_config("mixtral-8x7b"),
            seed=args.seed, devices=devices))]
    else:
        mesh = make_host_mesh((1, 1))
        phases = [
            ("executor/llama-7b", lambda: executor_phase(
                get_config("llama-7b"), seed=args.seed, mesh=mesh)),
            ("executor/hymba-1.5b", lambda: executor_phase(
                get_config("hymba-1.5b"), seed=args.seed, mesh=mesh)),
            ("serve", lambda: serve_phase(
                get_config("hymba-1.5b"), seed=args.seed, clock=clock)),
            ("train", lambda: train_phase(
                get_config("xlstm-125m"), seed=args.seed, clock=clock)),
        ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"chip_smoke: phase {name} FAILED", flush=True)
        print(f"chip_smoke: {name} took {time.perf_counter() - t0:.1f}s",
              flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
