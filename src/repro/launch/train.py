"""Training driver: real training loop with checkpoint/restart, elastic
resharding, deterministic data replay and async checkpointing.

On a real cluster each host runs this under ``jax.distributed.initialize``
(one process per host; the mesh spans all pods).  On this container it runs
the same code path over the local devices — ``examples/train_lm.py`` drives
a ~100M-param model for a few hundred steps.

Fault tolerance (DESIGN.md §7):
  * checkpoints carry {params, opt_state, step} + the mesh/plan manifest;
  * restore reshards onto whatever mesh the restarted job has (elastic) —
    EinDecomp replans for the new device count;
  * the data pipeline is counter-based, so step N's global batch is
    identical across restarts regardless of host count;
  * checkpoint writes happen on a background thread (never blocks a step).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.data.synthetic import SyntheticLM, batch_shardings
from repro.launch import steps
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, mesh_axes_dict
from repro.models import transformer as tf
from repro.models.eingraphs import fsdp_axes_for, program_for
from repro.optim import adamw_init
from repro.optim.schedules import cosine_schedule, wsd_schedule


def train(cfg, shape: ShapeConfig, *, steps_total: int = 100,
          mesh=None, ckpt_dir: str | None = None, ckpt_every: int = 50,
          schedule: str = "cosine", peak_lr: float = 3e-4,
          log_every: int = 10, seed: int = 0, plan_cache=None,
          executor: str = "gspmd", pp: int = 1,
          microbatches: int = 1) -> dict:
    mesh = mesh or make_host_mesh()
    axes = mesh_axes_dict(mesh)
    if pp > 1:
        _print_pipeline_summary(cfg, shape, axes, pp, microbatches)
    # warm-start planning from the persistent cache: on restart (or elastic
    # reshard onto a mesh some earlier job already planned) the §8 DP is a
    # cache hit instead of a re-run.  The training path runs on the Program
    # surface: declare -> trace -> decompose (cached) -> project to policy.
    compiled = program_for(cfg, shape).compile(
        mesh_axes=axes, cache=plan_cache,
        mesh=mesh if executor == "shard_map" else None, executor=executor)
    policy = compiled.policy(fsdp_axes=fsdp_axes_for(axes))
    if compiled.collectives is not None:
        print(f"[train] shard_map executor schedule for {cfg.name}:")
        print(compiled.collectives.summary())

    if schedule == "wsd":
        lr_fn = lambda s: wsd_schedule(s, peak_lr=peak_lr,
                                       warmup=max(steps_total // 10, 1),
                                       stable=steps_total // 2,
                                       decay=max(steps_total // 5, 1))
    else:
        lr_fn = lambda s: cosine_schedule(s, peak_lr=peak_lr,
                                          warmup=max(steps_total // 10, 1),
                                          total=steps_total)

    params = tf.init_params(cfg, jax.random.PRNGKey(seed))
    pshard = tf.param_shardings(cfg, policy, mesh)
    params = jax.device_put(params, pshard)
    opt_state = adamw_init(params)
    step_fn = jax.jit(
        steps.make_train_step(cfg, policy=policy, mesh=mesh, lr_fn=lr_fn),
        donate_argnums=(0, 1))

    data = SyntheticLM(cfg.vocab, shape.seq - cfg.prefix_len, shape.batch,
                       seed=seed)
    bshard = batch_shardings(policy, mesh,
                             tf.input_specs(cfg, shape))

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if mgr is not None:
        restored = mgr.restore_latest(
            (params, opt_state),
            shardings=(pshard, jax.tree.map(lambda s: None, opt_state)))
        if restored is not None:
            start, (params, opt_state), _ = restored
            print(f"[train] restored step {start} (elastic reshard onto "
                  f"{axes})")

    history = []
    t0 = time.time()
    for step in range(start, steps_total):
        hb = data.global_batch_at(step)
        batch = {"tokens": jax.device_put(hb["tokens"], bshard["tokens"]),
                 "labels": jax.device_put(hb["labels"], bshard["labels"])}
        if cfg.prefix_len:
            rng = np.random.default_rng(step)
            pe = rng.normal(size=(shape.batch, cfg.prefix_len,
                                  cfg.d_model)).astype(np.float32)
            batch["prefix_embeds"] = jax.device_put(
                pe, bshard["prefix_embeds"])
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % log_every == 0 or step == steps_total - 1:
            loss = float(metrics["loss"])
            history.append((step, loss))
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"({time.time() - t0:.1f}s)", flush=True)
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, (params, opt_state))
    if mgr is not None:
        mgr.save(steps_total, (params, opt_state), blocking=True)
    return {"history": history, "params": params, "opt_state": opt_state}


def _print_pipeline_summary(cfg, shape: ShapeConfig, intra_axes: dict,
                            pp: int, microbatches: int) -> None:
    """Static pipeline report for the forward program: partition the graph
    into ``pp`` stages over a combined (pp, intra) mesh, price the GPipe
    bubble and handoff wire, and print the fill/drain summary.  The
    training step itself still runs the unpipelined plan — 1F1B grad-path
    pipelining is the pipeline tier's documented stretch goal."""
    from repro.pipeline import PipelineSpec, build_pipeline_schedule

    prog = program_for(cfg, shape)
    combined = {"pp": pp, **intra_axes}
    psched = build_pipeline_schedule(
        prog.graph, PipelineSpec(stages=pp, microbatches=microbatches),
        combined, [prog._out[k] for k in prog._out])
    cut_b = sum(psched.cut_elems) * 4
    print(f"[train] pipeline (static): p={pp} m={psched.spec.microbatches} "
          f"bubble={psched.bubble:.3f} "
          f"(weighted {psched.bubble_weighted:.3f}) "
          f"cut={cut_b:,}B handoff={psched.handoff_elems:,} elems")
    for st in psched.stages:
        print(f"[train]   stage {st.index}: {len(st.nids)} nodes, "
              f"recv {len(st.recv)} tensors")
    print("[train] note: the optimizer step runs the unpipelined plan "
          "(1F1B grad pipelining is the tier's stretch goal)")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--schedule", default="cosine")
    ap.add_argument("--plan-cache", default=None,
                    help="path to a persistent plan-cache JSON store; "
                         "warm-starts the planner across restarts")
    ap.add_argument("--executor", default="gspmd",
                    choices=["gspmd", "shard_map"],
                    help="plan realization: GSPMD sharding hints, or the "
                         "explicit-collective shard_map executor "
                         "(prints its static collective schedule)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages: with --pp > 1, partition the "
                         "forward graph over a pp mesh axis and print the "
                         "static GPipe schedule (bubble, cut bytes, "
                         "handoff wire) before training")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="GPipe microbatches per step for the --pp summary "
                         "(must divide --batch)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    shape = ShapeConfig("cli", "train", args.seq, args.batch)
    train(cfg, shape, steps_total=args.steps, ckpt_dir=args.ckpt,
          schedule=args.schedule, plan_cache=args.plan_cache,
          executor=args.executor, pp=args.pp,
          microbatches=args.microbatches)


if __name__ == "__main__":
    main()
