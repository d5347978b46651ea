"""Execute an EinGraph with JAX, optionally under an EinDecomp plan.

This is the production counterpart of the TRA reference runtime
(core/tra.py): instead of physically pushing keyed sub-tensors through
join/agg/repartition operators, each node lowers to the corresponding jnp
op and the plan is applied as ``jax.lax.with_sharding_constraint`` on node
outputs.  GSPMD then materializes exactly the TRA dataflow — the join is the
per-device block computation, the aggregation is an all-reduce /
reduce-scatter over the mesh axes assigned to the contracted labels, and
repartitions appear as all-gather / all-to-all between nodes (DESIGN.md §2).

The engine is differentiable: ``jax.grad`` through ``run`` gives training
gradients (used by the FFNN experiment and the LM examples).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.einsum import EinGraph, EinSpec, Node, resolve_feeds

# ---------------------------------------------------------------------------
# Per-node lowering
# ---------------------------------------------------------------------------

_COMBINE2_J = {
    "mul": lambda x, y: x * y,
    "add": lambda x, y: x + y,
    "sub": lambda x, y: x - y,
    "div": lambda x, y: x / y,
    "sqdiff": lambda x, y: (x - y) ** 2,
    "absdiff": lambda x, y: jnp.abs(x - y),
    "maximum": jnp.maximum,
    "expsub": lambda x, y: jnp.exp(x - y),
}

_COMBINE1_J = {
    "id": lambda x: x,
    "exp": jnp.exp,
    "neg": lambda x: -x,
    "abs": jnp.abs,
    "square": lambda x: x * x,
}

_AGG_J = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min, "prod": jnp.prod}


def lower_einsum(spec: EinSpec, *args):
    """One EinSum node -> jnp.  Contractions go straight to jnp.einsum (XLA
    dot_general -> MXU; float32 operands at float32 precision, see
    ``kernels.tiling.mxu_precision``); general (⊗,⊕) nodes lower to
    broadcast + reduce."""
    if spec.is_contraction and (len(spec.in_labels) == 2 or (
            len(spec.in_labels) == 1 and spec.combine == "id")):
        from repro.kernels.tiling import mxu_precision

        return jnp.einsum(spec.einsum_str(), *args,
                          precision=mxu_precision(*(a.dtype for a in args)))

    all_labels = spec.all_labels

    def lift(arr, labels):
        perm_src = list(labels)
        for l in all_labels:
            if l not in perm_src:
                arr = arr[..., None]
                perm_src.append(l)
        return jnp.transpose(arr, [perm_src.index(l) for l in all_labels])

    lifted = [lift(a, ls) for a, ls in zip(args, spec.in_labels)]
    if len(lifted) == 2:
        joined = _COMBINE2_J[spec.combine](*lifted)
    else:
        joined = _COMBINE1_J[spec.combine](lifted[0])
    if spec.agg and spec.agg_labels:
        axes = tuple(i for i, l in enumerate(all_labels) if l in spec.agg_labels)
        joined = _AGG_J[spec.agg](joined, axis=axes)
    kept = [l for l in all_labels if l not in spec.agg_labels]
    return jnp.transpose(joined, [kept.index(l) for l in spec.out_labels])


# ---------------------------------------------------------------------------
# map / opaque execution registries.  Since the OpDef redesign these are
# *live views* over the one unified registry (core/opdef.py): built-in ops
# are declared in core/opdefs_builtin.py, new ops through ``ein.defop``.
# The views stay dict-compatible (shared with the dense numpy oracle — all
# impls are backend-polymorphic via jnp) so in-core callers and test
# monkeypatching keep working; direct use outside core/ is lint-banned.
# ---------------------------------------------------------------------------

from repro.core.opdef import MAP_FNS, OPAQUE_FNS  # noqa: E402


def register_opaque(name: str, fn: Callable) -> None:
    """Deprecated: register through the unified OpDef API instead —
    ``ein.defop(name, "<signature>", fn=...)`` bundles the signature, dense
    impl, kernel dispatcher, VJP, comm declaration, and shard rule in one
    record (this shim installs a bare impl with none of that metadata)."""
    from repro.core import opdef

    opdef.register_legacy(name, fn, surface="engine.register_opaque")


# ---------------------------------------------------------------------------
# Plan -> PartitionSpec
# ---------------------------------------------------------------------------


def mesh_axes_dict(mesh: Mesh) -> dict[str, int]:
    """{axis name: size} for a jax Mesh — the planner's mesh description.
    (Re-exported by launch/mesh.py; lives here so core never imports launch.)"""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for_node(node: Node, axes_by_label: dict[str, tuple[str, ...]]) -> P:
    """PartitionSpec for a node's output from its label->mesh-axes map."""
    entries = []
    for l in node.labels:
        ax = axes_by_label.get(l, ())
        if not ax:
            entries.append(None)
        elif len(ax) == 1:
            entries.append(ax[0])
        else:
            entries.append(tuple(ax))
    # trailing Nones can be dropped but keep explicit for clarity
    return P(*entries)


def plan_shardings(g: EinGraph, plan, mesh: Mesh) -> dict[int, NamedSharding]:
    """NamedSharding per node output for a mesh-mode plan."""
    out = {}
    for n in g.nodes:
        ax = plan.axes_by_node.get(n.nid, {})
        out[n.nid] = NamedSharding(mesh, spec_for_node(n, ax))
    return out


# ---------------------------------------------------------------------------
# Graph execution
# ---------------------------------------------------------------------------


def run(
    g: EinGraph,
    feeds: dict[int, Any],
    *,
    plan=None,
    mesh: Mesh | None = None,
    constrain: bool = True,
) -> dict[int, jnp.ndarray]:
    """Evaluate the graph with jnp.  If a mesh-mode plan is given, each node
    output gets a ``with_sharding_constraint`` so GSPMD realizes the
    EinDecomp decomposition.

    ``feeds`` may be keyed by input *name* or node id (resolve_feeds): the
    reference runtimes and the frontend agree on I/O keys."""
    feeds = resolve_feeds(g, feeds)
    specs = None
    if plan is not None and mesh is not None and plan.axes_by_node:
        specs = {nid: NamedSharding(
            mesh, spec_for_node(g.nodes[nid], plan.axes_by_node.get(nid, {})))
            for nid in range(len(g.nodes))}

    vals: dict[int, jnp.ndarray] = {}
    for nid in g.topo_order():
        n = g.nodes[nid]
        if n.kind == "input":
            v = jnp.asarray(feeds[nid])
        elif n.kind == "einsum":
            v = lower_einsum(n.spec, *[vals[a] for a in n.inputs])
        elif n.kind == "map":
            v = MAP_FNS[n.op](vals[n.inputs[0]], **n.params)
        else:
            v = OPAQUE_FNS[n.op](*[vals[a] for a in n.inputs], **n.call_params)
        if specs is not None and constrain and nid in specs:
            v = jax.lax.with_sharding_constraint(v, specs[nid])
        vals[nid] = v
    return vals


#: executors ``make_runner`` / ``Program.compile`` can build:
#:   gspmd     — per-node ``with_sharding_constraint`` hints; XLA's
#:               partitioner chooses the realized collective schedule.
#:   shard_map — core/spmd.py: the plan's TRA dataflow emitted literally as
#:               named collectives inside one ``jax.shard_map``; opaque
#:               nodes dispatch per-shard through the shard-rule registry
#:               (core/opaque_rules.py: ring attention, a2a expert
#:               parallelism, replicate fallback).
EXECUTORS = ("gspmd", "shard_map")


def make_runner(g: EinGraph, out_ids: Sequence[int] | None = None, *,
                plan=None, mesh: Mesh | None = None, cache=None,
                mesh_axes: dict[str, int] | None = None, p: int | None = None,
                cost_mode: str = "paper",
                offpath_repart: bool = True,
                executor: str = "gspmd",
                collective_trace=None,
                fuse: bool = True,
                lookahead: int = 1) -> Callable:
    """Build a jit-able ``f(feed_list) -> outputs`` for the graph.  Feeds are
    passed positionally in input-node order (differentiable wrt any of them).

    ``executor`` selects how the plan is realized (see ``EXECUTORS``):
    ``"gspmd"`` (default) applies sharding constraints and lets XLA pick the
    collectives; ``"shard_map"`` emits the plan's join→agg→repartition
    dataflow as explicit collectives (requires a mesh-mode plan and a mesh —
    a bare ``mesh`` therefore self-plans under shard_map, where the gspmd
    executor would run unconstrained).
    ``collective_trace`` (a ``core.spmd.CollectiveTrace``) receives the
    static collective schedule of the shard_map executor at build time —
    including the per-node / per-shard-rule attribution (``rule_by_node``,
    ``by_rule``) of the opaque ring/a2a programs.  ``fuse`` (default on,
    shard_map only) routes repartitions through the fused chain planner
    when it moves fewer wire elems; ``fuse=False`` restores the unfused
    per-step lowering.  ``lookahead`` (default 1, shard_map only) is the
    graph-wide overlap window — ready consumers' arg repartitions issue up
    to that many compute nodes early so collectives fly behind local
    compute; ``lookahead=0`` restores the serial issue order verbatim.

    If no ``plan`` is given but planning inputs are (``p``, ``mesh_axes``,
    or a ``mesh`` together with a ``cache``), the runner plans the graph
    itself — consulting ``cache`` (a ``core.plancache.PlanCache``) before
    running the DP, so repeated runner construction for isomorphic graphs
    pays planner latency once.  Sharding constraints only apply when a
    ``mesh`` is given; without one, self-planning is allowed solely to warm
    a ``cache`` (planning with neither is an error — the DP's result would
    be discarded).  An explicit ``plan`` always takes precedence: the other
    planning inputs (``cache``/``p``/``mesh_axes``/``cost_mode``/
    ``offpath_repart``) are then ignored, and in particular the cache is
    not warmed with a caller-provided plan (its planning inputs are
    unknown, so no sound cache key exists for it)."""
    if executor not in EXECUTORS:
        raise ValueError(f"make_runner: unknown executor {executor!r}; "
                         f"choose from {EXECUTORS}")
    if collective_trace is not None and executor != "shard_map":
        raise ValueError("make_runner: collective_trace is only produced by "
                         "the shard_map executor")
    if (plan is None and cache is not None and mesh is None
            and p is None and mesh_axes is None):
        raise ValueError(
            "make_runner: cache given but nothing to plan with — pass "
            "mesh, mesh_axes, or p")
    if plan is None and (p is not None or mesh_axes is not None
                         or (cache is not None and mesh is not None)
                         or (executor == "shard_map" and mesh is not None)):
        from repro.core.decomp import eindecomp

        if mesh is None and cache is None:
            raise ValueError(
                "make_runner: planning inputs (p/mesh_axes) have no effect "
                "without a mesh to shard by or a cache to warm")
        if mesh_axes is None and mesh is not None:
            mesh_axes = mesh_axes_dict(mesh)
        if p is None:
            if not mesh_axes:
                raise ValueError("make_runner: planning needs p or mesh/mesh_axes")
            p = math.prod(mesh_axes.values())
        plan = eindecomp(g, p, mesh_axes=mesh_axes, cost_mode=cost_mode,
                         offpath_repart=offpath_repart, cache=cache)
    in_ids = g.input_ids()
    out_ids = list(out_ids) if out_ids is not None else g.outputs()

    if executor == "shard_map":
        from repro.core import spmd

        if mesh is None or plan is None:
            raise ValueError("make_runner: executor='shard_map' needs a "
                             "mesh and a (mesh-mode) plan")
        mapped = spmd.make_spmd_runner(g, out_ids, plan=plan, mesh=mesh,
                                       trace=collective_trace, fuse=fuse,
                                       lookahead=lookahead)

        def f_spmd(*arrays):
            outs = mapped(*arrays)
            return outs[0] if len(outs) == 1 else outs

        return f_spmd

    def f(*arrays):
        feeds = dict(zip(in_ids, arrays))
        vals = run(g, feeds, plan=plan, mesh=mesh)
        outs = tuple(vals[o] for o in out_ids)
        return outs[0] if len(outs) == 1 else outs

    return f
