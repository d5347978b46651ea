"""Per-shard opaque dispatch (core/opaque_rules.py + core/spmd.py).

Four layers of coverage:

1. **Ring-step numerics** (device-free): chaining the online-softmax
   ``attention_step`` over every kv block — in any visit order, with the
   matching ``kv_offset`` — reproduces dense attention for causal,
   sliding-window, and GQA configs at every ring offset (the classic
   ring-attention off-by-one), for both the jnp reference and the Pallas
   step kernel (interpret mode).

2. **Schedule assertions** (device-free): the ring rule requests co-sharded
   q/kv layouts and emits exactly 2·(r-1) ppermute hops; the a2a rule emits
   the counts all-gather + two all_to_alls and lands the dispatch output in
   the plan's expert-sharded layout; structural precondition failures fall
   back to replicate; unknown/mixed rule declarations fail at plan time.

3. **Execution equivalence** on whatever host mesh exists: ring attention
   and a2a MoE (including real capacity drops) vs the dense oracle; the
   multi-device CI job re-runs this under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

4. **Cost accounting**: for every zoo family, traced wire elems of each
   ring/a2a-ruled opaque node stay within ``decomp.opaque_node_bound`` (the
   per-node slice of the §7 objective) — the bench_spmd --check property.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ShapeConfig
from repro.core import engine, opaque_rules, spmd
from repro.core.decomp import (Plan, eindecomp, opaque_node_bound, plan_cost)
from repro.core.einsum import EinGraph, eval_graph_dense
from repro.kernels import ref
from repro.launch.mesh import make_host_mesh
from repro.models.eingraphs import program_for
from repro.models.opaque_stubs import capacity_of, make_stub_opaques

RNG = np.random.default_rng(0)
N_DEV = len(jax.devices())


# ---------------------------------------------------------------------------
# 1. ring-step numerics: every offset, every config, any visit order
# ---------------------------------------------------------------------------

RING_CONFIGS = [
    # (causal, window, hq, hkv)
    (True, 0, 4, 4),    # causal MHA
    (True, 0, 4, 2),    # causal GQA
    (True, 0, 4, 1),    # causal MQA
    (True, 16, 4, 2),   # sliding window + GQA
    (False, 0, 4, 2),   # bidirectional
]


def _qkv(hq, hkv, b=2, s=32, d=16, scale=0.3):
    q = (RNG.normal(size=(b, hq, s, d)) * scale).astype(np.float32)
    k = (RNG.normal(size=(b, hkv, s, d)) * scale).astype(np.float32)
    v = (RNG.normal(size=(b, hkv, s, d)) * scale).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("r", [2, 4, 8])
@pytest.mark.parametrize("causal,window,hq,hkv", RING_CONFIGS)
def test_ring_chain_matches_dense_every_offset(causal, window, hq, hkv, r):
    """Fold the kv blocks in rotated ring order starting from every offset;
    each must reproduce the dense result (the rotation changes which blocks
    are causally masked — the off-by-one this test pins)."""
    q, k, v = _qkv(hq, hkv)
    s = q.shape[2]
    blk = s // r
    dense = np.asarray(ref.attention(q, k, v, causal=causal, window=window))
    for start in range(r):
        order = [(start - t) % r for t in range(r)]  # ring visit order
        carry = None
        for j in order:
            carry = ref.attention_step(
                q, k[:, :, j * blk:(j + 1) * blk],
                v[:, :, j * blk:(j + 1) * blk], carry,
                causal=causal, window=window, kv_offset=j * blk)
        got = np.asarray(ref.attention_finalize(carry, q.dtype))
        np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-6,
                                   err_msg=f"ring offset {start}")


@pytest.mark.parametrize("blk_q,blk_k", [(16, 8), (24, 6)],
                         ids=["aligned", "padded"])
@pytest.mark.parametrize("causal,window,hq,hkv", RING_CONFIGS[:3])
def test_pallas_step_kernel_matches_ref_chain(causal, window, hq, hkv,
                                              blk_q, blk_k):
    """(24, 6) blocks do not divide the 32-row q or the 8-row kv blocks:
    the kernel entry pads both, and padded keys must get zero weight."""
    from repro.kernels.flash_attention import flash_attention_step

    q, k, v = _qkv(hq, hkv)
    s = q.shape[2]
    r, blk = 4, s // 4
    dense = np.asarray(ref.attention(q, k, v, causal=causal, window=window))
    carry = None
    for j in [1, 3, 0, 2]:
        carry = flash_attention_step(
            q, k[:, :, j * blk:(j + 1) * blk],
            v[:, :, j * blk:(j + 1) * blk], carry,
            causal=causal, window=window, kv_offset=j * blk,
            blk_q=blk_q, blk_k=blk_k)
    got = np.asarray(ref.attention_finalize(carry, q.dtype))
    np.testing.assert_allclose(got, dense, rtol=1e-5, atol=1e-6)


def test_flash_attention_kernel_kv_offset():
    """The plain kernel's kv_offset shifts the mask exactly like the ref.
    Rows with no visible kv position are excluded: the kernel's block-skip
    outputs 0 there while the finite-NEG_INF reference averages (a corner
    no full-sequence chain ever hits)."""
    from repro.kernels.flash_attention import flash_attention

    q, k, v = _qkv(4, 2)
    blk = 8
    for off in (0, 8, 24):
        kb = k[:, :, off:off + blk]
        vb = v[:, :, off:off + blk]
        got = np.asarray(flash_attention(q, kb, vb, causal=True,
                                         kv_offset=off, blk_q=16, blk_k=8))
        want = np.asarray(ref.attention(q, kb, vb, causal=True,
                                        kv_offset=off))
        np.testing.assert_allclose(got[:, :, off:], want[:, :, off:],
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# helpers: hand-built graphs + plans
# ---------------------------------------------------------------------------

B, H, K, S, D = 2, 4, 2, 32, 16
E, CAP = 8, 4  # tiny capacity: 64 tokens, 32 slots -> real drops


def _attn_graph(window=0, kv_heads=K):
    g = EinGraph("ring")
    q = g.input("q", "b h s d", (B, H, S, D))
    k = g.input("k", "b k s d", (B, kv_heads, S, D))
    v = g.input("v", "b k s d", (B, kv_heads, S, D))
    o = g.opaque(
        "flash_attention", [q, k, v], "b h s d", (B, H, S, D),
        in_labels=[("b", "h", "s", "d"), ("b", "k", "s", "d"),
                   ("b", "k", "s", "d")],
        shardable={"b", "h", "k", "s"},
        comm=[{"kind": "ring", "label": "s", "input": 1, "rule": "ring"},
              {"kind": "ring", "label": "s", "input": 2, "rule": "ring"}],
        window=window)
    return g, o


def _moe_graph(seq=S):
    g = EinGraph("moe")
    x = g.input("x", "b s a", (B, seq, D))
    route = g.input("route", "b s e", (B, seq, E))
    disp = g.opaque(
        "moe_dispatch", [x, route], "e c a", (E, CAP, D),
        in_labels=[("b", "s", "a"), ("b", "s", "e")],
        shardable={"e", "c", "b", "s"},
        comm=[{"kind": "a2a", "label": "e", "input": 0, "rule": "a2a"}])
    comb = g.opaque(
        "moe_combine", [disp, route], "b s a", (B, seq, D),
        in_labels=[("e", "c", "a"), ("b", "s", "e")],
        shardable={"e", "c", "b", "s"},
        comm=[{"kind": "a2a", "label": "e", "input": -1, "rule": "a2a"}])
    return g, disp, comb


def _uniform_plan(g, axes_cfg, p=8):
    """Every non-input node gets the same label->axes map; graph inputs
    stay replicated (the executor then slices them locally, so the
    schedule assertions see only the rules' own collectives)."""
    plan = Plan(p=p, mode="mesh")
    for n in g.nodes:
        plan.d_by_node[n.nid] = {l: 1 for l in n.labels}
        plan.axes_by_node[n.nid] = {} if n.kind == "input" else dict(axes_cfg)
    return plan


# ---------------------------------------------------------------------------
# 2. schedule assertions (device-free)
# ---------------------------------------------------------------------------


def test_ring_schedule_ppermute_counts():
    g, o = _attn_graph()
    sizes = {"data": 2, "model": 4}
    plan = _uniform_plan(g, {"s": ("model",), "b": ("data",)})
    sched = spmd.build_schedule(g, plan, sizes, [o])
    tr = sched.trace
    assert tr.rule_by_node[o] == "ring"
    # 2 tensors x (r-1) hops, and never a kv all_gather
    assert tr.counts.get("ppermute", 0) == 2 * (4 - 1)
    assert tr.counts.get("all_gather", 0) == 0
    # q/k/v co-sharded: batch on data, sequence on model
    assert sched.layouts[o] == (("data",), (), ("model",), ())
    # ring wire == the declared (r-1) * numel per circulating tensor
    kv_numel = B * K * S * D
    perm_elems = sum(e.elems for e in tr.events if e.kind == "ppermute")
    assert perm_elems == 2 * (4 - 1) * kv_numel


def test_ring_schedule_local_when_sequence_unsharded():
    """b/h/k sharded, s unsharded: the rule runs fully local per shard —
    zero collectives, which is exactly what the DP priced (the replicated
    fallback would all_gather full K/V here)."""
    g, o = _attn_graph()
    sizes = {"data": 2, "model": 2}
    plan = _uniform_plan(g, {"b": ("data",), "h": ("model",)}, p=4)
    sched = spmd.build_schedule(g, plan, sizes, [o])
    assert sched.trace.rule_by_node[o] == "ring"
    assert len(sched.trace) == 0, sched.trace.summary()
    # kv heads co-sharded with q heads so the GQA group mapping is local
    assert sched.layouts[o] == (("data",), ("model",), (), ())


def test_ring_falls_back_when_heads_do_not_divide():
    g, o = _attn_graph()
    sizes = {"data": 2, "model": 4}
    # h sharded 4-way but only 2 kv heads: K % ph != 0 -> replicate
    plan = _uniform_plan(g, {"h": ("model",), "b": ("data",)})
    sched = spmd.build_schedule(g, plan, sizes, [o])
    assert sched.trace.rule_by_node[o] == "replicate"


def test_a2a_schedule_counts_and_layout():
    g, disp, comb = _moe_graph()
    sizes = {"data": 2, "model": 4}
    plan = _uniform_plan(g, {"e": ("data", "model")})
    sched = spmd.build_schedule(g, plan, sizes)
    tr = sched.trace
    assert tr.rule_by_node == {disp: "a2a", comb: "a2a"}
    per_node = {}
    for e in tr.events:
        per_node.setdefault(e.nid, []).append(e.kind)
    # dispatch: counts all-gather + slot a2a + payload a2a (inputs sliced
    # locally, never gathered)
    assert sorted(per_node[disp]) == ["all_gather", "all_to_all",
                                      "all_to_all"]
    # dispatch output lands expert-sharded: zero repartition into the
    # expert FFN einsums that want e on the mesh
    assert sched.layouts[disp] == (("data", "model"), (), ())
    # combine hands its consumers sequence-sharded tokens
    assert sched.layouts[comb] == ((), ("data", "model"), ())


def test_a2a_falls_back_when_sequence_does_not_divide():
    g, disp, comb = _moe_graph(seq=20)  # 20 % 8 != 0: no 8-way token shard
    sizes = {"data": 2, "model": 4}
    plan = _uniform_plan(g, {"e": ("data", "model")})
    sched = spmd.build_schedule(g, plan, sizes)
    assert sched.trace.rule_by_node[disp] == "replicate"


def test_unknown_rule_rejected_at_plan_time():
    g = EinGraph()
    x = g.input("x", "b s a", (2, 4, 8))
    g.opaque("mystery", [x], "b s a", (2, 4, 8),
             in_labels=[("b", "s", "a")],
             comm=[{"kind": "ring", "label": "s", "input": 0,
                    "rule": "warp-drive"}])
    with pytest.raises(ValueError, match="warp-drive"):
        eindecomp(g, 2)


def test_mixed_rules_rejected():
    g = EinGraph()
    x = g.input("x", "b s a", (2, 4, 8))
    g.opaque("mystery", [x], "b s a", (2, 4, 8),
             in_labels=[("b", "s", "a")],
             comm=[{"kind": "ring", "label": "s", "input": 0},
                   {"kind": "a2a", "label": "b", "input": 0}])
    with pytest.raises(ValueError, match="conflicting"):
        eindecomp(g, 2)


def test_bad_comm_kind_rejected():
    g = EinGraph()
    x = g.input("x", "b s a", (2, 4, 8))
    g.opaque("mystery", [x], "b s a", (2, 4, 8),
             in_labels=[("b", "s", "a")],
             comm=[{"kind": "broadcast", "label": "s", "input": 0,
                    "rule": "replicate"}])
    with pytest.raises(ValueError, match="broadcast"):
        eindecomp(g, 2)


def test_plan_repart_slices_before_all_to_all():
    """Replicated-prefix slices now run before the a2a pass: landing
    (data, model) on one dim when model arrives from another dim is
    slice + all_to_all, not gather + slice + slice."""
    steps = spmd.plan_repart(
        (("model",), (), ()), ((), ("data", "model"), ()))
    assert steps == [("slice", "data", 1), ("all_to_all", "model", 0, 1)]


# ---------------------------------------------------------------------------
# grouped reduce-scatter (satellite): one collective for two scattered axes
# ---------------------------------------------------------------------------


def _grouped_rs_graph():
    g = EinGraph("grouped")
    x = g.input("x", "b f g", (8, 8, 8))
    w = g.input("w", "f g c", (8, 8, 8))
    z = g.einsum("b f g, f g c -> b c", x, w)
    out = g.einsum("b c -> b c", z, combine="id", agg="")
    plan = Plan(p=8, mode="mesh")
    plan.d_by_node = {0: {"b": 1, "f": 2, "g": 4},
                      1: {"f": 2, "g": 4, "c": 1},
                      2: {"b": 1, "f": 2, "g": 4, "c": 1},
                      3: {"b": 2, "c": 4}}
    plan.axes_by_node = {0: {"f": ("data",), "g": ("model",)},
                         1: {"f": ("data",), "g": ("model",)},
                         2: {"f": ("data",), "g": ("model",)},
                         3: {"b": ("data",), "c": ("model",)}}
    return g, out, plan


def test_grouped_psum_scatter_schedule():
    """Two contracted axes scattering to distinct output dims fuse into ONE
    reduce-scatter event (regression-pinned count) at the same wire bytes
    as the sequential pair."""
    g, out, plan = _grouped_rs_graph()
    sched = spmd.build_schedule(g, plan, {"data": 2, "model": 4}, [out])
    assert sched.trace.counts == {"psum_scatter": 1}, sched.trace.counts
    prog = {p.nid: p for p in sched.programs}[2]
    assert prog.post_steps == [
        ("psum_scatter_grouped", (("data", 0), ("model", 1)))]
    assert sched.layouts[2] == (("data",), ("model",))
    # wire identical to the sequential pair: n*(k1k2-1)/(k1k2) summed
    n_loc = 8 * 8
    n_dev = 8
    assert sched.trace.total_elems == n_dev * (8 - 1) * n_loc // 8


def test_grouped_psum_scatter_executes_correctly():
    g, out, plan = _grouped_rs_graph()
    mesh = make_host_mesh((2, 4))
    fn = jax.jit(engine.make_runner(g, [out], plan=plan, mesh=mesh,
                                    executor="shard_map"))
    feeds = {n.nid: (RNG.normal(size=n.shape) * 0.3).astype(np.float32)
             for n in g.nodes if n.kind == "input"}
    got = np.asarray(fn(*[feeds[i] for i in g.input_ids()]))
    np.testing.assert_allclose(got, eval_graph_dense(g, feeds)[out],
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# 3. execution equivalence (adaptive to the host's device count)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("axes_cfg", [
    {"s": ("model",), "b": ("data",)},
    {"s": ("data", "model")},
    {"b": ("data",), "h": ("model",)},
], ids=["ring-model", "ring-all", "local-heads"])
def test_ring_execution_matches_dense(window, axes_cfg):
    # the local-heads case co-shards q and kv heads 4-way: MHA shapes
    g, o = _attn_graph(window=window,
                       kv_heads=H if "h" in axes_cfg else K)
    mesh = make_host_mesh((2, 4))
    sizes = engine.mesh_axes_dict(mesh)
    plan = _uniform_plan(g, axes_cfg, p=math.prod(sizes.values()))
    tr = spmd.CollectiveTrace()
    fn = jax.jit(engine.make_runner(g, [o], plan=plan, mesh=mesh,
                                    executor="shard_map",
                                    collective_trace=tr))
    feeds = {n.nid: (RNG.normal(size=n.shape) * 0.3).astype(np.float32)
             for n in g.nodes if n.kind == "input"}
    got = np.asarray(fn(*[feeds[i] for i in g.input_ids()]))
    want = eval_graph_dense(g, feeds)[o]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if N_DEV >= 8 and "s" in axes_cfg:
        assert tr.counts.get("ppermute", 0) > 0  # a real ring ran
        assert tr.counts.get("all_gather", 0) == 0  # and no kv gather


@pytest.mark.parametrize("axes_cfg", [
    {"e": ("data", "model")},
    {"e": ("model",)},
], ids=["e-all", "e-model"])
def test_a2a_moe_with_drops_matches_dense(monkeypatch, axes_cfg):
    """Real capacity drops (64 tokens, 32 slots): the sharded a2a program
    must agree with the dense stub bit-for-bit on routing decisions."""
    g, disp, comb = _moe_graph()
    for kind, fn in make_stub_opaques(CAP).items():
        monkeypatch.setitem(engine.OPAQUE_FNS, kind, fn)
    mesh = make_host_mesh((2, 4))
    sizes = engine.mesh_axes_dict(mesh)
    plan = _uniform_plan(g, axes_cfg, p=math.prod(sizes.values()))
    tr = spmd.CollectiveTrace()
    fn = jax.jit(engine.make_runner(g, [comb], plan=plan, mesh=mesh,
                                    executor="shard_map",
                                    collective_trace=tr))
    feeds = {0: (RNG.normal(size=(B, S, D)) * 0.3).astype(np.float32),
             1: (RNG.normal(size=(B, S, E)) * 2.0).astype(np.float32)}
    got = np.asarray(fn(feeds[0], feeds[1]))
    want = eval_graph_dense(g, feeds)[comb]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    if N_DEV >= 8:
        assert tr.counts.get("all_to_all", 0) >= 2
        # the payload crosses the all_to_all; gathers on the a2a path are
        # metadata/route-sized, never the dominant token-buffer movement
        by_rule = tr.by_rule().get("a2a", {})
        assert by_rule.get("all_gather", {"bytes": 0})["bytes"] < \
            by_rule["all_to_all"]["bytes"]


def test_decode_ring_over_cache_time():
    """Decode-shaped attention: q has a singleton sequence, the ring rides
    the kv-cache time label t."""
    g = EinGraph("decode")
    q = g.input("q", "b h s d", (B, H, 1, D))
    k = g.input("k", "b k t d", (B, K, S, D))
    v = g.input("v", "b k t d", (B, K, S, D))
    o = g.opaque(
        "flash_attention", [q, k, v], "b h s d", (B, H, 1, D),
        in_labels=[("b", "h", "s", "d"), ("b", "k", "t", "d"),
                   ("b", "k", "t", "d")],
        shardable={"b", "h", "k", "t"},
        comm=[{"kind": "ring", "label": "t", "input": 1, "rule": "ring"},
              {"kind": "ring", "label": "t", "input": 2, "rule": "ring"}],
        causal=False)
    mesh = make_host_mesh((2, 4))
    sizes = engine.mesh_axes_dict(mesh)
    plan = _uniform_plan(g, {"t": ("model",), "b": ("data",)},
                         p=math.prod(sizes.values()))
    fn = jax.jit(engine.make_runner(g, [o], plan=plan, mesh=mesh,
                                    executor="shard_map"))
    feeds = {n.nid: (RNG.normal(size=n.shape) * 0.3).astype(np.float32)
             for n in g.nodes if n.kind == "input"}
    got = np.asarray(fn(*[feeds[i] for i in g.input_ids()]))
    np.testing.assert_allclose(got, eval_graph_dense(g, feeds)[o],
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# 4. cost accounting: zoo-wide per-node bound (device-free)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama-7b", "mixtral-8x7b", "xlstm-125m",
                                  "hymba-1.5b"])
def test_zoo_ruled_opaques_within_node_bound(arch):
    """For every DP-planned zoo cell, each ring/a2a-ruled opaque node's
    traced wire elems stay within its slice of the §7 objective
    (opaque_node_bound) — no full K/V or token-buffer gathers — and the
    whole program stays within plan_cost."""
    cfg = reduced(get_config(arch))
    shape = ShapeConfig("eq", "prefill", 32, 4)
    g = program_for(cfg, shape).graph
    axes = {"data": 2, "model": 4}
    plan = eindecomp(g, 8, mesh_axes=axes, offpath_repart=True)
    sched = spmd.build_schedule(g, plan, axes)
    tr = sched.trace
    assert tr.total_elems <= plan_cost(g, plan)
    ruled = 0
    for n in g.nodes:
        if n.kind != "opaque":
            continue
        if tr.rule_by_node.get(n.nid) in ("ring", "a2a"):
            ruled += 1
            traced = tr.elems_by_node.get(n.nid, 0)
            bound = opaque_node_bound(g, plan, n.nid)
            assert traced <= bound, (n.name, traced, bound)
    if arch != "xlstm-125m":  # xlstm has no attention/moe opaques
        assert ruled >= 1


def test_zoo_equivalence_ring_and_a2a_active(monkeypatch):
    """mixtral through the Program surface: shard_map (ring + a2a rules
    active) vs gspmd vs nothing gathered beyond the declared schedules."""
    cfg = reduced(get_config("mixtral-8x7b"))
    shape = ShapeConfig("eq", "prefill", 32, 4)
    prog = program_for(cfg, shape)
    g = prog.graph
    for kind, fn in make_stub_opaques(capacity_of(g)).items():
        monkeypatch.setitem(engine.OPAQUE_FNS, kind, fn)
    mesh = make_host_mesh((2, 4))
    feeds = {}
    for n in g.nodes:
        if n.kind != "input":
            continue
        if str(np.dtype(n.dtype)) == "int32":
            feeds[n.name] = RNG.integers(0, cfg.vocab,
                                         size=n.shape).astype(np.int32)
        else:
            feeds[n.name] = (RNG.normal(size=n.shape) * 0.05).astype(
                np.float32)
    run_g = prog.compile(mesh=mesh)
    run_s = prog.compile(mesh=mesh, executor="shard_map")
    np.testing.assert_allclose(
        np.asarray(run_s(feeds)["logits"]),
        np.asarray(run_g(feeds)["logits"]), rtol=2e-4, atol=2e-4)
    by_rule = run_s.collectives_by_rule
    assert by_rule is not None
    if N_DEV >= 8:
        assert "a2a" in by_rule, by_rule  # expert parallelism realized
        rules = set(run_s.collectives.rule_by_node.values())
        assert "ring" in rules


# ---------------------------------------------------------------------------
# calibrated cost model (satellite)
# ---------------------------------------------------------------------------


def test_costmodel_with_measured_scales_prices(tmp_path):
    import json

    from repro.core.cost import CostModel

    measured = {"kinds": {"all_gather": {"ns_per_elem": 2.0},
                          "all_to_all": {"ns_per_elem": 4.0},
                          "psum_scatter": {"ns_per_elem": 6.0}}}
    path = tmp_path / "costs.json"
    path.write_text(json.dumps(measured))
    cm = CostModel.with_measured(path)
    assert cm.mode == "collective"
    assert cm.coeffs == {"all_gather": 1.0, "all_to_all": 2.0,
                         "psum_scatter": 3.0}
    base = CostModel("collective")
    # a pure gather reprices identically (coeff 1.0)...
    assert cm.repart((4, 1), (1, 1), (16, 8)) == \
        base.repart((4, 1), (1, 1), (16, 8))
    # ...a pure scatter doubles (coeff 2.0)
    assert cm.repart((1, 1), (4, 1), (16, 8)) == \
        2 * base.repart((1, 1), (4, 1), (16, 8))


def test_costmodel_instance_flows_through_compile():
    """Program.compile accepts a calibrated CostModel and the plan cache
    keys on its coefficients (calibrated != formula plans)."""
    from repro import frontend as ein
    from repro.core.cost import CostModel
    from repro.core.plancache import PlanCache

    x = ein.tensor("x", "b a", (8, 16))
    w = ein.tensor("w", "a f", (16, 32))
    prog = ein.Program({"y": ein.einsum("b a, a f -> b f", x, w)})
    cache = PlanCache()
    cm = CostModel.with_measured(
        {"kinds": {"all_gather": {"ns_per_elem": 1.0},
                   "all_to_all": {"ns_per_elem": 9.0}}})
    run1 = prog.compile(p=4, cost_model=cm, cache=cache)
    assert run1.plan is not None
    misses = cache.misses
    run2 = prog.compile(p=4, cost_model="collective", cache=cache)
    assert cache.misses == misses + 1  # different key: no false hit
    assert run2.plan is not None
