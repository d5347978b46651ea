"""What the program records about itself: host spans in a profiler trace
and the named parts of the compiled paged decode step (``repro.obs``)."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, reduced
from repro.models import transformer as tf
from repro.serving import ServingEngine


@pytest.fixture(scope="module")
def traced_engine(tmp_path_factory):
    """A tiny hymba engine that served two requests under the profiler:
    (engine, the trace's events named ``serve.*``)."""
    from jax.profiler import ProfileData

    cfg = reduced(get_config("hymba-1.5b"))
    eng = ServingEngine(cfg, batch=2, max_seq=24, block=8,
                        params=tf.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    for L in (5, 9):
        eng.submit(rng.integers(0, cfg.vocab, size=(L,)), 3)
    eng.run()                          # compiles outside the trace
    rids = [eng.submit(rng.integers(0, cfg.vocab, size=(L,)), 3)
            for L in (5, 9)]
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = out.glob("**/*.xplane.pb")
    events = [(e.name, dict(e.stats)) for p in ProfileData.from_file(
        str(path)).planes if p.name.startswith("/host:")
        for ln in p.lines for e in ln.events if e.name.startswith("serve.")]
    return eng, rids, events


def test_spans_land_in_the_profiler_trace_with_request_ids(traced_engine):
    _, rids, events = traced_engine
    names = [n for n, _ in events]
    assert names.count("serve.admit") == 1      # both admitted at once
    assert set(names) == set(obs.SPANS)
    for span in ("serve.admit_request", "serve.first_token"):
        assert sorted(st["rid"] for n, st in events if n == span) == rids


def test_compiled_decode_step_maps_instructions_to_parts(traced_engine):
    eng, _, _ = traced_engine
    table = eng.op_scopes()
    parts = set(table.values())
    assert parts == set(obs.PARTS) | {obs.CARRY}   # every part of hymba
    assert sum(p != obs.CARRY for p in table.values()) > len(table) // 10


def test_op_scopes_refuses_a_decode_step_without_parts(traced_engine,
                                                       monkeypatch):
    """A program whose scopes were lost (one served from a stale cache
    entry) would read all ``carry``: the engine raises rather than hand
    over a table that puts the whole step in ``carry``."""
    eng, _, _ = traced_engine
    no_meta = "\n".join([
        "ENTRY %main.2 (p: f32[4]) -> f32[4] {",
        "  %copy.1 = f32[4]{0} copy(%p)",
        "  ROOT %fusion.2 = f32[4]{0} fusion(%copy.1)",
        "}"])
    monkeypatch.setattr(obs, "compiled_text", lambda lowered: no_meta)
    with pytest.raises(RuntimeError, match="no part"):
        eng.op_scopes()


def test_part_and_span_take_only_their_listed_names():
    for name in obs.PARTS:
        with obs.part(name):
            pass
    with pytest.raises(ValueError, match="PARTS"):
        obs.part("kv_gahter")
    with obs.span("serve.admit_request", rid=3):
        pass
    with pytest.raises(ValueError, match="SPANS"):
        obs.span("serve.decode")


def test_admit_span_opens_only_for_a_phase_that_admits(monkeypatch):
    """Two slots but blocks for one request at a time: the second request
    waits with a slot free, and the admission phases that admit nothing
    open no ``serve.admit``."""
    opened = []

    def span(name, **ids):
        opened.append(name)
        return contextlib.nullcontext()

    cfg = reduced(get_config("llama-7b"))
    eng = ServingEngine(cfg, batch=2, max_seq=16, block=8, n_blocks=3,
                        params=tf.init_params(cfg, jax.random.PRNGKey(0)))
    calls = []
    phase = eng._admit_phase

    def admit_phase():
        calls.append(bool(eng._queue) and None in eng.slots)
        return phase()

    monkeypatch.setattr(obs, "span", span)
    monkeypatch.setattr(eng, "_admit_phase", admit_phase)
    rng = np.random.default_rng(0)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab, size=(9,)), 3)
    eng.run()
    assert eng.metrics.prefills == 2
    assert opened.count("serve.admit") == 2
    assert opened.count("serve.admit_request") == 2
    assert sum(calls) > 2          # phases with a request and a free slot


def test_op_scopes_takes_the_innermost_part():
    text = "\n".join([
        "HloModule jit_step, entry_computation_layout={()->f32[]}",
        "ENTRY %main.3 (p: f32[4]) -> f32[4] {",
        '  %dus.1 = f32[4]{0} dynamic-update-slice(%p), metadata={op_name='
        '"jit(step)/while/body/closed_call/ffn/kv_write/scatter"}',
        '  %ds.2 = f32[4]{0} dynamic-slice(%p), metadata={op_name='
        '"jit(step)/while/body/dynamic_slice"}',
        "  %copy.3 = f32[4]{0} copy(%ds.2)",
        '  ROOT fusion.4 = f32[4]{0} fusion(%copy.3), metadata={op_name='
        '"jit(step)/lm_head/dot_general"}',
        "}"])
    assert obs.op_scopes(text) == {"dus.1": "kv_write", "ds.2": obs.CARRY,
                                   "copy.3": obs.CARRY,
                                   "fusion.4": "lm_head"}


def test_compiled_text_keeps_its_scopes_past_a_cached_program(tmp_path):
    """The persistent compile cache keys a program without its metadata: a
    program cached under one scope serves the same program under another
    with the first one's op names, and the jitted function keeps that
    executable.  ``compiled_text`` of the same function gives the same
    instructions under its own names."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = [getattr(jax.config, n) for n in names]
    x = jnp.ones((8,))

    def jitted(part):
        def step(x):
            with jax.named_scope(part):
                return jnp.sin(x) * 2
        return jax.jit(step)

    try:
        for n, v in zip(names, (str(tmp_path), 0, 0)):
            jax.config.update(n, v)
        cc.reset_cache()
        jitted("ffn")(x).block_until_ready()
        step = jitted("attend")
        step(x).block_until_ready()
        stale = obs.op_scopes(step.lower(x).compile().as_text())
        fresh = obs.op_scopes(obs.compiled_text(step.lower(x)))
    finally:
        for n, v in zip(names, prev):
            jax.config.update(n, v)
        cc.reset_cache()
    assert "ffn" in stale.values() and "attend" not in stale.values()
    assert "attend" in fresh.values() and "ffn" not in fresh.values()
    assert stale.keys() == fresh.keys()
