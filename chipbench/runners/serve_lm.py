"""Serving cells: a language model behind ``repro.serving.ServingEngine``.

Set-up makes the weights from the seed, builds the engine and warms every
prompt length the mix can send (hymba gets one prefill program per exact
length) and the decode step.

The window is an open loop of ``seconds``: a client thread submits each
request of the mix's schedule (``traffic.serve_schedule``) when it is due,
whatever the engine is doing, while this thread keeps ``ServingEngine.run``
draining the queue (``submit`` only appends to it, so requests join the
running loop at its next admission).  When the window closes the client
takes the decode steps dispatched so far, waits until the device has run
the last of them, and stops sending; the engine then finishes the requests
in flight, outside the window.

End to end, on the host clock: ``serve_tok_s`` is every token generated in
the window (each admitted request's first token and one per live slot per
decode step) over the window, which ends when the device has run the steps
counted.  Per request, time to first token is taken from when it was due,
so a late client or a stall counts.

A traced run traces a slice of the window (``trace`` in the mix: seconds
from the opening, and length) from a timer thread, so that the trace stays
small enough to reduce within the run's time.

``correct``: once the window has closed and the engine is freed, a sample
of the finished requests drawn from the seed, the longest among them, is
run through the plain float32 reference over prompt and served tokens.  At
each served position the gap by which the served token's reference logit
lies below the reference's best is taken; the mix's ``check.limits`` name
the statistics of those gaps that are compared (``GAPS``).  Prefill logits at the last prompt token, the admit scatter into the paged
pool and every decode step through the pool and the SSM state all lie on
that path.  A control (``controls``) stands in the program's place: at the
same positions, the token the reference puts first when computed one
precision lower, compared under the same limits.
"""
from __future__ import annotations

import gc
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from chipbench import faults, flops, harness, spec, traffic, weights

# what the comparison with the reference reads; the mix's limits name the
# ones compared
GAPS = ("served_logit_gap", "mean_logit_gap", "mismatch_share")


@dataclass
class Sent:
    due: float            # perf_counter time it was due
    sent: float           # perf_counter time it was submitted
    rid: int
    prompt: np.ndarray
    max_new: int
    out: np.ndarray | None = None
    ttft_s: float | None = None      # from submit, as the engine reports it

    @property
    def ttft_due_s(self) -> float | None:
        """Time to first token from when the request was due."""
        return None if self.ttft_s is None else \
            self.sent - self.due + self.ttft_s


@dataclass
class Window:
    sent: list = field(default_factory=list)
    t0: float = 0.0                  # perf_counter at the opening
    t_end: float = 0.0               # the device ran the steps counted
    tokens: int = 0                  # generated inside the window
    prefills: int = 0
    compiles: int = 0                # backend compiles inside the window
    late_s: float = 0.0              # the client's worst lateness
    served_s: float = 0.0            # opening to the last request finished
    error: BaseException | None = None   # raised by the client thread

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


def model_config(m: dict):
    """The program's ``ModelConfig`` built from the configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(**{**m, "block_pattern": tuple(m["block_pattern"])})


def build(cell, seed: int, params=None):
    """The engine with its weights, every prompt length of the mix warmed."""
    import jax.numpy as jnp

    from repro.serving import ServingEngine

    m, mix = cell.config["model"], cell.mix
    ref = spec.load_reference(cell.config["reference"])
    if params is None:
        params = weights.make(ref.param_layout(m), seed, jnp.dtype(m["dtype"]))
    eng = ServingEngine(model_config(m), batch=mix["slots"],
                        max_seq=mix["max_seq"], block=mix["kv_block"],
                        params=params)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 4]))
    grid = traffic.prompt_grid(mix)
    # The first admission gets the caches as the engine allocated them, and
    # every later one the caches a step returned, with another sharding and
    # so another program: one request goes first, so that every length is
    # warmed as the window admits it.
    for lengths in ([grid[0]], grid):
        for length in lengths:
            eng.submit(rng.integers(0, m["vocab"], size=length,
                                    dtype=np.int32), 2)
        eng.run()
    return eng


def _client(eng, schedule, seconds: float, win: Window, clock,
            opened: threading.Event, finished: threading.Event) -> None:
    try:
        _send(eng, schedule, seconds, win, clock, opened)
    except BaseException as e:       # handed to the serving thread
        win.error = e
    finally:
        opened.set()
        finished.set()


def _send(eng, schedule, seconds: float, win: Window, clock,
          opened: threading.Event) -> None:
    met = eng.metrics                 # cumulative since the engine was built
    compiles0, steps0, prefills0 = (clock.backend_compiles, met.decode_steps,
                                    met.prefills)
    win.t0 = time.perf_counter()
    opened.set()
    for due, prompt, max_new in schedule:
        t_due = win.t0 + due
        wait = t_due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        t = time.perf_counter()
        win.sent.append(Sent(t_due, t, eng.submit(prompt, max_new), prompt,
                             max_new))
        win.late_s = max(win.late_s, t - t_due)
    wait = win.t0 + seconds - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    # steps dispatched so far; the last one's tokens are ready once the
    # device has run every step before it (each reads the last's caches)
    steps = met.decode_steps
    last = eng.tokens
    win.prefills = met.prefills - prefills0
    active = sum(met.occupancy[steps0:steps]) * eng.batch
    win.compiles = clock.backend_compiles - compiles0
    last.block_until_ready()
    win.t_end = time.perf_counter()
    win.tokens = win.prefills + int(round(active))


def serve_window(eng, mix, seed: int, vocab: int, seconds: float,
                 tracer: harness.Tracer, clock) -> Window:
    """The open-loop window; returns once every request sent has
    finished."""
    win = Window()
    opened, finished = threading.Event(), threading.Event()
    client = threading.Thread(
        target=_client, daemon=True,
        args=(eng, traffic.serve_schedule(mix, seed, vocab, seconds),
              seconds, win, clock, opened, finished))
    client.start()
    opened.wait()
    tracer.start_in(mix["trace"]["start_s"], mix["trace"]["seconds"])
    results: dict = {}
    while True:
        last_round = finished.is_set()
        out, met = eng.run()
        results.update(out)
        if last_round:
            break
        if not out:
            time.sleep(0.001)
    win.served_s = time.perf_counter() - win.t0
    client.join()
    tracer.join()
    if win.error is not None:
        raise win.error
    for r in win.sent:
        r.out = results.get(r.rid)
        r.ttft_s = met.ttft_s.get(r.rid)
    return win


def sample(done: list[Sent], k: int, seed: int) -> list[Sent]:
    """``k`` finished requests drawn from the seed, the longest among them."""
    ok = [r for r in done if r.out is not None and len(r.out) == r.max_new]
    if not ok:
        return []
    longest = max(range(len(ok)), key=lambda i: len(ok[i].prompt) + len(ok[i].out))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    rest = [i for i in range(len(ok)) if i != longest]
    pick = [longest] + [int(i) for i in rng.choice(rest, size=min(k - 1, len(rest)),
                                                   replace=False)]
    return [ok[i] for i in pick]


def served_gaps(cell, seed: int, reqs: list[Sent],
                precisions=("float32",)) -> dict:
    """``{precision: {statistic: value}}`` of the gaps by which the served
    tokens' float32 reference logits lie below the reference's best (under
    ``float32``), and for each further precision of the tokens that
    precision puts first: the widest gap (``served_logit_gap``), the mean
    over served positions (``mean_logit_gap``) and the share of positions
    whose token is not the reference's first (``mismatch_share``).  Runs
    with nothing of the engine alive."""
    import jax
    import jax.numpy as jnp

    m = cell.config["model"]
    ref = spec.load_reference(cell.config["reference"])
    params = weights.make(ref.param_layout(m), seed, jnp.dtype(m["dtype"]))
    S = max(len(r.prompt) + len(r.out) - 1 for r in reqs)
    toks = np.zeros((len(reqs), S), np.int32)
    pos = np.zeros((len(reqs), S), bool)        # positions that served a token
    want = np.zeros((len(reqs), S), np.int32)   # the token served there
    for i, r in enumerate(reqs):
        L, n = len(r.prompt), len(r.out)
        seq = np.concatenate([r.prompt, r.out[:-1]])
        toks[i, :len(seq)] = seq
        pos[i, L - 1:L - 1 + n] = True
        want[i, L - 1:L - 1 + n] = r.out
    out: dict = {"served_tokens": int(pos.sum())}
    if np.any(want[pos] >= m["vocab"]):          # a padded id was served
        out["float32"] = dict.fromkeys(GAPS, float("inf"))
        return out
    mask, want_j = jnp.asarray(pos), jnp.asarray(want)

    @jax.jit
    def stats(lg32, pick):
        best = jnp.max(lg32, axis=-1)
        gap = best - jnp.take_along_axis(lg32, pick[..., None], axis=-1)[..., 0]
        n = jnp.sum(mask)
        return (jnp.max(jnp.where(mask, gap, -jnp.inf)),
                jnp.sum(jnp.where(mask, gap, 0.0)) / n,
                jnp.sum(mask & (gap > 0)) / n)

    def read(lg32, pick):
        return dict(zip(GAPS, (float(v) for v in stats(lg32, pick))))

    lg32 = ref.logits(params, toks, m, "float32")
    out["float32"] = read(lg32, want_j)
    for prec in precisions:
        if prec == "float32":
            continue
        lgq = ref.logits(params, toks, m, prec)
        first = jnp.argmax(lgq, axis=-1).astype(jnp.int32)
        del lgq
        out[prec] = read(lg32, first)
    return out


def traced_counters(eng, win: Window, m: dict, tracer: harness.Tracer) -> dict:
    """What the traced run did: the model work of the window (for
    ``mfu.serve``), the prefills whose first token came inside the traced
    slice (for the readers of the trace)."""
    fin = [r for r in win.sent if r.out is not None]
    # decode operations per token vary with position only through the
    # attention window: the window's tokens take the finished requests' mean
    work = flops.serve_work(m, [(len(r.prompt), len(r.out)) for r in fin])
    decode_tokens = sum(len(r.out) - 1 for r in fin)
    per_token = work["decode"] / decode_tokens if decode_tokens else 0.0
    pre_in_window = win.sent[:win.prefills]      # admitted in sending order
    inside = [len(r.prompt) for r in fin if r.ttft_s is not None
              and tracer.t0 <= r.sent + r.ttft_s <= tracer.t1]
    return {
        "plan_s": eng.registry.stats.plan_time_s,
        "window_s": win.seconds,
        "window_flops": (sum(flops.prefill(m, len(r.prompt))
                             for r in pre_in_window)
                         + (win.tokens - win.prefills) * per_token),
        "prefill_tokens": int(sum(inside)),
        "prefill_flops": sum(flops.prefill(m, L) for L in inside),
        "flash_attn": flops.flash_attention_prefill(m, inside),
    }


def run(cell, *, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, controls: tuple = (),
        fault: str | None = None) -> harness.Result:
    """One run of the cell.  ``controls`` (precisions) each get a
    ``Result`` of their own in ``res.controls``, the control in the
    program's place; ``fault`` plants one of ``faults.FAULTS``."""
    import jax

    m, mix = cell.config["model"], cell.mix
    devs = harness.chips(cell.chips, require_chip)
    clock = harness.CompileClock()
    t0 = time.perf_counter()
    with faults.plant(fault):
        eng = build(cell, seed)
    setup_s = time.perf_counter() - t0
    compiles_setup = clock.backend_compiles

    tracer = harness.Tracer(trace, f"{cell.name}-{seed}")
    win = serve_window(eng, mix, seed, m["vocab"], seconds, tracer, clock)
    res = harness.Result(attempted=len(win.sent))
    res.failed = sum(1 for r in win.sent
                     if r.out is None or len(r.out) != r.max_new)
    res.notes.update(setup_s=setup_s, window_s=win.seconds,
                     served_s=win.served_s, client_late_s=win.late_s,
                     compiles_in_setup=compiles_setup,
                     compiles_in_window=win.compiles,
                     requests=len(win.sent), tokens_in_window=win.tokens,
                     prefills_in_window=win.prefills,
                     logits_finite=bool(eng.metrics.logits_finite))
    ttft = [r.ttft_due_s for r in win.sent if r.ttft_s is not None]
    if ttft:
        res.notes.update(ttft_p50_s=float(np.percentile(ttft, 50)),
                         ttft_p95_s=float(np.percentile(ttft, 95)))
    if trace:
        counters = dict(traced_counters(eng, win, m, tracer),
                        ttft_p95_s=res.notes.get("ttft_p95_s"))
    else:
        res.metrics = {"serve_tok_s": win.tokens / win.seconds,
                       "setup_s": setup_s}
    res.device = harness.device_info(devs)
    res.notes["memory_stats"] = harness.memory_stats(devs)
    chosen = sample(win.sent, int(mix["check"]["requests"]), seed)
    if not eng.metrics.logits_finite:
        res.failed = max(res.failed, 1)
    del eng
    gc.collect()

    if trace:
        from chipbench import layers

        res.metrics, res.device, res.breakdown = layers.read_all(
            cell, tracer, counters, res.device)
    t_ref = time.perf_counter()
    gaps = served_gaps(cell, seed, chosen, ("float32",) + tuple(controls)) \
        if chosen else {"served_tokens": 0,
                        "float32": dict.fromkeys(GAPS, float("inf"))}
    res.notes.update(served_tokens_checked=gaps["served_tokens"],
                     sampled_requests=len(chosen), gaps=gaps["float32"],
                     reference_s=time.perf_counter() - t_ref)
    limits = mix["check"]["limits"]

    def checks(read: dict) -> list:
        return [harness.Check(name, read[name], limit)
                for name, limit in limits.items()]

    res.checks = checks(gaps["float32"])
    for prec in controls:
        read = gaps.get(prec, gaps["float32"])
        res.controls[prec] = harness.Result(
            attempted=res.attempted, failed=res.failed, checks=checks(read),
            notes={"gaps": read})
    jax.clear_caches()
    return res
