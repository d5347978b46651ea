"""Continuous-batching serving engine on the plan cache.

``ServingEngine`` holds a fixed pool of decode *slots* (the persistent
paged-decode program's batch) plus an admission queue.  Each loop
iteration: (1) admit queued requests into free slots — a bucketed
batch-1 prefill through the ``BucketRegistry`` resolves the shape cell's
compiled handle (warm after first touch), then a jitted scatter moves the
prefill caches into the paged KV pool under the request's block table;
(2) evict finished requests and return their blocks; (3) run ONE batched
decode step for all live slots — per-slot positions and block tables mean
requests join and leave mid-flight without any recompilation.

Generated tokens stay on device (the decode step argmaxes inside the jit
and the per-step token vectors are simply accumulated); the host fetches
everything once at drain, so the loop never forces a per-token sync.  Each
prefill and decode step also leaves a device-side "all logits finite" flag,
reduced once at drain into ``ServeMetrics.logits_finite``.
An admission phase that admits a request, each request's admission and its
first-token sync are host spans (``repro.obs.span``: ``serve.admit``,
``serve.admit_request``, ``serve.first_token``) in any profiler trace taken
meanwhile; the profiler's Python tracer already shows the decode phase and
the drain as the calls ``_decode_phase`` and ``_drain``.
Length-based eviction is the default; passing ``eos_id`` enables early
exit at the cost of one host sync per step (documented, opt-in).
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.serving.buckets import BucketRegistry
from repro.serving.paged_kv import BlockAllocator, make_admit_fn


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new: int
    submit_t: float = 0.0         # perf_counter stamps
    admit_t: float = 0.0          # its admission started
    ttft_s: float | None = None   # submit -> first token (prefill argmax)
    slot: int = -1
    blocks: list[int] = field(default_factory=list)
    step_start: int = -1          # index of its first decode-step column
    n_dec: int = 0                # decode tokens produced so far
    first_tok: int = -1
    done: bool = False

    @property
    def total(self) -> int:
        return 1 + self.n_dec     # prefill token + decode tokens


@dataclass
class ServeMetrics:
    """Serving-tier observability: batch occupancy is sampled once per
    decode step; queue wait (submit -> admission started) and TTFT once per
    request, on the ``perf_counter`` clock."""

    occupancy: list[float] = field(default_factory=list)
    queue_s: dict[int, float] = field(default_factory=dict)
    ttft_s: dict[int, float] = field(default_factory=dict)
    prefills: int = 0
    decode_steps: int = 0
    tokens_generated: int = 0
    logits_finite: bool = True    # every prefill/decode logit was finite

    @property
    def mean_occupancy(self) -> float:
        return float(np.mean(self.occupancy)) if self.occupancy else 0.0

    def summary(self) -> dict:
        return {
            "prefills": self.prefills,
            "decode_steps": self.decode_steps,
            "tokens_generated": self.tokens_generated,
            "mean_occupancy": self.mean_occupancy,
            "logits_finite": self.logits_finite,
        }


class ServingEngine:
    """Continuous batching over a paged KV pool.

    Parameters
    ----------
    cfg:
        Model config (``repro.configs``).
    batch:
        Decode slots — the persistent decode program's batch bucket.
    max_seq:
        Per-request capacity ceiling (prompt + generated), rounded up to
        whole blocks; sets the block-table width ``W``.
    block:
        KV block size (pool rows per block).
    n_blocks:
        Pool capacity.  Default sizes for all slots at full length plus
        the scratch block.
    bucket:
        Prefill bucket policy (``buckets.bucket_len``): "auto" (pow2 for
        pad-free archs, exact otherwise), "pow2", or "exact".
    eos_id:
        Optional early-exit token id.  Checking it costs one host sync
        per decode step, so it is opt-in; default is length-based
        eviction only.
    """

    def __init__(self, cfg, *, batch: int = 4, max_seq: int = 128,
                 block: int = 16, n_blocks: int | None = None, mesh=None,
                 params=None, seed: int = 0, plan_cache=None,
                 bucket: str = "auto", eos_id: int | None = None):
        self.cfg = cfg
        self.batch = batch
        self.block = block
        self.W = -(-max_seq // block)
        self.seq = self.W * block
        self.eos_id = eos_id
        self.mesh = mesh or make_host_mesh()
        if n_blocks is None:
            n_blocks = 1 + batch * self.W
        self.alloc = BlockAllocator(n_blocks, block)
        self.registry = BucketRegistry(cfg, self.mesh, plan_cache=plan_cache,
                                       bucket=bucket)

        dent = self.registry.decode(self.seq, batch, block)
        self.policy = dent.policy
        self._decode = dent.step
        if params is None:
            params = tf.init_params(cfg, jax.random.PRNGKey(seed))
        self.params = jax.device_put(
            params, tf.param_shardings(cfg, self.policy, self.mesh))

        self.caches = tf.init_paged_caches(cfg, batch, n_blocks, block)
        self.tokens = jnp.zeros((batch, 1), jnp.int32)
        self.tables = np.zeros((batch, self.W), np.int32)
        self.pos = np.zeros((batch,), np.int32)
        self.slots: list[Request | None] = [None] * batch
        self._admit = make_admit_fn(cfg)
        self._queue: deque[Request] = deque()
        self._done: list[Request] = []
        self._next_rid = 0
        self._step_log: list = []     # per-step (batch, 1) device tokens
        self._finite_log: list = []   # per-step device "logits finite" flags
        self.metrics = ServeMetrics()

    # -- API ------------------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        need = self.alloc.blocks_for(len(prompt) + max_new)
        if need > self.W:
            raise ValueError(f"request needs {need} blocks > table width "
                             f"{self.W} (raise max_seq)")
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, max_new=max_new,
                      submit_t=time.perf_counter())
        self._queue.append(req)
        return rid

    def run(self) -> tuple[dict[int, np.ndarray], ServeMetrics]:
        """Drain the queue; returns ({rid: (n_tokens,) int32}, metrics)."""
        while self._queue or any(s is not None for s in self.slots):
            admitted = self._admit_phase()
            active = [s for s in self.slots if s is not None]
            if not active:
                if self._queue and not admitted:
                    raise RuntimeError(
                        "admission deadlock: empty batch but queued request "
                        "cannot get blocks — pool too small for one request")
                continue
            self.metrics.occupancy.append(len(active) / self.batch)
            self._decode_phase()
        results = self._drain()
        return results, self.metrics

    def op_scopes(self) -> dict[str, str]:
        """``{instruction name: part}`` of the compiled decode step
        (``obs.op_scopes``), lowered for the live arguments, so it is the
        program a decode phase runs now.  It compiles that program again
        (``obs.compiled_text``): call it outside any timed window.  Raises
        if no instruction carries a part, as a program compiled without its
        scopes (one served from a stale cache entry) would read."""
        table = obs.op_scopes(obs.compiled_text(self._decode.lower(
            self.params, self.tokens, self.caches, jnp.asarray(self.tables),
            jnp.asarray(self.pos))))
        if set(table.values()) <= {obs.CARRY}:
            raise RuntimeError(
                "the compiled decode step carries no part of obs.PARTS in "
                "its metadata: its scopes were lost, so no split by part")
        return table

    # -- loop phases ----------------------------------------------------------

    def _admit_phase(self) -> int:
        admitted = 0
        # serve.admit opens once the first request has its blocks, so it
        # spans only phases that admit
        with contextlib.ExitStack() as phase:
            while self._queue and None in self.slots:
                req = self._queue[0]
                blocks = self.alloc.alloc(
                    self.alloc.blocks_for(len(req.prompt) + req.max_new))
                if blocks is None:
                    break
                if not admitted:
                    phase.enter_context(obs.span("serve.admit"))
                self._queue.popleft()
                with obs.span("serve.admit_request", rid=req.rid):
                    self._prefill_into(req, self.slots.index(None), blocks)
                admitted += 1
        return admitted

    def _prefill_into(self, req: Request, slot: int, blocks: list[int]):
        req.admit_t = time.perf_counter()
        self.metrics.queue_s[req.rid] = req.admit_t - req.submit_t
        plen = len(req.prompt)
        ent = self.registry.prefill(plen)
        bl = ent.key[2]
        padded = np.zeros((1, bl), np.int32)
        padded[0, :plen] = req.prompt
        logits, pre_caches = ent.step(self.params, {"tokens": padded},
                                      jnp.int32(plen - 1))
        tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)  # (1,)
        self._finite_log.append(jnp.all(jnp.isfinite(logits)))
        # TTFT is defined at the first token's availability: sync here (one
        # per request, not per step)
        with obs.span("serve.first_token", rid=req.rid):
            req.first_tok = int(jax.device_get(tok0)[0])
        req.ttft_s = time.perf_counter() - req.submit_t
        self.metrics.ttft_s[req.rid] = req.ttft_s
        self.metrics.prefills += 1

        row = np.zeros((self.W,), np.int32)
        row[:len(blocks)] = blocks
        self.tables[slot] = row
        self.pos[slot] = plen
        self.caches, self.tokens = self._admit(
            self.caches, pre_caches, jnp.asarray(row), jnp.int32(slot),
            tok0, self.tokens)
        req.slot, req.blocks = slot, blocks
        req.step_start = len(self._step_log)
        self.slots[slot] = req
        if req.max_new == 1:
            self._evict(req)

    def _decode_phase(self):
        tok, self.caches, finite = self._decode(
            self.params, self.tokens, self.caches,
            jnp.asarray(self.tables), jnp.asarray(self.pos))
        self.tokens = tok
        self._step_log.append(tok)
        self._finite_log.append(finite)
        self.metrics.decode_steps += 1
        eos_row = (np.asarray(tok)[:, 0]
                   if self.eos_id is not None else None)  # opt-in sync
        for req in list(self.slots):
            if req is None:
                continue
            req.n_dec += 1
            self.pos[req.slot] += 1
            hit_eos = (eos_row is not None
                       and eos_row[req.slot] == self.eos_id)
            if req.total >= req.max_new or hit_eos:
                self._evict(req)

    def _evict(self, req: Request):
        self.alloc.release(req.blocks)
        self.tables[req.slot] = 0
        self.pos[req.slot] = 0
        self.slots[req.slot] = None
        req.done = True
        self._done.append(req)

    def _drain(self) -> dict[int, np.ndarray]:
        if self._step_log:
            mat = np.asarray(jnp.concatenate(self._step_log, axis=1))
        else:
            mat = np.zeros((self.batch, 0), np.int32)
        out: dict[int, np.ndarray] = {}
        for req in self._done:
            cols = range(req.step_start, req.step_start + req.n_dec)
            gen = np.asarray(
                [req.first_tok] + [int(mat[req.slot, j]) for j in cols],
                np.int32)
            self.metrics.tokens_generated += len(gen)
            out[req.rid] = gen
        if self._finite_log:
            self.metrics.logits_finite &= bool(
                jnp.all(jnp.stack(self._finite_log)))
        self._step_log.clear()
        self._finite_log.clear()
        self._done.clear()
        return out
