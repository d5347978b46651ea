"""Faults planted in the serving program's decode step, to show that the
comparison which decides ``correct`` catches them.

``plant(name)`` wraps every decode step the engine's bucket registry
builds while it is active:

* ``token_altered`` — each token is replaced by the next id where the
  step produces it, and fed back as such;
* ``state_unchanged`` — the step returns the caches it was given (the KV
  pool and the SSM state);
* ``ssm_state_unchanged`` — the step updates the KV pool but returns the
  SSM state it was given.

The benchmark's own runs never plant one; ``calibrate.py`` and the tests
do.
"""
from __future__ import annotations

import contextlib

FAULTS = ("token_altered", "state_unchanged", "ssm_state_unchanged")


def _is_hybrid(c) -> bool:
    """A hybrid layer's cache: (KV pool, SSM state)."""
    from repro.models.ssm import SSMState

    return isinstance(c, tuple) and len(c) == 2 and isinstance(c[1], SSMState)


def _broken(step, fault: str, vocab: int):
    import jax
    import jax.numpy as jnp

    def broken(params, tokens, caches, tables, pos):
        if fault == "token_altered":
            tok, new, finite = step(params, tokens, caches, tables, pos)
            return (tok + 1) % vocab, new, finite
        # the step donates its caches: copy what is to be returned
        if fault == "state_unchanged":
            kept = jax.tree.map(jnp.copy, caches)
            tok, _, finite = step(params, tokens, caches, tables, pos)
            return tok, kept, finite
        kept = [jax.tree.map(jnp.copy, c[1]) if _is_hybrid(c) else None
                for c in caches]
        tok, new, finite = step(params, tokens, caches, tables, pos)
        return tok, [(n[0], k) if _is_hybrid(n) else n
                     for n, k in zip(new, kept)], finite

    return broken


@contextlib.contextmanager
def plant(fault: str | None):
    """Every decode step built inside the block carries ``fault``."""
    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    from repro.serving import buckets

    make = buckets.BucketRegistry._make_step

    def make_step(self, kind, policy):
        step = make(self, kind, policy)
        return _broken(step, fault, self.cfg.vocab) if kind == "decode" \
            else step

    buckets.BucketRegistry._make_step = make_step
    try:
        yield
    finally:
        buckets.BucketRegistry._make_step = make
