"""What the program records about itself, for a profiler to read.

Host spans: ``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``
named from ``SPANS``, so it lands in the profiler's own trace
(``.xplane.pb``) on the line of the thread that opened it, on the host
clock the device trace is mapped onto, with ``ids`` as the event's stats.  When no profiler is recording it costs
about a microsecond.

Device parts: the paged decode step names its parts with ``part(name)``,
a ``jax.named_scope`` named from ``PARTS``.  A device trace's op events
carry only the instruction's name; the compiled program keeps each
instruction's scope path in its ``op_name`` metadata, and ``op_scopes``
reads it back, so the trace can be split by part.  ``compiled_text``
compiles for that: the persistent compile cache keys a program without its
metadata, so a program that differs from a cached one only in its scopes or
source lines would come back with the cached one's op names.
"""
from __future__ import annotations

import re

import jax

SPANS = ("serve.admit", "serve.admit_request", "serve.first_token")
PARTS = ("qkv", "kv_write", "kv_gather", "attend", "out_proj", "ssm", "ffn",
         "lm_head")
# what no part claims: the layer scan's slicing and updating of the stacked
# caches, the compiler's copies, instructions with no metadata
CARRY = "carry"

_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    if name not in SPANS:
        raise ValueError(f"span {name!r} is not in obs.SPANS")
    return jax.profiler.TraceAnnotation(name, **ids)


def part(name: str):
    """``jax.named_scope(name)`` for a name of ``PARTS``: a misspelt part
    would move its instructions into ``CARRY`` unseen."""
    if name not in PARTS:
        raise ValueError(f"part {name!r} is not in obs.PARTS")
    return jax.named_scope(name)


def compiled_text(lowered) -> str:
    """The compiled text of ``lowered`` with its own metadata: for this
    compile the persistent cache's key takes the metadata in, and a compiler
    option set to its default value keeps out the executable the jitted
    function already holds, which the cache may have served.  The
    instructions a device trace names, those outside fused computations,
    are those of the program that runs, names included; inside fusions the
    numbering may differ."""
    key = "jax_compilation_cache_include_metadata_in_key"
    prev = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return lowered.compile({"xla_dump_hlo_as_text": False}).as_text()
    finally:
        jax.config.update(key, prev)


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: part}`` of a compiled program's text: the
    innermost name of ``PARTS`` in the instruction's ``op_name``, else
    ``CARRY``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INST.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        parts = ([p for p in meta.group(1).split("/") if p in PARTS]
                 if meta else [])
        out[m.group(1)] = parts[-1] if parts else CARRY
    return out
