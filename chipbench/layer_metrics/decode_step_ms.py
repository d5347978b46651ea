"""Device milliseconds per execution of the paged decode program
(``jit_decode_step``, the registry's jitted ``paged_serve_step``)."""


def read(ctx):
    secs, n = ctx["trace"].module("jit_decode_step")
    return 1e3 * secs / n if n else None
