"""Device milliseconds of the prefill and admit programs
(``jit_bucket_prefill_step``, ``jit_admit``) per 1,000 prompt tokens
prefilled in the traced window."""


def read(ctx):
    tr, toks = ctx["trace"], ctx["counters"]["prefill_tokens"]
    secs = tr.module("jit_bucket_prefill_step")[0] + tr.module("jit_admit")[0]
    return 1e6 * secs / toks if secs and toks else None
