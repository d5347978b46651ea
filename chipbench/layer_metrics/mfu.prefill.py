"""The prefill step's share of the chip's bf16 peak: model operations of
the prefills whose first token came in the traced slice, over the device
time of the prefill programs there."""


def read(ctx):
    secs, _ = ctx["trace"].module("jit_bucket_prefill_step")
    if not secs:
        return None
    fl = ctx["counters"]["prefill_flops"]
    return 100.0 * fl / (secs * ctx["peak"]["bf16_flops"])
