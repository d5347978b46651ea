"""The flash-attention kernel's share of its roofline: the least time the
chip could take for the prefills' attention (operations over the bf16 peak
or bytes over HBM bandwidth, whichever is longer; see
``flops.flash_attention_prefill``) over the kernel's device time."""
# the prefill program's only Pallas kernel is flash attention
OPS, PROGRAM = r"^tpu_custom_call:", "jit_bucket_prefill_step"


def read(ctx):
    secs, _ = ctx["trace"].op(OPS, PROGRAM)
    if not secs:
        return None
    w, pk = ctx["counters"]["flash_attn"], ctx["peak"]
    bound = max(w["flops"] / pk["bf16_flops"], w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * bound / secs
