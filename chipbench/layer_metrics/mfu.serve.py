"""The window's model operations (``flops.py``: the prefills admitted in
it and one decode token per live slot per decode step run in it) over
the window, on the host clock, times chips times the bf16 peak."""


def read(ctx):
    c = ctx["counters"]
    return 100.0 * c["window_flops"] / (
        c["window_s"] * ctx["chips"] * ctx["peak"]["bf16_flops"])
