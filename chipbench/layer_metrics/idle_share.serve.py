"""The share of the traced slice in which no operation ran on the device,
averaged over the chips: busy time and the slice are read over the same
slice of the trace."""


def read(ctx):
    return 100.0 * (1.0 - ctx["trace"].busy_s / ctx["window_s"])
