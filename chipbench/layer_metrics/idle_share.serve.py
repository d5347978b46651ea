"""The share of the traced window in which no operation ran on the device,
averaged over the chips."""


def read(ctx):
    busy = ctx["trace"].busy_s
    return 100.0 * (1.0 - busy / ctx["window_s"]) if busy else None
