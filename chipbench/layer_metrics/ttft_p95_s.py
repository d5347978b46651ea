"""The 95th percentile over every request due in the window of its time
to first token, from when it was due (queueing and a late client count),
on the host clock."""


def read(ctx):
    return ctx["counters"].get("ttft_p95_s")
