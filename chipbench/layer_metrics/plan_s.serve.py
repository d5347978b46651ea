"""Host seconds of the planner (EinDecomp's DP through the plan cache) in
set-up, summed over every serving program: the bucket registry's own
RegistryStats.plan_time_s."""


def read(ctx):
    return ctx["counters"].get("plan_s")
