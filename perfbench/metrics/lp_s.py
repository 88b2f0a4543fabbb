"""Host seconds the set-up spent on the exact LP bounds the cell's jobs
need (`policy_bound_exact`), before the first job."""


def read(ctx):
    return ctx.setup.get("lp_s")
