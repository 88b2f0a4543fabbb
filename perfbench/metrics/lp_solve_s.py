"""Host seconds the program spent in HiGHS solving the exact LPs
(`repro.core.capacity.lp_counters()["solve_s"]`), read once the run is
over: the cell solves LPs in set-up only.  None where the program keeps
no such counter."""


def read(ctx):
    try:
        from repro.core.capacity import lp_counters
    except ImportError:
        return None
    return lp_counters()["solve_s"]
