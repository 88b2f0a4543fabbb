"""Host seconds the program spent building the exact LP's constraint
matrices (`repro.core.capacity.lp_counters()["build_s"]`), read once the
run is over: the cell solves LPs in set-up only.  None where the program
keeps no such counter."""


def read(ctx):
    try:
        from repro.core.capacity import lp_counters
    except ImportError:
        return None
    return lp_counters()["build_s"]
