"""Mean device-idle time between consecutive chunk-step executions, in
ms: the host loop between launches (readback, harvest, rewrite, record)
as the device sees it."""

STEP = "chunk_step"


def read(ctx):
    if ctx.trace is None:
        return None
    gaps = ctx.trace.gaps_between(STEP)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
