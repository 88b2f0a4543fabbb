"""Device time of the chunk-step program (`make_stream_runner`'s
`chunk_step`: a `lax.scan` of `slot_step`), per slot it advanced, in us:
the median execution wholly inside the traced window, over the mix's
`chunk` slots it advances every lane by (the mean over the chips)."""
import statistics

STEP = "chunk_step"


def read(ctx):
    if ctx.trace is None:
        return None
    per_device = [statistics.median(e - s for _, s, e in runs)
                  * 1e-3 / ctx.traffic["chunk"]
                  for runs in ctx.trace.module_runs(STEP) if runs]
    return sum(per_device) / len(per_device) if per_device else None
