"""Time from a job's submission (the harness's `perfbench.submit_<n>`
mark) to the start of its first chunk-step execution on the device, in
ms: the entry's per-job set-up (padding and stacking the lanes' problems,
placing them, the init program) as the device sees it.  The mean over the
traced submissions and the chips."""

STEP = "chunk_step"


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    hi = t.window[1]
    waits = []
    for _, at, _ in t.marks("submit_"):
        for d in t.devices:
            first = min((s for name, s, _ in d.modules
                         if STEP in name and s >= at), default=None)
            if first is not None and first <= hi:
                waits.append(first - at)
    return 1e-6 * sum(waits) / len(waits) if waits else None
