"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""
from bench import trace


def read(ctx):
    busy = [trace.busy_ns(ctx.trace.ops.get(d, []), ctx.lo, ctx.hi)
            for d in ctx.devices]
    if not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / (ctx.hi - ctx.lo))
