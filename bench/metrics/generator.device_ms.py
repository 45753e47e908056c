"""Device time of the generator's programs (fake batches and G steps),
per round, averaged over the chips the cell uses.  The programs are
matched by the name of the jitted function, as the trace's "XLA Modules"
line gives it."""
from bench import trace

PROGRAMS = ("jit_gen_batch", "jit_g_step")


def read(ctx):
    ns = [sum(v for k, v in trace.time_by_program(
        ctx.trace.modules.get(d, []), ctx.lo, ctx.hi).items()
        if k in PROGRAMS) for d in ctx.devices]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / ctx.rounds / 1e6
