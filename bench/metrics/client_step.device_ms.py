"""Device time of the clients' local-step programs, per round, averaged
over the chips the cell uses.  The programs are matched by the name of
the jitted function, as the trace's "XLA Modules" line gives it."""
from bench import trace

PROGRAMS = ("jit_step", "jit_per_client")


def read(ctx):
    ns = [sum(v for k, v in trace.time_by_program(
        ctx.trace.modules.get(d, []), ctx.lo, ctx.hi).items()
        if k in PROGRAMS) for d in ctx.devices]
    if not any(ns):
        return None
    return sum(ns) / len(ns) / ctx.rounds / 1e6
