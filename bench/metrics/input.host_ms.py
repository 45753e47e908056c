"""Host time inside the harness's span around the trainer's sampling of
a round's batches (real rows gathered and copied, fakes dispatched), per
round."""
from bench import trace


def read(ctx):
    ns = trace.span_ns(ctx.trace.spans, "bench.input", ctx.lo, ctx.hi)
    if not ns:
        return None
    return ns / ctx.rounds / 1e6
