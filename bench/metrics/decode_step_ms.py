"""Model step layer: device time of the decode programs in the traced
window, per decode step they executed (the engine counter
``engine.fused_iterations``: a megastep's executed steps, or one)."""

#: jitted decode programs: the megastep's scan and the single step
PROGRAMS = ("jit_run", "jit_step")


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace_program_time(PROGRAMS)
    steps = ctx.counters.get("engine.fused_iterations", 0)
    return secs / steps * 1e3 if calls and steps else None
