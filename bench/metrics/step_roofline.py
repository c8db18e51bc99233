"""Kernel layer (the decode step program: no Pallas kernel runs on the
served path): the least time the chip needs for the decode steps of the
traced window, by ``cost.StepCost`` and the peaks of ``peaks.json``,
over their device time, in %."""

#: jitted decode programs: the megastep's scan and the single step
PROGRAMS = ("jit_run", "jit_step")


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace_program_time(PROGRAMS)
    if not calls:
        return None
    peak = ctx.peaks()
    least = sum(ctx.cost.least_s(f, b, peak) for f, b in ctx.step_needs())
    return 100.0 * least / secs if least else None
