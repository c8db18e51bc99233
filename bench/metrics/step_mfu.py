"""Whole step: model FLOPs of the decode steps in the traced window over
their device time times the chip's bf16 peak, in %."""

#: jitted decode programs: the megastep's scan and the single step
PROGRAMS = ("jit_run", "jit_step")


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace_program_time(PROGRAMS)
    if not calls:
        return None
    flops = sum(f for f, _ in ctx.step_needs())
    peak = ctx.peaks()["bf16_flops_per_s"]
    return 100.0 * flops / (secs * peak) if flops else None
