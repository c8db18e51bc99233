"""Model step layer: device time per call of the chunked-prefill program
in the traced window."""

PROGRAMS = ("jit_run_chunk",)


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace_program_time(PROGRAMS)
    return secs / calls * 1e3 if calls else None
