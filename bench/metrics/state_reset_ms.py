"""Model step layer: device time per call of the program that clears
new tenants' SSM state and conv windows (``RESET_PROGRAM``, one call
per admission wave) in the traced window."""

#: the row-reset program
PROGRAMS = ("jit_reset",)


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace_program_time(PROGRAMS)
    return secs / calls * 1e3 if calls else None
