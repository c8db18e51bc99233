"""Device: 1 - (union of device-operation intervals) / traced window, in
%."""

from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.trace.window_s)
