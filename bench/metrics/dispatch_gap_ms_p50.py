"""Device / engine host: median gap between one step program's end and
the next one's start in the traced window (the host's turnaround between
engine steps: reconcile, admission, table refresh and argument transfer,
whose small conversion programs fall inside the gap), leaving out gaps in
which the harness sat idle waiting for an arrival, in ms."""

import numpy as np

from bench import trace

#: the engine's step programs: megastep, single decode step, prefill chunk
PROGRAMS = ("jit_run", "jit_step", "jit_run_chunk")


def read(ctx):
    if ctx.trace is None:
        return None
    g = trace.live_gaps(ctx.trace, PROGRAMS)
    return float(np.median(g)) * 1e3 if g else None
