"""Engine layer: the traced run's p95 time per output token, in ms (as
``end_to_end`` computes it, over the whole window).  A closed loop at the
batch runs at capacity, where this tail swings from run to run by more
than an end-to-end bound may allow; here it is read, not bounded."""

import math


def read(ctx):
    v = ctx.e2e.get("tpot_p95_ms")
    return v if v is not None and math.isfinite(v) else None
