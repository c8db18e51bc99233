"""Engine layer: the host time the engine puts on the critical path of
one step, in ms: the median over the measured window's ``iteration``
spans of their duration less the ``sync`` spans inside them (the
blocking reads of the step's outputs, where the host waits on the
device).  The engine's own spans, recorded with ``Telemetry(trace=True)``
and also written into the profiler trace as ``engine.<kind>``
annotations.  Read beside the device trace's gaps, so only in a run
whose device was traced; nothing where the engine records no ``sync``
spans."""

from bench import spans


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return spans.host_ms_p50(ctx.events)
