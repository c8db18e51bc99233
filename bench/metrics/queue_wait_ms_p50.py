"""Engine layer: median wait from ``submit`` to first ``admit``, in ms,
over the requests admitted during the measured window (the engine's own
telemetry points, recorded with ``Telemetry(trace=True)``)."""

import numpy as np


def read(ctx):
    submit, waits = {}, []
    for e in ctx.events:
        rid = e.get("request_id")
        if e["kind"] == "submit":
            submit[rid] = e["ts"]
        elif e["kind"] == "admit" and rid in submit:
            waits.append(e["ts"] - submit.pop(rid))
    return float(np.median(waits)) * 1e3 if waits else None
