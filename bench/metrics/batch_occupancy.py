"""Engine layer: rows per executed decode step over ``max_batch``, in %,
from the engine's ``megastep`` spans (``rows`` at launch times
``executed`` steps) and single-step ``decode`` spans during the measured
window."""


def read(ctx):
    rows = steps = 0
    for e in ctx.events:
        a = e.get("args", {})
        if e["kind"] == "megastep":
            rows += a["rows"] * a["executed"]
            steps += a["executed"]
        elif e["kind"] == "decode":
            rows += a["rows"]
            steps += 1
    return 100.0 * rows / steps / ctx.max_batch if steps else None
