"""Median wall time of the pipeline's cache stage over the window, in ms,
from the per-stage durations the bridge records."""
import numpy as np


def read(ctx):
    xs = []
    for path, stages in ctx.after["durations"].items():
        d = stages.get("cache")
        if not d:
            continue
        n = (ctx.after["paths"][path]["cache"]["count"]
             - ctx.before["paths"].get(path, {}).get("cache", {})
             .get("count", 0))
        if n > 0:
            xs += d[-n:]
    return float(np.median(xs) * 1e3) if xs else None
