"""Median time to first token of the streamed requests due in the window:
due time to the first content delta, in ms. A failed request misses the
window: its time runs to the deadline."""
import numpy as np


def read(ctx):
    xs = [(r["deltas"][0] if r["ok"] and r["deltas"] else r["deadline"])
          - r["due"] for r in ctx.records if r["stream"]]
    return float(np.percentile(xs, 50) * 1e3) if xs else None
