"""95th percentile of due time to the whole answer, over every request due
in the window, in ms. A failed request misses the window: its latency runs
to the deadline."""
import numpy as np


def read(ctx):
    xs = [(r["end"] if r["ok"] else r["deadline"]) - r["due"]
          for r in ctx.records]
    return float(np.percentile(xs, 95) * 1e3) if xs else None
