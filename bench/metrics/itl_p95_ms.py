"""95th percentile of every gap between consecutive deltas of every streamed
request due in the window, pooled, in ms. A failed request adds one gap
that runs from its due time to the deadline."""
import numpy as np


def read(ctx):
    gaps = []
    for r in ctx.records:
        if not r["stream"]:
            continue
        if r["ok"]:
            gaps.extend(np.diff(r["deltas"]).tolist())
        else:
            gaps.append(r["deadline"] - r["due"])
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None
