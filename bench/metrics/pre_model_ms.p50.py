"""Median over the window's requests of the proxy stages' wall time before
the model stage (every stage but ``model``), in ms, from the per-stage
durations the bridge records (``proxy.stats()`` paths)."""
import numpy as np


def read(ctx):
    per_req = []
    for path, stages in ctx.after["durations"].items():
        cols = []
        for stage, d in stages.items():
            if stage.startswith("model"):
                continue
            n = (ctx.after["paths"][path][stage]["count"]
                 - ctx.before["paths"].get(path, {}).get(stage, {})
                 .get("count", 0))
            if n > 0:
                cols.append(d[-n:])
        if cols:
            n = min(len(c) for c in cols)
            per_req += np.sum([c[-n:] for c in cols], axis=0).tolist()
    return float(np.median(per_req) * 1e3) if per_req else None
