"""Calls into ``Engine.decode`` that decode one token per row (counted by
the benchmark's wrapper) per output token of the window's answered
requests: 1 means one device step per token per stream."""


def read(ctx):
    toks = sum(r["n_tokens"] for r in ctx.records if r["ok"])
    calls = sum(1 for t, s, _ in ctx.calls
                if s == 1 and ctx.before["t"] <= t <= ctx.after["t"])
    return float(calls / toks) if toks else None
