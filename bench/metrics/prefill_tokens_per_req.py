"""Tokens the engine prefilled (padded batch x length, ``Engine.
n_prefill_tokens``) per engine-answered request of the window."""


def read(ctx):
    n = sum(1 for r in ctx.records if r["ok"] and r["n_tokens"])
    d = ctx.after["n_prefill_tokens"] - ctx.before["n_prefill_tokens"]
    return float(d / n) if n else None
