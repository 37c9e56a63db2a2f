"""The program's decode steps per output token of the window's answered
requests: each decode step of a scheduler waits on the device once (one
``sched.wait`` span, so the count is the ``sched.decode_steps`` counter's
share of these requests), over the tokens served.  1 means one step per
token per stream; the inside twin of ``decode_calls_per_token``."""
from bench import program_spans as ps


def read(ctx):
    spans = ps.window(ctx)
    toks = sum(r["n_tokens"] for r in ctx.records if r["ok"])
    if spans is None or not toks:
        return None
    return float(len(ps.named(ps.answered(ctx, spans), "sched.wait")) / toks)
