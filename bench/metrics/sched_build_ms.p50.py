"""Median wall time of building a request's scheduler (the program's
``adapter.scheduler_build`` span: the Scheduler, its paged KV cache and
any draft) over the window's requests, in ms."""
from bench import program_spans as ps


def read(ctx):
    spans = ps.window(ctx)
    if spans is None:
        return None
    return ps.median_ms([s.t1 - s.t0 for s in
                         ps.named(spans, "adapter.scheduler_build")])
