"""Median over the window's decoded tokens of the host's part of the token
cycle, in ms: on each stream's scheduler, the wall time from the end of one
``sched.wait`` (the device's token reached the host) to the start of the
next (the next step waits on the device): the token's bookkeeping,
detokenize, emit, admission check, page mapping and dispatch.  Read from
the program's spans (``bench/program_spans.py``)."""
from bench import program_spans as ps


def read(ctx):
    spans = ps.window(ctx)
    if spans is None:
        return None
    return ps.median_ms([h for h, _, _ in
                         ps.host_intervals(ps.answered(ctx, spans))])
