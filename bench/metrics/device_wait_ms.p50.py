"""Median wall time the host waited on the device per decode step of the
window's requests (the program's ``sched.wait`` spans), in ms.  Beside
``decode_step_ms`` it says how much of the device's step the host's own
work overlaps."""
from bench import program_spans as ps


def read(ctx):
    spans = ps.window(ctx)
    if spans is None:
        return None
    return ps.median_ms([s.t1 - s.t0 for s in
                         ps.named(ps.answered(ctx, spans), "sched.wait")])
