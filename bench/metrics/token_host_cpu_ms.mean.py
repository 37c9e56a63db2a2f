"""Mean over the window's decoded tokens of the CPU time the decode thread
spent in the host's part of the token cycle (the intervals of
``token_host_ms.p50``, on the thread's own clock, ``sched.wait`` to
``sched.wait``), in ms.  A mean, not a median: a TPU VM host's thread
clock advances in 10 ms ticks, so one interval reads 0, 10 or 20 ms, and
only the mean over many intervals is the time spent.  What the mean wall
time of the same intervals holds beyond it the thread could have run and
did not: the interpreter lock, other threads."""
from bench import program_spans as ps


def read(ctx):
    spans = ps.window(ctx)
    if spans is None:
        return None
    return ps.mean_ms([c for _, c, _ in
                       ps.host_intervals(ps.answered(ctx, spans))])
