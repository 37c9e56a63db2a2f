"""Device time of one decode-step program run (the jitted decode step with
one token per row, told from the prefill by the decode kernel inside it),
averaged over the traced window, in ms."""


def read(ctx):
    steps = ctx.decode_steps()
    if not steps:
        return None
    return float(sum(e - s for s, e in steps) / len(steps) * 1e3)
