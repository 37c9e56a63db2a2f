"""Model FLOPs of the traced window's decode steps (``bench/kernels/
decode_step.py``, one token per row at its position) over their device
time times the chip's bf16 peak, in %."""


def read(ctx):
    steps = ctx.decode_steps()
    calls = ctx.calls_in_trace(1)
    if not steps or not calls:
        return None
    k = ctx.kernel("decode_step")
    flops = sum(k.cost(ctx.config, pos)[0]
                for _, _, rows in calls for pos in rows)
    # the host issues a call, the device runs it: scale the calls' FLOPs to
    # the steps the trace holds, which differ by the window's edges
    flops *= len(steps) / len(calls)
    t = sum(e - s for s, e in steps)
    return float(100.0 * flops / (t * ctx.peaks["bf16_flops_per_s"]))
