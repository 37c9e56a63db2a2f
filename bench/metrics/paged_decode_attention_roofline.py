"""The paged decode attention kernel's share of its roofline over the
traced window, in %: the least time the chip could take for the calls
(larger of FLOPs over peak and bytes over HBM bandwidth, from
``bench/kernels/paged_decode_attention.py`` at each call's position, one
call per layer) over the kernel's device time."""


def read(ctx):
    k = ctx.kernel("paged_decode_attention")
    ops = ctx.kernel_ops(k)
    calls = ctx.calls_in_trace(1)
    t = sum(e - s for s, e in ops)
    n_ev = len(ops)
    if not t or not calls:
        return None
    L = ctx.config["num_hidden_layers"]
    least = 0.0
    for _, _, rows in calls:
        f = b = 0.0
        for pos in rows:                 # one kernel call serves every row
            df, db = k.cost(ctx.config, pos)
            f, b = f + df, b + db
        least += L * max(f / ctx.peaks["bf16_flops_per_s"],
                         b / ctx.peaks["hbm_bytes_per_s"])
    least *= n_ev / (L * len(calls))     # calls whose kernels the trace holds
    return float(100.0 * least / t)
