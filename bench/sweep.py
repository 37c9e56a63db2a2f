"""Find a cell's knee: the highest offered rate at which the front door still
answers a stated share of requests within the cell's limits with no
growing backlog.  Used once, when a cell is defined, to fix its rate.

    python3 bench/sweep.py --workload <cell> --seed 11 --seconds 20 \
        --rates 0.5,1,1.5,2,3

One process sets the cell up once and drives one window per rate, lowest
first.  Per rate it prints one JSON line: requests, failures, the share
that met the limits (streamed: time to first token and the 95th
percentile of the request's own gaps; whole answers: latency), medians
and tails, tokens/s and the backlog (last answer after the window
closed).  ``--trace-summary PATH`` also traces part of the first window and
writes what the device trace holds (op and program names, counts, times)
to PATH, for fitting the trace reduction to the names the chip shows.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import harness, run, traffic  # noqa: E402


def met(r, lim) -> bool:
    if not r["ok"]:
        return False
    if r["stream"]:
        if not r["deltas"]:
            return False
        ttft = r["deltas"][0] - r["due"]
        gaps = np.diff(r["deltas"])
        p95 = float(np.percentile(gaps, 95)) if len(gaps) else 0.0
        return ttft * 1e3 <= lim["ttft_ms"] and p95 * 1e3 <= lim["gap_ms"]
    return (r["end"] - r["due"]) * 1e3 <= lim["latency_ms"]


def summarize(records, seconds, lim, rate):
    ok = [r for r in records if r["ok"]]
    out = {"rate": rate, "requests": len(records),
           "failed": len(records) - len(ok),
           "met_share": sum(met(r, lim) for r in records) / len(records)}
    if ok:
        t0 = ok[0]["t0"]
        lat = [r["end"] - r["due"] for r in ok]
        out.update(latency_p50_ms=1e3 * float(np.percentile(lat, 50)),
                   latency_p95_ms=1e3 * float(np.percentile(lat, 95)),
                   backlog_s=max(r["end"] for r in ok) - (t0 + seconds),
                   out_tok_s=sum(r["n_tokens"] for r in ok)
                   / (max(r["end"] for r in ok) - t0),
                   cache_hits=sum(r["cache_hit"] for r in ok))
        st = [r for r in ok if r["stream"] and r["deltas"]]
        if st:
            ttft = [r["deltas"][0] - r["due"] for r in st]
            gaps = np.concatenate([np.diff(r["deltas"]) for r in st])
            out.update(ttft_p50_ms=1e3 * float(np.percentile(ttft, 50)),
                       ttft_p95_ms=1e3 * float(np.percentile(ttft, 95)),
                       itl_p50_ms=1e3 * float(np.percentile(gaps, 50)),
                       itl_p95_ms=1e3 * float(np.percentile(gaps, 95)))
    return out


def trace_summary(tr):
    per_op, per_mod, per_span = {}, {}, {}
    for n, s, e in tr.ops:
        k = n[:300]
        c = per_op.setdefault(k, [0, 0.0])
        c[0] += 1
        c[1] += e - s
    for n, s, e in tr.modules:
        c = per_mod.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += e - s
    for n, s, e in tr.spans:
        c = per_span.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += e - s
    top = sorted(per_op.items(), key=lambda kv: -kv[1][1])[:60]
    return {"window": [tr.t0, tr.t1], "device_planes": tr.device_planes,
            "n_ops": len(tr.ops), "n_modules": len(tr.modules),
            "ops": [[n, c, t] for n, (c, t) in top],
            "modules": sorted(([n, c, t] for n, (c, t) in per_mod.items()),
                              key=lambda x: -x[2])[:40],
            "spans": [[n, c, t] for n, (c, t) in per_span.items()],
            "first_ops": [[n, s, e] for n, s, e in tr.ops[:40]],
            "first_modules": [[n, s, e] for n, s, e in tr.modules[:20]]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--grace", type=float, default=30.0)
    ap.add_argument("--trace-summary", default=None)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("FAIL: no TPU", file=sys.stderr)
        return 3
    run.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    lim = cell.spec["sweep_limits"]
    server = harness.Server(cell, args.seed, trace=bool(args.trace_summary))
    objects = traffic.cache_objects(cell.mix, args.seed)
    t_fill = server.fill_cache(objects) if objects else 0.0
    if objects:
        server.warm_search(args.seed)
    t = time.monotonic()
    for r in traffic.warmup(cell.mix, args.seed):
        harness.post(server.address, r.body, rid=f"warm-{r.i}")
    print(json.dumps({"setup_s": time.monotonic() - T_START,
                      "weights_s": server.t_weights, "fill_s": t_fill,
                      "warmup_s": time.monotonic() - t,
                      "settle_s": harness.settle(server.bridge)}), flush=True)
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        reqs = traffic.schedule(cell.mix, rate, args.seconds,
                                args.seed + k, objects)
        tw = (0.3 * args.seconds, min(8.0, 0.4 * args.seconds)) \
            if (args.trace_summary and k == 0) else None
        records, before, after, cap = harness.run_window(
            server, reqs, args.seconds, args.grace, tw, tag=f"s{k}")
        harness.judge_records(records, server.recorder.served)
        line = summarize(records, args.seconds, lim, rate)
        line["overload"] = after["overload"]
        print(json.dumps(line), flush=True)
        if cap is not None:
            from bench import trace as btrace
            with open(args.trace_summary, "w") as f:
                json.dump(trace_summary(btrace.reduce(cap)), f, indent=1)
        harness.settle(server.bridge)
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
