"""The program's own spans (``repro.core.telemetry``) over the measured
window, for the readers of the per-layer metrics that need them and for
the breakdowns ``PERF.md`` reports.

The system under test runs in the benchmark's process, so its span ring is
read here directly after the window.  Its spans are on ``time.monotonic()``
like the load generator's records and the reduced device trace, so all
three compare without any alignment.

``window`` returns nothing where the program records no spans (a program
that predates them) or where its ring let go of spans that closed inside
the window: a reader then finds nothing to read, rather than a part of the
window.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

#: spans at the level of a whole request or its model stage; idle time that
#: lands on them (or on no span) is time the spans do not explain
UNEXPLAINED = ("http.request", "proxy.stage.model", "no span")


def window(ctx) -> Optional[list]:
    """The program's spans of the window's requests (by request id) that
    lie inside the window, or None."""
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    spans, dropped = telemetry.snapshot()
    lo, hi = ctx.before["t"], ctx.after["t"]
    if not spans or (dropped and spans[0].t1 >= lo):
        return None
    rids = {r["rid"] for r in ctx.records}
    return [s for s in spans
            if s.rid in rids and lo <= s.t0 and s.t1 <= hi]


def answered(ctx, spans) -> list:
    """The spans of the requests the window answered."""
    ok = {r["rid"] for r in ctx.records if r["ok"]}
    return [s for s in spans if s.rid in ok]


def named(spans, name: str) -> list:
    return [s for s in spans if s.name == name]


def by_worker(spans, name: str) -> Dict[tuple, list]:
    """Spans called ``name`` per (request, thread), in time order: one
    scheduler's steps, since each streamed request decodes on its own."""
    out: Dict[tuple, list] = {}
    for s in spans:
        if s.name == name:
            out.setdefault((s.rid, s.thread), []).append(s)
    for xs in out.values():
        xs.sort(key=lambda s: s.t0)
    return out


def host_intervals(spans) -> List[tuple]:
    """Per token, what the decode thread did between the device's answer
    reaching the host and its next wait on the device: (wall s, CPU s,
    the following wait's wall s), from the end of one ``sched.wait`` to the
    start of the next on the same scheduler."""
    out = []
    for waits in by_worker(spans, "sched.wait").values():
        for a, b in zip(waits, waits[1:]):
            out.append((b.t0 - a.t1, b.c0 - a.c1, b.t1 - b.t0))
    return out


def median_ms(xs) -> Optional[float]:
    return float(np.median(xs) * 1e3) if len(xs) else None


def mean_ms(xs) -> Optional[float]:
    return float(np.mean(xs) * 1e3) if len(xs) else None


def sse_lags(spans) -> List[float]:
    """Per chunk: from the end of its ``stream.emit`` on the decode thread
    to the end of its ``http.sse_write`` on the handler thread (same
    request, same sequence number), in s."""
    emit = {(s.rid, s.attrs["seq"]): s.t1 for s in spans
            if s.name == "stream.emit" and s.attrs}
    return [s.t1 - emit[(s.rid, s.attrs["seq"])] for s in spans
            if s.name == "http.sse_write" and s.attrs
            and (s.rid, s.attrs["seq"]) in emit]


# -- breakdowns for PERF.md ----------------------------------------------------

def token_cycle(ctx, spans) -> Dict[str, Optional[float]]:
    """The token cycle as the spans split it, beside the gap the load
    generator saw: medians over tokens of the host interval, the wait that
    follows it and their sum, the median gap between a stream's deltas, and
    the means of the host interval and its CPU part (a thread clock in
    10 ms ticks gives a mean, not a median), in ms."""
    iv = host_intervals(answered(ctx, spans))
    gaps = [g for r in ctx.records if r["ok"] and r["stream"]
            for g in np.diff(r["deltas"]).tolist()]
    return {"tokens": len(iv),
            "host_ms": median_ms([h for h, _, _ in iv]),
            "wait_ms": median_ms([w for _, _, w in iv]),
            "cycle_ms": median_ms([h + w for h, _, w in iv]),
            "delta_gap_ms": median_ms(gaps),
            "host_mean_ms": mean_ms([h for h, _, _ in iv]),
            "host_cpu_mean_ms": mean_ms([c for _, c, _ in iv])}


def _depth(s, by_id) -> int:
    d = 0
    while s.parent in by_id:
        s, d = by_id[s.parent], d + 1
    return d


def _innermost(spans, t: float, by_id):
    best = None
    for s in spans:
        if s.t0 <= t < s.t1:
            key = (_depth(s, by_id), s.t0)
            if best is None or key > best[0]:
                best = (key, s)
    return best[1] if best is not None else None


def idle_by_span(ctx, spans) -> Dict[str, float]:
    """The traced time with a request in flight and no device op, in s, by
    what the host was doing: the innermost program span on the worker
    thread of a request in flight, or, where that is the model stage or
    above, the innermost span on that request's handler thread."""
    from bench import trace as btrace
    tr = ctx.trace
    infl = btrace.inflight(ctx.records, tr.t0, tr.t1)
    idle = btrace.intersect(btrace.gaps(btrace.busy(tr), tr.t0, tr.t1), infl)
    by_id = {s.id: s for s in spans}
    handler = {s.thread for s in spans if s.name == "http.request"}
    flight = [(r["sent"], r["end"], r["rid"]) for r in ctx.records
              if r.get("sent") is not None and r.get("end") is not None]
    out: Dict[str, float] = {}
    for s, e in idle:
        near = [x for x in spans if x.t0 < e and x.t1 > s]
        cuts = sorted({s, e} | {x.t0 for x in near if s < x.t0 < e}
                      | {x.t1 for x in near if s < x.t1 < e})
        for a, b in zip(cuts, cuts[1:]):
            t = 0.5 * (a + b)
            rids = {rid for lo, hi, rid in flight if lo <= t <= hi}
            mine = [x for x in near if x.rid in rids]
            w = _innermost([x for x in mine if x.thread not in handler],
                           t, by_id)
            if w is None or w.name in UNEXPLAINED:
                w = _innermost([x for x in mine if x.thread in handler],
                               t, by_id) or w
            key = w.name if w is not None else "no span"
            out[key] = out.get(key, 0.0) + (b - a)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def spans_per_token(ctx, spans) -> Optional[float]:
    """Spans the answered requests recorded per token they were served."""
    toks = sum(r["n_tokens"] for r in ctx.records if r["ok"])
    return len(answered(ctx, spans)) / toks if toks else None


def span_cost(n: int = 100_000) -> float:
    """Wall time of one ``span()`` opened and closed with nothing inside,
    averaged over ``n`` (no profiler running), in s."""
    from repro.core import telemetry
    t = time.perf_counter()
    for _ in range(n):
        with telemetry.span("bench.span_cost"):
            pass
    return (time.perf_counter() - t) / n
