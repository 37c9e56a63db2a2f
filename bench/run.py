"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is ``bench/workloads/<cell>.json``;
its configuration, traffic mix and metrics are found by name from there and
from ``BENCHMARK.json``.  With ``--trace 0`` the last line of standard
output is one JSON object with the cell's end-to-end metrics; with
``--trace 1`` it holds the per-layer metrics, read from a profiler trace of
part of the window, the device's busy time and a breakdown.  The numbers
that decide ``correct`` are printed, each beside its limit, as the last
lines of standard error and under ``checks`` in the result.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than the
cell asks for, and 1 on any other failure.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, common, harness, traffic  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache in one fixed directory inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program goes in,
    however fast it compiled, so a second run compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compiles, with their times, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if "backend_compile" in event:
            self.times.append(time.monotonic())

    def between(self, lo, hi) -> int:
        return sum(1 for t in self.times if lo <= t <= hi)


def selected(bench, cell: str, kind: str):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    if kind == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             bench_dir: str = common.BENCH_DIR, require_tpu: bool = True,
             t_start: float = None, fault=None, control: bool = False,
             strict: bool = None):
    """One run of cell ``name``; returns the result object the last line
    prints.  ``require_tpu=False`` and ``fault`` (a callable given the
    ``Server`` after set-up, which may break the timed path) serve the
    harness's own tests.  ``control`` puts the control in the program's
    place for ``correct``: the reference in float8 picks the tokens at
    every position of the sampled answers (``bench/calibrate.py``, the
    control test); the program's own reading on the same answers goes
    under ``program``.  ``strict`` (by default where a TPU is required):
    a metric this cell lists that its reader finds nothing for is an
    error, not a gap in the line."""
    t_start = T_START if t_start is None else t_start
    strict = require_tpu if strict is None else strict
    import jax

    cell = harness.load_cell(name, bench_dir)
    root = os.path.dirname(os.path.abspath(bench_dir))
    bench = common.load_benchmark(root)
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < entry["chips"]):
        raise NoChip(f"cell {name} needs {entry['chips']} TPU chip(s); JAX "
                     f"finds {len(devs)} {devs[0].platform} device(s)")
    dev = devs[0]
    peaks = common.peaks(dev.device_kind) if require_tpu else \
        common.peaks("TPU v5 lite")
    cache_path = enable_compile_cache() if require_tpu else "off"
    compiles = CompileCounter()
    spec, mix = cell.spec, cell.mix

    # -- set-up: weights, front door, cache fill, warm-up --------------------
    server = harness.Server(cell, seed, trace=trace)
    t_fill = 0.0
    objects = traffic.cache_objects(mix, seed)
    if objects:
        t_fill = server.fill_cache(objects)
        server.warm_search(seed)
    t = time.monotonic()
    for r in traffic.warmup(mix, seed):
        harness.post(server.address, r.body, rid=f"warm-{r.i}")
    t_warm = time.monotonic() - t
    t_settle = harness.settle(server.bridge)
    if fault is not None:
        fault(server)
    requests = traffic.schedule(mix, spec["rate_rps"], seconds, seed,
                                objects)
    by_i = {r.i: r for r in requests}
    trace_window = None
    if trace:
        tlen = min(spec.get("trace_seconds", 8.0), 0.4 * seconds)
        trace_window = (trace_start(requests, seconds, tlen), tlen)
    log(f"setup: weights {server.t_weights:.3f}s, cache fill {t_fill:.3f}s "
        f"({len(objects)} objects, {len(server.bridge.cache.store)} rows, "
        f"index {server.bridge.cache.store.index_stats()['backend']}), "
        f"warm-up {t_warm:.3f}s, overload settle {t_settle:.3f}s; "
        f"compile cache {cache_path}; "
        f"{len(compiles.times)} compiles in set-up")

    records, before, after, cap = harness.run_window(
        server, requests, seconds, spec["grace_s"], trace_window,
        tag=f"r{seed}")
    t0 = records[0]["t0"] if records else time.monotonic()
    setup_s = t0 - t_start
    harness.judge_records(records, server.recorder.served)
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    late = sorted(r["sent"] - r["due"] for r in records if r["sent"])
    log(f"window: {len(records)} requests due in {seconds}s at "
        f"{spec['rate_rps']} req/s, {sum(r['ok'] for r in records)} "
        f"answered, {sum(r['cache_hit'] for r in records)} from the cache; "
        f"generator lateness p50 {1e3 * late[len(late) // 2]:.3f} ms, max "
        f"{1e3 * late[-1]:.3f} ms; {compiles.between(t0, after['t'])} "
        f"compiles inside the window; overload level {after['overload']}")

    # -- the trace ---------------------------------------------------------
    tr = None
    if cap is not None:
        from bench import trace as btrace
        tr = btrace.reduce(cap)
    calls = []
    for t, tokens, positions in server.recorder.calls:
        in_tr = tr is not None and tr.t0 <= t <= tr.t1
        rows = [int(p) for p in positions[:, 0]] if in_tr else None
        calls.append((t, int(tokens.shape[1]), rows))
    ctx = harness.Context(cell=cell, config=cell.config, records=records,
                          before=before, after=after, setup_s=setup_s,
                          calls=calls, searches=server.recorder.searches,
                          peaks=peaks, cache_dim=server.bridge.cache.store.dim,
                          trace=tr)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in selected(bench, name, kind):
        v = common.load_reader("metrics", m["name"], bench_dir).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    missing = [m["name"] for m in selected(bench, name, kind)
               if m["name"] not in metrics]
    if missing:
        log(f"{'FAIL' if strict else 'note'}: nothing to read for "
            f"{', '.join(missing)} in this run")
        if strict:
            raise RuntimeError(f"cell {name} lists {missing}, and their "
                               f"readers found nothing in this run")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": False, "attempted": len(records),
           "failed": sum(1 for r in records if not r["ok"]),
           "metrics": metrics, "device": device}
    if tr is not None:
        out["device"]["busy_s"], out["device"]["window_s"] = \
            busy_window(tr)
        out["breakdown"] = breakdown(tr, records)

    # -- correctness, after the program's state is freed --------------------
    served = server.recorder.served
    index = check.prefill_index(server.recorder.calls)
    params = server.params
    model_name = server.model_name
    server.close()
    server.recorder.calls.clear()
    del server
    gc.collect()
    lim = spec["limits"]
    picked = check.sample(records, served, seed, spec["sample_requests"])
    rows, unmatched = check.engine_rows(picked, by_i, served, index,
                                        cell.config["vocab_size"])
    gap = check.logit_gap(params, cell.config, rows, bench_dir=bench_dir)
    if control:
        out["program"] = {"logit_gap": gap}
        gap = check.logit_gap(params, cell.config, rows, fp8=True,
                              bench_dir=bench_dir)
    wrong, n_cache = check.cache_wrong(records, by_i, objects,
                                       ctx.cache_dim, spec["cache_relevance"],
                                       model_name)
    numbers = {
        "logit_gap": {"value": gap, "limit": lim["logit_gap"]},
        "unmatched": {"value": unmatched, "limit": 0},
        "cache_wrong": {"value": wrong, "limit": 0},
        "wire_mismatch": {"value": check.wire_mismatch(records, served),
                          "limit": 0},
        "lost": {"value": check.lost(records), "limit": 0},
    }
    log(f"compared: {len(rows)} engine answers, "
        f"{sum(len(s) for _, s in rows)} served tokens; {n_cache} cache "
        f"answers; {sum(r['ok'] for r in records)} wire texts"
        + ("; the control (the reference in float8) in the program's place "
           f"for logit_gap, the program reads {out['program']['logit_gap']}"
           if control else ""))
    out["correct"] = check.decide(numbers)
    out["checks"] = numbers
    for k, v in numbers.items():
        log(f"check {k} = {v['value']} (limit {v['limit']})")
    return out


def trace_start(requests, seconds: float, tlen: float) -> float:
    """Where the traced part of the window begins: at the arrival of the
    first request due from 30 % of the window on (the last one that leaves
    room for the trace, if none), so that a lightly loaded cell has a
    request in flight in the trace whatever the seed."""
    lo, hi = 0.3 * seconds, seconds - tlen
    due = sorted(r.due for r in requests)
    later = [d for d in due if lo <= d <= hi]
    if later:
        return later[0]
    return max([d for d in due if d <= hi] or [lo])


def busy_window(tr):
    from bench import trace as btrace
    return btrace.length(btrace.busy(tr)), tr.t1 - tr.t0


def breakdown(tr, records):
    """The device ops that took most time (control flow that holds other
    ops left out), and the idle time with a request in flight by what the
    host was doing (the innermost benchmark span)."""
    from bench import trace as btrace
    per = {}
    for name, s, e in tr.ops:
        if tr.t0 <= s <= tr.t1 and not btrace.is_container(name):
            key = btrace.op_label(name)
            per[key] = per.get(key, 0.0) + (e - s)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:10]
    infl = btrace.inflight(records, tr.t0, tr.t1)
    idle = btrace.intersect(btrace.gaps(btrace.busy(tr), tr.t0, tr.t1), infl)
    by = {}
    for s, e in idle:
        a = btrace.host_activity(tr.spans, 0.5 * (s + e))
        by[a] = by.get(a, 0.0) + (e - s)
    gaps = sorted(by.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        log(f"FAIL: {e}")
        return 3
    except Exception as e:  # noqa: BLE001 — report and exit nonzero
        import traceback
        traceback.print_exc()
        log(f"FAIL: {type(e).__name__}: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
