"""Profiler trace of part of the window, and its reduction to intervals.

``capture`` runs on the benchmark's main thread while the server's threads
serve: it starts JAX's profiler at a given ``time.monotonic()``, marks the
start and end with host spans, and stops.  ``reduce`` reads the written
``.xplane.pb`` after the window (so parsing steals no time from it) with
nothing but ``jax.profiler.ProfileData`` and returns plain lists:

* ``ops``: (name, start, end) of every operation on the device's op line,
  named by its HLO instruction (``%fusion.54 = bf16[...] fusion(...)``,
  cut to ``NAME_CHARS``), which is how kernels are told apart;
* ``modules``: (name, start, end) of every program run on the device;
* ``spans``: (name, start, end) of the benchmark's host spans (``bench.*``);

all in seconds on the host's ``time.monotonic()`` clock, using the start
mark to align the trace's clock with it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
import time
from typing import List, Optional, Tuple

Interval = Tuple[str, float, float]
NAME_CHARS = 240          # enough for an op's name, output shape and kind

MARK = "bench.trace_mark"


@dataclasses.dataclass
class Captured:
    path: str            # directory the profiler wrote
    m0: float            # time.monotonic() at the start mark
    m1: float            # time.monotonic() at the end mark


@dataclasses.dataclass
class Trace:
    t0: float
    t1: float
    ops: List[Interval]
    modules: List[Interval]
    spans: List[Interval]
    device_planes: List[str]


def capture(start: float, seconds: float) -> Captured:
    import jax
    wait = start - time.monotonic()
    if wait > 0:
        time.sleep(wait)
    path = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(path)
    with jax.profiler.TraceAnnotation(MARK):
        m0 = time.monotonic()
    time.sleep(seconds)
    with jax.profiler.TraceAnnotation(MARK):
        m1 = time.monotonic()
    jax.profiler.stop_trace()
    return Captured(path=path, m0=m0, m1=m1)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def reduce(cap: Captured, keep: bool = False) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(cap.path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no trace written under {cap.path}")
    try:
        return reduce_profile(ProfileData.from_file(files[0]), cap.m0, cap.m1)
    finally:
        if not keep:
            shutil.rmtree(cap.path, ignore_errors=True)


def reduce_profile(prof, m0: float, m1: float) -> Trace:
    """``prof``: a ``ProfileData`` (or anything with ``planes`` whose
    ``lines`` hold ``events`` with ``name``, ``start_ns``, ``duration_ns``)."""
    ops: List[Interval] = []
    modules: List[Interval] = []
    spans: List[Interval] = []
    marks: List[float] = []
    devices: List[str] = []
    for plane in prof.planes:
        if _is_device(plane.name):
            devices.append(plane.name)
            for line in plane.lines:
                lname = line.name
                if lname == "XLA Ops":
                    dest = ops
                elif lname == "XLA Modules":
                    dest = modules
                else:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dest.append((ev.name[:NAME_CHARS], s,
                                 s + ev.duration_ns * 1e-9))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARK:
                        marks.append(ev.start_ns * 1e-9)
                    elif ev.name.startswith("bench."):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name, s, s + ev.duration_ns * 1e-9))
    if not marks:
        raise RuntimeError("the trace holds no start mark")
    off = m0 - min(marks)            # trace clock -> time.monotonic()
    shift = lambda xs: sorted(((n, s + off, e + off) for n, s, e in xs),
                              key=lambda x: x[1])
    return Trace(t0=m0, t1=m1, ops=shift(ops), modules=shift(modules),
                 spans=shift(spans), device_planes=devices)


# -- interval arithmetic ------------------------------------------------------

def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged (start, end) pairs of ``intervals`` clipped to [lo, hi]."""
    xs = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                if e > lo and s < hi)
    out: List[Tuple[float, float]] = []
    for s, e in xs:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> List[Tuple[float, float]]:
    """Intersection of two merged, sorted interval lists."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Complement of merged ``busy`` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def busy(tr: Trace) -> List[Tuple[float, float]]:
    """When an operation ran on the device (programs where no op line)."""
    src = tr.ops or tr.modules
    return union([(s, e) for _, s, e in src], tr.t0, tr.t1)


def op_label(name: str) -> str:
    """An op's instruction name and kind, e.g. ``%fusion.54 fusion``."""
    head = name.split(" = ", 1)
    kind = re.search(r"[})] ([a-z][\w-]*)\(", name)
    return head[0] + (" " + kind.group(1) if kind else "")


CONTAINERS = ("while", "conditional", "call")


def is_container(name: str) -> bool:
    """Control flow whose interval holds other ops (a layer scan's while)."""
    return op_label(name).rsplit(" ", 1)[-1] in CONTAINERS


def inflight(records, lo: float, hi: float) -> List[Tuple[float, float]]:
    """When at least one request was in flight (sent, not yet answered)."""
    return union([(r["sent"], r["end"]) for r in records
                  if r.get("sent") is not None and r.get("end") is not None],
                 lo, hi)


def host_activity(spans, t: float) -> str:
    """The innermost benchmark span around ``t`` on any host thread."""
    best: Optional[Interval] = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best is not None else "no span"
