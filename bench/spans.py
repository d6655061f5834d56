"""Device idle time put down to the program's own host spans.

``SweepPlan.run`` marks its phases with ``iotsim.*`` host spans
(``jax.profiler.TraceAnnotation``, DESIGN.md §12.4), on the same clock as
the device planes.  :func:`reduce` adds to ``bench/trace.py``'s numbers,
for the first chip in the window of the ``bench.sweep`` spans:

* ``idle_by_span``: for each ``iotsim.*`` name, the seconds the chip sat
  idle inside that span's self intervals (the span minus its ``iotsim``
  children);
* ``idle_outside_s``: idle seconds under no ``iotsim`` span;
* ``idle_s``: all idle seconds of the window, which the two above sum to;
* ``programs``: device programs launched (``PJRT_LoadedExecutable_Execute``
  events of the host's Python thread) that start inside ``iotsim.run``
  spans.

Idle time is the complement, inside the window, of the union of the
chip's operation intervals, as ``bench.trace.reduce`` builds it.
"""
from __future__ import annotations

import numpy as np

from bench.trace import OPS_LINES, SWEEP_SPAN, _clip, _union

PREFIX = "iotsim."
RUN_SPAN = "iotsim.run"
PROGRAM_EVENT = "PJRT_LoadedExecutable_Execute"


def self_segments(spans) -> list[tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` segments of nested ``(start, end,
    name)`` spans, each named by the innermost span that covers it: the
    spans' self intervals."""
    out, stack, cursor = [], [], 0.0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, e, n = stack.pop()
            out.append((cursor, e, n))
            cursor = e

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if stack:
            out.append((cursor, s, stack[-1][2]))
        stack.append((s, e, n))
        cursor = s
    close_until(np.inf)
    return [seg for seg in out if seg[1] > seg[0]]


def idle_by_span(idle: np.ndarray, spans) -> tuple[dict[str, float], float]:
    """``({name: idle inside its self intervals}, idle under no span)`` for
    disjoint, sorted ``[start, end)`` idle rows and nested spans, in the
    units of the intervals."""
    idle = idle[idle[:, 1] > idle[:, 0]]
    edges = idle.reshape(-1)
    lengths = np.repeat(np.diff(idle, axis=1).ravel(), 2)
    lengths[0::2] = 0.0
    cum = np.cumsum(lengths)          # idle time up to each edge

    def idle_in(a, b):
        if not len(edges):
            return 0.0
        return float(np.interp(b, edges, cum) - np.interp(a, edges, cum))

    by_name = {n: 0.0 for _, _, n in spans}
    for s, e, n in self_segments(spans):
        by_name[n] += idle_in(s, e)
    covered = _union(np.array([(s, e) for s, e, _ in spans],
                              np.float64).reshape(-1, 2))
    total = float(np.sum(np.diff(idle, axis=1)))
    outside = total - sum(idle_in(s, e) for s, e in covered)
    return by_name, outside


def reduce(profile) -> dict:
    """``{idle_by_span, idle_outside_s, idle_s, programs}`` (seconds) of a
    trace holding ``bench.sweep`` spans; ``{}`` when it holds no device
    plane."""
    sweeps, spans, programs, planes = [], [], [], {}
    for plane in profile.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == SWEEP_SPAN:
                        sweeps.append(iv)
                    elif e.name.startswith(PREFIX):
                        spans.append(iv)
                    elif e.name.startswith(PROGRAM_EVENT):
                        programs.append(e.start_ns)
        elif plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name in OPS_LINES:
                    planes[plane.name] = [(e.start_ns,
                                           e.start_ns + e.duration_ns)
                                          for e in line.events]
    if not sweeps:
        raise RuntimeError("the trace holds no bench.sweep span")
    if not planes:
        return {}
    lo, hi = min(s for s, _, _ in sweeps), max(e for _, e, _ in sweeps)
    busy = _union(_clip(np.array(planes[min(planes)], np.float64)
                        .reshape(-1, 2), lo, hi))
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    by_name, outside = idle_by_span(edges, spans)
    runs = [(s, e) for s, e, n in spans if n == RUN_SPAN]
    return {"idle_by_span": {n: v * 1e-9 for n, v in by_name.items()},
            "idle_outside_s": outside * 1e-9,
            "idle_s": float(np.sum(np.diff(edges, axis=1))) * 1e-9,
            "programs": sum(any(s <= t < e for s, e in runs)
                            for t in programs)}
