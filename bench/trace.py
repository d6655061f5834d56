"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes one ``.xplane.pb`` per traced session.  Device
planes (``/device:TPU:n``) carry one event per operation run on the chip;
the host plane's Python thread carries the harness's spans (``bench.*``,
from ``jax.profiler.TraceAnnotation``) and JAX's own spans (dispatch,
``np.asarray`` readback, transfers).

* busy: the union of the device's operation intervals inside the traced
  window, averaged over the chips used;
* window: from the start of the first traced sweep to the end of the last;
* ops: summed device seconds per operation, named by its HLO instruction
  and, for a custom call, its target (``%_mr_epoch_impl.1
  [tpu_custom_call]``: Mosaic kernels are ``tpu_custom_call``s);
* idle gaps: each stretch of the window in which no operation ran, named
  by the innermost host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

SWEEP_SPAN = "bench.sweep"
OPS_LINES = ("XLA Ops",)              # one event per operation on the chip
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(event_name: str) -> str:
    """``%name`` of an HLO op event, with ``[target]`` for a custom call
    (the event's own name is the op's whole HLO text)."""
    head = event_name.split(" = ", 1)[0]
    m = _TARGET.search(event_name)
    return f"{head} [{m.group(1)}]" if m else head


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge ``[start, end)`` rows into disjoint sorted intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [iv[0].copy()]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append(np.array([s, e]))
    return np.array(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def load(trace_dir: str):
    """The ``ProfileData`` of the one session written under ``trace_dir``
    (``.xplane.pb``, or gzipped as ``.xplane.pb.gz``)."""
    import gzip

    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb*"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {paths}")
    if paths[0].endswith(".gz"):
        with gzip.open(paths[0], "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(paths[0])


def reduce(profile, n_chips: int) -> dict:
    """``{window_s, busy_s, sweeps, ops, idle_gaps, device_planes}``;
    ``busy_s`` is ``None`` when the trace holds no device plane."""
    host_spans, device_ops = [], {}
    for plane in profile.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    host_spans += [(e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events]
        elif plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name in OPS_LINES:
                    device_ops[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns,
                         op_name(e.name)) for e in line.events]
    sweeps = [(s, e) for s, e, n in host_spans if n == SWEEP_SPAN]
    if not sweeps:
        raise RuntimeError("the trace holds no bench.sweep span")
    lo, hi = min(s for s, _ in sweeps), max(e for _, e in sweeps)
    out = {"window_s": (hi - lo) * 1e-9, "sweeps": len(sweeps),
           "device_planes": sorted(device_ops), "busy_s": None,
           "ops": {}, "idle_gaps": []}
    if not device_ops:
        return out
    planes = sorted(device_ops)[:n_chips]
    busy, ops = [], {}
    for p in planes:
        iv = _clip(np.array([(s, e) for s, e, _ in device_ops[p]],
                            np.float64).reshape(-1, 2), lo, hi)
        busy.append(float(np.sum(np.diff(_union(iv), axis=1))) * 1e-9)
        for s, e, name in device_ops[p]:
            d = (min(e, hi) - max(s, lo)) * 1e-9
            if d > 0:
                ops[name] = ops.get(name, 0.0) + d / len(planes)
    out["busy_s"] = float(np.mean(busy))
    out["ops"] = ops
    # idle gaps of the first chip, named by the innermost host span
    u = _union(_clip(np.array([(s, e) for s, e, _ in device_ops[planes[0]]],
                              np.float64).reshape(-1, 2), lo, hi))
    edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
    gaps = [(s, e) for s, e in edges if e > s]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [(hs, he, n) for hs, he, n in host_spans if hs <= mid < he]
        name = min(cover, key=lambda c: c[1] - c[0])[2] if cover else "none"
        named.append((name, float(e - s) * 1e-9))
    out["idle_gaps"] = sorted(named, key=lambda g: -g[1])[:10]
    return out
