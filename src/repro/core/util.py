"""Small shared numeric utilities (host-side, numpy).

``pow2_pad``/``pow2_pads`` are the one shape-rounding rule every layer
of the adaptive schedule uses — bucket task/VM paddings (``sweep``),
compacted active-lane counts (``engine.simulate_batch_arrays_compact``,
``kernels.mr_sched.ops``), and the cost model's candidate partitions
(``costmodel``).  Hoisted here because the measured-cost bucket scorer
evaluates many candidate partitions per plan, which made the original
per-unique-value Python loop a hot spot.

``enable_compile_cache`` is the one place the entry points (``chip_smoke.py``
and the benchmarks) turn on JAX's persistent compilation cache.
"""
from __future__ import annotations

import os
import pathlib

import numpy as np

# the checkout root (src/repro/core/util.py -> three levels up)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache lives at ``.jax_cache/`` in
    the checkout: a fixed path, since the path is part of the cache key.
    Called by entry points, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path

# floor * 2**j ladder, precomputed far past any realistic padding; the
# table form makes the vectorized rounding exact (no float log2 edge
# cases at exact powers of two)
_MAX_DOUBLINGS = 50


def validate_pow2_floor(floor: int) -> int:
    """Reject nonsensical padding floors with ``ValueError``.

    The ``floor * 2**j`` ladder only makes sense for a positive
    power-of-two floor: zero/negative floors collapse the table to
    garbage (every pad rounds to 0) and a non-pow2 floor silently
    produces pads like 24 that defeat the compile-cache-friendly shape
    set the rounding exists to guarantee.  Every entry point that
    accepts a ``floor=`` kwarg funnels through here so the failure is
    loud at the call site, not downstream in a shape mismatch."""
    f = int(floor)
    if f < 1 or (f & (f - 1)) != 0:
        raise ValueError(
            f"pow2 padding floor must be a positive power of two, got "
            f"{floor!r}")
    return f


def pow2_pads(need, cap: int, floor: int = 4) -> np.ndarray:
    """Vectorized :func:`pow2_pad`: smallest ``floor * 2**j >= need``
    elementwise, clamped to ``cap``.  ``need`` may be any integer array;
    entries ``<= floor`` round to ``floor``, entries past ``cap`` clamp
    to ``cap`` (the grid-wide max or an explicit pad override)."""
    floor = validate_pow2_floor(floor)
    need = np.asarray(need, np.int64)
    table = floor * (np.int64(1) << np.arange(_MAX_DOUBLINGS, dtype=np.int64))
    idx = np.searchsorted(table, np.maximum(need, 1), side="left")
    return np.minimum(table[np.minimum(idx, _MAX_DOUBLINGS - 1)],
                      np.int64(cap))


def pow2_pad(need: int, cap: int, floor: int = 4) -> int:
    """Smallest of ``{floor, 2*floor, 4*floor, ...}`` that fits ``need``,
    clamped to ``cap``.  Power-of-two rounding keeps the set of compiled
    shapes small and stable across differently-composed grids/batches
    (compile-cache friendly)."""
    return int(pow2_pads(np.asarray([need]), cap, floor)[0])
