"""The comparison that decides ``correct``.

Every timed sweep keeps ``per_sweep`` cells drawn from ``(seed, index)``
and its longest cell (most event epochs).  Once the window has closed,
``cells`` of the kept cells, drawn from the seed, and the window's longest
cell are simulated again by the plain reference (``bench/reference.py``)
in float64, and compared on every metric of :data:`reference.METRICS`.

The number compared is the widest relative gap: over the sampled cells
and metrics, ``|program - reference| / scale``, where ``scale`` is the
larger of ``|reference|`` and the sample's median ``|reference|`` for
that metric (so a metric that is zero in one cell, such as the transfer
bytes of a data-local cell, is judged against its typical size).
"""
from __future__ import annotations

import numpy as np

from bench import generate, reference


def candidates(seed: int, index: int, n_cells: int, per_sweep: int):
    """Cell indices of sweep ``index`` kept for the comparison."""
    return generate.rng(seed, generate.CHECK, index).choice(
        n_cells, min(per_sweep, n_cells), replace=False)


class Keeper:
    """Keeps the candidate cells of each timed sweep, and the longest."""

    def __init__(self, seed: int, n_cells: int, per_sweep: int):
        self.seed, self.n_cells, self.per_sweep = seed, n_cells, per_sweep
        self.kept = []                 # (sweep, cell, {metric: value})
        self.longest = None            # (epochs, sweep, cell, values)

    def add(self, index: int, result) -> None:
        flat = {m: np.asarray(result[m]).reshape(-1)
                for m in reference.METRICS}
        for j in candidates(self.seed, index, self.n_cells, self.per_sweep):
            self.kept.append((index, int(j),
                              {m: float(v[j]) for m, v in flat.items()}))
        epochs = np.asarray(result["n_epochs"]).reshape(-1)
        j = int(np.argmax(epochs))
        if self.longest is None or epochs[j] > self.longest[0]:
            self.longest = (int(epochs[j]), index, j,
                            {m: float(v[j]) for m, v in flat.items()})

    def sample(self, cells: int):
        """``[(sweep, cell, program values)]`` to compare."""
        pick = generate.rng(self.seed, generate.CHECK, -1).choice(
            len(self.kept), min(cells, len(self.kept)), replace=False)
        out = [self.kept[k] for k in sorted(pick)]
        if self.longest is not None:
            _, i, j, vals = self.longest
            if all((i, j) != (a, b) for a, b, _ in out):
                out.append((i, j, vals))
        return out


def reference_values(traffic, seed: int, sample, dtype=np.float64):
    """The reference's metrics for every sampled cell, in ``dtype``."""
    out, cols_of = [], {}
    for i, j, _ in sample:
        if i not in cols_of:
            cols_of[i] = traffic.plan(seed, i).params()
        out.append(reference.simulate(traffic.reference_cell(cols_of[i], j),
                                      dtype))
    return out


def widest_gap(got: list[dict], want: list[dict]) -> float:
    """Widest relative gap of ``got`` against the reference's ``want``."""
    worst = 0.0
    for m in reference.METRICS:
        g = np.array([x[m] for x in got], np.float64)
        w = np.array([x[m] for x in want], np.float64)
        typical = float(np.median(np.abs(w))) if w.size else 0.0
        scale = np.maximum(np.abs(w), typical if typical > 0 else 1.0)
        gap = np.abs(g - w) / scale
        if not np.all(np.isfinite(gap)):
            return float("inf")
        worst = max(worst, float(gap.max(initial=0.0)))
    return worst
