"""Measured execution-cost model for the adaptive schedule (DESIGN.md §9).

The bucket-merge heuristic and the compaction interval used to be static
magic numbers (``min_cells = max(256, N // 4)``; check-every-epoch).  Both
decisions trade the same two measured quantities against each other:

* ``dispatch_us`` — the fixed overhead of one fused bucket dispatch
  (trace-cache lookup, argument staging, XLA call, readback).  Paying it
  once more is the *cost* of splitting a bucket or of a compaction
  round's gather/step/scatter chain.
* ``epoch_lane_us`` — the marginal cost of advancing one lane one event
  epoch per task slot (the epoch body is branch-free, so this is
  activity-independent).  Saving lane-epochs is the *benefit* of both a
  smaller-padded bucket and a compacted batch.
* ``sync_us`` — the cost of one blocking scalar device→host pull.  The
  dispatch-lean compact loop (DESIGN.md §13) pays exactly one of these
  per round (the fused ``[n_step, n_active]`` pair) instead of a full
  ``bool[N]`` mask transfer, so the round overhead it balances against
  wasted tail epochs is ``sync_us + dispatch_us`` — measured, not the
  retired ``ROUND_DISPATCHES`` guess.

All are measured once per device with a tiny seeded micro-benchmark
(min-of-reps: these feed scheduling decisions, so the noise floor is the
right statistic) and persisted to a small JSON cache keyed by device, so
every later process skips the measurement.  A pinned calibration file
makes every scoring decision deterministic (``tests/test_compaction.py``).

The scoring formulas live on :class:`CostModel` so the bucket scheduler
(``sweep._bucket_groups``), the compacted-stepping drivers
(``engine.simulate_batch_arrays_compact``, ``kernels.mr_sched.ops``) and
the ROADMAP item-2 request coalescer all price work with the same two
coefficients.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
from functools import partial

import numpy as np

ENV_PATH = "REPRO_COSTMODEL_PATH"
_DEFAULT_PATH = pathlib.Path.home() / ".cache" / "repro-iotsim" / \
    "costmodel.json"

# Persisted-cache schema version.  The cache file is
# ``{"schema": N, "models": {device: {coefficients...}}}``; bump this
# whenever the coefficient semantics change (e.g. a new measurement
# protocol) so stale caches are invalidated instead of silently feeding
# garbage coefficients into the schedulers.  Pre-schema files (a bare
# ``{device: {...}}`` mapping) fail the check and are re-measured.
# v2: adds the measured ``sync_us`` scalar-pull coefficient (the
# dispatch-lean compact loop prices rounds as sync + dispatch, replacing
# the fixed ROUND_DISPATCHES multiplier), so v1 caches re-measure.
SCHEMA_VERSION = 2

# Conservative CPU-ish coefficients used when measurement is disabled:
# chosen to reproduce the retired static
# heuristic's behaviour on the benchmark grids within a few percent.
_FALLBACK_DISPATCH_US = 1500.0
_FALLBACK_EPOCH_LANE_US = 0.030
_FALLBACK_SYNC_US = 250.0

# Clamp bounds for the auto compaction interval K*.  Named constants so
# re-derivations of the interval formula cannot silently change the
# clamp (regression-tested): K=1 is the check-every-epoch floor the
# pre-cost-model driver used; 64 caps the wasted-tail exposure of a
# degenerate calibration (a huge measured dispatch cost must not make
# the driver effectively never compact).
COMPACT_INTERVAL_MIN = 1
COMPACT_INTERVAL_MAX = 64

_CACHE: dict[str, "CostModel"] = {}


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Measured coefficients + the scoring rules built on them."""
    dispatch_us: float       # fixed overhead of one fused dispatch
    epoch_lane_us: float     # us per (lane x epoch x task-slot)
    # Cost of one blocking scalar device->host pull (the compact loop's
    # per-round [n_step, n_active] readback).  Defaulted so pinned
    # hand-constructed calibrations predating the split keep working.
    sync_us: float = _FALLBACK_SYNC_US
    device: str = "unknown"
    # Where the coefficients came from — "measured" (fresh micro-bench
    # this process), "cache" (persisted JSON hit), "fallback" (built-in
    # conservative constants), or "static" (hand-constructed, e.g. the
    # pinned test calibrations).  Surfaced through ``RunReport`` and the
    # BENCH meta so a recorded number can be traced to its calibration.
    # compare=False: provenance, not a coefficient — a save/load
    # round-trip must stay ``==`` to what was saved.
    source: str = dataclasses.field(default="static", compare=False)

    # -- derived scoring -------------------------------------------------
    @staticmethod
    def est_epochs(pad_t) -> np.ndarray:
        """Expected realized epochs for lanes padded to ``pad_t`` tasks.

        Tail-heavy (space-shared) lanes admit roughly one task per event
        epoch, so realized counts scale ~linearly with the task count —
        ``t + 2`` is half the engine's hard ``2t + 2`` bound and matches
        the recorded ``realized_epochs`` trajectory within ~2x across the
        BENCH_sweep rows, which is accurate enough to rank partitions."""
        return np.asarray(pad_t, np.float64) + 2.0

    def cell_cost_us(self, pad_t) -> np.ndarray:
        """Marginal simulation cost of ONE lane padded to ``pad_t`` tasks
        (dispatch overhead excluded — that is per bucket, not per lane)."""
        t = np.asarray(pad_t, np.float64)
        return self.epoch_lane_us * t * self.est_epochs(t)

    def bucket_cost_us(self, n_cells, pad_t) -> float:
        """Modelled cost of running ``n_cells`` lanes as one bucket."""
        return float(self.dispatch_us
                     + np.asarray(n_cells, np.float64)
                     * self.cell_cost_us(pad_t))

    def split_gain_us(self, n_cells, pad_t, cap_t) -> float:
        """Saving from running ``n_cells`` lanes in their own ``pad_t``
        bucket instead of merged up into a ``cap_t``-padded one — before
        subtracting the extra ``dispatch_us`` the split costs.  A split
        pays iff this exceeds ``dispatch_us``."""
        return float(np.asarray(n_cells, np.float64)
                     * (self.cell_cost_us(cap_t) - self.cell_cost_us(pad_t)))

    def compact_interval(self, n_lanes: int, pad_t: int) -> int:
        """Auto compaction interval K (epochs between active-lane checks).

        A dispatch-lean round (DESIGN.md §13) costs ``sync_us`` (the
        blocking ``[n_step, n_active]`` scalar pull) plus ``dispatch_us``
        (the chunk-step launch), paid ``1/K`` per epoch; the full
        gather/scatter chain is only paid on rounds that actually shrink
        the batch, so it does not belong in the steady-state round price
        (the retired ``ROUND_DISPATCHES = 6`` multiplier priced every
        round as if it compacted).  Checking late wastes work only on
        lanes that retire *mid-chunk* — on a tail-heavy grid lanes retire
        at roughly ``n / (2t + 2)`` per epoch (the batch drains over its
        epoch bound), and each such lane wastes on average ``K/2`` epochs
        of ``t``-wide stepping.  Balancing ``(sync + dispatch) / K``
        against ``K * epoch_lane * t * n / (2t + 2) / 2`` gives the root
        below; clamped to [:data:`COMPACT_INTERVAL_MIN`,
        :data:`COMPACT_INTERVAL_MAX`] so degenerate calibrations stay
        usable."""
        retire_rate = max(n_lanes, 1) / (2.0 * max(pad_t, 1) + 2.0)
        per_epoch = max(self.epoch_lane_us * max(pad_t, 1) * retire_rate,
                        1e-9)
        k = np.sqrt(2.0 * (self.sync_us + self.dispatch_us) / per_epoch)
        return int(np.clip(round(k), COMPACT_INTERVAL_MIN,
                           COMPACT_INTERVAL_MAX))

    def to_json(self) -> dict:
        return {"dispatch_us": self.dispatch_us,
                "epoch_lane_us": self.epoch_lane_us,
                "sync_us": self.sync_us}


def fallback_cost_model(device: str = "fallback") -> CostModel:
    return CostModel(dispatch_us=_FALLBACK_DISPATCH_US,
                     epoch_lane_us=_FALLBACK_EPOCH_LANE_US,
                     sync_us=_FALLBACK_SYNC_US, device=device,
                     source="fallback")


def device_key() -> str:
    import jax
    return f"{jax.default_backend()}:{jax.devices()[0].device_kind}"


# ---------------------------------------------------------------------------
# Measurement (once per device, persisted)
# ---------------------------------------------------------------------------

def _probe_batch(n: int, n_maps: int):
    """``n`` copies of one encoded scenario (numpy stack — host-side)."""
    import dataclasses as dc

    from . import engine
    from .config import JOB_SMALL, VM_SMALL, Scenario
    sc = Scenario(vms=(VM_SMALL,),
                  jobs=(dc.replace(JOB_SMALL, n_maps=n_maps),))
    arrs = engine.from_scenario(sc)
    return engine.ScenarioArrays(
        *(np.broadcast_to(np.asarray(x)[None],
                          (n,) + np.shape(np.asarray(x))).copy()
          for x in arrs))


def measure(reps: int = 5) -> CostModel:
    """Time the two coefficients on this device (min-of-reps noise floor).

    The epoch body is branch-free — its cost is independent of lane
    activity — so a fixed-trip ``fori_loop`` over the vmapped
    ``engine._epoch_step`` measures exactly the per-epoch work the
    bucketed/compacted schedules trade off, and the k-slope cancels the
    dispatch overhead out of ``epoch_lane_us`` while the small-batch
    intercept isolates it for ``dispatch_us``."""
    import jax

    from . import engine

    @partial(jax.jit, static_argnames="k")
    def run_epochs(batch, k: int):
        # the full per-bucket pipeline minus encode — setup, k fixed
        # epochs, output + metrics staging — so the intercept reflects
        # what one more *fused bucket dispatch* really costs (argument
        # staging and metric readback dominate it on small hosts, not
        # the bare XLA call)
        inv, c0 = jax.vmap(engine._epoch_setup)(batch)

        def body(_, c):
            return jax.vmap(engine._epoch_step)(batch, inv, c)

        c = jax.lax.fori_loop(0, k, body, c0)
        out = jax.vmap(engine._sim_output)(batch, c)
        return (jax.vmap(engine.job_metrics)(batch, out),
                jax.vmap(engine.scenario_metrics)(batch, out))

    def floor_us(batch, k):
        jax.block_until_ready(run_epochs(batch, k))    # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run_epochs(batch, k))
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    import jax.numpy as jnp

    @jax.jit
    def scalar_probe(i):
        # a fresh device scalar per rep (the +i defeats constant folding
        # across calls), shaped like the compact loop's fused
        # [n_step, n_active] readback
        return jnp.sum(jnp.arange(256, dtype=jnp.int32)) + i

    def sync_floor_us():
        # time ONLY the blocking device->host pull of a *ready* scalar:
        # the per-round overhead the lean loop pays is the readback
        # round-trip, not the compute the pull may happen to wait on
        best = float("inf")
        for r in range(max(reps, 3) * 3):
            s = scalar_probe(jnp.int32(r))
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            int(s)
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    small = _probe_batch(8, n_maps=7)                  # T = 8
    big = _probe_batch(64, n_maps=15)                  # T = 16
    t_small_1, t_small_9 = floor_us(small, 1), floor_us(small, 9)
    t_big_4, t_big_36 = floor_us(big, 4), floor_us(big, 36)
    slope_small = max((t_small_9 - t_small_1) / 8.0, 0.0)
    dispatch = max(t_small_1 - slope_small, 1.0)
    epoch_lane = max((t_big_36 - t_big_4) / 32.0, 1e-6) / (64 * 16)
    sync = max(sync_floor_us(), 0.01)
    return CostModel(dispatch_us=round(dispatch, 2),
                     epoch_lane_us=round(epoch_lane, 6),
                     sync_us=round(sync, 2),
                     device=device_key(), source="measured")


# ---------------------------------------------------------------------------
# Persistence
# ---------------------------------------------------------------------------

def _parse_cache(data) -> dict:
    """Validate the cache schema and return the device→entry mapping.
    Raises ``ValueError`` on any stale/foreign format (missing or
    mismatched ``schema``, pre-schema bare mappings) so callers
    re-measure instead of consuming drifted coefficients."""
    if not isinstance(data, dict) or data.get("schema") != SCHEMA_VERSION:
        raise ValueError(
            "costmodel cache: stale or unknown schema "
            f"(found {data.get('schema') if isinstance(data, dict) else data!r}, "
            f"expected {SCHEMA_VERSION}) — cache will be re-measured")
    models = data.get("models")
    if not isinstance(models, dict):
        raise ValueError("costmodel cache: missing 'models' mapping")
    return models


def load_cost_model(path, device: str | None = None) -> CostModel:
    """Load one device's calibration from a JSON cache file.  With
    ``device=None`` and a single-entry file, that entry is returned —
    the pinned-calibration form the determinism tests use.  A cache
    whose ``schema`` field is missing or mismatched raises ``ValueError``
    (stale-cache invalidation; ``default_cost_model`` then re-measures)."""
    models = _parse_cache(json.loads(pathlib.Path(path).read_text()))
    if device is None:
        if len(models) != 1:
            raise ValueError(
                f"load_cost_model: {path} holds calibrations for "
                f"{sorted(models)}; pass device= to pick one")
        device = next(iter(models))
    if device not in models:
        raise KeyError(
            f"load_cost_model: no calibration for device {device!r} in "
            f"{path} (have {sorted(models)})")
    entry = models[device]
    return CostModel(dispatch_us=float(entry["dispatch_us"]),
                     epoch_lane_us=float(entry["epoch_lane_us"]),
                     sync_us=float(entry["sync_us"]),
                     device=device, source="cache")


def save_cost_model(model: CostModel, path) -> None:
    """Merge one device's calibration into the cache file, stamping the
    current :data:`SCHEMA_VERSION`.  Entries from an unreadable or
    stale-schema file are discarded — never carried forward."""
    path = pathlib.Path(path)
    models = {}
    if path.exists():
        try:
            models = _parse_cache(json.loads(path.read_text()))
        except (OSError, ValueError):
            models = {}
    models[model.device] = model.to_json()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({"schema": SCHEMA_VERSION, "models": models},
                               indent=2) + "\n")


def default_cost_model(path=None, *, allow_measure: bool = True) -> CostModel:
    """The process-wide cost model: cached in memory, then in the JSON
    file at ``path`` (default ``$REPRO_COSTMODEL_PATH`` or
    ``~/.cache/repro-iotsim/costmodel.json``), then measured.  An
    unwritable cache is tolerated; a failed measurement raises, so a
    device is never scheduled with another device's constants.  The
    conservative built-in coefficients serve only ``allow_measure=False``
    with no cached calibration."""
    key = device_key()
    if key in _CACHE:
        return _CACHE[key]
    path = pathlib.Path(path or os.environ.get(ENV_PATH, _DEFAULT_PATH))
    model = None
    if path.exists():
        try:
            model = load_cost_model(path, device=key)
        except (OSError, ValueError, KeyError):
            model = None
    if model is None and allow_measure:
        model = measure()
        try:
            save_cost_model(model, path)
        except OSError:                        # pragma: no cover - env
            pass
    if model is None:
        model = fallback_cost_model(key)
    _CACHE[key] = model
    return model
