"""Massive scenario sweeps: vmap over scenarios, pjit over the pod mesh.

CloudSim/IOTSim runs one scenario per JVM process; every figure in the paper
is a parameter sweep re-run by hand.  Here a sweep is one ``vmap`` of the
vectorized engine over a stacked :class:`ScenarioArrays` batch, sharded over
every mesh axis — a pod simulates millions of datacentre scenarios in one
``pjit`` call.  This is the headline TPU adaptation of the paper's technique
(DESIGN.md §2) and the subject of ``benchmarks/sweep_throughput.py``.

The declarative experiment API (DESIGN.md §4):

* :func:`axis` — one labeled sweep dimension over any ``Scenario``-level
  parameter (MR combination, VM count, per-VM mips/pes/cost vectors,
  policies, network knobs, VM/job presets);
* :func:`zip_` / :func:`product` — compose axes into a :class:`SweepPlan`
  (zipped axes advance together as one dimension; product axes span the
  full cartesian grid);
* :meth:`SweepPlan.run` — compile the plan into device-side
  :class:`ScenarioArrays` batches and execute them (plain vmap, pod-sharded
  over a ``mesh``, or host-memory-``chunk``-ed), returning a labeled
  :class:`SweepResult` with ``select(**coords)`` / ``to_dict()`` lookup.

``run()`` executes an *adaptive schedule* (DESIGN.md §6): cells are grouped
into a small set of padded-shape buckets (heterogeneous grids stop paying
for the grid-wide max (T, V) padding), each bucket runs the batch-level
early-exit engine (``engine.simulate_batch_arrays`` — one shared epoch loop
that stops at the batch's realized epoch count), and the realized count is
exposed as the ``realized_epochs`` metric.  ``bucket=False`` restores the
single max-shape batch; results are bit-identical either way.

Lower-level builders (the compile targets — still public):

* :func:`stack_scenarios` — host-side: encode arbitrary ``Scenario`` objects
  (heterogeneous jobs/VMs) and stack with common padding;
* :func:`encode_cell` / :func:`grid_arrays` — device-side: build experiment
  cells (homogeneous *or* per-VM-heterogeneous) directly from traced
  parameters, entirely in jnp, so huge grids never materialize on the host.
"""
from __future__ import annotations

import dataclasses
import enum
import inspect
import itertools
import math
import time
from functools import lru_cache, partial
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import costmodel as costmodel_mod
from . import elasticity as elasticity_mod
from . import storage as storage_mod
from . import telemetry
from .config import (JOB_SMALL, VM_SMALL, BindingPolicy, Scenario,
                     SchedPolicy, as_job_spec, as_vm_spec,
                     base_task_lengths_f32)
from .control import ControlPolicy, as_control_policy
from .control import DeadlinePolicy, as_deadline_policy
from .control import failure_times as _failure_times
from .elasticity import ElasticitySpec, as_arrival_process
from .engine import (_BIG, JobMetrics, ScenarioArrays, ScenarioMetrics,
                     bind_tasks, from_scenario, job_metrics,
                     scenario_metrics, simulate_arrays,
                     simulate_batch_arrays, simulate_batch_arrays_compact)
from .util import pow2_pad, pow2_pads
from .storage import Placement, StorageSpec, as_placement

_DEFAULT_STORAGE = StorageSpec()    # encode_cell defaults == Scenario's
_DEFAULT_ELASTICITY = ElasticitySpec()
_RUN_IDS = itertools.count()        # ``run`` attribute of the iotsim.* spans


# ---------------------------------------------------------------------------
# Host-side batch builder
# ---------------------------------------------------------------------------

def stack_scenarios(scenarios: Sequence[Scenario]) -> ScenarioArrays:
    """Encode + stack scenarios with shared padding (leading batch dim)."""
    T = max(s.total_tasks() for s in scenarios)
    J = max(len(s.jobs) for s in scenarios)
    V = max(len(s.vms) for s in scenarios)
    encoded = [from_scenario(s, pad_tasks=T, pad_jobs=J, pad_vms=V)
               for s in scenarios]
    return ScenarioArrays(*(np.stack([np.asarray(getattr(e, f))
                                      for e in encoded])
                            for f in ScenarioArrays._fields))


# ---------------------------------------------------------------------------
# Device-side cell encoder (paper §5 experiment cells + heterogeneous VMs)
# ---------------------------------------------------------------------------

def encode_cell(n_maps, n_reduces, n_vms, vm_mips, vm_pes, vm_cost,
                job_length, job_data, *, pad_tasks: int, pad_vms: int,
                reduce_factor=0.5, net_enabled=1.0, net_bw=1000.0,
                kappa_in=17.0, kappa_shuffle=4.25, net_cost_per_unit=1.0,
                task_mult=None, sched_policy=0, binding_policy=0,
                storage_enabled=0.0,
                block_size_mb=_DEFAULT_STORAGE.block_size_mb,
                replication=_DEFAULT_STORAGE.replication,
                placement=int(_DEFAULT_STORAGE.placement),
                storage_seed=_DEFAULT_STORAGE.seed,
                job_submit=0.0, vm_start=0.0, vm_stop=_BIG,
                spinup_delay=_DEFAULT_ELASTICITY.spinup_delay,
                billing_granularity=_DEFAULT_ELASTICITY.billing_granularity,
                task_prio=None, vm_fail=_BIG, vm_restore=_BIG, vm_auto=0.0,
                control_policy=0, ctl_queue=0.0, ctl_busy=0.0,
                redispatch_delay=0.0, task_deadline=None,
                deadline_policy=0, deadline_slack=0.0, preempt=0,
                preempt_resume=0) -> ScenarioArrays:
    """One paper cell as traced arrays — homogeneous or per-VM heterogeneous.

    ``vm_mips`` / ``vm_pes`` / ``vm_cost`` are **per-VM vectors** of length
    ``pad_vms`` (entries past ``n_vms`` are ignored); plain scalars are
    broadcast, reproducing the original homogeneous cells bit for bit.  With
    distinct per-VM values, LEAST_LOADED/PACKED binding differentiates inside
    device-side grids just as it does for host-encoded scenarios.

    The storage model (DESIGN.md §7) is realized device-side when
    ``storage_enabled`` is on: the seeded block placement
    (``storage.map_block_placement`` — the same uint32/f32 op sequence the
    host encoder runs, bit for bit) becomes per-task ``block_vm`` /
    ``block_size`` data, LOCALITY binding draws its candidate mask from
    it, and every policy's off-replica map tasks pick up the remote-fetch
    delay inside the engine.  A *statically* disabled store (the plain
    Python default) skips the placement math entirely, so pre-storage
    grids pay nothing.

    Elasticity (DESIGN.md §8): ``vm_start``/``vm_stop`` are per-VM lease
    windows (scalars broadcast; ``vm_stop`` clamps to the engine's ``_BIG``
    +inf stand-in), ``spinup_delay`` delays admission past the lease
    start, ``billing_granularity`` sets the pay-as-you-go charge unit, and
    ``job_submit`` is the cell's job arrival instant (an arrival-process
    draw under :func:`arrivals`).  ``task_prio`` is a per-task priority
    vector (``pad_tasks`` wide, like ``task_mult``).  The defaults — lease
    ``[0, inf)``, no spinup, zero priorities — reproduce the static-fleet
    encoding bit for bit.

    Closed-loop control (DESIGN.md §10): ``vm_fail``/``vm_restore`` are
    per-VM failure/restore instants (scalars broadcast; ``_BIG`` = never —
    draw them host-side with :func:`repro.core.control.failure_times` or
    the :func:`failures` axis so every layer shares one f32 stream),
    ``vm_auto`` marks reserve VMs (0/1 per VM), ``control_policy`` is the
    i32 :class:`~repro.core.control.ControlPolicy` id, and
    ``ctl_queue``/``ctl_busy``/``redispatch_delay`` are the f32 autoscale
    thresholds and broker re-dispatch latency.  The defaults encode the
    open-loop scenario bit for bit — and the sweep runners only take the
    control-enabled engine path when one of these columns is present in
    the plan at all.

    Graceful degradation (DESIGN.md §11): ``task_deadline`` is a per-task
    completion-deadline vector (``pad_tasks`` wide, like ``task_mult``;
    ``_BIG`` = none, the default), ``deadline_policy`` is the i32
    :class:`~repro.core.control.DeadlinePolicy` id, ``deadline_slack``
    widens the BOOST urgency window, and ``preempt``/``preempt_resume``
    are the 0/1 priority-preemption knobs (pair them with a ``task_prio``
    column — preemption acts on raw priorities).  These ride the same
    control path gate; the defaults reproduce the §10 encoding bit for
    bit.

    All parameters may be traced — ``vmap`` this over parameter grids;
    ``sched_policy``/``binding_policy`` are plain i32 scalars, so one grid
    may mix policies (Group 5).  ``pad_tasks``/``pad_vms`` are static
    paddings (>= max M+R / max V).
    """
    f32 = partial(jnp.asarray, dtype=jnp.float32)
    i32 = partial(jnp.asarray, dtype=jnp.int32)
    t = jnp.arange(pad_tasks)
    n_maps, n_reduces, n_vms = i32(n_maps), i32(n_reduces), i32(n_vms)
    n_tasks = n_maps + n_reduces
    is_red = t >= n_maps
    valid = t < n_tasks
    if task_mult is None:
        task_mult = jnp.ones(pad_tasks, jnp.float32)
    if task_prio is None:
        task_prio = jnp.zeros(pad_tasks, jnp.float32)
    if task_deadline is None:
        task_deadline = jnp.full(pad_tasks, _BIG, jnp.float32)
    vm_valid = jnp.arange(pad_vms) < n_vms
    vm_mips_a = jnp.where(vm_valid,
                          jnp.broadcast_to(f32(vm_mips), (pad_vms,)), 1.0)
    vm_pes_a = jnp.where(vm_valid,
                         jnp.broadcast_to(f32(vm_pes), (pad_vms,)), 1.0)
    vm_cost_a = jnp.where(vm_valid,
                          jnp.broadcast_to(f32(vm_cost), (pad_vms,)), 0.0)
    vm_start_a = jnp.where(vm_valid,
                           jnp.broadcast_to(f32(vm_start), (pad_vms,)), 0.0)
    vm_stop_a = jnp.where(
        vm_valid,
        jnp.minimum(jnp.broadcast_to(f32(vm_stop), (pad_vms,)),
                    jnp.float32(_BIG)), jnp.float32(_BIG))
    # control arrays: padding / invalid VMs never fail and are not reserves
    vm_fail_a = jnp.where(
        vm_valid,
        jnp.minimum(jnp.broadcast_to(f32(vm_fail), (pad_vms,)),
                    jnp.float32(_BIG)), jnp.float32(_BIG))
    vm_restore_a = jnp.where(
        vm_valid,
        jnp.minimum(jnp.broadcast_to(f32(vm_restore), (pad_vms,)),
                    jnp.float32(_BIG)), jnp.float32(_BIG))
    vm_auto_a = vm_valid & (jnp.broadcast_to(f32(vm_auto), (pad_vms,)) > 0.5)
    map_len, red_len = base_task_lengths_f32(
        f32(job_length), n_maps.astype(jnp.float32),
        n_reduces.astype(jnp.float32), f32(reduce_factor))
    base_len = jnp.where(is_red, red_len, map_len)

    static_off = (not isinstance(storage_enabled, jax.core.Tracer)
                  and np.ndim(storage_enabled) == 0
                  and float(storage_enabled) == 0.0)
    if static_off:
        block_vm = jnp.full((pad_tasks, pad_vms), -1, jnp.int32)
        block_mb = jnp.zeros(pad_tasks, jnp.float32)
        cand = None     # LOCALITY falls back to the LEAST_LOADED scan
    else:
        # maps occupy task slots [0, n_maps) for the single encoded job,
        # so the slot index doubles as the map index
        rep_vm, rep_mb = storage_mod.map_block_placement(
            jnp, t, jnp.zeros(pad_tasks, jnp.int32), seed=storage_seed,
            placement=placement, replication=replication,
            block_size_mb=block_size_mb, job_data=job_data, n_vms=n_vms,
            pad_vms=pad_vms)
        on = f32(storage_enabled) > 0.5
        is_map = valid & ~is_red
        block_vm = jnp.where(on & is_map[:, None], rep_vm, -1)
        block_mb = jnp.where(on & is_map, rep_mb, 0.0)
        cand = storage_mod.locality_candidates(jnp, block_vm, vm_valid)
    return ScenarioArrays(
        task_job=jnp.zeros(pad_tasks, jnp.int32),
        task_is_reduce=is_red & valid,
        task_vm=bind_tasks(binding_policy, valid, base_len, vm_mips_a,
                           vm_pes_a, vm_valid, locality_cand=cand),
        task_valid=valid,
        task_mult=task_mult,
        job_length=f32(job_length)[None],
        job_data=f32(job_data)[None],
        job_n_maps=n_maps[None],
        job_n_reduces=n_reduces[None],
        job_submit=f32(job_submit)[None],
        job_reduce_factor=f32(reduce_factor)[None],
        job_valid=jnp.ones(1, bool),
        vm_mips=vm_mips_a,
        vm_pes=vm_pes_a,
        vm_cost=vm_cost_a,
        vm_valid=vm_valid,
        net_enabled=f32(net_enabled), net_bw=f32(net_bw),
        kappa_in=f32(kappa_in), kappa_shuffle=f32(kappa_shuffle),
        net_cost_per_unit=f32(net_cost_per_unit),
        sched_policy=i32(sched_policy),
        binding_policy=i32(binding_policy),
        block_vm=block_vm,
        block_size=block_mb,
        storage_enabled=f32(storage_enabled),
        vm_start=vm_start_a,
        vm_stop=vm_stop_a,
        spinup_delay=f32(spinup_delay),
        bill_gran=f32(billing_granularity),
        task_prio=jnp.asarray(task_prio, jnp.float32),
        vm_fail=vm_fail_a,
        vm_restore=vm_restore_a,
        vm_auto=vm_auto_a,
        control_policy=i32(control_policy),
        ctl_queue=f32(ctl_queue),
        ctl_busy=f32(ctl_busy),
        redispatch_delay=f32(redispatch_delay),
        task_deadline=jnp.minimum(
            jnp.asarray(task_deadline, jnp.float32), jnp.float32(_BIG)),
        deadline_policy=i32(deadline_policy),
        deadline_slack=f32(deadline_slack),
        preempt=i32(preempt),
        preempt_resume=i32(preempt_resume),
    )


# encode_cell parameters an axis/grid may target (pads are static).
_CELL_PARAMS = tuple(p for p in inspect.signature(encode_cell).parameters
                     if p not in ("pad_tasks", "pad_vms"))
_INT_PARAMS = frozenset(
    {"n_maps", "n_reduces", "n_vms", "sched_policy", "binding_policy",
     "replication", "placement", "storage_seed", "control_policy",
     "deadline_policy", "preempt", "preempt_resume"})
_PER_VM = frozenset({"vm_mips", "vm_pes", "vm_cost", "vm_start", "vm_stop",
                     "vm_fail", "vm_restore", "vm_auto"})
_PER_TASK = frozenset({"task_mult", "task_prio", "task_deadline"})
# storage knobs that are dead weight unless storage_enabled is set
_STORAGE_KNOBS = frozenset(
    {"block_size_mb", "replication", "placement", "storage_seed"})
# columns that switch the engines onto the closed-loop control path
# (DESIGN.md §10) — a plan without any of them never pays for control
_CONTROL_PARAMS = frozenset(
    {"vm_fail", "vm_restore", "vm_auto", "control_policy", "ctl_queue",
     "ctl_busy", "redispatch_delay", "task_deadline", "deadline_policy",
     "deadline_slack", "preempt", "preempt_resume"})
# per-VM pad fill: "no event" sentinels, not zero (a zero-filled failure
# column would fail every padding VM at t=0 before vm_valid masks it)
_PER_VM_FILL = {"vm_fail": _BIG, "vm_restore": _BIG}


def _validate_cell_columns(cols: Mapping[str, Any]) -> None:
    """Plan-build-time checks for the storage/placement parameter columns —
    a bad replication vector or placement id must fail here with a named
    error, not deep inside the vmapped encoder (and a silently-ignored
    storage knob must not masquerade as a swept axis).  Traced values are
    skipped (the caller is inside someone else's jit)."""
    conc = {n: np.asarray(v) for n, v in cols.items()
            if not isinstance(v, jax.core.Tracer)}
    for n in conc:
        if n in _INT_PARAMS and not np.issubdtype(conc[n].dtype, np.integer):
            raise ValueError(
                f"grid_arrays: parameter {n!r} is integer-valued; got "
                f"dtype {conc[n].dtype} (a float column here would be "
                "silently truncated per cell)")
    if "placement" in conc:
        bad = np.setdiff1d(conc["placement"], [int(p) for p in Placement])
        if bad.size:
            raise ValueError(
                f"grid_arrays: placement values {bad.tolist()} are not "
                f"Placement members {[f'{int(p)}={p.name}' for p in Placement]}")
    if "replication" in conc and (conc["replication"] < 1).any():
        raise ValueError(
            "grid_arrays: replication must be >= 1 in every cell (disable "
            "the store with storage_enabled=0 instead of replication=0)")
    if "block_size_mb" in conc and (conc["block_size_mb"] <= 0).any():
        raise ValueError(
            "grid_arrays: block_size_mb must be > 0 in every cell")
    if "billing_granularity" in conc \
            and (conc["billing_granularity"] <= 0).any():
        raise ValueError(
            "grid_arrays: billing_granularity must be > 0 in every cell")
    if "spinup_delay" in conc and (conc["spinup_delay"] < 0).any():
        raise ValueError(
            "grid_arrays: spinup_delay must be >= 0 in every cell")
    if "vm_start" in conc and (conc["vm_start"] < 0).any():
        raise ValueError(
            "grid_arrays: vm_start must be >= 0 in every cell (leases "
            "start on the simulation clock; a negative start would bill "
            "phantom lease time)")
    if "job_submit" in conc and (conc["job_submit"] < 0).any():
        raise ValueError(
            "grid_arrays: job_submit must be >= 0 in every cell (arrival "
            "instants are absolute simulation times)")
    if "control_policy" in conc:
        bad = np.setdiff1d(conc["control_policy"],
                           [int(p) for p in ControlPolicy])
        if bad.size:
            raise ValueError(
                f"grid_arrays: control_policy values {bad.tolist()} are not "
                f"ControlPolicy members "
                f"{[f'{int(p)}={p.name}' for p in ControlPolicy]}")
    if "deadline_policy" in conc:
        bad = np.setdiff1d(conc["deadline_policy"],
                           [int(p) for p in DeadlinePolicy])
        if bad.size:
            raise ValueError(
                f"grid_arrays: deadline_policy values {bad.tolist()} are not "
                f"DeadlinePolicy members "
                f"{[f'{int(p)}={p.name}' for p in DeadlinePolicy]}")
    if "task_deadline" in conc:
        dl = conc["task_deadline"].astype(np.float64)
        if not np.isfinite(dl).all():
            raise ValueError(
                "grid_arrays: task_deadline must be finite in every cell "
                "(use the _BIG sentinel, not inf/nan, for 'no deadline')")
        live = dl < _BIG / 2                      # _BIG sentinel = no deadline
        submit = conc.get("job_submit")
        sub = np.asarray(0.0 if submit is None else submit, np.float64)
        while sub.ndim < dl.ndim:
            sub = sub[..., None]
        if (live & (dl <= sub)).any():
            raise ValueError(
                "grid_arrays: task_deadline must exceed the job's submit "
                "time in every cell (a deadline at or before job_submit is "
                "unmeetable by construction — raise task_deadline or drop "
                "the axis)")
    for n in ("preempt", "preempt_resume"):
        if n in conc and (conc[n] != 0).any() and "task_prio" not in cols:
            raise ValueError(
                f"grid_arrays: {n!r} enables priority preemption but no "
                "'task_prio' column is set, so every task has equal rank "
                "and the knob would silently do nothing — add a task_prio "
                f"axis/base or drop {n!r}")
    if "deadline_slack" in conc and (conc["deadline_slack"] < 0).any():
        raise ValueError(
            "grid_arrays: deadline_slack must be >= 0 in every cell")
    if "redispatch_delay" in conc and (conc["redispatch_delay"] < 0).any():
        raise ValueError(
            "grid_arrays: redispatch_delay must be >= 0 in every cell")
    for n in ("ctl_queue", "ctl_busy"):
        if n in conc and (conc[n] < 0).any():
            raise ValueError(
                f"grid_arrays: {n} must be >= 0 in every cell")
    knobs = sorted(_STORAGE_KNOBS & set(cols))
    if knobs and "storage_enabled" not in cols:
        raise ValueError(
            f"grid_arrays: {knobs} configure the storage model but "
            "'storage_enabled' is never set, so they would silently do "
            "nothing — add axis('storage', [True]) / storage=True (or an "
            "explicit storage_enabled column)")


# ---------------------------------------------------------------------------
# One buffer each way per launch (DESIGN.md §4.3)
# ---------------------------------------------------------------------------

def _word_type(dtype) -> np.dtype:
    """The 4-byte type a device dtype crosses as: the dtype itself, or for
    a narrower one (``bool``, ``int8``, ``float16``, …) the 32-bit type of
    its kind, which holds every value exactly."""
    dtype = np.dtype(dtype)
    if dtype.itemsize == 4:
        return dtype
    if dtype.itemsize > 4:
        raise TypeError(f"no 32-bit word layout for dtype {dtype}")
    if dtype == np.bool_ or dtype.kind == "u":
        return np.dtype(np.uint32)
    return np.dtype(np.int32 if dtype.kind == "i" else np.float32)


def _upload_layout(cols: Mapping[str, Any], names: Sequence[str]
                   ) -> tuple[tuple[str, np.dtype, tuple[int, ...]], ...]:
    """``(name, device dtype, per-cell shape)`` of each host column, in
    buffer order.  The dtype is the one ``jnp.asarray`` gives the column
    (float64 -> float32, int64 -> int32).  A pure function of the names,
    dtypes and widths, so it keys the signature caches."""
    return tuple((n, jax.dtypes.canonicalize_dtype(np.asarray(cols[n]).dtype),
                  tuple(np.shape(cols[n])[1:])) for n in names)


def _pack_columns(cols: Mapping[str, Any], layout) -> np.ndarray:
    """A launch's host columns as one contiguous uint32 buffer, column
    after column, each cast exactly as ``jnp.asarray`` casts it."""
    n = len(cols[layout[0][0]])
    sizes = [n * math.prod(shape) for _, _, shape in layout]
    buf = np.empty(sum(sizes), np.uint32)
    off = 0
    for (name, dtype, shape), size in zip(layout, sizes):
        words = buf[off:off + size].view(_word_type(dtype))
        words.reshape((n,) + shape)[...] = np.asarray(cols[name], dtype)
        off += size
    return buf


def _unpack_columns(buf, layout) -> list:
    """Inverse of :func:`_pack_columns` inside the program: static slices,
    bitcasts and reshapes give back every column bit for bit."""
    if not layout:
        return []
    n = buf.shape[0] // sum(math.prod(shape) for _, _, shape in layout)
    cols, off = [], 0
    for _, dtype, shape in layout:
        size = n * math.prod(shape)
        words = lax.slice(buf, (off,), (off + size,))
        x = lax.bitcast_convert_type(words, _word_type(dtype))
        cols.append(x.reshape((n,) + shape).astype(dtype))
        off += size
    return cols


def _avals(args) -> tuple:
    """Shapes and dtypes of a call's array arguments: the key of the
    readback layout that tracing the call recorded."""
    return tuple((tuple(x.shape), np.dtype(x.dtype))
                 for x in jax.tree.leaves(args))


class _OneReadback:
    """``jax.jit(fn)`` whose pytree of results leaves the device as one
    flat uint32 buffer, so reading a launch back is one pull.  Tracing
    records the layout (tree structure, leaf shapes and dtypes) under the
    arguments' shapes and dtypes; :meth:`pull` slices the host copy of the
    buffer with it."""

    def __init__(self, fn):
        layouts: dict = {}

        def packed(*args):
            leaves, tree = jax.tree.flatten(fn(*args))
            layouts[_avals(args)] = tree, tuple(
                (tuple(x.shape), np.dtype(x.dtype)) for x in leaves)
            return jnp.concatenate([
                lax.bitcast_convert_type(x.astype(_word_type(x.dtype)),
                                         jnp.uint32).reshape(-1)
                for x in leaves])

        self._layouts = layouts
        self.jit = jax.jit(packed)

    def __call__(self, *args):
        """Launch; returns ``(device buffer, layout key)`` for :meth:`pull`."""
        return self.jit(*args), _avals(args)

    def pull(self, launched, stats: dict | None):
        """One blocking pull of a launch's buffer; its results on the host
        as views of that one array."""
        buf, key = launched
        tree, leaves = self._layouts[key]
        host = telemetry.pull(buf, stats)
        out, off = [], 0
        for shape, dtype in leaves:
            size = math.prod(shape)
            x = host[off:off + size].view(_word_type(dtype)).reshape(shape)
            out.append(x.astype(dtype, copy=False))
            off += size
        return tree.unflatten(out)


def grid_arrays(params: dict[str, np.ndarray], *, pad_tasks: int,
                pad_vms: int,
                static_params: Mapping[str, int] | None = None,
                stats: dict | None = None) -> ScenarioArrays:
    """vmap :func:`encode_cell` over equal-length parameter arrays.

    Each value is ``[N]`` (one scalar per cell) or ``[N, pad_vms]``
    (per-VM vectors for ``vm_mips``/``vm_pes``/``vm_cost``) /
    ``[N, pad_tasks]`` (``task_mult``).  Keys and leading lengths are
    validated up front — a mismatched key used to surface as an opaque
    vmap shape error deep inside the encoder.

    ``static_params`` pins encode_cell parameters as Python compile-time
    constants instead of per-cell columns — the bucketed ``run()`` path
    uses it to bake a bucket's uniform ``binding_policy`` into the
    lowering, letting XLA dead-code-eliminate the unused binding
    strategies (the sequential LEAST_LOADED load scan dominates encode
    time when it can't be eliminated).

    Host columns cross in one uploaded buffer (:func:`_pack_columns`);
    ``jax.Array`` columns are already on the device and pass through as
    they are.  ``stats`` (a dict, mutated in place) counts the upload
    (:func:`telemetry.put`).
    """
    names = list(params)
    static = tuple(sorted((static_params or {}).items()))
    for n, _ in static:
        if n not in _CELL_PARAMS:
            raise ValueError(f"grid_arrays: unknown static parameter {n!r}")
        if n in names:
            raise ValueError(
                f"grid_arrays: parameter {n!r} passed both as a column and "
                "as a static parameter")
    if not names:
        raise ValueError("grid_arrays: empty parameter dict")
    unknown = [n for n in names if n not in _CELL_PARAMS]
    if unknown:
        raise ValueError(
            f"grid_arrays: unknown encode_cell parameter(s) {unknown}; "
            f"valid: {list(_CELL_PARAMS)}")
    sizes = {}
    for n in names:
        shape = np.shape(params[n])
        if len(shape) == 0:
            raise ValueError(
                f"grid_arrays: parameter {n!r} must be an array with a "
                "leading grid dimension (got a scalar)")
        if len(shape) == 2:
            if n in _PER_VM:
                want, pad = "pad_vms", pad_vms
            elif n in _PER_TASK:
                want, pad = "pad_tasks", pad_tasks
            else:
                raise ValueError(
                    f"grid_arrays: parameter {n!r} takes one scalar per "
                    f"cell, got 2-D shape {shape}")
            if shape[1] != pad:
                raise ValueError(
                    f"grid_arrays: {n!r} has trailing width {shape[1]}, "
                    f"expected {want}={pad}")
        elif len(shape) > 2:
            raise ValueError(
                f"grid_arrays: parameter {n!r} has {len(shape)} dims; "
                "at most [N, width] is supported")
        sizes[n] = shape[0]
    n0 = sizes[names[0]]
    bad = [f"{n} has length {sizes[n]}" for n in names if sizes[n] != n0]
    if bad:
        raise ValueError(
            "grid_arrays: parameter arrays must share one leading grid "
            f"length; {names[0]!r} has length {n0} but " + ", ".join(bad))
    _validate_cell_columns(params)
    on_device = tuple(n for n in names if isinstance(params[n], jax.Array))
    layout = _upload_layout(params, [n for n in names if n not in on_device])
    encoder = _grid_encoder(layout, on_device, pad_tasks, pad_vms, static)
    with jax.profiler.TraceAnnotation("iotsim.upload"):
        buf = (telemetry.put(_pack_columns(params, layout), stats)
               if layout else None)
    return encoder(buf, *(params[n] for n in on_device))


@lru_cache(maxsize=None)
def _grid_encoder(layout: tuple, on_device: tuple[str, ...], pad_tasks: int,
                  pad_vms: int, static: tuple[tuple[str, int], ...] = ()):
    """One jitted vmapped encode_cell per (column layout, device columns,
    padding, statics) signature — repeated ``SweepPlan.run()`` calls
    re-encode at compiled speed instead of dispatching the encoder op by
    op.  It takes the packed host columns and the device columns."""
    names = tuple(n for n, _, _ in layout) + on_device

    def one(*xs):
        kw = dict(zip(names, xs))
        kw.update(static)
        with jax.named_scope("encode"):
            return encode_cell(**kw, pad_tasks=pad_tasks, pad_vms=pad_vms)

    @jax.jit
    def encode(buf, *device_cols):
        return jax.vmap(one)(*_unpack_columns(buf, layout), *device_cols)
    return encode


# ---------------------------------------------------------------------------
# Declarative sweep plans (DESIGN.md §4)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Axis:
    """One labeled sweep dimension.

    ``names`` are the coordinate names addressable in
    :meth:`SweepResult.select` (more than one after :func:`zip_`);
    ``labels`` holds one tuple of coordinate values per point (aligned with
    ``names``); ``columns`` maps encode_cell parameters to ``[n, ...]``
    encoded value columns.  Build through :func:`axis`, compose with
    :func:`zip_` / :func:`product`.
    """
    names: tuple[str, ...]
    labels: tuple[tuple[Any, ...], ...]
    columns: Mapping[str, np.ndarray]

    def __len__(self) -> int:
        return len(self.labels)


def axis(name: str, values: Sequence[Any]) -> Axis:
    """One sweep dimension: ``name`` + the values it takes.

    ``name`` is either a raw :func:`encode_cell` parameter (``n_maps``,
    ``n_vms``, ``vm_mips`` …, values scalars — or per-VM vectors for the
    ``vm_*`` parameters) or a convenience spec axis:

    * ``"vm"``/``"vm_type"`` — values are ``VMSpec`` or Table-II type names;
      expands to homogeneous ``vm_mips``/``vm_pes``/``vm_cost``;
    * ``"vms"`` — values are *sequences* of VMSpec/type names (one cluster
      per point, may differ in size): per-VM heterogeneous cells, expands
      to ``n_vms`` + per-VM ``vm_mips``/``vm_pes``/``vm_cost`` vectors;
    * ``"job"``/``"job_type"`` — ``JobSpec`` or Table-III names; expands to
      ``job_length``/``job_data``/``reduce_factor`` (MR combination stays
      a separate ``n_maps``/``n_reduces`` axis, as in the paper);
    * ``"sched_policy"``/``"binding_policy"`` — enum members or ints;
    * ``"network_delay"`` — bools, expands to ``net_enabled``;
    * ``"storage"`` — bools, expands to ``storage_enabled`` (the block
      store, DESIGN.md §7; combine with the raw ``replication`` /
      ``block_size_mb`` / ``storage_seed`` parameters);
    * ``"placement"`` — :class:`~repro.core.storage.Placement` members,
      ints, or the names ``"uniform"`` / ``"skewed"``;
    * ``"control_policy"`` — :class:`~repro.core.control.ControlPolicy`
      members, ints, or the names ``"none"`` / ``"autoscale"`` (the
      closed-loop control hook, DESIGN.md §10; combine with the raw
      ``ctl_queue``/``ctl_busy`` threshold parameters and per-VM
      ``vm_auto`` reserve markers, and with :func:`failures` streams).
    """
    values = list(values)
    if not values:
        raise ValueError(f"axis {name!r}: empty value list")
    f32 = partial(np.asarray, dtype=np.float32)
    if name in ("vm", "vm_type"):
        specs = [as_vm_spec(v) for v in values]
        return Axis((name,), tuple((s.name,) for s in specs), {
            "vm_mips": f32([s.mips for s in specs]),
            "vm_pes": f32([float(s.pes) for s in specs]),
            "vm_cost": f32([s.cost_per_sec for s in specs]),
        })
    if name == "vms":
        clusters = [tuple(as_vm_spec(v) for v in vs) for vs in values]
        if any(not c for c in clusters):
            raise ValueError("axis 'vms': every point needs >= 1 VM")
        V = max(len(c) for c in clusters)

        def col(get):
            out = np.zeros((len(clusters), V), np.float32)
            for i, c in enumerate(clusters):
                out[i, :len(c)] = [get(s) for s in c]
            return out

        return Axis((name,),
                    tuple((tuple(s.name for s in c),) for c in clusters), {
            "n_vms": np.asarray([len(c) for c in clusters], np.int32),
            "vm_mips": col(lambda s: s.mips),
            "vm_pes": col(lambda s: float(s.pes)),
            "vm_cost": col(lambda s: s.cost_per_sec),
        })
    if name in ("job", "job_type"):
        specs = [as_job_spec(v) for v in values]
        return Axis((name,), tuple((s.name,) for s in specs), {
            "job_length": f32([s.length_mi for s in specs]),
            "job_data": f32([s.data_mb for s in specs]),
            "reduce_factor": f32([s.reduce_factor for s in specs]),
        })
    if name == "network_delay":
        labels = tuple((bool(v),) for v in values)
        return Axis((name,), labels,
                    {"net_enabled": f32([1.0 if v else 0.0 for v in values])})
    if name == "storage":
        labels = tuple((bool(v),) for v in values)
        return Axis((name,), labels, {
            "storage_enabled": f32([1.0 if v else 0.0 for v in values])})
    if name == "placement":
        members = [as_placement(v) for v in values]
        return Axis((name,), tuple((m,) for m in members),
                    {name: np.asarray(members, np.int32)})
    if name == "sched_policy":
        members = [SchedPolicy(v) for v in values]
        return Axis((name,), tuple((m,) for m in members),
                    {name: np.asarray(members, np.int32)})
    if name == "binding_policy":
        members = [BindingPolicy(v) for v in values]
        return Axis((name,), tuple((m,) for m in members),
                    {name: np.asarray(members, np.int32)})
    if name == "control_policy":
        members = [as_control_policy(v) for v in values]
        return Axis((name,), tuple((m,) for m in members),
                    {name: np.asarray(members, np.int32)})
    if name == "deadline_policy":
        members = [as_deadline_policy(v) for v in values]
        return Axis((name,), tuple((m,) for m in members),
                    {name: np.asarray(members, np.int32)})
    if name not in _CELL_PARAMS:
        raise ValueError(
            f"axis {name!r}: not an encode_cell parameter or spec axis; "
            f"valid: {list(_CELL_PARAMS)} + ['vm', 'vm_type', 'vms', 'job', "
            "'job_type', 'network_delay', 'storage', 'placement', "
            "'control_policy', 'deadline_policy']")
    if any(np.ndim(v) > 0 for v in values):        # per-VM / per-task vectors
        if name not in _PER_VM and name not in _PER_TASK:
            raise ValueError(
                f"axis {name!r}: vector values only make sense for the "
                f"per-VM parameters {sorted(_PER_VM)} or the per-task "
                f"parameters {sorted(_PER_TASK)}; "
                f"{name!r} takes one scalar per cell")
        if not all(np.ndim(v) == 1 for v in values):
            raise ValueError(
                f"axis {name!r}: vector values must all be 1-D with one "
                "shared length (use the 'vms' axis for ragged clusters)")
        widths = {int(np.shape(v)[0]) for v in values}
        if len(widths) != 1:
            raise ValueError(
                f"axis {name!r}: vector values must share one length, got "
                f"{sorted(widths)} (use the 'vms' axis for ragged clusters)")
        return Axis((name,), tuple((tuple(np.asarray(v).tolist()),)
                                   for v in values),
                    {name: np.stack([f32(v) for v in values])})
    dtype = np.int32 if name in _INT_PARAMS else np.float32
    return Axis((name,), tuple((v,) for v in values),
                {name: np.asarray(values, dtype)})


def zip_(*axes: Axis) -> Axis:
    """Fuse equal-length axes into one dimension that advances together
    (e.g. co-varying ``n_maps`` with ``job_length``), like Python ``zip``."""
    if not axes:
        raise ValueError("zip_: need at least one axis")
    lens = {"x".join(a.names): len(a) for a in axes}
    if len(set(lens.values())) != 1:
        raise ValueError(f"zip_: axes must share one length; got {lens}")
    columns: dict[str, np.ndarray] = {}
    for a in axes:
        for cname, col in a.columns.items():
            if cname in columns:
                raise ValueError(
                    f"zip_: parameter {cname!r} set by more than one axis")
            columns[cname] = col
    names = tuple(n for a in axes for n in a.names)
    if len(set(names)) != len(names):
        raise ValueError(f"zip_: duplicate coordinate names in {names}")
    labels = tuple(tuple(part for a in axes for part in a.labels[i])
                   for i in range(len(axes[0])))
    return Axis(names, labels, columns)


def arrivals(n: int, *, rate, process="poisson", seed: int = 0,
             burst: int = 4) -> Axis:
    """An arrival-stream dimension (DESIGN.md §8): ``n`` seeded draws from
    an inter-arrival process become ``job_submit`` instants — each grid
    point simulates one arrival of the stream against the leased fleet, so
    offered load is a grid axis like any other parameter.

    ``rate`` is arrivals per simulated second; pass a *sequence* of rates
    to sweep offered load (the axis flattens rates × arrivals into one
    labeled dimension, ``select(arrival_rate=...)`` filters it).
    ``process`` is an :class:`~repro.core.elasticity.ArrivalProcess`
    member or name (``"poisson"`` | ``"uniform"`` | ``"burst"``); draws
    reuse the storage subsystem's counter-hash idiom, so streams are
    reproducible pure arithmetic of ``(seed, k)``.
    """
    proc = as_arrival_process(process)
    rates = list(rate) if np.ndim(rate) > 0 else [rate]
    if not rates:
        raise ValueError("arrivals: empty rate list")
    times = [elasticity_mod.arrival_times(n, rate=float(r), process=proc,
                                          seed=seed, burst=burst)
             for r in rates]
    col = np.concatenate(times).astype(np.float32)
    if np.ndim(rate) > 0:
        labels = tuple((float(r), k) for r in rates for k in range(n))
        return Axis(("arrival_rate", "arrival"), labels,
                    {"job_submit": col})
    return Axis(("arrival",), tuple((k,) for k in range(n)),
                {"job_submit": col})


def failures(n: int, *, rate, n_vms: int, seed: int = 0,
             repair_delay: float = np.inf) -> Axis:
    """A failure-stream dimension (DESIGN.md §10): ``n`` seeded draws of
    per-VM failure/restore instants become ``vm_fail``/``vm_restore``
    columns — each grid point injects one realization of the VM fault
    process, so fault exposure is a grid axis like any other parameter.

    ``rate`` is per-VM failures per simulated second; pass a *sequence*
    of rates to sweep fault intensity (the axis flattens rates × draws
    into one labeled dimension, ``select(failure_rate=...)`` filters it).
    Draw ``k`` of the stream uses seed ``seed + k`` of
    :func:`repro.core.control.failure_times` — the counter-hash idiom the
    host encoder shares, so a sweep cell and the equivalent
    ``Scenario(control=ControlSpec(...))`` encode bit-identical streams.
    ``n_vms`` fixes the stream width (pin the grid's ``n_vms`` to match).
    """
    rates = list(rate) if np.ndim(rate) > 0 else [rate]
    if not rates:
        raise ValueError("failures: empty rate list")
    cols_f, cols_r = [], []
    for r in rates:
        for k in range(n):
            f, rr = _failure_times(n_vms, rate=float(r), seed=seed + k,
                                   repair_delay=float(repair_delay))
            cols_f.append(f)
            cols_r.append(rr)
    col_f = np.stack(cols_f).astype(np.float32)
    col_r = np.stack(cols_r).astype(np.float32)
    if np.ndim(rate) > 0:
        labels = tuple((float(r), k) for r in rates for k in range(n))
        return Axis(("failure_rate", "failure"), labels,
                    {"vm_fail": col_f, "vm_restore": col_r})
    return Axis(("failure",), tuple((k,) for k in range(n)),
                {"vm_fail": col_f, "vm_restore": col_r})


def product(*dims: Axis, **base: Any) -> "SweepPlan":
    """Cartesian :class:`SweepPlan` over ``dims`` (row-major: the last axis
    varies fastest).  ``base`` pins non-swept parameters for every cell —
    any :func:`axis` name with a single value (``vm_type="medium"``,
    ``network_delay=False``, ``vms=("medium", "small")``, ``n_maps=12`` …).
    """
    return SweepPlan(dims=tuple(dims), base=dict(base))


# Paper defaults for parameters no axis/base sets: the §5 baseline cell
# (3 small VMs, one small M1R1 job) — same defaults as config.paper_scenario.
_DEFAULTS: dict[str, float] = dict(
    n_maps=1, n_reduces=1, n_vms=3,
    vm_mips=VM_SMALL.mips, vm_pes=float(VM_SMALL.pes),
    vm_cost=VM_SMALL.cost_per_sec,
    job_length=JOB_SMALL.length_mi, job_data=JOB_SMALL.data_mb,
)


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """A declarative experiment plan: labeled axes × pinned base parameters.

    Compiles to one device-side :class:`ScenarioArrays` batch
    (:meth:`arrays`) and executes through :meth:`run`, which returns a
    labeled :class:`SweepResult`.  ``pad_tasks``/``pad_vms`` override the
    inferred paddings (e.g. to share one lowering across several plans).
    """
    dims: tuple[Axis, ...]
    base: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    pad_tasks: int | None = None
    pad_vms: int | None = None

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(d) for d in self.dims)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) if self.dims else 1

    def replace(self, **kw) -> "SweepPlan":
        return dataclasses.replace(self, **kw)

    def arrivals(self, n: int, *, rate, process="poisson", seed: int = 0,
                 burst: int = 4) -> "SweepPlan":
        """Append an arrival-stream dimension (see module-level
        :func:`arrivals`): ``plan.arrivals(64, rate=0.01)`` simulates each
        existing grid point against 64 seeded Poisson arrival instants,
        with ``job_submit`` populated per cell."""
        dim = arrivals(n, rate=rate, process=process, seed=seed, burst=burst)
        return self.replace(dims=self.dims + (dim,))

    def failures(self, n: int, *, rate, n_vms: int, seed: int = 0,
                 repair_delay: float = np.inf) -> "SweepPlan":
        """Append a failure-stream dimension (see module-level
        :func:`failures`): ``plan.failures(16, rate=1e-3, n_vms=4)``
        simulates each existing grid point against 16 seeded realizations
        of the VM fault process, with ``vm_fail``/``vm_restore`` populated
        per cell."""
        dim = failures(n, rate=rate, n_vms=n_vms, seed=seed,
                       repair_delay=repair_delay)
        return self.replace(dims=self.dims + (dim,))

    def _compiled(self) -> tuple[dict[str, np.ndarray], int, int]:
        """Flatten axes + base + defaults into N-cell parameter columns."""
        shape, N = self.shape, self.size
        cols: dict[str, np.ndarray] = {}
        owner: dict[str, str] = {}
        for k, dim in enumerate(self.dims):
            outer = int(np.prod(shape[:k], dtype=np.int64))
            inner = int(np.prod(shape[k + 1:], dtype=np.int64))
            idx = np.tile(np.repeat(np.arange(shape[k]), inner), outer)
            src = "axis " + "×".join(dim.names)
            for cname, col in dim.columns.items():
                if cname in cols:
                    raise ValueError(
                        f"SweepPlan: parameter {cname!r} set by both "
                        f"{owner[cname]} and {src}")
                cols[cname] = np.asarray(col)[idx]
                owner[cname] = src
        for bname, value in self.base.items():
            for cname, col in axis(bname, [value]).columns.items():
                if cname in cols:
                    raise ValueError(
                        f"SweepPlan: parameter {cname!r} set by both "
                        f"{owner[cname]} and base argument {bname!r}")
                c = np.asarray(col)
                cols[cname] = np.broadcast_to(c[0], (N,) + c.shape[1:])
                owner[cname] = f"base argument {bname!r}"
        for cname, default in _DEFAULTS.items():
            if cname not in cols:
                dtype = np.int32 if cname in _INT_PARAMS else np.float32
                cols[cname] = np.full(N, default, dtype)
        n_tasks = int((cols["n_maps"].astype(np.int64)
                       + cols["n_reduces"].astype(np.int64)).max())
        pad_tasks = self.pad_tasks if self.pad_tasks is not None else n_tasks
        v_needed = max(int(cols["n_vms"].max()),
                       *(c.shape[1] for n, c in cols.items()
                         if n in _PER_VM and c.ndim == 2), 1)
        pad_vms = self.pad_vms if self.pad_vms is not None else v_needed
        if pad_tasks < n_tasks or pad_vms < v_needed:
            raise ValueError(
                f"SweepPlan: padding too small — need pad_tasks>={n_tasks} "
                f"(got {pad_tasks}), pad_vms>={v_needed} (got {pad_vms})")
        n_vms_max = int(cols["n_vms"].max())
        for cname in _PER_VM:
            c = cols.get(cname)     # vm_start/vm_stop default off-column
            if c is None or c.ndim != 2:
                continue
            if c.shape[1] < n_vms_max:
                raise ValueError(
                    f"SweepPlan: per-VM column {cname!r} has width "
                    f"{c.shape[1]} but some cell has n_vms={n_vms_max}; "
                    "give every VM vector >= n_vms entries (or use the "
                    "'vms' axis, which sets n_vms itself)")
            if c.shape[1] < pad_vms:
                cols[cname] = np.pad(
                    c, ((0, 0), (0, pad_vms - c.shape[1])),
                    constant_values=_PER_VM_FILL.get(cname, 0.0))
        for cname, fill in (("task_mult", 1.0), ("task_prio", 0.0),
                            ("task_deadline", _BIG)):
            if cname in cols and cols[cname].ndim == 2 \
                    and cols[cname].shape[1] != pad_tasks:
                tm = cols[cname]
                if tm.shape[1] > pad_tasks:
                    raise ValueError(
                        f"SweepPlan: {cname} width {tm.shape[1]} exceeds "
                        f"pad_tasks={pad_tasks}")
                cols[cname] = np.pad(
                    tm, ((0, 0), (0, pad_tasks - tm.shape[1])),
                    constant_values=fill)
        # storage/placement columns fail here, at plan build, with a named
        # error — the fused bucket runner would otherwise trace them
        # straight into the vmapped encoder
        _validate_cell_columns(cols)
        return cols, pad_tasks, pad_vms

    def params(self) -> dict[str, np.ndarray]:
        """The flattened ``grid_arrays`` parameter columns (host numpy)."""
        return self._compiled()[0]

    def arrays(self) -> ScenarioArrays:
        """Compile to one device-side batch (leading dim = flattened grid)."""
        cols, pad_tasks, pad_vms = self._compiled()
        return grid_arrays(cols, pad_tasks=pad_tasks, pad_vms=pad_vms)

    def run(self, mesh: jax.sharding.Mesh | None = None,
            chunk: int | None = None, *, bucket: object = "auto",
            backend: str = "xla", stream_to=None, compact: object = None,
            cost_model: "costmodel_mod.CostModel | None" = None,
            report: bool = False):
        """Execute the plan and return a labeled :class:`SweepResult`.

        Execution modes (combine with bucketing orthogonally):

        * default — one jitted vmap per shape bucket;
        * ``mesh`` — scenarios sharded over every mesh axis (the pod path;
          each bucket is padded up to a device-count multiple and trimmed);
        * ``chunk`` — at most ``chunk`` cells encoded + simulated per call
          (one shared lowering per bucket; results accumulate in host
          memory), for grids larger than device memory.

        ``bucket`` controls the adaptive schedule (DESIGN.md §6):
        ``"auto"`` (default) groups cells into power-of-two padded-shape
        buckets keyed on (task count, VM count, binding policy), so
        heterogeneous grids stop simulating phantom tasks at the grid-wide
        max padding; ``False`` runs the whole grid as one max-shape batch.
        Plan-level ``pad_tasks``/``pad_vms`` overrides act as bucket caps.
        Metric values are bit-identical either way (padding only adds
        exact-zero/identity lanes); only ``realized_epochs`` — the number
        of event epochs the executed batch actually ran, the new
        observability metric — reflects the schedule that produced it.

        ``backend`` selects the engine: ``"xla"`` (default) is
        :func:`engine.simulate_batch_arrays`; ``"pallas"`` runs the fused
        ``mr_epoch`` megakernel (``kernels/mr_sched``) with per-VM/task
        state resident in VMEM across epochs: compiled by Mosaic on a TPU
        backend (``chip_smoke.py`` runs it on one v5e chip against the
        XLA engine and the oracle), interpreted on any other backend,
        which only the CPU tests use; single-device only — combine with
        ``chunk``, not ``mesh``.

        ``stream_to`` (with ``chunk``) streams results to disk instead of
        accumulating them: each ``chunk``-cell slice of the grid is
        simulated and its long-form :meth:`SweepResult.to_table` rows
        appended to one parquet file, so million-cell grids never hold
        their metrics in host memory.  Returns a :class:`StreamedSweep`
        summary rather than a :class:`SweepResult` (the ROADMAP
        columnar-export item's second slice; needs the optional
        ``pyarrow`` dependency).

        ``compact`` turns on sparse active-lane compaction (DESIGN.md §9):
        every K epochs the still-active lanes are gathered into a
        pow2-padded compacted batch, stepped, and scattered back, so a
        tail-heavy bucket whose last 40 lanes are still running steps 64
        lanes instead of 2048.  ``compact="auto"`` (or ``True``) derives K
        from the measured cost model; an int pins K.  Results are
        bit-identical to the dense path — ``_epoch_step`` is idempotent
        for finished lanes — including per-lane ``n_epochs`` and the
        bucket's ``realized_epochs``.  Composes with ``bucket``/``chunk``
        (compaction runs per bucket resp. per chunk) and with
        ``backend="pallas"`` (the megakernel re-tiles the compacted
        batch).  The ``mesh`` path ignores ``compact``: it shards
        *per-lane* epoch loops with no cross-lane batch coupling, so
        there is no dense tail to compact away.  ``cost_model`` overrides
        the per-device measured calibration (pin one for deterministic
        scheduling decisions across hosts).

        ``report=True`` (DESIGN.md §12) additionally returns a
        :class:`~repro.core.telemetry.RunReport` — ``(result, report)``
        — recording what the adaptive schedule actually did: one
        :class:`~repro.core.telemetry.BucketReport` per dispatched
        bucket (cells, padded shape, statics, the cost-model split gain
        that justified it, dispatch/compaction-sync counts, wall time),
        fused-runner/encoder compile-cache hit+miss deltas, the resolved
        cost-model coefficients with their calibration ``source``, and
        run provenance.  Purely observational: the executed schedule and
        every metric value are unchanged.  Composes with every mode
        (streaming returns ``(StreamedSweep, RunReport)``; each streamed
        chunk re-buckets, so its report holds one entry per bucket *per
        chunk*).  Its counters also give the explicit host↔device
        transfers (``h2d_*``/``d2h_*``) and the lane-epochs the launches
        allotted against those the cells needed.

        Every call marks its phases with host spans on the JAX profiler's
        clock (``jax.profiler.TraceAnnotation``, DESIGN.md §12.4):
        ``iotsim.run`` (attributes ``run``, ``cells``, ``backend``,
        ``compact``) holds ``iotsim.plan``, ``iotsim.assemble`` and one
        ``iotsim.bucket`` per bucket, which holds ``iotsim.upload``,
        ``iotsim.launch``, ``iotsim.readback`` and, under compaction,
        ``iotsim.metrics`` and ``iotsim.compact.{prepare, step, poll,
        regather, finish}``.  With no profiler running each costs a
        no-op.  To see them, run the sweep inside ``with
        jax.profiler.trace(log_dir):`` and open the trace in Perfetto or
        TensorBoard: each idle gap of the device then lies under the
        phase that held it.
        """
        if mesh is not None and chunk is not None:
            raise ValueError("run: pass mesh or chunk, not both")
        if chunk is not None and chunk < 1:
            raise ValueError(f"run: chunk must be >= 1, got {chunk}")
        if backend not in ("xla", "pallas"):
            raise ValueError(
                f"run: backend must be 'xla' or 'pallas', got {backend!r}")
        if backend == "pallas" and mesh is not None:
            raise ValueError(
                "run: backend='pallas' is single-device (use chunk=, "
                "not mesh=)")
        compact = _check_compact(compact)
        buckets: list | None = None
        if report:
            # resolve the calibration up front so the schedule and the
            # report price with the *same* coefficients
            cost_model = cost_model or costmodel_mod.default_cost_model()
            t0 = time.perf_counter()
            ci0, ei0 = _cache_infos()
            buckets = []
        run_id = next(_RUN_IDS)
        with jax.profiler.TraceAnnotation("iotsim.run", run=run_id,
                                          cells=self.size, backend=backend,
                                          compact=str(compact)):
            if stream_to is not None:
                if chunk is None:
                    raise ValueError(
                        "run: stream_to= needs chunk= (the streamed write "
                        "appends one chunk of cells at a time)")
                streamed = self._run_streaming(stream_to, chunk, bucket,
                                               backend, compact, cost_model,
                                               buckets, run_id)
                if buckets is None:
                    return streamed
                return streamed, _finish_report(buckets, self.size, backend,
                                                compact, cost_model, ci0,
                                                ei0, t0)
            with jax.profiler.TraceAnnotation("iotsim.plan"):
                cols, pad_tasks, pad_vms = self._compiled()
            metrics, n_jobs = _execute_grid(cols, self.size, pad_tasks,
                                            pad_vms, bucket, mesh, chunk,
                                            backend, compact, cost_model,
                                            report=buckets, run_id=run_id)
            with jax.profiler.TraceAnnotation("iotsim.assemble"):
                shaped = {
                    name: (m.reshape(self.shape)
                           if m.ndim == 1 or n_jobs == 1
                           else m.reshape(self.shape + (n_jobs,)))
                    for name, m in metrics.items()}
                result = SweepResult(
                    axis_names=tuple(d.names for d in self.dims),
                    axis_labels=tuple(d.labels for d in self.dims),
                    metrics=shaped, n_jobs=n_jobs)
        if buckets is None:
            return result
        return result, _finish_report(buckets, self.size, backend, compact,
                                      cost_model, ci0, ei0, t0)

    def _run_streaming(self, path, chunk: int, bucket, backend,
                       compact=None, cost=None, report=None,
                       run_id: int = 0) -> "StreamedSweep":
        """Chunked execute + parquet append (see :meth:`run`)."""
        try:
            import pyarrow as pa
            import pyarrow.parquet as pq
        except ImportError as e:                  # pragma: no cover - env
            raise ImportError(
                "run(stream_to=...) requires the optional pyarrow "
                "dependency (pip install pyarrow); without it use "
                "run(chunk=...) and to_table()") from e
        with jax.profiler.TraceAnnotation("iotsim.plan"):
            cols, pad_tasks, pad_vms = self._compiled()
        N, shape = self.size, self.shape
        axis_names = tuple(d.names for d in self.dims)
        axis_labels = tuple(d.labels for d in self.dims)
        writer, n_rows, n_chunks = None, 0, 0
        try:
            for lo in range(0, N, chunk):
                hi = min(lo + chunk, N)
                sub = {k: v[lo:hi] for k, v in cols.items()}
                metrics, n_jobs = _execute_grid(
                    sub, hi - lo, pad_tasks, pad_vms, bucket, None, None,
                    backend, compact, cost, report=report, run_id=run_id)
                table = pa.table(_long_form_columns(
                    axis_names, axis_labels, shape, metrics, n_jobs,
                    lo, hi))
                # run provenance rides in the file-level schema metadata
                # (DESIGN.md §12) — pyarrow schema equality ignores
                # metadata, so later chunks append without re-stamping
                table = table.replace_schema_metadata(
                    {**(table.schema.metadata or {}),
                     **telemetry.parquet_metadata()})
                if writer is None:
                    writer = pq.ParquetWriter(path, table.schema)
                writer.write_table(table)
                n_rows += table.num_rows
                n_chunks += 1
        finally:
            if writer is not None:
                writer.close()
        return StreamedSweep(path=str(path), n_cells=N, n_rows=n_rows,
                             n_chunks=n_chunks)


def _check_compact(compact):
    """Normalize the ``compact`` knob: None/False off, True -> 'auto',
    'auto' or a positive int interval pass through."""
    if compact is None or compact is False:
        return None
    if compact is True:
        return "auto"
    if compact == "auto" or (isinstance(compact, int) and compact >= 1):
        return compact
    raise ValueError(
        f"run: compact must be None, False, True, 'auto', or an int "
        f">= 1; got {compact!r}")


def _cache_infos():
    """Hit/miss counters of the two lru caches the adaptive schedule
    leans on (deltas around a run feed :class:`telemetry.RunReport`)."""
    return _fused_runner.cache_info(), _grid_encoder.cache_info()


def _finish_report(buckets, n_cells: int, backend, compact, cost,
                   ci0, ei0, t0) -> "telemetry.RunReport":
    """Assemble the :class:`telemetry.RunReport` for one ``run()``."""
    ci1, ei1 = _cache_infos()
    return telemetry.RunReport(
        n_cells=n_cells, n_buckets=len(buckets), backend=backend,
        compact=compact, buckets=buckets,
        compile_cache_hits=ci1.hits - ci0.hits,
        compile_cache_misses=ci1.misses - ci0.misses,
        encoder_cache_hits=ei1.hits - ei0.hits,
        encoder_cache_misses=ei1.misses - ei0.misses,
        compaction_syncs=sum(b.compact_syncs for b in buckets),
        scalar_syncs=sum(b.compact_scalar_syncs for b in buckets),
        dispatches=sum(b.dispatches for b in buckets),
        **{f: sum(getattr(b, f) for b in buckets) for f in _COUNTERS},
        cost_model={"dispatch_us": cost.dispatch_us,
                    "epoch_lane_us": cost.epoch_lane_us,
                    "sync_us": cost.sync_us,
                    "device": cost.device, "source": cost.source},
        device=costmodel_mod.device_key(),
        provenance=dict(telemetry.provenance()),
        wall_s=time.perf_counter() - t0)


# Transfer and lane-epoch counters: ``stats`` keys of the drivers and
# fields of BucketReport / RunReport under the same names.
_COUNTERS = ("h2d_transfers", "h2d_bytes", "d2h_transfers", "d2h_bytes",
             "lane_epochs_allotted", "lane_epochs_useful")


def _execute_grid(cols: dict[str, np.ndarray], N: int, pad_tasks: int,
                  pad_vms: int, bucket, mesh, chunk, backend,
                  compact=None, cost=None, report: list | None = None,
                  run_id: int = 0) -> tuple[dict[str, np.ndarray], int]:
    """Bucket + simulate ``N`` flattened cells; returns ``(metrics,
    n_jobs)`` with per-job metric columns shaped ``[N, n_jobs]`` and
    per-scenario columns ``[N]`` (callers reshape to grid/table form).
    ``report`` (a list, appended in place) collects one
    :class:`telemetry.BucketReport` per dispatched bucket; ``run_id``
    tags the ``iotsim.bucket`` spans with their run."""
    if (compact is not None or report is not None) and cost is None:
        cost = costmodel_mod.default_cost_model()
    with jax.profiler.TraceAnnotation("iotsim.plan"):
        groups = _bucket_groups(cols, pad_tasks, pad_vms, bucket, cost)
    parts = []
    for b, (idx, gcols, statics, tb, vb) in enumerate(groups):
        stats = {"dispatches": 0, "syncs": 0, "scalar_syncs": 0,
                 "compactions": 0, **dict.fromkeys(_COUNTERS, 0)}
        w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("iotsim.bucket", run=run_id,
                                          bucket=b, cells=len(idx),
                                          pad_tasks=tb, pad_vms=vb):
            part = _run_cells(gcols, len(idx), tb, vb, statics, mesh,
                              chunk, backend, compact, cost, stats=stats)
        parts.append((idx, *part))
        stats["lane_epochs_useful"] = int(np.sum(part[1].n_epochs,
                                                 dtype=np.int64))
        if report is not None:
            report.append(telemetry.BucketReport(
                cells=len(idx), pad_tasks=tb, pad_vms=vb, backend=backend,
                control=bool(_CONTROL_PARAMS
                             & (set(gcols) | set(statics or {}))),
                statics=dict(statics or {}),
                # the modelled lane-epoch saving vs running these cells
                # at the grid cap — the quantity _bucket_groups weighed
                # against dispatch_us (None: bucket already at the cap)
                split_gain_us=(cost.split_gain_us(len(idx), tb, pad_tasks)
                               if tb < pad_tasks else None),
                dispatches=stats["dispatches"],
                compact_syncs=stats["syncs"],
                compact_scalar_syncs=stats["scalar_syncs"],
                wall_s=time.perf_counter() - w0,
                **{f: stats[f] for f in _COUNTERS}))
    with jax.profiler.TraceAnnotation("iotsim.assemble"):
        n_jobs = int(parts[0][1].makespan.shape[-1])
        metrics: dict[str, np.ndarray] = {}
        for f in JobMetrics._fields:
            out = np.empty((N, n_jobs),
                           np.asarray(getattr(parts[0][1], f)).dtype)
            for idx, jm, _, _ in parts:
                out[idx] = np.asarray(getattr(jm, f))
            metrics[f] = out
        for f in ScenarioMetrics._fields:
            out = np.empty(N, np.asarray(getattr(parts[0][2], f)).dtype)
            for idx, _, sm, _ in parts:
                out[idx] = np.asarray(getattr(sm, f))
            metrics[f] = out
        realized = np.empty(N, np.int32)
        for idx, _, _, rz in parts:
            realized[idx] = rz
        metrics["realized_epochs"] = realized
    return metrics, n_jobs


@dataclasses.dataclass(frozen=True)
class StreamedSweep:
    """Summary of a ``run(chunk=..., stream_to=...)`` streamed export:
    the grid's metrics live in the parquet file at ``path`` (long-form
    ``to_table`` columns), not in host memory."""
    path: str
    n_cells: int
    n_rows: int
    n_chunks: int


def _pad_cells(cols: dict[str, np.ndarray], n: int) -> dict[str, np.ndarray]:
    """Pad parameter columns to ``n`` cells by repeating the last cell."""
    have = len(next(iter(cols.values())))
    if have == n:
        return cols
    return {k: np.concatenate([v, np.repeat(v[-1:], n - have, axis=0)])
            for k, v in cols.items()}


# ---------------------------------------------------------------------------
# Adaptive execution schedule: shape buckets + per-bucket execution
# ---------------------------------------------------------------------------

# Per-cell padded sizes: smallest of {floor, 2·floor, 4·floor, …, cap}
# that fits.  Power-of-two rounding keeps the set of compiled shapes small
# and stable across differently-composed grids (compile-cache friendly);
# ``cap`` is the grid-wide max (or the plan's explicit pad override).
# Vectorized in core.util — the measured-cost scorer calls it on every
# candidate partition, which made the old per-unique-value loop hot.
_bucket_pads = pow2_pads
_pow2_pad = pow2_pad


def _bucket_groups(cols: dict[str, np.ndarray], pad_tasks: int, pad_vms: int,
                   bucket, cost: "costmodel_mod.CostModel | None" = None
                   ) -> list[tuple[np.ndarray, dict[str, np.ndarray],
                                   dict[str, int] | None, int, int]]:
    """Partition grid cells into padded-shape buckets.

    Returns ``[(cell_indices, columns, static_params, pad_tasks, pad_vms)]``
    with indices ascending inside every bucket (so scattering results back
    by index reproduces the unbucketed cell order exactly).  The schedule
    (DESIGN.md §6, scored since §9 by the measured cost model):

    * **policy split** — when the grid mixes ``sched_policy`` /
      ``binding_policy`` values *and* every combination can amortize a
      dispatch (``N >= combos × 64``), cells split per combination and
      the uniform values become *static* encoder parameters — inside the
      fused bucket runner they are trace constants, so XLA eliminates the
      policy branches (admission ranking for time-shared buckets, the
      sequential LEAST_LOADED scan for non-LL buckets) the bucket cannot
      take, and each combination exits at its *own* realized epoch count
      (time-shared cells stop subsidizing space-shared serialization).
      A policy column that is uniform across the whole grid (e.g.
      base-pinned) is static without any split;
    * **task padding** — ``n_maps + n_reduces`` rounded up to a power of
      two (stable shapes across differently-composed grids), then
      ascending-size runs stand alone exactly when the *measured* cost
      model says the split pays: the lane-epoch work the run saves by
      running at its own padding instead of the grid cap
      (``cost.split_gain_us``) must exceed the one extra fused dispatch
      the split costs (``cost.dispatch_us``).  This replaces the old
      static ``min_cells = max(256, N // 4)`` magic number — on a fast
      device dispatches are cheap and grids shatter into more, tighter
      buckets; on a slow-dispatch host small runs merge upward;
    * **VM padding** — each bucket's ``n_vms`` max rounded up likewise
      (per-VM / per-task vector columns are sliced to the bucket width;
      entries past a cell's ``n_vms``/task count are ignored by
      ``encode_cell``, so slicing cannot change results).
    """
    N = len(next(iter(cols.values())))
    all_idx = np.arange(N)
    if bucket is False or bucket is None or N <= 1:
        return [(all_idx, cols, None, pad_tasks, pad_vms)]
    if bucket is not True and bucket != "auto":
        raise ValueError(
            f"run: bucket must be 'auto', True, or False; got {bucket!r}")
    cost = cost or costmodel_mod.default_cost_model()
    need_t = (cols["n_maps"].astype(np.int64)
              + cols["n_reduces"].astype(np.int64))
    need_v = cols["n_vms"].astype(np.int64)
    tb = _bucket_pads(need_t, pad_tasks)

    policy_cols = [p for p in ("sched_policy", "binding_policy")
                   if p in cols]
    # grid-uniform policy columns are *always* static (no split needed —
    # the whole grid shares the value, e.g. a base-pinned policy)
    uniform_pols = {p: int(cols[p][0]) for p in policy_cols
                    if len(np.unique(cols[p])) == 1}
    policy_names = [p for p in policy_cols if p not in uniform_pols]
    if policy_names:
        combo_key = np.stack([cols[p].astype(np.int64)
                              for p in policy_names], axis=1)
        combos, combo_id = np.unique(combo_key, axis=0, return_inverse=True)
        # policy split pays for itself far sooner than shape splits: each
        # combo exits at its own realized epoch count (time-shared combos
        # stop subsidizing space-shared serialization) and the statics DCE
        # the other policy's machinery — so it only needs each combo to
        # amortize one dispatch, not a full shape bucket
        if N < len(combos) * 64:            # too fragmented to specialize
            policy_names, combo_id = [], np.zeros(N, np.int64)
    else:
        combo_id = np.zeros(N, np.int64)

    merged: list[np.ndarray] = []
    for c in np.unique(combo_id):
        cidx = all_idx[combo_id == c]
        sizes = tb[cidx]
        pend: list[np.ndarray] = []
        done_here: list[np.ndarray] = []
        for t in np.unique(sizes):          # ascending shape runs
            pend.append(cidx[sizes == t])
            # stand alone exactly when the modelled lane-epoch saving of
            # running these cells at pad t instead of the grid cap buys
            # back the extra dispatch the split costs (near-max-shape
            # runs never qualify: the gain tends to zero as t -> cap)
            n_pend = sum(map(len, pend))
            if cost.split_gain_us(n_pend, int(t), pad_tasks) \
                    >= cost.dispatch_us:
                done_here.append(np.sort(np.concatenate(pend)))
                pend = []
        if pend:                            # tail that never paid alone
            tail = np.concatenate(pend)
            if done_here:
                # merging the tail down pulls the previous bucket's cells
                # UP to the tail's padding — keep the previous bucket
                # separate iff its own split gain vs the tail pad still
                # beats a dispatch
                prev = done_here[-1]
                t_prev = int(tb[prev].max())
                t_tail = int(tb[tail].max())
                if cost.split_gain_us(len(prev), t_prev, t_tail) \
                        < cost.dispatch_us:
                    tail = np.concatenate([done_here.pop(), tail])
            done_here.append(np.sort(tail))
        merged.extend(done_here)

    groups = []
    for idx in merged:
        t = _pow2_pad(int(need_t[idx].max()), pad_tasks)
        vb = _pow2_pad(int(need_v[idx].max()), pad_vms)
        statics = dict(uniform_pols)
        statics.update({p: int(cols[p][idx[0]]) for p in policy_names})
        gcols = {}
        for cname, cvals in cols.items():
            if cname in statics:
                continue
            cv = cvals[idx]
            if cv.ndim == 2:
                cv = cv[:, :t] if cname in _PER_TASK else cv[:, :vb]
            gcols[cname] = cv
        groups.append((idx, gcols, statics or None, t, vb))
    return groups


@lru_cache(maxsize=None)
def _fused_runner(layout: tuple, pad_tasks: int, pad_vms: int,
                  statics: tuple[tuple[str, int], ...], backend: str,
                  max_pes: int = 0, control: bool = False):
    """encode + simulate + metrics as ONE jitted callable per bucket
    signature.  A single dispatch per bucket (the bucketed schedule's fixed
    cost is dominated by per-call overhead on small hosts), and — the key
    effect — ``statics`` and encode_cell's scalar defaults become trace
    constants *inside the engine*, so XLA folds the per-bucket policy
    branches instead of carrying both policies' machinery at runtime.
    The columns arrive as one buffer in ``layout`` (:func:`_pack_columns`)
    and the results and realized epoch count leave as one
    (:class:`_OneReadback`)."""
    names = tuple(n for n, _, _ in layout)
    static_kw = dict(statics)

    def run(buf):
        def one(*cell):
            kw = dict(zip(names, cell))
            kw.update(static_kw)
            return encode_cell(**kw, pad_tasks=pad_tasks, pad_vms=pad_vms)

        with jax.named_scope("encode"):
            batch = jax.vmap(one)(*_unpack_columns(buf, layout))
        with jax.named_scope("epoch_loop"):
            if backend == "pallas":
                from repro.kernels.mr_sched import \
                    epoch_schedule  # lazy: ref.py cycle
                out = epoch_schedule(batch, max_pes=max_pes, control=control)
                realized = jnp.max(out.n_epochs)
            else:
                out, realized = simulate_batch_arrays(batch, control=control)
        return _metrics_of(batch, out) + (realized,)

    return _OneReadback(run)


def _metrics_of(batch, out):
    """Job and scenario metrics of a simulated batch (traced inside the
    bucket runner and the compacted path's metrics pass)."""
    with jax.named_scope("metrics"):
        return (jax.vmap(job_metrics)(batch, out),
                jax.vmap(scenario_metrics)(batch, out))


def _metrics_and_realized(batch, out, realized):
    """Fused metrics pass for the compacted path (its epoch stepping is
    host-driven, so metrics dispatch separately from simulation); the
    realized epoch count rides in its one readback buffer."""
    return _metrics_of(batch, out) + (realized,)


_metrics_batch = _OneReadback(_metrics_and_realized)


def _run_compact(cols: dict[str, np.ndarray], pad_tasks: int, pad_vms: int,
                 statics: dict[str, int] | None, backend: str, k, cost,
                 max_pes: int, control: bool = False,
                 stats: dict | None = None):
    """One compacted-stepping execution of a cell slice (DESIGN.md §9):
    jitted encode -> host-driven compacted epoch stepping -> jitted
    metrics.  Encode and metrics stay fused and signature-cached exactly
    like the dense runner; only the epoch loop leaves jit, because
    compaction needs host control flow over the active-lane count (XLA
    shapes are static).  Returns host ``(JobMetrics, ScenarioMetrics,
    realized)``: one column upload and one readback besides the loop's
    own transfers."""
    batch = grid_arrays(cols, pad_tasks=pad_tasks, pad_vms=pad_vms,
                        static_params=statics, stats=stats)
    if backend == "pallas":
        from repro.kernels.mr_sched import \
            epoch_schedule_compact  # lazy: ref.py cycle
        out, realized = epoch_schedule_compact(batch, k=k, max_pes=max_pes,
                                               cost_model=cost,
                                               control=control, stats=stats)
    else:
        out, realized = simulate_batch_arrays_compact(batch, k=k,
                                                      cost_model=cost,
                                                      control=control,
                                                      stats=stats)
    with jax.profiler.TraceAnnotation("iotsim.metrics"):
        launched = _metrics_batch(batch, out, realized)
    with jax.profiler.TraceAnnotation("iotsim.readback"):
        jm, sm, realized = _metrics_batch.pull(launched, stats)
    return jm, sm, int(realized)


def _run_cells(cols: dict[str, np.ndarray], n: int, pad_tasks: int,
               pad_vms: int, statics: dict[str, int] | None,
               mesh, chunk, backend, compact=None, cost=None,
               stats: dict | None = None) -> tuple[
                   JobMetrics, ScenarioMetrics, np.ndarray]:
    """Encode + simulate one bucket's cells; returns host-side
    ``(JobMetrics, ScenarioMetrics, realized_epochs[n])``.  ``stats``
    (a dict, mutated in place) counts device ``dispatches``, the compact
    drivers' host ``syncs``/``compactions``, the transfers
    (:func:`telemetry.put`/:func:`telemetry.pull`: one column upload and
    one result readback per launch off the mesh) and
    ``lane_epochs_allotted``."""
    if stats is None:
        stats = {}
    stats.setdefault("dispatches", 0)
    stats.setdefault("lane_epochs_allotted", 0)
    # the control path is keyed on column *presence* (host-decidable even
    # for traced columns — engine._control_active is not, under trace):
    # a plan that never names a control parameter pays zero control cost
    control = bool(_CONTROL_PARAMS & (set(cols) | set(statics or {})))
    if mesh is not None:
        # pod path: per-lane epoch loops (no per-epoch any() collective,
        # hence no dense tail for `compact` to trim — it is ignored here)
        n_dev = int(mesh.devices.size)
        full = -(-n // n_dev) * n_dev
        batch = grid_arrays(_pad_cells(cols, full), pad_tasks=pad_tasks,
                            pad_vms=pad_vms, static_params=statics,
                            stats=stats)
        with jax.profiler.TraceAnnotation("iotsim.launch"):
            jm, sm = _simulate_full_sharded(batch, mesh, control)
        stats["dispatches"] += 1
        with jax.profiler.TraceAnnotation("iotsim.readback"):
            jm, sm = jax.tree.map(lambda x: telemetry.pull(x, stats)[:n],
                                  (jm, sm))
        realized = int(np.max(sm.n_epochs))
        stats["lane_epochs_allotted"] += full * realized
        return jm, sm, np.full(n, realized, np.int32)
    max_pes = (max(int(np.ceil(float(np.max(cols["vm_pes"])))), 1)
               if backend == "pallas" else 0)
    if compact is not None:
        if chunk is not None:
            parts, realized = [], np.empty(n, np.int32)
            for lo in range(0, n, chunk):
                part = _pad_cells(
                    {k: v[lo:lo + chunk] for k, v in cols.items()},
                    min(chunk, n))
                take = min(chunk, n - lo)
                jm, sm, rz = _run_compact(part, pad_tasks, pad_vms, statics,
                                          backend, compact, cost, max_pes,
                                          control, stats)
                parts.append(jax.tree.map(lambda x: x[:take], (jm, sm)))
                realized[lo:lo + take] = rz
            jm, sm = jax.tree.map(lambda *xs: np.concatenate(xs), *parts)
            return jm, sm, realized
        jm, sm, rz = _run_compact(cols, pad_tasks, pad_vms, statics,
                                  backend, compact, cost, max_pes, control,
                                  stats)
        return jm, sm, np.full(n, rz, np.int32)
    layout = _upload_layout(cols, sorted(cols))
    runner = _fused_runner(layout, pad_tasks, pad_vms,
                           tuple(sorted((statics or {}).items())),
                           backend, max_pes, control)

    def launch(part):
        """Upload, run and read back one batch of cells, one transfer each
        way; ``(jm, sm, realized)`` on the host."""
        with jax.profiler.TraceAnnotation("iotsim.upload"):
            buf = telemetry.put(_pack_columns(part, layout), stats)
        with jax.profiler.TraceAnnotation("iotsim.launch"):
            launched = runner(buf)
        stats["dispatches"] += 1
        with jax.profiler.TraceAnnotation("iotsim.readback"):
            jm, sm, rz = runner.pull(launched, stats)
        rz = int(rz)
        stats["lane_epochs_allotted"] += (
            _lanes(len(part[layout[0][0]]), backend) * rz)
        return jm, sm, rz

    if chunk is not None:
        parts, realized = [], np.empty(n, np.int32)
        for lo in range(0, n, chunk):
            part = _pad_cells({k: v[lo:lo + chunk] for k, v in cols.items()},
                              min(chunk, n))
            take = min(chunk, n - lo)
            jm, sm, rz = launch(part)
            parts.append(jax.tree.map(lambda x: x[:take], (jm, sm)))
            realized[lo:lo + take] = rz
        jm, sm = jax.tree.map(lambda *xs: np.concatenate(xs), *parts)
        return jm, sm, realized
    jm, sm, rz = launch(cols)
    return jm, sm, np.full(n, rz, np.int32)


def _lanes(n: int, backend: str) -> int:
    """Lanes a dense launch of ``n`` cells steps: the Pallas kernel pads
    the batch to whole tiles (``ops.lane_pad``), the XLA engine steps the
    batch as given."""
    if backend != "pallas":
        return n
    from repro.kernels.mr_sched import ops  # lazy: ref.py cycle
    return n + ops.lane_pad(n, ops.resolve_mode(None, None)[1])


def _plain_label(v):
    """One coordinate label as a column-friendly scalar (enum -> name,
    nested sequences -> string)."""
    if isinstance(v, enum.Enum):
        return v.name
    if isinstance(v, (tuple, list, np.ndarray)):
        return ",".join(str(_plain_label(x)) for x in np.asarray(v).tolist())
    return v


def _long_form_columns(axis_names, axis_labels, shape, flat_metrics,
                       n_jobs, lo, hi) -> dict[str, np.ndarray]:
    """Long-form rows for the flat grid cells ``[lo, hi)`` — the ONE
    row encoding behind :meth:`SweepResult.to_table` (whole grid) and
    the streamed parquet writer (one chunk at a time), so the two
    export paths cannot drift.  ``flat_metrics`` maps metric names to
    ``[n, n_jobs]`` (per-job) or ``[n]`` (per-scenario) columns for the
    slice; axis coordinates expand through :func:`_plain_label`, cells
    with several jobs gain a ``job`` index column.
    """
    n = hi - lo
    flat = np.arange(lo, hi)
    cols: dict[str, np.ndarray] = {}
    for d, (names, labs) in enumerate(zip(axis_names, axis_labels)):
        inner = int(np.prod(shape[d + 1:], dtype=np.int64))
        di = (flat // inner) % shape[d]
        for ci, cname in enumerate(names):
            vals = np.asarray([_plain_label(lab[ci]) for lab in labs])
            cols[cname] = np.repeat(vals[di], n_jobs)
    if n_jobs > 1:
        cols["job"] = np.tile(np.arange(n_jobs), n)
    for mname, m in flat_metrics.items():
        cols[mname] = (m.reshape(n * n_jobs) if m.ndim == 2
                       else np.repeat(m, n_jobs))
    return cols


def _match_label(label, want) -> bool:
    if label is want:
        return True
    if isinstance(label, enum.Enum) and isinstance(want, str):
        return label.name == want
    try:
        return bool(label == want)
    except (TypeError, ValueError):
        return False


@dataclasses.dataclass(frozen=True)
class SweepResult:
    """Labeled sweep output: axis coordinates + named metric arrays.

    ``metrics[name]`` has the plan's grid shape (per-job metrics gain a
    trailing job dim when a cell holds more than one job).  Per-job metrics
    are the paper's §5.3 dependent variables (:class:`JobMetrics` fields,
    including ``completion``); per-scenario extras are ``finish_time``,
    ``utilization`` and ``n_epochs`` (:class:`ScenarioMetrics`).
    """
    axis_names: tuple[tuple[str, ...], ...]
    axis_labels: tuple[tuple[tuple[Any, ...], ...], ...]
    metrics: Mapping[str, np.ndarray]
    n_jobs: int = 1

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(len(labs) for labs in self.axis_labels)

    @property
    def metric_names(self) -> tuple[str, ...]:
        return tuple(self.metrics)

    def __getitem__(self, name: str) -> np.ndarray:
        try:
            return self.metrics[name]
        except KeyError:
            raise KeyError(f"no metric {name!r}; "
                           f"available: {list(self.metrics)}") from None

    def coord(self, index: Sequence[int]) -> dict[str, Any]:
        """Axis coordinates of one grid point (e.g. from unravel_index)."""
        out: dict[str, Any] = {}
        for d, (names, labs) in enumerate(zip(self.axis_names,
                                              self.axis_labels)):
            out.update(zip(names, labs[int(index[d])]))
        return out

    def select(self, **coords: Any) -> "SweepResult":
        """Slice by axis-coordinate labels (``select(n_maps=4,
        vm_type="medium")``).  Coordinates matching exactly one point drop
        their dimension; several matches keep a filtered dimension.  Zipped
        dimensions are addressed through any of their component names —
        several components of one zipped dimension constrain it jointly."""
        names = list(self.axis_names)
        labels = list(self.axis_labels)
        metrics = dict(self.metrics)
        by_dim: dict[int, dict[str, Any]] = {}
        for key, want in coords.items():
            for d, ns in enumerate(names):
                if key in ns:
                    by_dim.setdefault(d, {})[key] = want
                    break
            else:
                raise KeyError(
                    f"select: no axis {key!r}; axes: "
                    f"{[n for ns in names for n in ns]}")
        for d in sorted(by_dim, reverse=True):   # right-to-left: stable axes
            wants = by_dim[d]
            comp = {k: names[d].index(k) for k in wants}
            hits = [i for i, lab in enumerate(labels[d])
                    if all(_match_label(lab[comp[k]], w)
                           for k, w in wants.items())]
            if not hits:
                raise KeyError(
                    f"select: {wants} not on the axis "
                    f"{'×'.join(names[d])}; labels: {list(labels[d])}")
            if len(hits) == 1:
                metrics = {k: v.take(hits[0], axis=d)
                           for k, v in metrics.items()}
                del names[d], labels[d]
            else:
                metrics = {k: v.take(hits, axis=d) for k, v in metrics.items()}
                labels[d] = tuple(labels[d][i] for i in hits)
        return SweepResult(tuple(names), tuple(labels), metrics, self.n_jobs)

    def to_dict(self) -> dict[str, Any]:
        """Metrics as plain ``{name: ndarray}`` (0-d arrays as scalars)."""
        return {k: (v.item() if np.ndim(v) == 0 else np.asarray(v))
                for k, v in self.metrics.items()}

    def to_table(self) -> dict[str, np.ndarray]:
        """Columnar (long-form) export: equal-length numpy columns, one
        row per grid cell — times ``n_jobs`` (plus a ``job`` index column)
        when cells hold several jobs.  Axis coordinates come first in
        row-major grid order, metric columns follow.  Enum labels export
        as their names and tuple labels (``vms`` clusters, per-VM vectors)
        as strings, so every column is numeric/bool/string — directly
        consumable by pandas/pyarrow (:meth:`to_parquet`); the first slice
        of the ROADMAP columnar-export item."""
        shape = self.shape
        N = int(np.prod(shape, dtype=np.int64)) if shape else 1
        nj = self.n_jobs
        flat = {}
        for mname, m in self.metrics.items():
            arr = np.asarray(m)
            flat[mname] = (arr.reshape(N, nj)        # trailing per-job dim
                           if arr.ndim == len(shape) + 1
                           else arr.reshape(N))      # per-scenario metric
        return _long_form_columns(self.axis_names, self.axis_labels, shape,
                                  flat, nj, 0, N)

    def to_parquet(self, path) -> None:
        """Write :meth:`to_table` to a parquet file, stamping run
        provenance (repro/jax versions, device, git sha) into the schema
        metadata (DESIGN.md §12).  Needs the *optional* ``pyarrow``
        dependency — import-guarded so the simulator core never depends
        on it."""
        try:
            import pyarrow as pa
            import pyarrow.parquet as pq
        except ImportError as e:                  # pragma: no cover - env
            raise ImportError(
                "SweepResult.to_parquet requires the optional pyarrow "
                "dependency (pip install pyarrow); to_table() returns the "
                "same columns as plain numpy") from e
        table = pa.table(dict(self.to_table()))
        table = table.replace_schema_metadata(
            {**(table.schema.metadata or {}),
             **telemetry.parquet_metadata()})
        pq.write_table(table, path)

    def __repr__(self) -> str:
        ax = ", ".join(f"{'×'.join(ns)}[{len(labs)}]"
                       for ns, labs in zip(self.axis_names, self.axis_labels))
        return (f"SweepResult(axes=({ax}), n_jobs={self.n_jobs}, "
                f"metrics={list(self.metrics)})")


# ---------------------------------------------------------------------------
# Batched simulation entry points
# ---------------------------------------------------------------------------

def _one_full(sc: ScenarioArrays,
              control: bool = False) -> tuple[JobMetrics, ScenarioMetrics]:
    out = simulate_arrays(sc, control=control)
    return job_metrics(sc, out), scenario_metrics(sc, out)


@lru_cache(maxsize=None)
def _sharded_runner(mesh: jax.sharding.Mesh, control: bool = False):
    """One jitted sharded simulate per mesh — repeated ``run(mesh=…)`` calls
    reuse the compilation instead of retracing through a fresh lambda."""
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(mesh.axis_names))
    return jax.jit(jax.vmap(partial(_one_full, control=control)),
                   in_shardings=sharding, out_shardings=sharding)


def _simulate_full_sharded(batch: ScenarioArrays, mesh: jax.sharding.Mesh,
                           control: bool = False):
    return _sharded_runner(mesh, control)(batch)


@partial(jax.jit, static_argnames="control")
def _simulate_batch_jit(batch: ScenarioArrays,
                        control: bool = False) -> JobMetrics:
    def one(sc):
        return job_metrics(sc, simulate_arrays(sc, control=control))
    return jax.vmap(one)(batch)


def simulate_batch(batch: ScenarioArrays) -> JobMetrics:
    """vmap the engine + metrics over a leading scenario dim."""
    from .engine import _control_active
    return _simulate_batch_jit(batch, control=_control_active(batch))


def simulate_batch_sharded(batch: ScenarioArrays,
                           mesh: jax.sharding.Mesh) -> JobMetrics:
    """The pod-scale path: scenarios sharded over every mesh axis.

    The engine is embarrassingly parallel across scenarios, so the batch dim
    is sharded over the flattened mesh; no collectives are emitted (verified
    in the dry-run — this workload is the compute-roofline end of the
    simulator story).
    """
    from .engine import _control_active
    control = _control_active(batch)
    spec = jax.sharding.PartitionSpec(mesh.axis_names)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    fn = jax.jit(
        lambda b: jax.vmap(lambda s: job_metrics(
            s, simulate_arrays(s, control=control)))(b),
        in_shardings=(jax.tree.map(lambda _: sharding, batch),),
        out_shardings=sharding)
    return fn(batch)

