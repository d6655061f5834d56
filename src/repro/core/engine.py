"""Vectorized discrete-event engine (the TPU-native IOTSim core).

The sequential CloudSim event loop (``refsim.py``) is re-expressed as a
fixed-shape state machine advanced by ``jax.lax.while_loop``: each iteration
processes one *event epoch* — it advances the processor-sharing fluid state
to the earliest next completion/arrival and fires every event at that
instant.  Rates only change at events, so the fluid dynamics are exact (this
is not time-stepping).

Because every per-scenario state is a fixed-shape array bundle
(:class:`ScenarioArrays`), the whole simulation is ``vmap``-able over
scenarios and ``pjit``-able over a pod mesh — one lowering simulates millions
of IOTSim scenarios in parallel (see ``sweep.py``).  This is the
hardware-adaptation of the paper's sequential Java architecture (DESIGN.md
§2).

Semantics are tested to match ``refsim.py`` exactly
(``tests/test_engine_vs_refsim.py``).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import elasticity, network, storage
from .config import (BindingPolicy, Scenario, SchedPolicy,
                     base_task_lengths_f32)
from .control import (ControlPolicy, DeadlinePolicy, earliest_finish,
                      failover_targets, scenario_control)
from .telemetry import (EV_FINISH, EV_KILL, EV_PREEMPT, EV_SCALE_CLOSE,
                        EV_SCALE_OPEN, EV_SHED, EV_START, TraceBuffers,
                        event_capacity, pull, put, timeseries_capacity)
from .util import pow2_pad, validate_pow2_floor

_BIG = 1e30          # stand-in for +inf that survives arithmetic
_TIME_EPS = 1e-6     # relative tie window for simultaneous events


# ---------------------------------------------------------------------------
# Array-of-structs scenario encoding
# ---------------------------------------------------------------------------

class ScenarioArrays(NamedTuple):
    """One scenario as fixed-shape arrays (all leaves vmappable).

    Shapes: T = padded task count, J = padded job count, V = padded VM count.
    Task structure (which job, map/reduce, VM binding) is *data*, so sweeps
    may vary MR combination, job sizes, VM speeds … under ``vmap`` without
    re-tracing.
    """
    # tasks
    task_job: jax.Array        # i32[T] job index
    task_is_reduce: jax.Array  # bool[T]
    task_vm: jax.Array         # i32[T] policy-resolved VM binding
    task_valid: jax.Array      # bool[T]
    task_mult: jax.Array       # f32[T] straggler length multiplier
    # jobs
    job_length: jax.Array      # f32[J] MI
    job_data: jax.Array        # f32[J] MB
    job_n_maps: jax.Array      # i32[J]
    job_n_reduces: jax.Array   # i32[J]
    job_submit: jax.Array      # f32[J]
    job_reduce_factor: jax.Array  # f32[J]
    job_valid: jax.Array       # bool[J]
    # vms
    vm_mips: jax.Array         # f32[V]
    vm_pes: jax.Array          # f32[V]
    vm_cost: jax.Array         # f32[V]
    vm_valid: jax.Array        # bool[V]
    # network (scalars)
    net_enabled: jax.Array     # f32 (0/1)
    net_bw: jax.Array          # f32
    kappa_in: jax.Array        # f32
    kappa_shuffle: jax.Array   # f32
    net_cost_per_unit: jax.Array  # f32
    # policies (i32 scalars — data, not trace constants: one lowering serves
    # batches mixing policies under vmap; see config.SchedPolicy)
    sched_policy: jax.Array    # i32 (0 time-shared | 1 space-shared)
    binding_policy: jax.Array  # i32 (0 RR | 1 least-loaded | 2 packed |
    #                            3 locality); already resolved into task_vm,
    #                            kept as provenance alongside the binding
    # storage (DESIGN.md §7): realized block placement as per-task data —
    # replication / block size / placement skew are sweepable like any
    # other parameter because only their *realization* reaches the engine
    block_vm: jax.Array        # i32[T, V] replica VMs of the task's input
    #                            block in replica-slot order; -1 = no slot
    #                            (reduces, padding, storage disabled)
    block_size: jax.Array      # f32[T] input-block size in MB (0 = none)
    storage_enabled: jax.Array  # f32 (0/1) provenance gate
    # elasticity (DESIGN.md §8): per-VM lease windows + pay-as-you-go knobs.
    # The degenerate static fleet is vm_start=0 / vm_stop=_BIG everywhere —
    # every availability op below is a bitwise identity there.
    vm_start: jax.Array        # f32[V] lease start (billing runs from here)
    vm_stop: jax.Array         # f32[V] lease stop; _BIG = never torn down
    spinup_delay: jax.Array    # f32 scalar — admission opens at start+spinup
    bill_gran: jax.Array       # f32 scalar — billing granularity (seconds)
    task_prio: jax.Array       # f32[T] space-shared admission priority
    #                            (higher admitted first; 0 = legacy rank)
    # closed-loop control (DESIGN.md §10): seeded failure streams realized
    # as per-VM instants (control.failure_times — host f64, cast once) and
    # the autoscale rule's inputs, all device-side sweepable data.  The
    # degenerate fill (_BIG fails, no reserves, NONE policy) is detected
    # host-side (_control_active) and skips the control code entirely.
    vm_fail: jax.Array         # f32[V] failure instant; _BIG = never fails
    vm_restore: jax.Array      # f32[V] restore instant; _BIG = never
    vm_auto: jax.Array         # bool[V] autoscale reserve (lease
    #                            materializes only when control opens it)
    control_policy: jax.Array  # i32 (0 NONE | 1 AUTOSCALE)
    ctl_queue: jax.Array       # f32 scalar — scale up while queue depth
    #                            (ready, unstarted tasks) exceeds this
    ctl_busy: jax.Array        # f32 scalar — … and the open fleet's busy
    #                            fraction is at least this
    redispatch_delay: jax.Array  # f32 scalar — failure-detection +
    #                              re-queue latency added on task kill
    # graceful degradation (DESIGN.md §11): per-task decision windows and
    # the overload-policy knobs.  The degenerate fill (deadline _BIG,
    # NONE policy, preemption off) is a bitwise identity with §10.
    task_deadline: jax.Array   # f32[T] completion deadline; _BIG = none
    deadline_policy: jax.Array  # i32 (0 NONE | 1 SHED | 2 BOOST)
    deadline_slack: jax.Array  # f32 scalar — BOOST urgency window
    preempt: jax.Array         # i32 (0/1) — priority preemption on
    preempt_resume: jax.Array  # i32 (0/1) — evicted tasks keep progress


class SimOutput(NamedTuple):
    """Raw per-task schedule + bookkeeping, all f32/i32 arrays."""
    start: jax.Array     # f32[T]
    finish: jax.Array    # f32[T]
    ready: jax.Array     # f32[T]
    exec_time: jax.Array  # f32[T]
    n_epochs: jax.Array  # i32 — event epochs executed (bench metric)
    finish_time: jax.Array  # f32 — last completion
    # closed-loop control results (degenerate fills reproduce the encoded
    # scenario: hit all-false, vm_open/vm_close the static lease window)
    hit: jax.Array       # bool[T] task was killed by a VM failure at
    #                      least once (now bound to its failover VM)
    task_vm2: jax.Array  # i32[T] failover binding (== task_vm when
    #                      control is off; current VM = hit ? vm2 : vm)
    vm_open: jax.Array   # f32[V] realized lease open (_BIG = never)
    vm_close: jax.Array  # f32[V] realized lease close (_BIG = never)
    n_scale: jax.Array   # i32 — autoscale open+close events executed
    # graceful degradation (DESIGN.md §11; exact zero fills when off)
    shed: jax.Array      # bool[T] task shed by deadline admission control
    #                      (never started, deadline unmeetable — includes
    #                      reduces orphaned by a shed map of their job)
    n_evict: jax.Array   # i32[T] times the task was preempted (<= 2)
    work_lost: jax.Array  # f32 — MI of progress discarded by failure
    #                       kills + non-resume preemptions


class JobMetrics(NamedTuple):
    """Paper §5.3 dependent variables, per job (padded J)."""
    avg_exec: jax.Array
    max_exec: jax.Array
    min_exec: jax.Array
    makespan: jax.Array
    delay_time: jax.Array
    vm_cost: jax.Array
    network_cost: jax.Array
    map_avg_exec: jax.Array
    reduce_avg_exec: jax.Array
    completion: jax.Array      # wall-clock last-reduce finish (0 for padding)


class ScenarioMetrics(NamedTuple):
    """Per-scenario (not per-job) dependent variables for sweep results."""
    finish_time: jax.Array   # f32 — wall-clock end of the scenario
    utilization: jax.Array   # f32 — delivered MI / (cluster capacity × time)
    n_epochs: jax.Array      # i32 — event epochs executed (bench metric)
    locality_fraction: jax.Array  # f32 — data-local maps / maps with a
    #                               placed input block (0 if storage off)
    transfer_bytes: jax.Array  # f32 — remote-fetched block bytes (decimal
    #                            MB × 1e6; 0 under LOCALITY's ideal case)
    billed_cost: jax.Array   # f32 — pay-as-you-go fleet cost: per-VM
    #                          realized lease, ceil'd to the billing
    #                          granularity, × cost_per_sec (DESIGN.md §8)
    vm_busy_fraction: jax.Array  # f32 — delivered MI / leased MI capacity
    #                              (capacity-weighted busy share of the
    #                              fleet's realized leases)
    queue_wait: jax.Array    # f32 — mean start − ready over started tasks
    #                          (slot + lease-availability + spinup waits)
    # closed-loop control metrics (DESIGN.md §10; 0 in open-loop runs)
    failures_injected: jax.Array   # f32 — valid-VM failures fired within
    #                                the scenario's wall-clock span
    tasks_redispatched: jax.Array  # f32 — tasks killed + re-dispatched
    scale_events: jax.Array        # f32 — autoscale lease opens + closes
    recovered_fraction: jax.Array  # f32 — re-dispatched tasks that still
    #                                completed / re-dispatched (0 if none)
    # SLO metrics layer (DESIGN.md §11; exact zeros without deadlines)
    deadline_miss_fraction: jax.Array  # f32 — finite-deadline tasks that
    #                                    finished late or never / all
    #                                    finite-deadline tasks
    shed_tasks: jax.Array          # f32 — tasks shed by admission control
    preemptions: jax.Array         # f32 — priority evictions executed
    wasted_work_frac: jax.Array    # f32 — (discarded progress + late
    #                                completions' MI) / (delivered MI +
    #                                discarded progress)
    p99_slack: jax.Array           # f32 — nearest-rank p99 of
    #                                finish − deadline over *completed*
    #                                finite-deadline tasks (<= 0 is good)


def task_lengths(sc: ScenarioArrays) -> jax.Array:
    """Effective per-task lengths in MI (straggler multiplier applied).

    The exact op sequence ``simulate_arrays`` integrates, factored out so
    metrics layers (utilization) account the same work the engine runs.
    """
    n_maps_f = sc.job_n_maps.astype(jnp.float32)
    n_red_f = sc.job_n_reduces.astype(jnp.float32)
    map_len = sc.job_length / n_maps_f
    red_len = sc.job_reduce_factor * sc.job_length / n_red_f
    task_len = jnp.where(sc.task_is_reduce, red_len[sc.task_job],
                         map_len[sc.task_job]) * sc.task_mult
    return jnp.where(sc.task_valid, task_len, 0.0)


def bind_tasks(binding_policy, task_valid, task_len, vm_mips, vm_pes,
               vm_valid, locality_cand=None) -> jax.Array:
    """Resolve the broker's task→VM binding as data (DESIGN.md §3.2).

    ``binding_policy`` may be a traced i32 scalar, so a vmapped batch can
    mix :class:`~repro.core.config.BindingPolicy` values without retracing;
    all four strategies are computed and selected branch-free.  ``task_len``
    is the *base* (pre-straggler-multiplier) length — the broker binds
    before execution, so multipliers must not influence placement.  The
    LEAST_LOADED estimate is ``assigned_MI / (mips * pes)`` (full-VM
    capacity, so multi-PE VMs are not undervalued) accumulated in float32,
    matching the oracle's bookkeeping bit for bit so both layers pick
    identical VMs.

    ``locality_cand`` is LOCALITY's ``bool[T, V]`` candidate mask
    (``storage.locality_candidates``: replica holders for tasks with an
    input block, all valid VMs otherwise).  ``None`` — no storage model —
    makes LOCALITY bind exactly as LEAST_LOADED (same scan, all-true
    mask), which is also what an all-true mask produces bit for bit.
    """
    task_valid = jnp.asarray(task_valid, bool)
    task_len = jnp.asarray(task_len, jnp.float32)
    vm_mips = jnp.asarray(vm_mips, jnp.float32)
    vm_valid = jnp.asarray(vm_valid, bool)
    T = task_valid.shape[0]
    bp = jnp.asarray(binding_policy, jnp.int32)
    validi = task_valid.astype(jnp.int32)
    counter = jnp.cumsum(validi) - validi          # submission-order index
    n_vms = jnp.maximum(jnp.sum(vm_valid.astype(jnp.int32)), 1)
    rr = counter % n_vms

    # PACKED: fill PE slots [vm0]*pes0 ++ [vm1]*pes1 ++ … cyclically.
    pes_i = jnp.where(vm_valid, jnp.asarray(vm_pes, jnp.int32), 0)
    total_pes = jnp.maximum(jnp.sum(pes_i), 1)
    slot = counter % total_pes
    cum_pes = jnp.cumsum(pes_i)
    packed = jnp.sum((slot[:, None] >= cum_pes[None, :]).astype(jnp.int32),
                     axis=1)

    # LEAST_LOADED: greedy argmin over f32 load estimate (MI / mips).
    load0 = jnp.where(vm_valid, 0.0, jnp.float32(_BIG))

    vm_pes_f = jnp.asarray(vm_pes, jnp.float32)

    vm_iota = jnp.arange(vm_mips.shape[0])

    def ll_step(i, carry):
        load, out = carry
        v = jnp.argmin(load).astype(jnp.int32)
        add = jnp.where(task_valid[i],
                        task_len[i] / (vm_mips[v] * vm_pes_f[v]), 0.0)
        # one-hot add instead of load.at[v].add: under vmap the scatter
        # serializes on CPU and dominated mixed-binding encode time; adding
        # 0.0 to untouched lanes is bit-identical (loads are never -0.0)
        return (load + jnp.where(vm_iota == v, add, 0.0),
                out.at[i].set(v))

    _, ll = jax.lax.fori_loop(0, T, ll_step,
                              (load0, jnp.zeros(T, jnp.int32)))

    # LOCALITY: the same greedy f32 scan, argmin restricted per task to its
    # candidate mask.  Masking with _BIG reproduces load0's invalid-VM fill,
    # so an all-true row replays LEAST_LOADED's argmin sequence bit for bit
    # (the degenerate-parity property: replication == n_vms, reduces, or a
    # disabled store).  A separate fori_loop, not a branch inside ll_step:
    # under a *static* binding_policy (the bucketed sweep path) XLA DCEs
    # whichever scan the bucket cannot take.
    if locality_cand is None:
        loc = ll
    else:
        cand = jnp.asarray(locality_cand, bool)

        def loc_step(i, carry):
            load, out = carry
            v = jnp.argmin(jnp.where(cand[i], load, jnp.float32(_BIG))
                           ).astype(jnp.int32)
            add = jnp.where(task_valid[i],
                            task_len[i] / (vm_mips[v] * vm_pes_f[v]), 0.0)
            return (load + jnp.where(vm_iota == v, add, 0.0),
                    out.at[i].set(v))

        _, loc = jax.lax.fori_loop(0, T, loc_step,
                                   (load0, jnp.zeros(T, jnp.int32)))

    vm = jnp.select([bp == BindingPolicy.ROUND_ROBIN,
                     bp == BindingPolicy.LEAST_LOADED,
                     bp == BindingPolicy.PACKED], [rr, ll, packed], loc)
    return jnp.where(task_valid, vm, 0).astype(jnp.int32)


def from_scenario(sc: Scenario, *, pad_tasks: int | None = None,
                  pad_jobs: int | None = None,
                  pad_vms: int | None = None) -> ScenarioArrays:
    """Encode one :class:`Scenario` into padded arrays (numpy, host-side)."""
    T = pad_tasks or sc.total_tasks()
    J = pad_jobs or len(sc.jobs)
    V = pad_vms or len(sc.vms)
    if T < sc.total_tasks() or J < len(sc.jobs) or V < len(sc.vms):
        raise ValueError(
            f"from_scenario: padding too small — need pad_tasks>="
            f"{sc.total_tasks()} (got {T}), pad_jobs>={len(sc.jobs)} "
            f"(got {J}), pad_vms>={len(sc.vms)} (got {V})")

    f32 = np.float32
    t_job = np.zeros(T, np.int32)
    t_red = np.zeros(T, bool)
    t_val = np.zeros(T, bool)
    t_prio = np.zeros(T, f32)
    # Binding-load base lengths via the one shared f32 op sequence
    # (config.base_task_lengths_f32) so every layer resolves LEAST_LOADED
    # argmin ties identically.
    t_len = np.zeros(T, f32)
    t_dl = np.full(T, _BIG, f32)
    k = 0
    for ji, job in enumerate(sc.jobs):
        map_l, red_l = base_task_lengths_f32(
            f32(job.length_mi), f32(job.n_maps), f32(job.n_reduces),
            f32(job.reduce_factor))
        for phase, n in ((False, job.n_maps), (True, job.n_reduces)):
            for _ in range(n):
                t_job[k], t_red[k], t_val[k] = ji, phase, True
                t_len[k] = red_l if phase else map_l
                t_prio[k] = job.priority
                t_dl[k] = f32(min(job.deadline, _BIG))
                k += 1

    vm_mips = _padf([v.mips for v in sc.vms], V, fill=1.0)
    vm_pes = _padf([v.pes for v in sc.vms], V, fill=1.0)
    vm_valid = np.arange(V) < len(sc.vms)

    # Storage model (DESIGN.md §7): realized block placement, host-side.
    # Disabled -> all-(-1)/0 arrays, and every policy binds exactly as
    # before (the candidate mask degenerates to vm_valid).
    block_vm = np.full((T, V), -1, np.int32)
    block_mb = np.zeros(T, f32)
    bvm, bmb = storage.scenario_placement(sc, V)
    block_vm[:len(bvm)] = bvm
    block_mb[:len(bmb)] = bmb

    # Closed-loop control (DESIGN.md §10): realized failure/restore
    # streams + reserve flags via the one shared helper the oracle uses.
    vm_fail, vm_restore, vm_auto = scenario_control(sc, V)

    if sc.binding_policy in (BindingPolicy.LEAST_LOADED,
                             BindingPolicy.LOCALITY):
        # f32-sensitive: go through the one shared jnp implementation
        cand = (storage.locality_candidates(np, block_vm, vm_valid)
                if sc.binding_policy == BindingPolicy.LOCALITY else None)
        t_vm = np.asarray(bind_tasks(int(sc.binding_policy), t_val, t_len,
                                     vm_mips, vm_pes, vm_valid,
                                     locality_cand=cand), np.int32)
    else:
        # integer-exact fast paths — skip a JAX dispatch (+ per-padding
        # compile) per encoded scenario on the host path; equality with
        # bind_tasks is pinned by the encode_cell round-trip test
        counter = np.cumsum(t_val) - t_val      # submission-order index
        if sc.binding_policy == BindingPolicy.PACKED:
            slots = np.repeat(np.arange(len(sc.vms)),
                              [int(v.pes) for v in sc.vms])
            t_vm = slots[counter % len(slots)]
        else:                                   # ROUND_ROBIN
            t_vm = counter % len(sc.vms)
        t_vm = np.where(t_val, t_vm, 0).astype(np.int32)
    return ScenarioArrays(
        task_job=t_job, task_is_reduce=t_red, task_vm=t_vm, task_valid=t_val,
        task_mult=np.ones(T, f32),
        job_length=_padf([j.length_mi for j in sc.jobs], J),
        job_data=_padf([j.data_mb for j in sc.jobs], J),
        job_n_maps=_padi([j.n_maps for j in sc.jobs], J),
        job_n_reduces=_padi([j.n_reduces for j in sc.jobs], J),
        job_submit=_padf([j.submit_time for j in sc.jobs], J),
        job_reduce_factor=_padf([j.reduce_factor for j in sc.jobs], J),
        job_valid=np.arange(J) < len(sc.jobs),
        vm_mips=vm_mips,
        vm_pes=vm_pes,
        vm_cost=_padf([v.cost_per_sec for v in sc.vms], V),
        vm_valid=vm_valid,
        net_enabled=f32(1.0 if sc.network.enabled else 0.0),
        net_bw=f32(sc.network.bw_mbps),
        kappa_in=f32(sc.network.kappa_in),
        kappa_shuffle=f32(sc.network.kappa_shuffle),
        net_cost_per_unit=f32(sc.network.cost_per_unit),
        sched_policy=np.int32(sc.sched_policy),
        binding_policy=np.int32(sc.binding_policy),
        block_vm=block_vm,
        block_size=block_mb,
        storage_enabled=f32(1.0 if sc.storage.enabled else 0.0),
        vm_start=_padf([v.lease_start for v in sc.vms], V),
        vm_stop=_padf([elasticity.encode_lease_stop(v.lease_stop)
                       for v in sc.vms], V, fill=_BIG),
        spinup_delay=f32(sc.elasticity.spinup_delay),
        bill_gran=f32(sc.elasticity.billing_granularity),
        task_prio=t_prio,
        vm_fail=vm_fail,
        vm_restore=vm_restore,
        vm_auto=vm_auto,
        control_policy=np.int32(sc.control.policy),
        ctl_queue=f32(sc.control.queue_threshold),
        ctl_busy=f32(sc.control.busy_threshold),
        redispatch_delay=f32(sc.control.redispatch_delay),
        task_deadline=t_dl,
        deadline_policy=np.int32(sc.control.deadline_policy),
        deadline_slack=f32(sc.control.deadline_slack),
        preempt=np.int32(bool(sc.control.preempt)),
        preempt_resume=np.int32(bool(sc.control.preempt_resume)),
    )


def _padf(xs, n, fill=0.0):
    out = np.full(n, fill, np.float32)
    out[:len(xs)] = xs
    return out


def _padi(xs, n):
    out = np.ones(n, np.int32)
    out[:len(xs)] = xs
    return out


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class _Carry(NamedTuple):
    """Per-scenario event-loop state advanced one epoch at a time.

    The trailing control leaves are ``None`` (empty pytree — zero cost)
    whenever the static ``control`` flag is off; the open-loop carry is
    unchanged byte for byte.
    """
    time: jax.Array
    rem: jax.Array        # f32[T] remaining MI
    running: jax.Array    # bool[T]
    start: jax.Array      # f32[T]
    finish: jax.Array     # f32[T]
    ready: jax.Array      # f32[T]
    maps_left: jax.Array  # i32[J]
    epoch: jax.Array      # i32 — realized event epochs for *this* lane
    hit: jax.Array | None = None       # bool[T] killed at least once
    vm_open: jax.Array | None = None   # f32[V] realized lease open
    vm_close: jax.Array | None = None  # f32[V] realized lease close
    n_scale: jax.Array | None = None   # i32 autoscale events so far
    shed: jax.Array | None = None      # bool[T] deadline-shed so far
    n_evict: jax.Array | None = None   # i32[T] preemptions per task
    work_lost: jax.Array | None = None  # f32 discarded progress (MI)
    # trace recorder leaves (DESIGN.md §12): ``None`` unless the static
    # ``trace`` flag is on — an observe-only layer, never read by any
    # dynamics op, so traced schedules stay bitwise-identical
    ts: jax.Array | None = None        # f32[C, 8] per-epoch time series
    ev_t: jax.Array | None = None      # f32[E] event timestamps
    ev_kind: jax.Array | None = None   # i32[E] event kinds (-1 empty)
    ev_task: jax.Array | None = None   # i32[E] task id (-1 scale events)
    ev_vm: jax.Array | None = None     # i32[E] VM id
    ev_n: jax.Array | None = None      # i32 events attempted (cursor)


class _EpochInv(NamedTuple):
    """Loop-invariant derived arrays shared by every epoch of one lane.

    Control leaves (``None`` unless the static ``control`` flag is on):
    the failover binding slot and its derived gathers, plus the per-task
    failure/restore instants of both binding slots.
    """
    shuffle: jax.Array     # f32[J]
    task_pes: jax.Array    # f32[T]
    vm_onehot: jax.Array   # f32[T, V]
    job_onehot: jax.Array  # f32[T, J]
    same_vm: jax.Array     # bool[T, T]
    idx_earlier: jax.Array  # bool[T, T]
    is_space: jax.Array    # bool scalar
    avail_t: jax.Array     # f32[T] bound VM's admission-open time
    #                        (lease start + spinup; 0 for a static fleet)
    close_t: jax.Array     # f32[T] bound VM's lease stop (_BIG = never)
    task_len: jax.Array | None = None    # f32[T] full length (kill reset)
    task_vm2: jax.Array | None = None    # i32[T] failover binding
    vm_onehot2: jax.Array | None = None  # f32[T, V]
    task_pes2: jax.Array | None = None   # f32[T]
    refetch: jax.Array | None = None     # f32[T] re-replication fetch to
    #                                      the failover VM (0 if it holds
    #                                      a replica / no block)
    fail1: jax.Array | None = None       # f32[T] vm_fail[task_vm]
    rest1: jax.Array | None = None       # f32[T] vm_restore[task_vm]
    fail2: jax.Array | None = None       # f32[T] vm_fail[task_vm2]
    rest2: jax.Array | None = None       # f32[T] vm_restore[task_vm2]


def _epoch_setup(sc: ScenarioArrays, *, control: bool = False,
                 trace: tuple[int, int] | None = None
                 ) -> tuple[_EpochInv, _Carry]:
    """Derived quantities + initial carry for one encoded scenario.

    ``trace`` is the static ``(timeseries_rows, event_rows)`` capacity
    pair (DESIGN.md §12) — ``None`` keeps the trace leaves empty pytrees.
    """
    T = sc.task_job.shape[0]
    J = sc.job_length.shape[0]
    V = sc.vm_mips.shape[0]

    # --- derived per-task/per-job quantities (traced: sweepable) ----------
    n_maps_f = sc.job_n_maps.astype(jnp.float32)
    stage_in = network.transfer_delay(sc.kappa_in, sc.job_data, n_maps_f,
                                      sc.net_bw, sc.net_enabled)
    shuffle = network.transfer_delay(sc.kappa_shuffle, sc.job_data, n_maps_f,
                                     sc.net_bw, sc.net_enabled)
    task_len = task_lengths(sc)

    # Maps ready at submit + stage-in (+ the storage remote-fetch delay
    # when the bound VM holds no replica of the task's input block —
    # exactly 0.0 for local tasks and storage-less scenarios, so the
    # pre-storage op sequence is reproduced bit for bit); reduces unknown
    # until maps complete.
    fetch = storage.remote_fetch_delay(sc.block_vm, sc.block_size,
                                       sc.task_vm, sc.kappa_in, sc.net_bw,
                                       sc.net_enabled, xp=jnp)
    ready0 = jnp.where(
        sc.task_valid & ~sc.task_is_reduce,
        (sc.job_submit + stage_in)[sc.task_job] + fetch, _BIG)

    is_map = sc.task_valid & ~sc.task_is_reduce
    maps_left0 = jax.ops.segment_sum(is_map.astype(jnp.int32), sc.task_job,
                                     num_segments=J)

    is_space = sc.sched_policy == SchedPolicy.SPACE_SHARED
    task_pes = sc.vm_pes[sc.task_vm]
    # One-hot encodings of the task->VM / task->job maps, hoisted out of the
    # loop: per-epoch reductions become small dense matmuls instead of
    # scatters (segment_sum), which XLA:CPU serializes — this halves the
    # sweep benchmark's time per call.  The sums are exact (0/1 operands),
    # so results are bit-identical to the scatter formulation.
    vm_onehot = (sc.task_vm[:, None] == jnp.arange(V)[None, :]
                 ).astype(jnp.float32)
    job_onehot = (sc.task_job[:, None] == jnp.arange(J)[None, :]
                  ).astype(jnp.float32)
    # Loop-invariant pieces of the space-shared admission priority.
    idx = jnp.arange(T)
    same_vm = sc.task_vm[:, None] == sc.task_vm[None, :]
    idx_earlier = idx[None, :] < idx[:, None]

    # Lease windows as per-task gathers (DESIGN.md §8): admission on VM v
    # opens at vm_start[v] + spinup and closes at vm_stop[v].  For the
    # static fleet (start 0, stop _BIG) every use below is a bitwise
    # identity: max(ready, 0) == ready for the non-negative ready times and
    # the close comparison is always true for live events.
    avail_t = (sc.vm_start + sc.spinup_delay)[sc.task_vm]
    close_t = sc.vm_stop[sc.task_vm]

    inv = _EpochInv(shuffle=shuffle, task_pes=task_pes, vm_onehot=vm_onehot,
                    job_onehot=job_onehot, same_vm=same_vm,
                    idx_earlier=idx_earlier, is_space=is_space,
                    avail_t=avail_t, close_t=close_t)
    c0 = _Carry(time=jnp.float32(0.0), rem=task_len,
                running=jnp.zeros(T, bool),
                start=jnp.full(T, _BIG, jnp.float32),
                finish=jnp.full(T, _BIG, jnp.float32),
                ready=ready0, maps_left=maps_left0,
                epoch=jnp.int32(0))
    if control:
        # Failover binding slot (DESIGN.md §10): a killed task's second —
        # and final — VM, precomputed so the epoch body stays a fixed
        # dataflow: the only dynamic binding state is the bool ``hit``
        # switch between the two slots.  Re-replication rides the PR-4
        # block store: moving off the replica set pays the shared
        # remote-fetch delay toward the new VM.
        task_vm2 = failover_targets(sc.task_vm, sc.vm_valid, sc.vm_auto,
                                    sc.block_vm, xp=jnp)
        refetch = storage.remote_fetch_delay(sc.block_vm, sc.block_size,
                                             task_vm2, sc.kappa_in,
                                             sc.net_bw, sc.net_enabled,
                                             xp=jnp)
        inv = inv._replace(
            task_len=task_len,
            task_vm2=task_vm2,
            vm_onehot2=(task_vm2[:, None] == jnp.arange(V)[None, :]
                        ).astype(jnp.float32),
            task_pes2=sc.vm_pes[task_vm2],
            refetch=refetch,
            fail1=sc.vm_fail[sc.task_vm], rest1=sc.vm_restore[sc.task_vm],
            fail2=sc.vm_fail[task_vm2], rest2=sc.vm_restore[task_vm2])
        # Reserve VMs have no lease until the control rule opens one; the
        # non-reserve fleet's realized open is just its encoded start.
        c0 = c0._replace(
            hit=jnp.zeros(T, bool),
            vm_open=jnp.where(sc.vm_auto, jnp.float32(_BIG), sc.vm_start),
            vm_close=jnp.asarray(sc.vm_stop, jnp.float32),
            n_scale=jnp.int32(0),
            shed=jnp.zeros(T, bool),
            n_evict=jnp.zeros(T, jnp.int32),
            work_lost=jnp.float32(0.0))
    if trace is not None:
        ts_cap, ev_cap = trace
        c0 = c0._replace(
            ts=jnp.zeros((ts_cap, 8), jnp.float32),
            ev_t=jnp.zeros(ev_cap, jnp.float32),
            ev_kind=jnp.full(ev_cap, -1, jnp.int32),
            ev_task=jnp.full(ev_cap, -1, jnp.int32),
            ev_vm=jnp.full(ev_cap, -1, jnp.int32),
            ev_n=jnp.int32(0))
    return inv, c0


def _has_unfinished(sc: ScenarioArrays, c: _Carry) -> jax.Array:
    unfin = sc.task_valid & (c.finish >= _BIG / 2)
    if c.shed is not None:
        # a shed task never finishes by design — it must not keep its
        # lane alive (shedding *terminates* otherwise-unbounded backlogs)
        unfin &= ~c.shed
    return jnp.any(unfin)


def _lane_bound(sc: ScenarioArrays) -> jax.Array:
    """Per-lane epoch bound (i32, data-dependent under control).

    Open-loop, every live epoch fires a start or a completion: ``2T + 2``.
    Each robustness mechanism widens the bound *additively*, and each
    term is paid only by lanes whose encoded data can trigger it — so
    degenerate lanes keep the exact open-loop bound (and stranded lanes'
    realized ``n_epochs`` stay bit-identical):

    * failures — a task restarts at most twice (its bound VM and its
      failover VM each fail at most once): +``2T`` starts + ``V``
      failure instants;
    * deadline shedding — marking epochs piggyback on live events, but
      ``+T + 1`` margins the tail where the last events only shed;
    * preemption — at most two evictions per task: +``2T`` restarts
      (eviction epochs coincide with the challenger's start)."""
    T = sc.task_job.shape[0]
    V = sc.vm_mips.shape[0]
    any_fail = jnp.any(sc.vm_valid & (sc.vm_fail < _BIG / 2))
    any_shed = (sc.deadline_policy == jnp.int32(DeadlinePolicy.SHED)) \
        & jnp.any(sc.task_valid & (sc.task_deadline < _BIG / 2))
    pre_on = sc.preempt != 0
    return (jnp.int32(2 * T + 2)
            + jnp.where(any_fail, jnp.int32(2 * T + V), jnp.int32(0))
            + jnp.where(any_shed, jnp.int32(T + 1), jnp.int32(0))
            + jnp.where(pre_on, jnp.int32(2 * T), jnp.int32(0)))


def _lane_active(sc: ScenarioArrays, c: _Carry, *,
                 control: bool = False) -> jax.Array:
    """A lane still takes epochs: unfinished work below its epoch bound.
    Open-loop drivers bound epochs globally (the per-lane bound is the
    static ``2T + 2``), so the extra term is control-only."""
    act = _has_unfinished(sc, c)
    if control:
        act &= c.epoch < _lane_bound(sc)
    return act


def _epoch_step(sc: ScenarioArrays, inv: _EpochInv, c: _Carry, *,
                control: bool = False, trace: bool = False) -> _Carry:
    """Advance one event epoch.  Idempotent for finished lanes (every
    update is gated on ``live``/``running``), so a vmapped batch may keep
    stepping a lane past its last event without changing its state — the
    property the batched early-exit driver relies on.  Leaves ``epoch``
    untouched; the drivers count realized epochs.

    ``control=True`` (a static flag — open-loop lowerings carry zero
    control code) threads the closed loop through the same dataflow:

    * the *control hook* runs first, at the epoch's opening clock
      ``c.time`` (i.e. observing the state all previous events left
      behind): AUTOSCALE compares the observed queue depth and open-fleet
      busy fraction against the encoded thresholds, opens one reserve VM
      per epoch while both exceed, and closes idle opened reserves;
    * every per-task gather switches between the two binding slots on the
      ``hit`` mask (one-hot matmuls stay exact 0/1 sums);
    * failure instants of valid VMs join the next-event min; at a firing
      instant every unfinished task on the failing VM is killed and
      re-dispatched (first hit: to the failover slot + re-replication
      fetch; second: restart in place after restore), and eligibility is
      gated around each VM's ``[fail, restore)`` down window.

    With degenerate control data (no failures, no reserves, NONE policy)
    every control op is a ``where`` over an all-false mask or a gate that
    never matches — the open-loop schedule is reproduced bit for bit
    (pinned in tests/test_control.py)."""
    # --- binding-slot switch + control hook (clock = c.time) --------------
    if control:
        cur_oh = jnp.where(c.hit[:, None], inv.vm_onehot2, inv.vm_onehot)
        task_pes = jnp.where(c.hit, inv.task_pes2, inv.task_pes)
        f_t = jnp.where(c.hit, inv.fail2, inv.fail1)
        r_t = jnp.where(c.hit, inv.rest2, inv.rest1)
        cur_vm = jnp.where(c.hit, inv.task_vm2, sc.task_vm)
        same_vm = cur_vm[:, None] == cur_vm[None, :]

        V = sc.vm_mips.shape[0]
        pol_on = sc.control_policy == jnp.int32(ControlPolicy.AUTOSCALE)
        # shed tasks are out of the system: refused backlog neither holds
        # a reserve open nor counts toward scaling pressure (all-true
        # ~shed under NONE — bitwise identity with the §10 hook)
        unfinished = sc.task_valid & (c.finish >= _BIG / 2) & ~c.shed
        # queue depth over *raw* ready times: tasks bound to unopened
        # reserves must count toward the backlog or the rule that would
        # open their VM could never trigger
        qdepth = jnp.sum((unfinished & (c.start >= _BIG / 2)
                          & (c.ready <= c.time)).astype(jnp.float32))
        busy_v = (c.running.astype(jnp.float32) @ cur_oh) > 0.5
        open_v = sc.vm_valid & (c.vm_open + sc.spinup_delay <= c.time) \
            & (c.time < c.vm_close)
        n_open = jnp.sum(open_v.astype(jnp.float32))
        busy_frac = (jnp.sum((open_v & busy_v).astype(jnp.float32))
                     / jnp.maximum(n_open, 1.0))
        trigger = pol_on & (qdepth > sc.ctl_queue) \
            & (busy_frac >= sc.ctl_busy)
        reserve = sc.vm_valid & sc.vm_auto
        unopened = reserve & (c.vm_open >= _BIG / 2)
        vidx = jnp.arange(V, dtype=jnp.int32)
        first = jnp.argmin(jnp.where(unopened, vidx, jnp.int32(V + 1)))
        open_mask = trigger & unopened & (vidx == first)
        bound_unfin = unfinished.astype(jnp.float32) @ cur_oh
        close_mask = pol_on & reserve & (c.vm_open < _BIG / 2) \
            & (c.time < c.vm_close) & (bound_unfin < 0.5)
        vm_open = jnp.where(open_mask, c.time, c.vm_open)
        vm_close = jnp.where(close_mask, c.time, c.vm_close)
        n_scale = c.n_scale + jnp.sum(open_mask.astype(jnp.int32)) \
            + jnp.sum(close_mask.astype(jnp.int32))
        # lease windows re-derived from carry: exactly the setup gathers
        # when no reserve ever opens (one-hot sums are exact)
        avail_t = cur_oh @ (vm_open + sc.spinup_delay)
        close_t = cur_oh @ vm_close
        # graceful-degradation policy masks (DESIGN.md §11) — i32/bool
        # *data*, so one lowering serves batches mixing NONE/SHED/BOOST
        # lanes; every op they gate is a bitwise no-op when all-false
        mips_t = cur_oh @ sc.vm_mips
        dl_shed = sc.deadline_policy == jnp.int32(DeadlinePolicy.SHED)
        dl_boost = sc.deadline_policy == jnp.int32(DeadlinePolicy.BOOST)
        pre_on = (sc.preempt != 0) & inv.is_space
        res_on = sc.preempt_resume != 0
        prio = sc.task_prio
    else:
        cur_oh, task_pes, same_vm = inv.vm_onehot, inv.task_pes, inv.same_vm
        avail_t, close_t = inv.avail_t, inv.close_t

    # single rates evaluation per epoch (space-shared keeps n <= pes, so
    # the min() clamp makes this formula serve both policies)
    def vm_counts(running):
        return running.astype(jnp.float32) @ cur_oh

    n_on_vm = vm_counts(c.running)
    share = sc.vm_mips * jnp.minimum(1.0, sc.vm_pes
                                     / jnp.maximum(n_on_vm, 1.0))
    r = jnp.where(c.running, cur_oh @ share, 0.0)

    eta = jnp.where(c.running, c.time + c.rem / jnp.maximum(r, 1e-30),
                    _BIG)
    not_started = sc.task_valid & ~c.running & (c.finish >= _BIG / 2) \
        & (c.start >= _BIG / 2)
    # Lease-aware eligibility (DESIGN.md §8): a pending task becomes
    # admissible at max(ready, lease avail) — so lease-start edges join
    # the next-event min through the arrival candidates below — and only
    # while its event time lands strictly before the VM's lease stop.  A
    # candidate whose time falls at/past the close never defines an event
    # again (stranded); the static fleet reproduces the old ops bitwise.
    elig = jnp.maximum(c.ready, avail_t)
    if control:
        # failure-window gating: any admission instant landing inside the
        # current VM's [fail, restore) down window slides to the restore
        # edge — which is how restore instants join the event min (no
        # separate restore event stream is needed)
        def gate(x):
            return jnp.where((x >= f_t) & (x < r_t), r_t, x)

        elig = gate(elig)
        cand_t = gate(jnp.maximum(elig, c.time))
        # SHED admission control at the arrival-candidate instant
        # (DESIGN.md §11): a pending task whose earliest possible finish
        # already exceeds its deadline stops defining arrival events.
        # The close_t gate keeps stranded tasks out — the oracle never
        # re-examines an arrival it could not schedule.
        evaluable = not_started & (elig < _BIG / 2)
        efin_c = earliest_finish(cand_t, c.rem, mips_t, xp=jnp)
        shed_c = c.shed | (dl_shed & evaluable & (cand_t < close_t)
                           & (efin_c > sc.task_deadline))
    else:
        cand_t = jnp.maximum(elig, c.time)
    # Space-shared: a pending task only defines an arrival event while
    # its VM has a free PE slot; otherwise a completion epoch admits it.
    has_slot = (task_pes - cur_oh @ n_on_vm) > 0.5
    if control:
        # preemption arrival gate (DESIGN.md §11): a pending task whose
        # raw priority strictly beats a running, still-evictable task on
        # its VM defines an arrival event even with no free slot — the
        # eviction below frees one at that instant.  Raw priority only
        # (not the BOOST urgency tier): the gate and the eviction rule
        # must agree or a same-instant arrival event could repeat with
        # no state change.
        evictable = c.running & (c.n_evict < jnp.int32(2))
        prey = same_vm & evictable[None, :] \
            & (prio[:, None] > prio[None, :])
        can_pre = pre_on & jnp.any(prey, axis=1)
        arr = jnp.where(not_started & ~shed_c
                        & (~inv.is_space | has_slot | can_pre)
                        & (cand_t < close_t), cand_t, _BIG)
    else:
        arr = jnp.where(not_started & (~inv.is_space | has_slot)
                        & (cand_t < close_t), cand_t, _BIG)
    t_next = jnp.minimum(jnp.min(eta), jnp.min(arr))
    if control:
        # pending failure instants of valid VMs are calendar events too
        fail_ev = jnp.where(sc.vm_valid & (sc.vm_fail > c.time),
                            sc.vm_fail, _BIG)
        t_next = jnp.minimum(t_next, jnp.min(fail_ev))
    live = t_next < _BIG / 2
    tie = _TIME_EPS * jnp.maximum(t_next, 1.0)

    # advance fluid state
    rem = jnp.where(c.running, c.rem - (t_next - c.time) * r, c.rem)

    # completions (all tied events fire in this one epoch)
    done_now = live & c.running & (eta <= t_next + tie)
    finish = jnp.where(done_now, t_next, c.finish)
    running = c.running & ~done_now
    rem = jnp.where(done_now, 0.0, rem)

    # job map-phase completion -> release reduces after shuffle delay
    maps_done_now = ((done_now & ~sc.task_is_reduce)
                     .astype(jnp.float32) @ inv.job_onehot).astype(jnp.int32)
    maps_left = c.maps_left - maps_done_now
    phase_done = (maps_left == 0) & (c.maps_left > 0)
    red_ready = jnp.where(phase_done, t_next + inv.shuffle, _BIG)
    ready = jnp.where(
        sc.task_is_reduce & phase_done[sc.task_job],
        red_ready[sc.task_job], c.ready)

    # failure kills — after completions (a task finishing exactly at the
    # failure instant completes: the oracle's completions-first tie
    # order), before admissions
    start_base = c.start
    if control:
        fired = live & (f_t > c.time) & (f_t <= t_next)
        # shed tasks are out of the system — a failure must not
        # re-dispatch (or failover-rebind) work that was already refused
        affected = sc.task_valid & fired & (finish >= _BIG / 2) & ~shed_c
        first_hit = affected & ~c.hit
        lost_fail = jnp.where(affected, inv.task_len - rem, 0.0)
        rem = jnp.where(affected, inv.task_len, rem)
        running = running & ~affected
        start_base = jnp.where(affected, jnp.float32(_BIG), start_base)
        # re-dispatch: detection/re-queue latency from the failure
        # instant; the first hit moves to the failover slot and pays the
        # re-replication fetch, a second hit restarts in place (its
        # eligibility then slides to the failover VM's restore edge)
        ready = jnp.where(affected,
                          jnp.maximum(ready, f_t + sc.redispatch_delay),
                          ready)
        ready = jnp.where(first_hit, ready + inv.refetch, ready)
        hit = c.hit | first_hit

    # arrivals: time-shared starts every admissible task immediately;
    # space-shared admits the (priority desc, eligible time, index)-first
    # waiting tasks into the PE slots left free after this epoch's
    # completions.  The admission key is the *eligible* time (ready
    # joined with the lease-open edge) and the whole rank is gated on the
    # lease still being open at t_next; all-zero priorities and a static
    # fleet reduce every term to the classic (ready, index) rank bitwise.
    eligible = live & not_started & (elig <= t_next + tie) \
        & (t_next < close_t)
    key = elig
    prio = sc.task_prio
    if control:
        # never admit onto a VM that is down at (or fails exactly at)
        # this epoch's instant — the killed set was computed above and a
        # same-instant admission would dodge it
        eligible &= ~((t_next >= f_t) & (t_next < r_t))
        # SHED at the admission instant (the oracle's pop-time check):
        # queue wait grows pressure, so a task admissible when it
        # arrived may be unmeetable by the time a PE slot frees
        efin_t = earliest_finish(t_next, c.rem, mips_t, xp=jnp)
        shed_t = shed_c | (dl_shed & evaluable & (t_next < close_t)
                           & (efin_t > sc.task_deadline))
        eligible &= ~shed_t
        # Priority preemption (DESIGN.md §11): on each full space-shared
        # VM, the single weakest still-evictable running task (lowest
        # raw priority, latest index) loses its PE when an eligible
        # pending task strictly outranks it; further victims fall in the
        # repeated same-instant epochs the arrival gate above keeps
        # scheduling.  The kill reuses the §10 failure op sequence:
        # progress reset (kept under preempt_resume), re-dispatch
        # latency on readiness, first hit moves to the failover slot and
        # pays the re-replication fetch.
        vic_cand = pre_on & running & (c.n_evict < jnp.int32(2))
        full = (task_pes - cur_oh @ (n_on_vm - vm_counts(done_now))) \
            <= 0.5
        beats = same_vm & vic_cand[:, None] & eligible[None, :] \
            & (prio[None, :] > prio[:, None])
        cand_e = vic_cand & full & jnp.any(beats, axis=1)
        weaker = same_vm & cand_e[None, :] & (
            (prio[None, :] < prio[:, None])
            | ((prio[None, :] == prio[:, None]) & inv.idx_earlier.T))
        evicted = cand_e & ~jnp.any(weaker, axis=1)
        lost_evict = jnp.where(evicted & ~res_on,
                               inv.task_len - rem, 0.0)
        e_first = evicted & ~hit
        rem = jnp.where(evicted & ~res_on, inv.task_len, rem)
        running = running & ~evicted
        start_base = jnp.where(evicted, jnp.float32(_BIG), start_base)
        ready = jnp.where(evicted,
                          jnp.maximum(ready,
                                      t_next + sc.redispatch_delay),
                          ready)
        ready = jnp.where(e_first, ready + inv.refetch, ready)
        hit = hit | e_first
        n_evict = c.n_evict + evicted.astype(jnp.int32)
        work_lost = c.work_lost + jnp.sum(lost_fail) \
            + jnp.sum(lost_evict)
        free_after = task_pes - cur_oh @ (n_on_vm - vm_counts(done_now)
                                          - vm_counts(evicted))
        # BOOST urgency tier (DESIGN.md §11): a pending task whose
        # earliest finish is within deadline_slack of its deadline
        # outranks every non-urgent task; ties inside a tier keep the §8
        # (priority, eligible, index) key.  All-false urgency (NONE/SHED
        # lanes, _BIG deadlines) collapses to the §8 rank bitwise.
        urg = (dl_boost & evaluable
               & (efin_t + sc.deadline_slack >= sc.task_deadline)
               ).astype(jnp.float32)
        higher_prio = same_vm & (
            (urg[None, :] > urg[:, None])
            | ((urg[None, :] == urg[:, None])
               & ((prio[None, :] > prio[:, None])
                  | ((prio[None, :] == prio[:, None])
                     & ((key[None, :] < key[:, None])
                        | ((key[None, :] == key[:, None])
                           & inv.idx_earlier))))))
    else:
        free_after = task_pes - cur_oh @ (n_on_vm - vm_counts(done_now))
        higher_prio = same_vm & (
            (prio[None, :] > prio[:, None])
            | ((prio[None, :] == prio[:, None])
               & ((key[None, :] < key[:, None])
                  | ((key[None, :] == key[:, None]) & inv.idx_earlier))))
    rank = jnp.sum((higher_prio & eligible[None, :])
                   .astype(jnp.float32), axis=1)
    start_now = eligible & (~inv.is_space | (rank < free_after))
    start = jnp.where(start_now, t_next, start_base)
    running = running | start_now

    time = jnp.where(live, t_next, c.time)
    extra = {}
    if control:
        # persist the shed set; reduces of a job with a shed map can
        # never become ready (the map phase cannot complete) — marking
        # these orphans ends their lane instead of spinning it to the
        # epoch bound
        map_shed = (shed_t & ~sc.task_is_reduce).astype(jnp.float32)
        job_dead = (map_shed @ inv.job_onehot) > 0.5
        shed = shed_t | (sc.task_valid & sc.task_is_reduce
                         & job_dead[sc.task_job]
                         & (finish >= _BIG / 2) & ~running)
        extra = dict(hit=hit, vm_open=vm_open, vm_close=vm_close,
                     n_scale=n_scale, shed=shed, n_evict=n_evict,
                     work_lost=work_lost)
    if trace:
        # --- trace recorder (DESIGN.md §12): observe, never act -----------
        # Gated on the same per-lane activity predicate the drivers count
        # epochs with, so traces from the per-lane while_loop, the batched
        # driver (which keeps stepping inactive lanes) and the compacted
        # driver are bitwise-identical.
        act = _lane_active(sc, c, control=control)
        actf = act.astype(jnp.float32)
        T = sc.task_job.shape[0]
        if control:
            new_shed = shed & ~c.shed
            n_fail = jnp.sum(affected.astype(jnp.float32))
            n_shed = jnp.sum(new_shed.astype(jnp.float32))
            n_ev = jnp.sum(evicted.astype(jnp.float32))
        else:
            # open-loop lanes compute the control hook's observables here,
            # with the identical op sequence over the static lease windows
            unfin_t = sc.task_valid & (c.finish >= _BIG / 2)
            qdepth = jnp.sum((unfin_t & (c.start >= _BIG / 2)
                              & (c.ready <= c.time)).astype(jnp.float32))
            busy_v = (c.running.astype(jnp.float32) @ cur_oh) > 0.5
            open_v = sc.vm_valid \
                & (sc.vm_start + sc.spinup_delay <= c.time) \
                & (c.time < sc.vm_stop)
            n_open = jnp.sum(open_v.astype(jnp.float32))
            busy_frac = (jnp.sum((open_v & busy_v).astype(jnp.float32))
                         / jnp.maximum(n_open, 1.0))
            n_fail = n_shed = n_ev = jnp.float32(0.0)
        # One time-series row per realized epoch, set by a one-hot add:
        # the row index is this lane's own epoch counter, which advances
        # exactly when ``act`` holds, so each row is written once (an
        # index past capacity would write nothing — the capacity equals
        # the lane's epoch bound, so it never overflows).
        row = (jnp.arange(c.ts.shape[0]) == c.epoch
               ).astype(jnp.float32) * actf
        vals = jnp.stack([time, qdepth, busy_frac, n_open, actf,
                          n_fail, n_shed, n_ev])
        ts = c.ts + row[:, None] * vals[None, :]
        # Bounded event log: every event firing this epoch, in canonical
        # in-epoch order (scale decisions at the opening clock, then
        # completions / kills / evictions / starts / sheds), written by
        # one-hot scatter at cursor positions.  Events past capacity fall
        # off the one-hot and are counted by the cursor (dropped_events).
        tvec = jnp.full(T, t_next, jnp.float32)
        tidx = jnp.arange(T, dtype=jnp.int32)

        def kvec(kind, n):
            return jnp.full(n, kind, jnp.int32)

        if control:
            V = sc.vm_mips.shape[0]
            vvec = jnp.arange(V, dtype=jnp.int32)
            novm = jnp.full(V, -1, jnp.int32)
            scale_t = jnp.full(V, c.time, jnp.float32)
            cur_vm_i = cur_vm.astype(jnp.int32)
            m = jnp.concatenate([open_mask, close_mask, done_now, affected,
                                 evicted, start_now, new_shed])
            # kills stamp the failure instant; sheds the epoch's clock
            # (their detection is epoch-quantized — see DESIGN.md §12.3)
            e_t = jnp.concatenate([scale_t, scale_t, tvec, f_t, tvec, tvec,
                                   jnp.full(T, time, jnp.float32)])
            e_kind = jnp.concatenate([kvec(EV_SCALE_OPEN, V),
                                      kvec(EV_SCALE_CLOSE, V),
                                      kvec(EV_FINISH, T), kvec(EV_KILL, T),
                                      kvec(EV_PREEMPT, T),
                                      kvec(EV_START, T), kvec(EV_SHED, T)])
            e_task = jnp.concatenate([novm, novm, tidx, tidx, tidx, tidx,
                                      tidx])
            e_vm = jnp.concatenate([vvec, vvec, cur_vm_i, cur_vm_i,
                                    cur_vm_i, cur_vm_i, cur_vm_i])
        else:
            m = jnp.concatenate([done_now, start_now])
            e_t = jnp.concatenate([tvec, tvec])
            e_kind = jnp.concatenate([kvec(EV_FINISH, T), kvec(EV_START, T)])
            e_task = jnp.concatenate([tidx, tidx])
            e_vm = jnp.concatenate([sc.task_vm, sc.task_vm]
                                   ).astype(jnp.int32)
        m = m & act
        mf = m.astype(jnp.float32)
        E = c.ev_t.shape[0]
        pos = c.ev_n + (jnp.cumsum(mf) - mf).astype(jnp.int32)
        slot = (pos[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]) \
            & m[:, None]
        written = jnp.any(slot, axis=0)

        def pick_f(v):
            return jnp.sum(jnp.where(slot, v[:, None], 0.0), axis=0)

        def pick_i(v):
            return jnp.sum(jnp.where(slot, v[:, None], 0), axis=0)

        extra.update(
            ts=ts,
            ev_t=jnp.where(written, pick_f(e_t), c.ev_t),
            ev_kind=jnp.where(written, pick_i(e_kind), c.ev_kind),
            ev_task=jnp.where(written, pick_i(e_task), c.ev_task),
            ev_vm=jnp.where(written, pick_i(e_vm), c.ev_vm),
            ev_n=c.ev_n + jnp.sum(mf).astype(jnp.int32))
    return _Carry(time, rem, running, start, finish, ready,
                  maps_left, c.epoch, **extra)


def _sim_output(sc: ScenarioArrays, cf: _Carry) -> SimOutput:
    exec_time = jnp.where(sc.task_valid, cf.finish - cf.start, 0.0)
    # both lowerings report the failover binding control *would* use, so
    # the field is bitwise-comparable across open-loop and control runs
    task_vm2 = failover_targets(sc.task_vm, sc.vm_valid, sc.vm_auto,
                                sc.block_vm, xp=jnp)
    if cf.hit is None:
        # open-loop: the realized control outputs are the encoded scenario
        hit = jnp.zeros_like(sc.task_valid)
        vm_open = jnp.asarray(sc.vm_start, jnp.float32)
        vm_close = jnp.asarray(sc.vm_stop, jnp.float32)
        n_scale = jnp.int32(0)
        shed = jnp.zeros_like(sc.task_valid)
        n_evict = jnp.zeros(sc.task_valid.shape[0], jnp.int32)
        work_lost = jnp.float32(0.0)
    else:
        hit, vm_open, vm_close = cf.hit, cf.vm_open, cf.vm_close
        n_scale = cf.n_scale
        shed, n_evict, work_lost = cf.shed, cf.n_evict, cf.work_lost
    # shed tasks never finish (finish == _BIG): the makespan is over the
    # work the system kept — all-false ~shed is the pre-§11 op sequence
    return SimOutput(start=cf.start, finish=cf.finish, ready=cf.ready,
                     exec_time=exec_time, n_epochs=cf.epoch,
                     finish_time=jnp.max(jnp.where(sc.task_valid & ~shed,
                                                   cf.finish, 0.0)),
                     hit=hit, task_vm2=task_vm2, vm_open=vm_open,
                     vm_close=vm_close, n_scale=n_scale,
                     shed=shed, n_evict=n_evict, work_lost=work_lost)


def _control_active(sc: ScenarioArrays) -> bool:
    """Host-side detection of control inputs in an encoded scenario (or
    stacked batch).  Under a trace the data is unreadable — report active
    (the control path with degenerate data is a bitwise identity, just
    not free); batch drivers that know better pass ``control=`` instead.
    """
    try:
        vf = np.asarray(sc.vm_fail)
        vv = np.asarray(sc.vm_valid)
        va = np.asarray(sc.vm_auto)
        cp = np.asarray(sc.control_policy)
        dp = np.asarray(sc.deadline_policy)
        pe = np.asarray(sc.preempt)
    except Exception:                     # traced values
        return True
    return bool((vv & (vf < _BIG / 2)).any() or (vv & va).any()
                or (cp != 0).any() or (dp != 0).any() or (pe != 0).any())


def _trace_caps(T: int, V: int, control: bool, trace: bool,
                trace_events: int | None) -> tuple[int, int] | None:
    """Static trace capacities (DESIGN.md §12.2), or ``None`` when off."""
    if not trace:
        return None
    ev = (int(trace_events) if trace_events is not None
          else event_capacity(T, V, control))
    return (timeseries_capacity(T, V, control), ev)


def _trace_of(cf: _Carry) -> TraceBuffers:
    return TraceBuffers(ts=cf.ts, ev_t=cf.ev_t, ev_kind=cf.ev_kind,
                        ev_task=cf.ev_task, ev_vm=cf.ev_vm, ev_n=cf.ev_n)


def simulate_arrays(sc: ScenarioArrays, *, control: bool | None = None,
                    trace: bool = False,
                    trace_events: int | None = None):
    """Run one encoded scenario.  Pure function of arrays: jit/vmap-friendly.

    Both scheduling policies run branch-free inside the one while_loop body:

    * TIME_SHARED — every ready task runs; the fluid share
      ``mips * min(1, pes / n)`` throttles crowded VMs.
    * SPACE_SHARED — the admission gate keeps at most ``pes`` tasks running
      per VM (so the same share formula degenerates to full ``mips``), and
      pending tasks are admitted in (ready time, task index) priority order
      as slots free up.

    Every live epoch fires at least one start or completion (arrival events
    are only scheduled when a PE slot is free), so ``2T + 2`` epochs bound
    the loop (``_lane_bound`` widens this only for lanes that encode VM
    failures); rates are evaluated exactly once per epoch.  Batches should
    prefer :func:`simulate_batch_arrays`, which shares one epoch loop across
    all lanes and stops at the batch's realized epoch count.

    ``trace=True`` (DESIGN.md §12) returns ``(SimOutput, TraceBuffers)``
    — the schedule is bitwise-identical to the untraced run.
    """
    if control is None:
        control = _control_active(sc)
    tr = _trace_caps(sc.task_job.shape[0], sc.vm_mips.shape[0], control,
                     trace, trace_events)
    inv, c0 = _epoch_setup(sc, control=control, trace=tr)
    bound = _lane_bound(sc) if control \
        else jnp.int32(2 * sc.task_job.shape[0] + 2)

    def cond(c: _Carry):
        return _has_unfinished(sc, c) & (c.epoch < bound)

    def body(c: _Carry):
        return _epoch_step(sc, inv, c, control=control,
                           trace=tr is not None
                           )._replace(epoch=c.epoch + 1)

    cf = jax.lax.while_loop(cond, body, c0)
    out = _sim_output(sc, cf)
    if tr is not None:
        return out, _trace_of(cf)
    return out


def simulate_batch_arrays(
        batch: ScenarioArrays, *, control: bool | None = None,
        trace: bool = False, trace_events: int | None = None):
    """Run a stacked batch with one shared epoch loop (batch early exit).

    Instead of vmapping the per-lane ``while_loop`` (whose batching rule
    masks every carry leaf with a per-lane ``select`` each iteration), the
    epoch loop lives *outside* the vmap: an outer ``while_loop`` advances a
    vmapped epoch body while ``any(lane active)``, so the batch stops at its
    own realized epoch count instead of the static ``2T + 2`` worst-case
    bound.  :func:`_epoch_step` is idempotent for finished lanes, so no
    masking is needed and every lane's result is bit-identical to
    ``jax.vmap(simulate_arrays)`` (pinned in the parity suite).

    Returns ``(SimOutput, realized_epochs)`` where ``realized_epochs`` is
    the i32 scalar number of epoch iterations the batch actually executed
    (== the max per-lane ``n_epochs``); ``(SimOutput, realized_epochs,
    TraceBuffers)`` under ``trace=True``.
    """
    if control is None:
        control = _control_active(batch)
    T = batch.task_job.shape[1]
    V = batch.vm_mips.shape[1]
    tr = _trace_caps(T, V, control, trace, trace_events)
    # under control the per-lane bound is data-dependent (_lane_bound,
    # folded into each lane's activity); the global count only needs the
    # static worst case (all additive widenings active at once)
    bound = jnp.int32(7 * T + V + 3 if control else 2 * T + 2)
    inv, c0 = jax.vmap(partial(_epoch_setup, control=control,
                               trace=tr))(batch)

    def lanes_active(c: _Carry) -> jax.Array:
        return jax.vmap(partial(_lane_active, control=control))(batch, c)

    # per-lane activity rides in the carry, so each epoch pays exactly one
    # O(N·T) activity scan (cond and body are separate XLA computations and
    # could not share a recomputed one)
    def cond(state):
        _, active, n = state
        return jnp.any(active) & (n < bound)

    def body(state):
        c, active, n = state
        c2 = jax.vmap(partial(_epoch_step, control=control,
                              trace=tr is not None))(batch, inv, c)
        # per-lane realized epochs: only lanes that still had work count
        # this iteration (matches the per-lane while_loop's count exactly)
        c2 = c2._replace(epoch=c.epoch + active.astype(jnp.int32))
        return c2, lanes_active(c2), n + 1

    cf, _, realized = jax.lax.while_loop(
        cond, body, (c0, lanes_active(c0), jnp.int32(0)))
    out = jax.vmap(_sim_output)(batch, cf)
    if tr is not None:
        return out, realized, _trace_of(cf)
    return out, realized


# ---------------------------------------------------------------------------
# Sparse/compacted epoch stepping (DESIGN.md §9)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("control", "trace"))
def _setup_batch(batch: ScenarioArrays, control: bool = False,
                 trace: tuple[int, int] | None = None):
    return jax.vmap(partial(_epoch_setup, control=control,
                            trace=trace))(batch)


@partial(jax.jit, static_argnames="control")
def _active_batch(batch: ScenarioArrays, c: _Carry, control: bool = False):
    return jax.vmap(partial(_lane_active, control=control))(batch, c)


_output_batch = jax.jit(jax.vmap(_sim_output))


def _step_epoch_chunk_impl(batch: ScenarioArrays, inv: _EpochInv,
                           carry: _Carry, active: jax.Array,
                           remaining: jax.Array, k: int,
                           control: bool = False, trace: bool = False):
    """Advance the batch up to ``k`` epochs (early-exiting on
    ``any(active)`` and the dynamic ``remaining`` budget) — the one
    compiled stepper both the dense-resume and compacted shapes share.

    Returns ``(carry, active, counts, order)`` where ``counts`` is the
    fused ``i32[2] = [epochs_executed, n_still_active]`` pair — the ONLY
    value the dispatch-lean host loop pulls per round — and ``order`` is
    the on-device active-first permutation (``argsort`` of ``~active``;
    jnp argsort is stable, so it reproduces the host-side
    ``concatenate([nonzero(act), nonzero(~act)])`` order bit for bit).
    The host pulls ``order`` only on rounds that actually compact.
    Identical epoch-body ops to :func:`simulate_batch_arrays`, so
    chaining chunks reproduces the single while_loop bit for bit."""
    def cond(state):
        _, act, i = state
        return jnp.any(act) & (i < jnp.minimum(jnp.int32(k), remaining))

    def body(state):
        c, act, i = state
        c2 = jax.vmap(partial(_epoch_step, control=control,
                              trace=trace))(batch, inv, c)
        c2 = c2._replace(epoch=c.epoch + act.astype(jnp.int32))
        return (c2,
                jax.vmap(partial(_lane_active, control=control))(batch, c2),
                i + 1)

    with jax.named_scope("epoch_loop"):
        carry, act, i = jax.lax.while_loop(cond, body,
                                           (carry, active, jnp.int32(0)))
        counts = jnp.stack([i, jnp.sum(act, dtype=jnp.int32)])
        return carry, act, counts, jnp.argsort(~act)


_step_epoch_chunk = jax.jit(_step_epoch_chunk_impl,
                            static_argnames=("k", "control", "trace"))
# Donating variant (the train/trainer.py idiom): the carry pytree and
# activity mask buffers are reused in place across rounds instead of
# copied per chunk.  Safe because the host loop never re-reads a carry
# it has stepped past (see _compact_loop_lean's store-merge invariant).
_step_epoch_chunk_donated = jax.jit(_step_epoch_chunk_impl,
                                    static_argnames=("k", "control",
                                                     "trace"),
                                    donate_argnums=(2, 3))


@partial(jax.jit, static_argnames="control")
def _activity_batch(batch: ScenarioArrays, c: _Carry,
                    control: bool = False):
    """Initial-round twin of the stepper's activity reduction: the lane
    mask plus the on-device still-active count and active-first order,
    so round zero also costs one scalar pull, not a ``bool[N]`` mask."""
    act = jax.vmap(partial(_lane_active, control=control))(batch, c)
    return act, jnp.sum(act, dtype=jnp.int32), jnp.argsort(~act)


@jax.jit
def _take_lanes(tree, idx: jax.Array):
    """Gather a lane subset of any stacked pytree (exact: pure indexing)."""
    return jax.tree.map(lambda x: x[idx], tree)


def _put_lanes_impl(store, idx: jax.Array, sub):
    """Scatter a lane subset back into the dense store (distinct indices,
    so the write order cannot matter)."""
    return jax.tree.map(lambda s, x: s.at[idx].set(x), store, sub)


_put_lanes = jax.jit(_put_lanes_impl)
# Donates only the store (arg 0): its output leaves match the input
# shapes exactly so XLA reuses the buffers; ``sub`` is the pad-sized
# working carry whose shapes differ, and donating unusable buffers just
# trips jax's donation warning.
_put_lanes_donated = jax.jit(_put_lanes_impl, donate_argnums=(0,))


def simulate_batch_arrays_compact(
        batch: ScenarioArrays, *, k: int | str = "auto",
        floor: int = 8, cost_model=None, control: bool | None = None,
        trace: bool = False, trace_events: int | None = None,
        stats: dict | None = None, donate: bool = True,
        legacy: bool = False):
    """:func:`simulate_batch_arrays` with sparse active-lane compaction.

    Tail-heavy batches (mixed-policy / elastic grids) realize 20+ epochs
    while most lanes finish within ~5 — yet the dense driver keeps
    stepping every lane through the long tail because the epoch body is
    branch-free.  This host-driven variant checks the per-lane activity
    mask every ``k`` epochs; when the still-active count (pow2-padded,
    ``floor`` minimum) drops below the current working-set size, the
    active lanes are gathered into a compacted batch, the same compiled
    epoch chunk advances only those, and final carries scatter back by
    original lane index.  A b2048 batch whose tail is 40 active lanes
    then steps 64 lanes per epoch, not 2048.

    **Bitwise identical** to the dense driver: the vmapped epoch body is
    a per-lane function (gather/scatter cannot change any lane's
    arithmetic), finished lanes are idempotent under further stepping
    (so freezing them early changes nothing), and stranded lanes stay
    active until the shared ``2T + 2`` bound exactly as the dense loop
    keeps stepping them.  ``realized_epochs`` is preserved too: a global
    epoch executes iff some lane is active, in both drivers.

    ``k="auto"`` derives the interval from the measured cost model
    (``costmodel.default_cost_model().compact_interval`` — balancing the
    per-check dispatch against the work wasted stepping lanes that
    finished mid-chunk).  Host control flow means this entry point is
    NOT jit-able — it *contains* jitted chunks; callers inside jit use
    the dense driver.

    The trace leaves ride the carry through the gather/scatter like any
    other leaf, so traced compacted runs are bitwise-identical to the
    dense driver's.  ``stats`` (a dict, mutated in place) collects host
    telemetry for :class:`~repro.core.telemetry.RunReport`: ``syncs``
    (full mask/permutation device→host pulls — paid only on rounds that
    actually compact), ``scalar_syncs`` (the per-round fused
    ``[n_step, n_active]`` scalar pulls), ``compactions`` (gather
    rounds), ``dispatches`` (chunk-stepper launches),
    ``lane_epochs_allotted`` (launched lanes × the epochs each chunk may
    run) and the transfer counts of :func:`~repro.core.telemetry.put` /
    ``pull``.  The host spans ``iotsim.compact.{prepare,step,poll,
    regather,finish}`` mark the loop's phases on the profiler's clock
    (DESIGN.md §12.4).

    ``donate=True`` routes rounds through the buffer-donating stepper /
    store-scatter jits (carries update in place instead of copying every
    chunk); ``legacy=True`` runs the pre-dispatch-lean host loop — one
    full ``bool[N]`` mask pull per round, host-side ordering, no
    donation — kept as the honest benchmark comparator and the
    reference semantics for the lean loop's tests.
    """
    if control is None:
        control = _control_active(batch)
    if stats is None:
        stats = {}
    for key in ("syncs", "scalar_syncs", "compactions", "dispatches",
                "lane_epochs_allotted"):
        stats.setdefault(key, 0)
    N, T = batch.task_job.shape[:2]
    bound = 2 * T + 2
    if control:
        # lanes widen their own epoch bound (_lane_bound, additive per
        # mechanism); the host budget only needs the batch-wide worst
        # case — per-lane counts stay exact through the activity mask
        if bool(np.any(pull(batch.vm_valid, stats)
                       & (pull(batch.vm_fail, stats) < _BIG / 2))):
            bound += 2 * T + batch.vm_mips.shape[1]
        if bool(np.any((pull(batch.deadline_policy, stats)
                        == int(DeadlinePolicy.SHED))
                       & np.any(pull(batch.task_valid, stats)
                                & (pull(batch.task_deadline, stats)
                                   < _BIG / 2), axis=1))):
            bound += T + 1
        if bool(np.any(pull(batch.preempt, stats) != 0)):
            bound += 2 * T
    if k == "auto":
        from . import costmodel as costmodel_mod
        cm = cost_model or costmodel_mod.default_cost_model()
        k = cm.compact_interval(N, T)
    k = int(k)
    if k < 1:
        raise ValueError(f"simulate_batch_arrays_compact: k must be >= 1 "
                         f"or 'auto', got {k}")
    validate_pow2_floor(floor)
    tr = _trace_caps(T, batch.vm_mips.shape[1], control, trace,
                     trace_events)
    loop = _compact_loop_legacy if legacy else _compact_loop_lean
    return loop(batch, bound=bound, k=k, floor=floor, control=control,
                tr=tr, stats=stats, donate=donate)


def _compact_loop_lean(batch: ScenarioArrays, *, bound: int, k: int,
                       floor: int, control: bool, tr, stats: dict,
                       donate: bool):
    """Dispatch-lean host loop (DESIGN.md §13): one fused scalar pull per
    round; the full active-first permutation crosses the host boundary
    only on rounds that actually compact; carries are donated in place.

    Store-merge invariant (what makes donation safe): ``carry_store`` is
    ``None`` until the first compaction — before that, ``cur_carry`` IS
    the full batch in original lane order, so there is no N-sized copy
    aliasing ``c0`` for the donating stepper to invalidate.  Afterwards
    the store holds exactly the lanes *outside* ``cur_idx`` (plus stale
    copies of lanes inside it, which every merge overwrites), and the
    host never re-reads a carry object after passing it to a donating
    jit — each round rebinds ``cur_carry`` to the stepper's output, and
    the final ``_output_batch``/``_trace_of`` reads only the merged
    result, never a donated argument."""
    N = batch.task_job.shape[0]
    with jax.profiler.TraceAnnotation("iotsim.compact.prepare"):
        inv, c0 = _setup_batch(batch, control=control, trace=tr)
        cur_batch, cur_inv, cur_carry = batch, inv, c0
        cur_active, n_act_dev, order_dev = _activity_batch(batch, c0,
                                                           control=control)
        with jax.profiler.TraceAnnotation("iotsim.compact.poll"):
            n_act = int(pull(n_act_dev, stats))
        stats["scalar_syncs"] += 1
    carry_store = None
    # freshness flags: ``_epoch_setup``/``initial_state``-style jits can
    # forward an input array unchanged, so the t=0 carry may alias batch
    # leaves — donating a buffer that also rides in the same call's
    # operands is an XLA error.  Only carries/stores produced by a
    # compute op inside this loop (gather or stepper output) are donated.
    carry_fresh = store_fresh = False
    cur_idx = np.arange(N)
    realized = 0
    while realized < bound:
        if n_act == 0:
            break
        pad = pow2_pad(n_act, cap=len(cur_idx), floor=floor)
        if pad < len(cur_idx):
            # retire the working set into the dense store, then gather the
            # active lanes (pow2-padded with finished lanes, which step
            # idempotently) into a compacted view of the original batch —
            # the device-computed order crosses the host boundary here
            # and only here
            with jax.profiler.TraceAnnotation("iotsim.compact.regather"):
                order = pull(order_dev, stats)[:pad]
                stats["syncs"] += 1
                if carry_store is None:
                    carry_store, store_fresh = cur_carry, carry_fresh
                else:
                    carry_store = (_put_lanes_donated
                                   if donate and store_fresh
                                   else _put_lanes)(carry_store,
                                                    put(cur_idx, stats),
                                                    cur_carry)
                    store_fresh = True
                cur_idx = cur_idx[order]
                take = put(cur_idx, stats)
                cur_batch = _take_lanes(batch, take)
                cur_inv = _take_lanes(inv, take)
                cur_carry = _take_lanes(carry_store, take)
                carry_fresh = True
                cur_active = _active_batch(cur_batch, cur_carry,
                                           control=control)
                stats["compactions"] += 1
        step = (_step_epoch_chunk_donated if donate and carry_fresh
                else _step_epoch_chunk)
        limit = min(k, bound - realized)    # epochs the chunk may run
        stats["lane_epochs_allotted"] += len(cur_idx) * limit
        with jax.profiler.TraceAnnotation("iotsim.compact.step",
                                          lanes=len(cur_idx),
                                          epoch_limit=limit):
            cur_carry, cur_active, counts, order_dev = step(
                cur_batch, cur_inv, cur_carry, cur_active,
                put(np.int32(bound - realized), stats), k, control=control,
                trace=tr is not None)
        carry_fresh = True
        stats["dispatches"] += 1
        with jax.profiler.TraceAnnotation("iotsim.compact.poll"):
            n_step, n_act = (int(v) for v in pull(counts, stats))
        stats["scalar_syncs"] += 1
        realized += n_step
    with jax.profiler.TraceAnnotation("iotsim.compact.finish"):
        if carry_store is None:
            final = cur_carry
        else:
            final = (_put_lanes_donated if donate and store_fresh
                     else _put_lanes)(carry_store, put(cur_idx, stats),
                                      cur_carry)
        out = _output_batch(batch, final), put(np.int32(realized), stats)
        if tr is not None:
            return out + (_trace_of(final),)
        return out


def _compact_loop_legacy(batch: ScenarioArrays, *, bound: int, k: int,
                         floor: int, control: bool, tr, stats: dict,
                         donate: bool):
    """The pre-dispatch-lean host loop: a full ``bool[N]`` mask pull +
    host-side ordering every round, no donation.  Kept as the honest A/B
    comparator for the recorded compaction benches and as the reference
    the lean loop's bitwise tests pin against.  Its per-round mask pull
    sits in the ``iotsim.compact.regather`` span."""
    del donate                     # the legacy loop never donated
    N = batch.task_job.shape[0]
    with jax.profiler.TraceAnnotation("iotsim.compact.prepare"):
        inv, c0 = _setup_batch(batch, control=control, trace=tr)
        carry_store = c0
        cur_batch, cur_inv, cur_carry = batch, inv, c0
        cur_active = _active_batch(batch, c0, control=control)
    cur_idx = np.arange(N)
    realized = 0
    while realized < bound:
        with jax.profiler.TraceAnnotation("iotsim.compact.regather"):
            act_np = pull(cur_active, stats)
            stats["syncs"] += 1
            n_act = int(act_np.sum())
            if n_act == 0:
                break
            pad = pow2_pad(n_act, cap=len(cur_idx), floor=floor)
            if pad < len(cur_idx):
                carry_store = _put_lanes(carry_store, put(cur_idx, stats),
                                         cur_carry)
                order = np.concatenate([np.nonzero(act_np)[0],
                                        np.nonzero(~act_np)[0]])[:pad]
                cur_idx = cur_idx[order]
                take = put(cur_idx, stats)
                cur_batch = _take_lanes(batch, take)
                cur_inv = _take_lanes(inv, take)
                cur_carry = _take_lanes(carry_store, take)
                cur_active = _active_batch(cur_batch, cur_carry,
                                           control=control)
                stats["compactions"] += 1
        limit = min(k, bound - realized)    # epochs the chunk may run
        stats["lane_epochs_allotted"] += len(cur_idx) * limit
        with jax.profiler.TraceAnnotation("iotsim.compact.step",
                                          lanes=len(cur_idx),
                                          epoch_limit=limit):
            cur_carry, cur_active, counts, _ = _step_epoch_chunk(
                cur_batch, cur_inv, cur_carry, cur_active,
                put(np.int32(bound - realized), stats), k, control=control,
                trace=tr is not None)
        stats["dispatches"] += 1
        with jax.profiler.TraceAnnotation("iotsim.compact.poll"):
            n_step = int(pull(counts[0], stats))
        stats["scalar_syncs"] += 1
        realized += n_step
    with jax.profiler.TraceAnnotation("iotsim.compact.finish"):
        carry_store = _put_lanes(carry_store, put(cur_idx, stats),
                                 cur_carry)
        out = (_output_batch(batch, carry_store),
               put(np.int32(realized), stats))
        if tr is not None:
            return out + (_trace_of(carry_store),)
        return out


# ---------------------------------------------------------------------------
# Dependent variables (paper §5.3) as JAX ops
# ---------------------------------------------------------------------------

def job_metrics(sc: ScenarioArrays, out: SimOutput) -> JobMetrics:
    J = sc.job_length.shape[0]
    is_map = sc.task_valid & ~sc.task_is_reduce
    is_red = sc.task_valid & sc.task_is_reduce
    # Segment reductions as one-hot contractions / masked maxima instead of
    # jax.ops.segment_* scatters: vmapped scatters serialize on XLA:CPU and
    # dominated the sweep's per-call time (they cost more than the event
    # loop itself).  XLA:CPU accumulates both a dot's contraction dim and a
    # scatter-add in task-index order, so the sums are bit-identical
    # (pinned in the adaptive-schedule parity suite); maxima are exact in
    # any order.
    job_onehot = (sc.task_job[:, None] == jnp.arange(J)[None, :]
                  ).astype(jnp.float32)

    def seg_sum(x, m):
        return jnp.where(m, x, 0.0) @ job_onehot

    def seg_max(x, m):
        # two-level identity mirrors segment_max exactly: a job whose
        # tasks are all masked out maxes the -_BIG fill values, while a
        # job no task maps to at all (padded J rows) stays at the true
        # max identity, -inf
        return jnp.max(jnp.where(job_onehot > 0.5,
                                 jnp.where(m, x, -_BIG)[:, None],
                                 -jnp.inf), axis=0)

    def seg_min(x, m):
        return -seg_max(-x, m)

    nm = jnp.maximum(seg_sum(jnp.ones_like(out.exec_time), is_map), 1.0)
    nr = jnp.maximum(seg_sum(jnp.ones_like(out.exec_time), is_red), 1.0)
    m_avg = seg_sum(out.exec_time, is_map) / nm
    r_avg = seg_sum(out.exec_time, is_red) / nr
    m_max, r_max = seg_max(out.exec_time, is_map), seg_max(out.exec_time, is_red)
    m_min, r_min = seg_min(out.exec_time, is_map), seg_min(out.exec_time, is_red)

    last_map_fin = seg_max(out.finish, is_map)
    last_red_fin = seg_max(out.finish, is_red)
    last_map_st = seg_max(out.start, is_map)
    last_red_st = seg_max(out.start, is_red)
    delay = last_map_st + last_red_st - last_map_fin

    # cost accrues on the task's *current* VM (the failover slot once a
    # failure re-dispatched it; == task_vm bitwise in open-loop runs)
    cur_vm = jnp.where(out.hit, out.task_vm2, sc.task_vm)
    cost_rate = sc.vm_cost[cur_vm]
    vm_cost = seg_sum(out.exec_time * cost_rate, is_map | is_red)

    return JobMetrics(
        avg_exec=m_avg + r_avg,
        max_exec=m_max + r_max,
        min_exec=m_min + r_min,
        makespan=last_red_fin - sc.job_submit,
        delay_time=delay,
        vm_cost=vm_cost,
        network_cost=delay * sc.net_cost_per_unit * sc.net_enabled,
        map_avg_exec=m_avg,
        reduce_avg_exec=r_avg,
        completion=jnp.where(sc.job_valid, last_red_fin, 0.0),
    )


def scenario_metrics(sc: ScenarioArrays, out: SimOutput) -> ScenarioMetrics:
    """Whole-scenario dependent variables (sweep-result companions to the
    per-job :class:`JobMetrics`).  Utilization is the fraction of the
    cluster's MI capacity delivered over the scenario's wall-clock span —
    every valid task completes, so delivered MI is just the summed task
    lengths."""
    total_mi = jnp.sum(task_lengths(sc))
    capacity = jnp.sum(jnp.where(sc.vm_valid, sc.vm_mips * sc.vm_pes, 0.0))
    util = total_mi / jnp.maximum(capacity * out.finish_time, 1e-30)
    # Transfer-aware storage metrics (DESIGN.md §7): pure functions of the
    # encoded placement + binding (the broker binds before execution, so
    # locality is decided at encode time, not by the event loop).
    blocked = storage.has_block(sc.block_vm) & sc.task_valid
    local = blocked & storage.is_local(sc.block_vm, sc.task_vm)
    n_blocked = jnp.sum(blocked.astype(jnp.float32))
    loc_frac = (jnp.sum(local.astype(jnp.float32))
                / jnp.maximum(n_blocked, 1.0))
    xfer = jnp.sum(jnp.where(blocked & ~local, sc.block_size, 0.0)) * 1e6
    # Pay-as-you-go accounting (DESIGN.md §8).  Billing runs over each
    # VM's *realized* lease (elasticity.billed_lease: open-ended leases
    # end with the workload, finite leases bill their declared window
    # extended by any admitted work still draining), rounded up to the
    # billing granularity.  Stranded tasks (finish at the _BIG stand-in)
    # are excluded from delivered work and wait times.  The only [T, V]
    # intermediates are one bool one-hot + one masked-max: for a
    # statically open-ended fleet XLA folds ``sc.vm_stop`` to the _BIG
    # constant and DCEs the whole busy_end chain.
    V = sc.vm_mips.shape[0]
    # Billing runs over the *realized* windows the control loop left
    # behind (SimOutput.vm_open/vm_close == the encoded vm_start/vm_stop
    # in open-loop runs, so the pre-control op sequence is bitwise): a
    # never-opened reserve (open at _BIG) clamps to zero billed seconds,
    # an opened-never-closed lease ends with the workload.  Task→VM
    # attribution uses the current binding slot (failover once hit).
    cur_vm = jnp.where(out.hit, out.task_vm2, sc.task_vm)
    vm_onehot_b = cur_vm[:, None] == jnp.arange(V)[None, :]
    ran = sc.task_valid & (out.finish < _BIG / 2)
    fin_ran = jnp.where(ran, out.finish, 0.0)
    busy_end = jnp.max(jnp.where(vm_onehot_b, fin_ran[:, None], 0.0),
                       axis=0)
    billed_t = elasticity.billed_lease(out.vm_open, out.vm_close, busy_end,
                                       out.finish_time, sc.bill_gran, xp=jnp)
    billed = jnp.sum(jnp.where(sc.vm_valid, billed_t * sc.vm_cost, 0.0))
    lease_end = jnp.where(out.vm_close >= _BIG / 2, out.finish_time,
                          jnp.maximum(out.vm_close, busy_end))
    lease_dur = jnp.maximum(lease_end - out.vm_open, 0.0)
    delivered = jnp.sum(jnp.where(ran, task_lengths(sc), 0.0))
    leased_cap = jnp.sum(jnp.where(sc.vm_valid,
                                   sc.vm_mips * sc.vm_pes * lease_dur, 0.0))
    busy_frac = delivered / jnp.maximum(leased_cap, 1e-30)
    started = sc.task_valid & (out.start < _BIG / 2)
    q_wait = jnp.sum(jnp.where(started, out.start - out.ready, 0.0)) \
        / jnp.maximum(jnp.sum(started.astype(jnp.float32)), 1.0)
    # closed-loop control metrics (DESIGN.md §10; exact zeros open-loop)
    fail_fired = sc.vm_valid & (sc.vm_fail < _BIG / 2) \
        & (sc.vm_fail <= out.finish_time)
    n_failures = jnp.sum(fail_fired.astype(jnp.float32))
    hit_tasks = sc.task_valid & out.hit
    n_hit = jnp.sum(hit_tasks.astype(jnp.float32))
    n_recovered = jnp.sum((hit_tasks & ran).astype(jnp.float32))
    recovered = n_recovered / jnp.maximum(n_hit, 1.0)
    # SLO metrics layer (DESIGN.md §11): pure functions of the encoded
    # deadlines and the realized schedule, so they accumulate even under
    # DeadlinePolicy.NONE (observe without acting); all exact zeros when
    # no finite deadline / preemption is encoded.
    fin_dl = sc.task_valid & (sc.task_deadline < _BIG / 2)
    n_dl = jnp.sum(fin_dl.astype(jnp.float32))
    missed = fin_dl & ((out.finish >= _BIG / 2)
                       | (out.finish > sc.task_deadline))
    miss_frac = jnp.sum(missed.astype(jnp.float32)) / jnp.maximum(n_dl, 1.0)
    shed_tasks = jnp.sum((sc.task_valid & out.shed).astype(jnp.float32))
    preemptions = jnp.sum(out.n_evict).astype(jnp.float32)
    late = fin_dl & ran & (out.finish > sc.task_deadline)
    wasted = out.work_lost + jnp.sum(jnp.where(late, task_lengths(sc), 0.0))
    wasted_frac = wasted / jnp.maximum(delivered + out.work_lost, 1e-30)
    # nearest-rank p99 over completed finite-deadline tasks: members sort
    # below the _BIG fill, so index ceil(0.99 n) - 1 lands on a member
    comp_dl = fin_dl & ran
    n_comp = jnp.sum(comp_dl.astype(jnp.float32))
    slack_sorted = jnp.sort(jnp.where(comp_dl,
                                      out.finish - sc.task_deadline,
                                      jnp.float32(_BIG)))
    p_idx = jnp.clip(jnp.ceil(0.99 * n_comp).astype(jnp.int32) - 1,
                     0, slack_sorted.shape[0] - 1)
    p99 = jnp.where(n_comp > 0.5, slack_sorted[p_idx], 0.0)
    return ScenarioMetrics(finish_time=out.finish_time, utilization=util,
                           n_epochs=out.n_epochs,
                           locality_fraction=loc_frac, transfer_bytes=xfer,
                           billed_cost=billed, vm_busy_fraction=busy_frac,
                           queue_wait=q_wait,
                           failures_injected=n_failures,
                           tasks_redispatched=n_hit,
                           scale_events=out.n_scale.astype(jnp.float32),
                           recovered_fraction=recovered,
                           deadline_miss_fraction=miss_frac,
                           shed_tasks=shed_tasks,
                           preemptions=preemptions,
                           wasted_work_frac=wasted_frac,
                           p99_slack=p99)


@partial(jax.jit, static_argnames="control")
def _simulate_jit(arrs: ScenarioArrays, control: bool = False) -> JobMetrics:
    return job_metrics(arrs, simulate_arrays(arrs, control=control))


def simulate(sc: Scenario) -> JobMetrics:
    """Convenience single-scenario entry point (returns device arrays)."""
    arrs = from_scenario(sc)
    return _simulate_jit(arrs, control=_control_active(arrs))
