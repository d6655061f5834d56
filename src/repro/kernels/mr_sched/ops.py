"""Wrappers: ScenarioArrays (J=1) -> kernel inputs -> schedules.

The derived per-task quantities (task lengths, stage-in readiness,
shuffle delays) are computed in plain jnp — cheap, O(N·T) — and the
event-loop hot path runs in a Pallas kernel:

* :func:`schedule` — the PR-1 ``mr_schedule`` kernel (static ``2T + 2``
  epoch bound, T×T admission rank), returns ``(start, finish)``;
* :func:`epoch_schedule` — the fused ``mr_epoch`` megakernel (tile-level
  early exit + per-VM admission scan), returns a full
  :class:`~repro.core.engine.SimOutput` so the sweep metrics layers can
  consume it directly (``SweepPlan.run(backend="pallas")``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import network, storage
from repro.core.control import failover_targets
from repro.core.engine import (ScenarioArrays, SimOutput, _take_lanes,
                               _put_lanes, _put_lanes_donated)
from repro.core.telemetry import pull, put, timeseries_capacity
from repro.core.util import pow2_pad, validate_pow2_floor

from .kernel import mr_schedule
from .megakernel import _BIG, initial_state, mr_epoch, mr_epoch_donated


def _derived_inputs(batch: ScenarioArrays):
    """The engine's exact derived-quantity op sequence, J=1 layout."""
    nm = batch.job_n_maps.astype(jnp.float32)[:, 0]        # (N,)
    nr = batch.job_n_reduces.astype(jnp.float32)[:, 0]
    stage_in = network.transfer_delay(batch.kappa_in, batch.job_data[:, 0],
                                      nm, batch.net_bw, batch.net_enabled)
    shuffle = network.transfer_delay(batch.kappa_shuffle,
                                     batch.job_data[:, 0], nm,
                                     batch.net_bw, batch.net_enabled)
    map_len = batch.job_length[:, 0] / nm
    red_len = batch.job_reduce_factor[:, 0] * batch.job_length[:, 0] / nr
    task_len = jnp.where(batch.task_is_reduce, red_len[:, None],
                         map_len[:, None]) * batch.task_mult
    task_len = jnp.where(batch.task_valid, task_len, 0.0)
    # storage remote-fetch delay (DESIGN.md §7): same broadcastable op
    # sequence as engine._epoch_setup, so off-replica map tasks enter the
    # kernel's (ready, index) admission scan at identical f32 ready times
    fetch = storage.remote_fetch_delay(
        batch.block_vm, batch.block_size, batch.task_vm,
        batch.kappa_in[:, None], batch.net_bw[:, None],
        batch.net_enabled[:, None], xp=jnp)
    ready0 = jnp.where(
        batch.task_valid & ~batch.task_is_reduce,
        (batch.job_submit[:, 0] + stage_in)[:, None] + fetch, 1e30)
    return task_len, ready0, shuffle


def _control_derived(batch: ScenarioArrays):
    """The engine's control-mode derived inputs (DESIGN.md §10): each
    task's precomputed failover binding slot and the re-replication fetch
    it pays toward that VM — the exact op sequences ``_epoch_setup`` runs
    per scenario, vmapped over the batch (integer logic + the shared
    broadcastable f32 fetch, so the results are bit-identical)."""
    task_vm2 = jax.vmap(
        lambda tv, vv, va, bv: failover_targets(tv, vv, va, bv, xp=jnp)
    )(batch.task_vm, batch.vm_valid, batch.vm_auto, batch.block_vm)
    refetch = storage.remote_fetch_delay(
        batch.block_vm, batch.block_size, task_vm2,
        batch.kappa_in[:, None], batch.net_bw[:, None],
        batch.net_enabled[:, None], xp=jnp)
    return task_vm2, refetch


# Lanes per ``mr_epoch`` block when compiled by Mosaic, which unrolls the
# epoch body over the block's vector registers: on v5e the T=32 open-loop
# kernel compiles in ~2 s at 8 lanes and ~24 s at 64, and the compacted
# driver compiles once per pow2 batch size.  8 is the f32 sublane tile,
# the smallest block Mosaic accepts.  Interpret mode keeps 64-lane tiles.
COMPILED_TILE = 8
INTERPRET_TILE = 64


def resolve_mode(interpret: bool | None, tile: int | None):
    """``(interpret, tile)`` for a kernel call: ``interpret=None``
    compiles with Mosaic on a TPU backend and interprets elsewhere (the
    CPU test path); ``tile=None`` takes the mode's default tile."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if tile is None:
        tile = INTERPRET_TILE if interpret else COMPILED_TILE
    return interpret, tile


def lane_pad(n: int, tile: int) -> int:
    """Empty lanes that pad ``n`` lanes to whole ``tile``-lane blocks (a
    batch smaller than a tile is one block of its own size)."""
    return (-n) % min(tile, max(n, 1))


def schedule(batch: ScenarioArrays, *, tile: int = 64,
             interpret: bool | None = None):
    """batch: stacked single-job scenarios (leading dim N)."""
    interpret, tile = resolve_mode(interpret, tile)
    task_len, ready0, shuffle = _derived_inputs(batch)
    return mr_schedule(
        task_len.astype(jnp.float32), batch.task_vm.astype(jnp.int32),
        ready0.astype(jnp.float32),
        batch.task_is_reduce.astype(jnp.int32),
        batch.task_valid.astype(jnp.int32),
        shuffle.astype(jnp.float32)[:, None],
        batch.vm_mips.astype(jnp.float32),
        batch.vm_pes.astype(jnp.float32),
        batch.sched_policy.astype(jnp.int32)[:, None],
        tile=tile, interpret=interpret)


def _control_lane_data(batch: ScenarioArrays, pad, task_vm2, refetch):
    """The fifteen control lane-data arrays, padded, in ``mr_epoch``'s
    positional order (the §11 graceful-degradation block rides at the
    end so earlier indices — e.g. ``lanes[15]`` = vm_auto in the compact
    driver — stay stable).  Pad lanes zero-fill — their ``vm_valid`` is
    all zero, so they encode no failure events, a NONE policy (both
    control and deadline), no preemption, and the open-loop 2T+2 lane
    bound (zero task_deadline rows are inert: pad lanes hold no valid
    tasks)."""
    return (pad(batch.vm_valid.astype(jnp.int32)),
            pad(batch.vm_fail.astype(jnp.float32)),
            pad(batch.vm_restore.astype(jnp.float32)),
            pad(batch.vm_auto.astype(jnp.int32)),
            pad(batch.control_policy.astype(jnp.int32)[:, None]),
            pad(batch.ctl_queue.astype(jnp.float32)[:, None]),
            pad(batch.ctl_busy.astype(jnp.float32)[:, None]),
            pad(batch.redispatch_delay.astype(jnp.float32)[:, None]),
            pad(task_vm2.astype(jnp.int32)),
            pad(refetch.astype(jnp.float32)),
            pad(batch.task_deadline.astype(jnp.float32)),
            pad(batch.deadline_policy.astype(jnp.int32)[:, None]),
            pad(batch.deadline_slack.astype(jnp.float32)[:, None]),
            pad(batch.preempt.astype(jnp.int32)[:, None]),
            pad(batch.preempt_resume.astype(jnp.int32)[:, None]))


def epoch_schedule(batch: ScenarioArrays, *, tile: int | None = None,
                   max_pes: int | None = None,
                   interpret: bool | None = None,
                   control: bool = False, trace: bool = False,
                   block_lanes: int | None = None):
    """Run the fused ``mr_epoch`` megakernel over a stacked J=1 batch.

    ``max_pes`` bounds the static per-VM admission scan and must cover the
    largest PE count in the batch; when ``vm_pes`` is concrete it is
    derived automatically, under a trace it defaults to 8 (pass it
    explicitly for bigger VMs — ``SweepPlan.run`` does).  The batch is
    padded up to a ``tile`` multiple with empty lanes (zero valid tasks,
    so they exit immediately) and trimmed back.

    ``control=True`` (static — host-decided from column presence, see
    ``sweep._CONTROL_PARAMS``) threads the closed-loop lane data through
    the kernel (DESIGN.md §10); degenerate control data reproduces the
    open-loop schedule bit for bit.

    ``trace=True`` (static, DESIGN.md §12) additionally returns the
    per-epoch time-series rows ``(N, C, 8)`` in ``telemetry.TS_COLUMNS``
    layout — bitwise the engine recorder's in interpret mode:
    ``(SimOutput, ts)`` instead of ``SimOutput``.

    ``block_lanes`` re-tiles each macro tile across a minor grid
    dimension (double-buffered HBM→VMEM streaming on real TPUs, bitwise
    in interpret mode — see ``mr_epoch``).

    ``interpret``/``tile`` default per :func:`resolve_mode`.
    """
    interpret, tile = resolve_mode(interpret, tile)
    if max_pes is None:
        if isinstance(batch.vm_pes, jax.core.Tracer):
            max_pes = 8
        else:
            max_pes = max(int(np.ceil(float(jnp.max(batch.vm_pes)))), 1)
    task_len, ready0, shuffle = _derived_inputs(batch)
    N = task_len.shape[0]
    n_pad = lane_pad(N, tile)

    def pad(x):
        widths = ((0, n_pad),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, widths)

    ctl = ()
    if control:
        ctl = _control_lane_data(batch, pad, *_control_derived(batch))
    elif trace:
        # open-loop traces need the real-VM mask — positionally the next
        # mr_epoch arg after prio is vm_valid
        ctl = (pad(batch.vm_valid.astype(jnp.int32)),)
    st = mr_epoch(
        pad(task_len.astype(jnp.float32)),
        pad(batch.task_vm.astype(jnp.int32)),
        pad(ready0.astype(jnp.float32)),
        pad(batch.task_is_reduce.astype(jnp.int32)),
        pad(batch.task_valid.astype(jnp.int32)),
        pad(shuffle.astype(jnp.float32)[:, None]),
        pad(batch.vm_mips.astype(jnp.float32)),
        pad(batch.vm_pes.astype(jnp.float32)),
        pad(batch.sched_policy.astype(jnp.int32)[:, None]),
        # elasticity lane data (DESIGN.md §8) — pad lanes hold no valid
        # tasks, so their zero lease windows never define events
        pad(batch.vm_start.astype(jnp.float32)),
        pad(batch.vm_stop.astype(jnp.float32)),
        pad(batch.spinup_delay.astype(jnp.float32)[:, None]),
        pad(batch.task_prio.astype(jnp.float32)),
        *ctl,
        tile=tile, max_pes=max_pes, interpret=interpret, control=control,
        trace=trace, block_lanes=block_lanes)
    out = _sim_output_of_state(batch, st, N, control=control)
    if trace:
        C = st[-1].shape[1] // 8
        return out, st[-1][:N].reshape(N, C, 8)
    return out


def _sim_output_of_state(batch: ScenarioArrays, st, N: int, *,
                         control: bool = False) -> SimOutput:
    """Trim a (padded) mr_epoch carry state back to ``N`` lanes and shape
    it into the engine's :class:`SimOutput` (exact op sequence —
    including the engine's ``_sim_output`` control fields: open-loop
    states report the encoded scenario as the realized control outputs,
    control states read the seven extra carry leaves; ``task_vm2`` is the
    failover binding control *would* use in either lowering)."""
    start, finish, ready = st[3][:N], st[4][:N], st[5][:N]
    n_epochs = st[7][:N, 0]
    exec_time = jnp.where(batch.task_valid, finish - start, 0.0)
    task_vm2, _ = _control_derived(batch)
    if control:
        hit = st[8][:N] != 0
        vm_open, vm_close = st[9][:N], st[10][:N]
        n_scale = st[11][:N, 0]
        shed = st[12][:N] != 0
        n_evict = st[13][:N]
        work_lost = st[14][:N, 0]
    else:
        hit = jnp.zeros_like(batch.task_valid)
        vm_open = jnp.asarray(batch.vm_start, jnp.float32)
        vm_close = jnp.asarray(batch.vm_stop, jnp.float32)
        n_scale = jnp.zeros(N, jnp.int32)
        shed = jnp.zeros_like(batch.task_valid)
        n_evict = jnp.zeros(batch.task_valid.shape, jnp.int32)
        work_lost = jnp.zeros(N, jnp.float32)
    # mirrors engine._sim_output: shed tasks are out of the makespan
    finish_time = jnp.max(jnp.where(batch.task_valid & ~shed, finish, 0.0),
                          axis=1)
    return SimOutput(start=start, finish=finish, ready=ready,
                     exec_time=exec_time, n_epochs=n_epochs,
                     finish_time=finish_time, hit=hit, task_vm2=task_vm2,
                     vm_open=vm_open, vm_close=vm_close, n_scale=n_scale,
                     shed=shed, n_evict=n_evict, work_lost=work_lost)


@jax.jit
def _state_activity(valid, finish, shed):
    """On-device activity reduction for the Pallas compact loop: the
    still-active lane count (ONE scalar crosses the host boundary per
    round) and the stable active-first permutation (pulled only on
    rounds that compact).  ``shed`` is the control carry's shed leaf or
    ``None`` open-loop (a static pytree difference, like the engine's
    ``control`` flag)."""
    unfin = (valid != 0) & (finish >= _BIG / 2)
    if shed is not None:
        # shed tasks never finish by design — they must not keep their
        # lane in the gather (engine._has_unfinished)
        unfin &= shed == 0
    act = jnp.any(unfin, axis=1)
    return jnp.sum(act, dtype=jnp.int32), jnp.argsort(~act)


@partial(jax.jit, static_argnames=("n_pad", "control", "trace_capacity"))
def _compact_prepare(batch: ScenarioArrays, *, n_pad: int, control: bool,
                     trace_capacity: int | None):
    """The Pallas compact driver's set-up, one program: derived inputs,
    the padded lane tuple (``_control_lane_data`` under ``control``, the
    ``vm_valid`` lane for an open-loop trace), the t=0 carry state and its
    activity reduction.  Returns ``(lanes, state, n_act, order)``."""
    task_len, ready0, shuffle = _derived_inputs(batch)

    def pad(x):     # pad lanes hold no valid tasks -> inactive from t=0
        widths = ((0, n_pad),) + ((0, 0),) * (x.ndim - 1)
        return jnp.pad(x, widths)

    lanes = (pad(task_len.astype(jnp.float32)),
             pad(batch.task_vm.astype(jnp.int32)),
             pad(batch.task_is_reduce.astype(jnp.int32)),
             pad(batch.task_valid.astype(jnp.int32)),
             pad(shuffle.astype(jnp.float32)[:, None]),
             pad(batch.vm_mips.astype(jnp.float32)),
             pad(batch.vm_pes.astype(jnp.float32)),
             pad(batch.sched_policy.astype(jnp.int32)[:, None]),
             pad(batch.vm_start.astype(jnp.float32)),
             pad(batch.vm_stop.astype(jnp.float32)),
             pad(batch.spinup_delay.astype(jnp.float32)[:, None]),
             pad(batch.task_prio.astype(jnp.float32)))
    if control:
        lanes = lanes + _control_lane_data(batch, pad,
                                           *_control_derived(batch))
    elif trace_capacity is not None:
        # vm_valid joins the lane data (and the gather) — positionally
        # the next mr_epoch arg after prio
        lanes = lanes + (pad(batch.vm_valid.astype(jnp.int32)),)
    state = initial_state(lanes[0], pad(ready0.astype(jnp.float32)),
                          lanes[2], lanes[3],
                          vm_start=lanes[8], vm_stop=lanes[9],
                          vm_auto=lanes[15] if control else None,
                          trace_capacity=trace_capacity)
    return (lanes, state,
            *_state_activity(lanes[3], state[4],
                             state[12] if control else None))


@partial(jax.jit, static_argnames=("control", "trace"))
def _compact_finish(batch: ScenarioArrays, store, *, control: bool,
                    trace: bool):
    """The Pallas compact driver's output, one program: the merged
    (padded) store as a :class:`SimOutput`, its realized epoch count and,
    under ``trace``, the time-series rows ``(N, C, 8)``."""
    N = batch.task_vm.shape[0]
    out = _sim_output_of_state(batch, store, N, control=control)
    if trace:
        C = store[-1].shape[1] // 8
        return (out, jnp.max(out.n_epochs),
                store[-1][:N].reshape(N, C, 8))
    return out, jnp.max(out.n_epochs)


def epoch_schedule_compact(batch: ScenarioArrays, *, k="auto",
                           tile: int | None = None,
                           max_pes: int | None = None,
                           interpret: bool | None = None, floor: int = 8,
                           cost_model=None, control: bool = False,
                           trace: bool = False, stats: dict | None = None,
                           donate: bool = True,
                           block_lanes: int | None = None):
    """Sparse active-lane compaction over the ``mr_epoch`` megakernel
    (DESIGN.md §9) — the Pallas twin of
    ``engine.simulate_batch_arrays_compact``.

    A host loop steps the batch in ``k``-epoch chunks through the
    *resumable* kernel (``state`` in/out, static ``epoch_limit``).  After
    each chunk the still-active lanes are gathered front-first into a
    pow2-padded compacted batch — re-tiled automatically, since the
    compacted count is a power of two the kernel's tile divisibility
    reduction never degrades — and the advanced carry scatters back into
    the dense lane store.  Dropped lanes are finished, and the epoch body
    is idempotent for finished lanes, so the result is **bitwise
    identical** to the dense path, per-lane ``n_epochs`` included.

    ``k="auto"`` derives the chunk size from the measured cost model.
    Returns ``(SimOutput, realized_epochs)`` with realized the batch max
    of the per-lane counts (the same reduction the dense pallas sweep
    path exposes).

    ``control=True`` composes the closed loop with compaction
    (DESIGN.md §10): killed-then-restored lanes stay in the host-side
    active set (their tasks are unfinished), so a failure that re-opens
    work after a lane looked nearly done simply keeps the lane in the
    gather — the epoch body stays idempotent for finished lanes and the
    result stays bitwise identical to the dense control path.  The host
    bound widens to the control epoch bound; the kernel's per-lane bound
    keeps degenerate lanes' realized counts at the open-loop ``2T + 2``.

    ``trace=True`` (DESIGN.md §12): the time-series leaf rides the
    gather/scatter like any other carry leaf, so the rows stay bitwise
    the dense traced path's; returns ``(SimOutput, realized, ts)``.

    ``stats`` (a dict, mutated in place) collects host-loop counters
    with the engine compact driver's keys — ``syncs`` (full permutation
    device→host pulls, paid only on rounds that actually compact),
    ``scalar_syncs`` (the per-round still-active scalar pulls),
    ``compactions`` (gather/scatter re-tiles), ``dispatches`` (kernel
    chunk launches), ``lane_epochs_allotted`` (launched lanes ×
    ``epoch_limit``, per chunk) and the transfer counts of
    :func:`~repro.core.telemetry.put`/``pull`` — feeding the sweep
    :class:`~repro.core.telemetry.RunReport`.  The host spans
    ``iotsim.compact.{prepare,step,poll,regather,finish}`` mark the
    loop's phases on the profiler's clock (DESIGN.md §12.4).

    ``donate=True`` steps chunks through the state-donating kernel jit
    (``mr_epoch_donated``) and the donating store-scatter, so the carry
    updates in place instead of copying every chunk (the engine lean
    loop's store-merge invariant, see
    ``engine._compact_loop_lean``).  The t=0 state is never donated:
    ``initial_state`` forwards lane arrays as state leaves.

    The set-up and output glue run as one compiled program each,
    :func:`_compact_prepare` and :func:`_compact_finish`, whose shapes
    are fixed by the batch.  Only the final store merge (``_put_lanes``)
    stays a call of its own: its index length is the last round's pow2
    pad, which varies from call to call, and would otherwise multiply
    the output program's compile variants.
    """
    if stats is None:
        stats = {}
    stats.setdefault("syncs", 0)
    stats.setdefault("scalar_syncs", 0)
    stats.setdefault("compactions", 0)
    stats.setdefault("dispatches", 0)
    stats.setdefault("lane_epochs_allotted", 0)
    validate_pow2_floor(floor)
    interpret, tile = resolve_mode(interpret, tile)
    if max_pes is None:
        max_pes = max(int(np.ceil(float(pull(jnp.max(batch.vm_pes),
                                             stats)))), 1)
    N, T = batch.task_vm.shape
    V = batch.vm_mips.shape[1]
    # host budget = the batch-wide worst case of the additive per-lane
    # bound (engine.simulate_batch_arrays_compact's exact host rule);
    # per-lane counts stay exact through the kernel's lane_bound
    bound = 2 * T + 2
    if control:
        if bool(np.any(pull(batch.vm_valid, stats)
                       & (pull(batch.vm_fail, stats) < _BIG / 2))):
            bound += 2 * T + V
        if bool(np.any((pull(batch.deadline_policy, stats) == 1)
                       & np.any(pull(batch.task_valid, stats)
                                & (pull(batch.task_deadline, stats)
                                   < _BIG / 2), axis=1))):
            bound += T + 1
        if bool(np.any(pull(batch.preempt, stats) != 0)):
            bound += 2 * T
    if k == "auto":
        from repro.core import costmodel as costmodel_mod
        cm = cost_model or costmodel_mod.default_cost_model()
        k = cm.compact_interval(N, T)
    k = int(k)
    if k < 1:
        raise ValueError(f"epoch_schedule_compact: k must be >= 1, got {k}")
    with jax.profiler.TraceAnnotation("iotsim.compact.prepare"):
        n_pad = lane_pad(N, tile)
        lanes, cur_state, n_act_dev, order_dev = _compact_prepare(
            batch, n_pad=n_pad, control=control,
            trace_capacity=(timeseries_capacity(T, V, control) if trace
                            else None))
        # ``store`` is None until the first compaction (before that,
        # ``cur_state`` IS the dense store in original lane order) — the
        # engine lean loop's store-merge invariant, which is what makes
        # donating ``cur_state`` into each chunk safe: no N-sized alias of
        # the donated carry ever exists on the host side.  The freshness
        # flags guard the other aliasing hazard: ``initial_state``
        # forwards some lane arrays as state leaves unchanged (state[1]
        # IS task_len), so the t=0 state out of ``_compact_prepare`` may
        # share buffers with the lane operands, and donating a buffer
        # that also rides in the same call's lane operands is an XLA
        # error — so only carries/stores produced by a compute op inside
        # this loop are ever donated.
        store = None
        state_fresh = store_fresh = False
        cur_idx = np.arange(N + n_pad)
        cur_lanes = lanes
        with jax.profiler.TraceAnnotation("iotsim.compact.poll"):
            n_act = int(pull(n_act_dev, stats))
        stats["scalar_syncs"] += 1
    total = 0
    while total < bound:
        if n_act == 0:
            break
        pad_n = pow2_pad(n_act, cap=len(cur_idx), floor=floor)
        if pad_n < len(cur_idx):
            # active lanes first; the pow2 padding is filled with
            # finished lanes, which step idempotently — the
            # device-computed order crosses the host boundary here and
            # only here
            with jax.profiler.TraceAnnotation("iotsim.compact.regather"):
                order = pull(order_dev, stats)[:pad_n]
                stats["syncs"] += 1
                if store is None:
                    store, store_fresh = cur_state, state_fresh
                else:
                    store = (_put_lanes_donated if donate and store_fresh
                             else _put_lanes)(store, put(cur_idx, stats),
                                              cur_state)
                    store_fresh = True
                cur_idx = cur_idx[order]
                take = put(cur_idx, stats)
                cur_lanes = _take_lanes(lanes, take)
                cur_state = _take_lanes(store, take)
                state_fresh = True
                stats["compactions"] += 1
        limit = min(k, bound - total)
        stats["dispatches"] += 1
        stats["lane_epochs_allotted"] += len(cur_idx) * limit
        step = mr_epoch_donated if donate and state_fresh else mr_epoch
        with jax.profiler.TraceAnnotation("iotsim.compact.step",
                                          lanes=len(cur_idx),
                                          epoch_limit=limit):
            cur_state = step(*cur_lanes[:2], None, *cur_lanes[2:],
                             state=cur_state, tile=tile, max_pes=max_pes,
                             interpret=interpret, epoch_limit=limit,
                             control=control, trace=trace,
                             block_lanes=block_lanes)
            state_fresh = True
            total += limit
            n_act_dev, order_dev = _state_activity(
                cur_lanes[3], cur_state[4],
                cur_state[12] if control else None)
        with jax.profiler.TraceAnnotation("iotsim.compact.poll"):
            n_act = int(pull(n_act_dev, stats))
        stats["scalar_syncs"] += 1
    with jax.profiler.TraceAnnotation("iotsim.compact.finish"):
        if store is None:
            store = cur_state
        else:
            store = (_put_lanes_donated if donate and store_fresh
                     else _put_lanes)(store, put(cur_idx, stats), cur_state)
        return _compact_finish(batch, store, control=control, trace=trace)
