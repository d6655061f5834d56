"""The compaction host loop (DESIGN.md §9, §13), shared by both backends.

Tail-heavy batches realize many epochs while most lanes finish within a
few, and a branch-free epoch body steps every lane to the end.  The
compacted drivers step a batch in ``k``-epoch chunks instead, and
between chunks gather the still-active lanes (pow2-padded with finished
ones, which step idempotently) into a smaller working set.  Finished
lanes merge back into a dense store by original lane index, so the
result is bitwise the dense driver's.

:func:`run` is that loop, written once.  A backend plugs in a few
callables — ``engine.simulate_batch_arrays_compact`` the XLA epoch
chunk, ``ops.epoch_schedule_compact`` the resumable ``mr_epoch`` kernel:

* ``prepare() -> (lanes, state, aux, n_act, order)``: the per-lane
  inputs (a tuple of pytrees, one gather program each), the t=0 carry
  state, backend-private data that rides beside it, and the device's
  still-active count and active-first lane order;
* ``step(lanes, state, aux, k, remaining) -> (state, aux, polled,
  order)`` and ``step_donated``, the same program donating ``state``:
  advance at most ``min(k, remaining)`` epochs; ``polled`` is the one
  value the host pulls per round;
* ``read(polled, limit) -> (epochs run, lanes still active)``;
* ``regathered(lanes, state) -> aux`` (optional), after a gather;
* ``finish(store, realized)``, the driver's result.

Every round costs one scalar pull; the active-first order crosses to the
host only on rounds that compact.  The host spans
``iotsim.compact.{prepare,step,poll,regather,finish}`` mark the phases
on the profiler's clock (DESIGN.md §12.4), and ``stats`` counts
``syncs`` (order pulls), ``scalar_syncs`` (per-round pulls),
``compactions`` (gathers), ``dispatches`` (chunk launches) and
``lane_epochs_allotted`` (launched lanes × the epochs each chunk may
run), besides the transfers of :func:`~repro.core.telemetry.put` and
``pull``.

Store-merge invariant, which makes donation safe: ``store`` is ``None``
until the first compaction — before that the working state IS the full
batch in original lane order, so no N-sized alias of a donated carry
exists.  Afterwards the store holds exactly the lanes outside
``cur_idx`` (plus stale copies of lanes inside it, which every merge
overwrites), and the host never re-reads a state after passing it to a
donating jit.  The freshness flags guard the other hazard: a set-up jit
may forward an input array unchanged as a state leaf, and donating a
buffer that also rides in the same call's operands is an XLA error — so
only states and stores that a compute op of this loop produced (a gather,
a scatter or a step) are donated.
"""
from __future__ import annotations

import jax
import numpy as np

from . import costmodel as costmodel_mod
from .control import _BIG, DeadlinePolicy
from .telemetry import pull, put
from .util import pow2_pad, validate_pow2_floor

_STATS = ("syncs", "scalar_syncs", "compactions", "dispatches",
          "lane_epochs_allotted")


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


def epoch_bound(batch, control: bool, stats: dict | None) -> int:
    """The host's epoch budget: ``2T + 2``, widened under ``control`` by
    the batch-wide worst case of each additive per-lane widening
    (``engine._lane_bound``) — failures, SHED deadlines, preemption.
    Per-lane counts stay exact through the backends' own lane bounds."""
    T = batch.task_valid.shape[1]
    bound = 2 * T + 2
    if control:
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
    return bound


def _merge(store, fresh: bool, idx: np.ndarray, state, stats: dict):
    """Scatter the working ``state`` back into ``store`` at ``idx``,
    donating the store when this loop produced it."""
    scatter = _put_lanes_donated if fresh else _put_lanes
    return scatter(store, put(idx, stats), state)


def run(batch, *, k, floor: int, control: bool, cost_model,
        stats: dict | None, prepare, step, step_donated, read, finish,
        regathered=None):
    """Step ``batch`` through a backend's hooks (module docstring) with
    compaction every ``k`` epochs; returns ``finish(store, realized)``.

    ``k="auto"`` takes the interval from the cost model
    (``costmodel.default_cost_model().compact_interval`` unless
    ``cost_model`` is given); ``floor`` is the smallest pow2 working
    set."""
    if stats is None:
        stats = {}
    for key in _STATS:
        stats.setdefault(key, 0)
    bound = epoch_bound(batch, control, stats)
    if k == "auto":
        cm = cost_model or costmodel_mod.default_cost_model()
        k = cm.compact_interval(*batch.task_valid.shape)
    k = int(k)
    if k < 1:
        raise ValueError(f"compaction: k must be >= 1 or 'auto', got {k}")
    validate_pow2_floor(floor)
    with jax.profiler.TraceAnnotation("iotsim.compact.prepare"):
        lanes, state, aux, n_act_dev, order_dev = prepare()
        with jax.profiler.TraceAnnotation("iotsim.compact.poll"):
            n_act = int(pull(n_act_dev, stats))
        stats["scalar_syncs"] += 1
    cur_lanes = lanes
    cur_idx = np.arange(len(jax.tree.leaves(state)[0]))
    store = None
    state_fresh = store_fresh = False
    realized = 0
    while realized < bound and n_act:
        pad = pow2_pad(n_act, cap=len(cur_idx), floor=floor)
        if pad < len(cur_idx):
            with jax.profiler.TraceAnnotation("iotsim.compact.regather"):
                order = pull(order_dev, stats)[:pad]
                stats["syncs"] += 1
                if store is None:
                    store, store_fresh = state, state_fresh
                else:
                    store = _merge(store, store_fresh, cur_idx, state, stats)
                    store_fresh = True
                cur_idx = cur_idx[order]
                take = put(cur_idx, stats)
                cur_lanes = tuple(_take_lanes(x, take) for x in lanes)
                state = _take_lanes(store, take)
                state_fresh = True
                if regathered is not None:
                    aux = regathered(cur_lanes, state)
                stats["compactions"] += 1
        limit = min(k, bound - realized)    # epochs the chunk may run
        stats["lane_epochs_allotted"] += len(cur_idx) * limit
        with jax.profiler.TraceAnnotation("iotsim.compact.step",
                                          lanes=len(cur_idx),
                                          epoch_limit=limit):
            state, aux, polled, order_dev = (
                step_donated if state_fresh else step)(
                    cur_lanes, state, aux, k, bound - realized)
        state_fresh = True
        stats["dispatches"] += 1
        with jax.profiler.TraceAnnotation("iotsim.compact.poll"):
            n_step, n_act = read(pull(polled, stats), limit)
        stats["scalar_syncs"] += 1
        realized += n_step
    with jax.profiler.TraceAnnotation("iotsim.compact.finish"):
        if store is not None:
            state = _merge(store, store_fresh, cur_idx, state, stats)
        return finish(state, realized)
