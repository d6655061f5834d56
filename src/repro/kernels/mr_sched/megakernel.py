"""``mr_epoch``: the fused epoch megakernel (adaptive-schedule backend).

One ``pl.pallas_call`` advances a *tile* of scenario lanes through their
whole event history: rates evaluation, the one-hot reductions, fluid-state
advance, the next-event min, completions, shuffle release, and space-shared
admission are fused into a single kernel body whose per-VM/per-task state
(remaining MI, readiness, running masks, per-VM occupancy) stays resident
in VMEM across epochs — the XLA engine (``repro.core.engine``) round-trips
that state through HBM once per epoch.

Two structural choices set it apart from a plain port of the engine epoch:

* **Tile-level early exit** — the epoch loop is a ``lax.while_loop`` gated
  on ``any(lane unfinished)`` (plus the ``2T + 2`` safety bound), so a tile
  stops at its own realized epoch count instead of always burning the
  worst-case bound; the per-lane realized counts come back as ``n_epochs``.
* **Per-VM admission scan** — the space-shared (ready, index) admission
  rank was a ``T×T`` higher-priority matrix (O(T²) VMEM + flops per
  epoch); here admission extracts per-VM minima ``max_pes`` times
  (O(max_pes·T·V)), admitting exactly the tasks whose per-VM rank is below
  the free PE count — the ROADMAP "fold the T×T rank into a per-VM scan"
  item.

Every float-bearing step reuses the engine's exact op sequence (the one-hot
contractions are 0/1-weighted sums, so any accumulation order is exact),
which makes the kernel's schedule **bit-identical** to
``engine.simulate_arrays`` — pinned by ``tests/test_adaptive_schedule.py``,
not just approximately close.  Scope: single-job scenarios (J = 1 — what
``sweep.encode_cell`` emits), arbitrary M/R/VM mix, both sched policies per
lane (``sched_policy`` is lane data, so one tile may mix policies).

Storage subsystem (DESIGN.md §7): LOCALITY binding and the remote-fetch
penalty reach this kernel entirely through lane data — ``task_vm`` carries
the replica-aware binding and ``ready0`` carries the per-task fetch delay
(``storage.remote_fetch_delay``, applied in ``ops._derived_inputs`` with
the engine's exact f32 op sequence).  Off-replica map tasks therefore
enter the per-VM ``(ready, index)`` admission scan at their delayed ready
times and lose admission priority to data-local peers, with no kernel-side
branching — one lowering serves all five policy axes' values mixed per
lane, bit-identical to the engine (``tests/test_storage.py``).

Elasticity (DESIGN.md §8): VM lease windows are lane data too —
``vm_start``/``vm_stop`` (+ the ``spinup`` boot delay) gate admission
per VM: a pending task's eligible time is ``max(ready, lease open)``
(lease-start edges therefore join the next-event min through the
arrival candidates) and candidates whose event time lands at/past the
lease close are stranded, never defining an event again.  The
space-shared admission scan extracts per-VM minima of the lexicographic
``(priority desc, eligible time, index)`` key — the per-task
``prio`` input generalizes the classic ``(ready, index)`` rank; zero
priorities and the static-fleet window ``[0, 1e30)`` reproduce the
pre-elastic schedule bit for bit (``tests/test_elasticity.py``).

Closed-loop control (DESIGN.md §10): a static ``control`` flag threads
the engine's control dataflow through the same kernel — open-loop
lowerings carry **zero** control code.  When on, fifteen extra lane-data
refs (failure/restore instants, reserve flags, policy id + thresholds,
the precomputed failover binding ``task_vm2`` and its re-replication
fetch, plus the §11 graceful-degradation block: per-task deadlines,
deadline policy id + slack, preemption knobs) and seven extra carry
leaves (``hit``, realized ``vm_open``/``vm_close``, ``n_scale``,
``shed``, ``n_evict``, ``work_lost``) join the loop; every epoch runs
the control hook at its opening clock, switches each task's one-hot row
between its two binding slots on ``hit``, joins pending failure instants
into the next-event min, kills + re-dispatches tasks on fired VMs, and
gates admission around each VM's ``[fail, restore)`` down window — the
exact engine op sequence, so seeded-failure and autoscale grids stay
bit-identical to ``engine.simulate_arrays`` (``tests/test_control.py``).

Graceful degradation under overload (DESIGN.md §11,
``tests/test_deadlines.py``): SHED lanes drop pending tasks whose
earliest possible finish already exceeds their deadline (evaluated with
the shared ``control.earliest_finish`` f32 op sequence at both the
arrival-candidate and admission instants), BOOST lanes wrap an urgency
tier around the space-shared admission key, and preemption lets an
eligible higher-raw-priority task evict the weakest still-evictable
running task on its full VM (the §10 failure-kill op sequence driven by
a policy mask).  The T×T relations the engine uses lower here as per-VM
extrema through the same one-hot masks the admission scan uses.  The
per-lane epoch bound is additive data (``engine._lane_bound``), so
degenerate lanes keep the exact open-loop ``2T + 2`` realized counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# THE shared f32 deadline-pressure op sequence (DESIGN.md §11) — imported
# so the kernel's SHED/BOOST predicates cannot drift from the oracle's
from repro.core.control import earliest_finish
# trace capacity math (DESIGN.md §12) shared with the engine recorder
from repro.core.telemetry import timeseries_capacity

_BIG = 1e30
_TIME_EPS = 1e-6


def _kernel(*refs, T: int, V: int, max_pes: int, epoch_bound: int,
            control: bool, trace: bool):
    (task_len_ref, task_vm_ref, ready0_ref, is_red_ref, valid_ref,
     shuffle_ref, vm_mips_ref, vm_pes_ref, sched_ref,
     vm_start_ref, vm_stop_ref, spinup_ref, prio_ref) = refs[:13]
    n_data = 13
    if control:
        (vm_valid_ref, vm_fail_ref, vm_restore_ref, vm_auto_ref,
         ctl_policy_ref, ctl_queue_ref, ctl_busy_ref, redispatch_ref,
         task_vm2_ref, refetch_ref, task_deadline_ref, dl_policy_ref,
         dl_slack_ref, preempt_ref, resume_ref) = refs[13:28]
        n_data = 28
    elif trace:
        # open-loop traces need vm_valid for the open-VM observable (the
        # control lowering already carries it as lane data)
        vm_valid_ref = refs[13]
        n_data = 14
    n_state = (14 if control else 7) + (1 if trace else 0)
    state_in = refs[n_data:n_data + n_state]
    out_refs = refs[n_data + n_state:]

    task_len = task_len_ref[...]                 # (tile, T) f32
    task_vm = task_vm_ref[...]                   # (tile, T) i32
    is_red = is_red_ref[...] != 0                # (tile, T)
    valid = valid_ref[...] != 0
    shuffle = shuffle_ref[...]                   # (tile, 1) f32
    vm_mips = vm_mips_ref[...]                   # (tile, V)
    vm_pes = vm_pes_ref[...]                     # (tile, V)
    is_space = sched_ref[...] != 0               # (tile, 1) policy gate
    vm_start = vm_start_ref[...]                 # (tile, V) lease open
    vm_stop = vm_stop_ref[...]                   # (tile, V) lease close
    spinup = spinup_ref[...]                     # (tile, 1) boot delay
    prio = prio_ref[...]                         # (tile, T) admission prio

    onehot_b = (task_vm[..., None]
                == jax.lax.broadcasted_iota(jnp.int32,
                                            (1, 1, V), 2))   # (tile,T,V)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)     # (1, T)
    vidx = jax.lax.broadcasted_iota(jnp.int32, (1, V), 1)    # (1, V)

    # The one-hot contractions lower as masked select-and-reduce on the
    # VPU, not as dot_generals (Mosaic has no batched (T,V) contraction,
    # and an f32 MXU pass could round).  Both are exact, so the results
    # are bitwise what a contraction gives: a gather sums one selected
    # value with zeros, and every per-VM sum below adds 0/1 indicators.
    def gather(oh_b, per_vm):
        """Each task's value of a per-VM quantity (tile,V) -> (tile,T)."""
        return jnp.sum(jnp.where(oh_b, per_vm[:, None, :], 0.0), axis=2)

    def vm_sum(oh_b, per_task):
        """Per-VM sum of a per-task 0/1 indicator (tile,T) -> (tile,V)."""
        return jnp.sum(jnp.where(oh_b, per_task[..., None], 0.0), axis=1)

    task_pes0 = gather(onehot_b, vm_pes)

    if control:
        vm_valid = vm_valid_ref[...] != 0        # (tile, V)
        vm_fail = vm_fail_ref[...]               # (tile, V) f32
        vm_restore = vm_restore_ref[...]         # (tile, V) f32
        vm_auto = vm_auto_ref[...] != 0          # (tile, V) reserve flag
        pol_on = ctl_policy_ref[...][:, 0] == 1  # (tile,) AUTOSCALE
        ctl_queue = ctl_queue_ref[...][:, 0]     # (tile,)
        ctl_busy = ctl_busy_ref[...][:, 0]       # (tile,)
        redispatch = redispatch_ref[...]         # (tile, 1)
        task_vm2 = task_vm2_ref[...]             # (tile, T) failover slot
        refetch = refetch_ref[...]               # (tile, T) re-repl fetch
        task_deadline = task_deadline_ref[...]   # (tile, T) f32 (_BIG=none)
        dl_shed = dl_policy_ref[...] == 1        # (tile, 1) SHED
        dl_boost = dl_policy_ref[...] == 2       # (tile, 1) BOOST
        dl_slack = dl_slack_ref[...]             # (tile, 1) f32
        pre_onl = (preempt_ref[...] != 0) & is_space   # (tile, 1)
        res_onl = resume_ref[...] != 0           # (tile, 1)
        # per-lane epoch bound (engine._lane_bound, additive): each
        # robustness mechanism's term is paid only by lanes whose encoded
        # data can trigger it — degenerate lanes keep the exact open-loop
        # bound (and stranded lanes' realized n_epochs stay bit-identical)
        any_fail = jnp.any(vm_valid & (vm_fail < _BIG / 2), axis=1)
        any_shed = dl_shed[:, 0] & jnp.any(
            valid & (task_deadline < _BIG / 2), axis=1)
        lane_bound = (
            jnp.int32(2 * T + 2)
            + jnp.where(any_fail, jnp.int32(2 * T + V), jnp.int32(0))
            + jnp.where(any_shed, jnp.int32(T + 1), jnp.int32(0))
            + jnp.where(preempt_ref[...][:, 0] != 0,
                        jnp.int32(2 * T), jnp.int32(0)))

    # Lease admission windows (DESIGN.md §8), gathered per task with the
    # exact f32 ops the engine's _epoch_setup uses (one-hot gathers are
    # exact; vm_stop carries the _BIG stand-in, never inf).  Static
    # fleets make every use below a bitwise identity with the
    # pre-elastic kernel.  Under control these are re-derived every
    # epoch from the carried realized windows instead.
    avail_t0 = gather(onehot_b, vm_start + spinup)
    close_t0 = gather(onehot_b, vm_stop)

    # carry state arrives as refs (the wrapper builds the canonical
    # initial state with the exact constants this kernel used to
    # initialize in VMEM — compacted/chunked drivers resume mid-history
    # by feeding a previous call's state back in).  The loop carry keeps
    # the refs' layout — every leaf 2-D and 32-bit (masks as int32,
    # per-lane scalars as (tile, 1)), which is what Mosaic's scf.while
    # lowering accepts; ``unpack``/``pack`` convert at the loop edges.
    state = tuple(r[...] for r in state_in[:5]) + (
        ready0_ref[...],                                 # ready
        state_in[5][...],                                # maps_left
        state_in[6][...],                                # lane epochs
        jnp.zeros((1, 1), jnp.int32),                    # epochs this call
    )
    if control:
        state = state + tuple(r[...] for r in state_in[7:14])
    if trace:
        vm_valid_t = vm_valid_ref[...] != 0              # (tile, V)
        state = state + (state_in[-1][...],)             # ts rows (tile,C*8)

    # carry leaves held as (tile, 1) per-lane scalars / int32 masks
    lane_leaves = (0, 6, 7) + ((12, 15) if control else ())
    mask_leaves = (2,) + ((9, 13) if control else ())

    def unpack(st):
        return tuple(x[:, 0] if k in lane_leaves
                     else x != 0 if k in mask_leaves else x
                     for k, x in enumerate(st))

    def pack(st):
        return tuple(x[:, None] if k in lane_leaves
                     else x.astype(jnp.int32) if k in mask_leaves else x
                     for k, x in enumerate(st))

    def lanes_active(finish, lane_ep, shed=None):
        unfin = valid & (finish >= _BIG / 2)
        if control:
            # a shed task never finishes by design — it must not keep
            # its lane alive (shedding *terminates* backlogs)
            unfin &= ~shed
        act = jnp.any(unfin, axis=1)                     # (tile,)
        if control:
            act &= lane_ep < lane_bound
        return act

    def cond(st):
        act = lanes_active(st[4], st[7][:, 0],
                           st[13] != 0 if control else None)
        return jnp.any(act) & (st[8][0, 0] < epoch_bound)

    def epoch(st):
        st = unpack(st)
        (time, rem, running, start, finish, ready, maps_left, lane_ep,
         n) = st[:9]
        active = lanes_active(finish, lane_ep,
                              st[13] if control else None)
        runf = running.astype(jnp.float32)
        if trace and not control:
            # pre-update carry snapshot: the engine's open-loop recorder
            # reads the observables off ``c.*`` before the epoch mutates
            t0, start0, finish0, ready0c = time, start, finish, ready

        # --- binding-slot switch + control hook (clock = time) ------------
        if control:
            (hit, vm_open, vm_close, n_scale, shed0, n_evict0,
             work_lost) = st[9:16]
            # one-hot of each task's current slot (Mosaic cannot insert
            # a minor dim into a bool vector, so select the VM ids first)
            cur_oh_b = (jnp.where(hit, task_vm2, task_vm)[..., None]
                        == jax.lax.broadcasted_iota(jnp.int32, (1, 1, V), 2))
        else:
            cur_oh_b = onehot_b

        def to_task(per_vm):
            """Gather a per-VM quantity to each task's current VM."""
            return gather(cur_oh_b, per_vm)

        def per_vm_sum(per_task):
            return vm_sum(cur_oh_b, per_task)

        if control:
            task_pes = to_task(vm_pes)
            f_t = to_task(vm_fail)
            r_t = to_task(vm_restore)
            mips_t = to_task(vm_mips)
            # shed tasks are out of the system: refused backlog neither
            # holds a reserve open nor counts toward scaling pressure
            unfinished = valid & (finish >= _BIG / 2) & ~shed0
            # queue depth over *raw* ready times: tasks bound to unopened
            # reserves must count toward the backlog or the rule that
            # would open their VM could never trigger
            qdepth = jnp.sum((unfinished & (start >= _BIG / 2)
                              & (ready <= time[:, None]))
                             .astype(jnp.float32), axis=1)
            busy_v = per_vm_sum(runf) > 0.5
            open_v = vm_valid & (vm_open + spinup <= time[:, None]) \
                & (time[:, None] < vm_close)
            n_open = jnp.sum(open_v.astype(jnp.float32), axis=1)
            busy_frac = (jnp.sum((open_v & busy_v).astype(jnp.float32),
                                 axis=1) / jnp.maximum(n_open, 1.0))
            trigger = pol_on & (qdepth > ctl_queue) & (busy_frac >= ctl_busy)
            reserve = vm_valid & vm_auto
            unopened = reserve & (vm_open >= _BIG / 2)
            # lowest-index unopened reserve: the min of the masked index
            # key IS the argmin index (keys are the indices themselves)
            first = jnp.min(jnp.where(unopened, vidx, jnp.int32(V + 1)),
                            axis=1)
            open_mask = trigger[:, None] & unopened & (vidx == first[:, None])
            bound_unfin = per_vm_sum(unfinished.astype(jnp.float32))
            close_mask = pol_on[:, None] & reserve & (vm_open < _BIG / 2) \
                & (time[:, None] < vm_close) & (bound_unfin < 0.5)
            vm_open = jnp.where(open_mask, time[:, None], vm_open)
            vm_close = jnp.where(close_mask, time[:, None], vm_close)
            n_scale = n_scale + jnp.sum(open_mask.astype(jnp.int32), axis=1) \
                + jnp.sum(close_mask.astype(jnp.int32), axis=1)
            # lease windows re-derived from carry: exactly the hoisted
            # gathers when no reserve ever opens (one-hot sums are exact)
            avail_t = to_task(vm_open + spinup)
            close_t = to_task(vm_close)
        else:
            task_pes = task_pes0
            avail_t, close_t = avail_t0, close_t0

        # single rates evaluation per epoch (space-shared keeps n <= pes,
        # so the min() clamp makes this formula serve both policies)
        n_on_vm = per_vm_sum(runf)
        share = vm_mips * jnp.minimum(1.0, vm_pes
                                      / jnp.maximum(n_on_vm, 1.0))
        r = jnp.where(running, to_task(share), 0.0)
        eta = jnp.where(running,
                        time[:, None] + rem / jnp.maximum(r, 1e-30), _BIG)
        not_started = valid & ~running & (finish >= _BIG / 2) \
            & (start >= _BIG / 2)
        # lease-aware eligibility: admissible from max(ready, lease open)
        # — start edges join the next-event min through the candidates —
        # and only while the event time lands before the lease close
        # (candidates at/past it are stranded and define no event).
        elig = jnp.maximum(ready, avail_t)
        if control:
            # failure-window gating: any admission instant landing inside
            # the current VM's [fail, restore) down window slides to the
            # restore edge — which is how restore instants join the event
            # min (no separate restore event stream is needed)
            def gate(x):
                return jnp.where((x >= f_t) & (x < r_t), r_t, x)

            elig = gate(elig)
            cand_t = gate(jnp.maximum(elig, time[:, None]))
            # SHED admission control at the arrival-candidate instant
            # (DESIGN.md §11): a pending task whose earliest possible
            # finish already exceeds its deadline stops defining arrival
            # events.  The close_t gate keeps stranded tasks out — the
            # oracle never re-examines an arrival it could not schedule.
            # Pressure is evaluated on the *carried* rem (engine: c.rem).
            rem_c = rem
            evaluable = not_started & (elig < _BIG / 2)
            efin_c = earliest_finish(cand_t, rem_c, mips_t, xp=jnp)
            shed_c = shed0 | (dl_shed & evaluable & (cand_t < close_t)
                              & (efin_c > task_deadline))
        else:
            cand_t = jnp.maximum(elig, time[:, None])
        # space-shared: pending tasks only define arrival events while a
        # PE slot is free; otherwise a completion epoch admits them.
        has_slot = (task_pes - to_task(n_on_vm)) > 0.5
        if control:
            # preemption arrival gate (DESIGN.md §11): a pending task
            # strictly beating the weakest still-evictable running task
            # on its VM defines an arrival event even with no free slot —
            # per-VM min of evictable raw priorities instead of the
            # engine's T×T prey relation (same set: beats some evictable
            # iff beats the weakest)
            evictable = running & (n_evict0 < jnp.int32(2))
            ev_m = jnp.where(evictable, prio, _BIG)
            min_ev_v = jnp.min(
                jnp.where(cur_oh_b, ev_m[..., None], _BIG), axis=1)
            can_pre = pre_onl & (prio > to_task(min_ev_v))
            arr = jnp.where(not_started & ~shed_c
                            & (~is_space | has_slot | can_pre)
                            & (cand_t < close_t), cand_t, _BIG)
        else:
            arr = jnp.where(not_started & (~is_space | has_slot)
                            & (cand_t < close_t), cand_t, _BIG)
        t_next = jnp.minimum(jnp.min(eta, axis=1), jnp.min(arr, axis=1))
        if control:
            # pending failure instants of valid VMs are calendar events too
            fail_ev = jnp.where(vm_valid & (vm_fail > time[:, None]),
                                vm_fail, _BIG)
            t_next = jnp.minimum(t_next, jnp.min(fail_ev, axis=1))
        live = t_next < _BIG / 2
        tie = _TIME_EPS * jnp.maximum(t_next, 1.0)

        # advance fluid state (engine op order: guard with running, not dt)
        rem = jnp.where(running, rem - (t_next[:, None] - time[:, None]) * r,
                        rem)

        # completions (all tied events fire in this one epoch)
        done_now = live[:, None] & running & (eta <= (t_next + tie)[:, None])
        finish = jnp.where(done_now, t_next[:, None], finish)
        running = running & ~done_now
        rem = jnp.where(done_now, 0.0, rem)

        # job map-phase completion -> release reduces after shuffle delay
        maps_done_now = jnp.sum((done_now & ~is_red).astype(jnp.int32),
                                axis=1)
        maps_left_new = maps_left - maps_done_now
        phase_done = (maps_left_new == 0) & (maps_left > 0)
        ready = jnp.where(is_red & phase_done[:, None],
                          (t_next + shuffle[:, 0])[:, None], ready)

        # failure kills — after completions (a task finishing exactly at
        # the failure instant completes: the oracle's completions-first
        # tie order), before admissions
        start_base = start
        if control:
            fired = live[:, None] & (f_t > time[:, None]) \
                & (f_t <= t_next[:, None])
            # shed tasks are out of the system — a failure must not
            # re-dispatch (or failover-rebind) work already refused
            affected = valid & fired & (finish >= _BIG / 2) & ~shed_c
            first_hit = affected & ~hit
            lost_fail = jnp.where(affected, task_len - rem, 0.0)
            rem = jnp.where(affected, task_len, rem)
            running = running & ~affected
            start_base = jnp.where(affected, jnp.float32(_BIG), start_base)
            # re-dispatch: detection/re-queue latency from the failure
            # instant; the first hit moves to the failover slot and pays
            # the re-replication fetch, a second hit restarts in place
            ready = jnp.where(affected,
                              jnp.maximum(ready, f_t + redispatch), ready)
            ready = jnp.where(first_hit, ready + refetch, ready)
            hit = hit | first_hit

        # arrivals: time-shared starts every admissible task; space-shared
        # admits the (priority desc, eligible time, index)-first waiting
        # tasks into the PE slots left free after this epoch's
        # completions.  Instead of ranking through a T×T priority matrix,
        # extract per-VM lexicographic minima max_pes times: the task
        # picked at scan step s has per-VM rank s, and is admitted iff
        # s < free slots on its VM — the same set the engine's rank
        # formulation admits.  The admission key is (prio, elig, idx);
        # all-zero priorities collapse the first stage to a no-op
        # bitwise, and a static fleet makes elig == ready.
        eligible = live[:, None] & not_started \
            & (elig <= (t_next + tie)[:, None]) \
            & (t_next[:, None] < close_t)
        if control:
            # never admit onto a VM that is down at (or fails exactly at)
            # this epoch's instant — the killed set was computed above
            # and a same-instant admission would dodge it
            eligible &= ~((t_next[:, None] >= f_t)
                          & (t_next[:, None] < r_t))
            # SHED at the admission instant (the oracle's pop-time
            # check): queue wait grows pressure, so a task admissible
            # when it arrived may be unmeetable by the time a slot frees
            efin_t = earliest_finish(t_next[:, None], rem_c, mips_t,
                                     xp=jnp)
            shed_t = shed_c | (dl_shed & evaluable
                               & (t_next[:, None] < close_t)
                               & (efin_t > task_deadline))
            eligible &= ~shed_t
            # Priority preemption (DESIGN.md §11): on each full
            # space-shared VM the single weakest still-evictable running
            # task (lowest raw priority, latest index) loses its PE when
            # an eligible pending task strictly outranks it; further
            # victims fall in the repeated same-instant epochs the
            # arrival gate keeps scheduling.  The engine's T×T
            # beats/weaker relations lower as per-VM extrema; the kill
            # reuses the §10 failure op sequence.
            done_f = done_now.astype(jnp.float32)
            vic_cand = pre_onl & running & (n_evict0 < jnp.int32(2))
            full_t = (task_pes - to_task(n_on_vm - per_vm_sum(done_f))) \
                <= 0.5
            el_m = jnp.where(eligible, prio, -_BIG)
            max_el_v = jnp.max(
                jnp.where(cur_oh_b, el_m[..., None], -_BIG), axis=1)
            cand_e = vic_cand & full_t & (to_task(max_el_v) > prio)
            low_m = jnp.where(cand_e, prio, _BIG)
            min_low_v = jnp.min(
                jnp.where(cur_oh_b, low_m[..., None], _BIG), axis=1)
            low = cand_e & (prio == to_task(min_low_v))
            idxe_m = jnp.where(low, idx, -1)
            max_idx_v = jnp.max(
                jnp.where(cur_oh_b, idxe_m[..., None], -1), axis=1)
            evicted = low & (idx == to_task(
                max_idx_v.astype(jnp.float32)).astype(jnp.int32))
            lost_evict = jnp.where(evicted & ~res_onl,
                                   task_len - rem, 0.0)
            e_first = evicted & ~hit
            rem = jnp.where(evicted & ~res_onl, task_len, rem)
            running = running & ~evicted
            start_base = jnp.where(evicted, jnp.float32(_BIG), start_base)
            ready = jnp.where(evicted,
                              jnp.maximum(ready,
                                          t_next[:, None] + redispatch),
                              ready)
            ready = jnp.where(e_first, ready + refetch, ready)
            hit = hit | e_first
            n_evict = n_evict0 + evicted.astype(jnp.int32)
            work_lost = work_lost + jnp.sum(lost_fail, axis=1) \
                + jnp.sum(lost_evict, axis=1)
            free_v = vm_pes - (n_on_vm - per_vm_sum(done_f)
                               - per_vm_sum(evicted.astype(jnp.float32)))
            # BOOST urgency tier (DESIGN.md §11): urgent pending tasks
            # outrank every non-urgent task; ties inside a tier keep the
            # §8 (priority, eligible, index) key.  All-false urgency
            # collapses the extra scan stage to a no-op bitwise.
            urg = (dl_boost & evaluable
                   & (efin_t + dl_slack >= task_deadline)
                   ).astype(jnp.float32)
        else:
            free_v = vm_pes - (n_on_vm
                               - per_vm_sum(done_now.astype(jnp.float32)))
        free_after = to_task(free_v)
        admit = jnp.zeros_like(eligible)
        remaining = eligible
        for s in range(max_pes):
            if control:
                urg_m = jnp.where(remaining, urg, -_BIG)
                max_urg_v = jnp.max(
                    jnp.where(cur_oh_b, urg_m[..., None], -_BIG), axis=1)
                tier = remaining & (urg_m == to_task(max_urg_v))
            else:
                tier = remaining
            prio_m = jnp.where(tier, prio, -_BIG)
            max_prio_v = jnp.max(
                jnp.where(cur_oh_b, prio_m[..., None], -_BIG), axis=1)
            top = tier & (prio_m == to_task(max_prio_v))
            elig_m = jnp.where(top, elig, _BIG)
            min_elig_v = jnp.min(
                jnp.where(cur_oh_b, elig_m[..., None], _BIG), axis=1)
            cand = top & (elig_m == to_task(min_elig_v))
            idx_m = jnp.where(cand, idx, T)
            min_idx_v = jnp.min(
                jnp.where(cur_oh_b, idx_m[..., None], T), axis=1)
            pick = cand & (idx == to_task(
                min_idx_v.astype(jnp.float32)).astype(jnp.int32))
            admit = admit | (pick & (jnp.float32(s) < free_after))
            remaining = remaining & ~pick
        start_now = eligible & (~is_space | admit)
        start = jnp.where(start_now, t_next[:, None], start_base)
        running = running | start_now
        time = jnp.where(live, t_next, time)
        new = (time, rem, running, start, finish, ready, maps_left_new,
               lane_ep + active.astype(jnp.int32), n + 1)
        if control:
            # persist the shed set; reduces of a job with a shed map can
            # never become ready (J = 1 lanes: any shed map dooms the
            # lane's reduces) — marking these orphans ends their lane
            # instead of spinning it to the epoch bound
            map_shed_any = jnp.sum((shed_t & ~is_red).astype(jnp.float32),
                                   axis=1) > 0.5
            shed = shed_t | (valid & is_red & map_shed_any[:, None]
                             & (finish >= _BIG / 2) & ~running)
            new = new + (hit, vm_open, vm_close, n_scale, shed, n_evict,
                         work_lost)
        if trace:
            # --- trace recorder (DESIGN.md §12): observe, never act -------
            # One time-series row per realized epoch, the engine's exact
            # f32 op sequence and one-hot add — bitwise in interpret mode
            # (tests/test_telemetry.py).  The event log stays engine/refsim
            # scope to bound kernel churn.
            actf = active.astype(jnp.float32)
            if control:
                new_shed = shed & ~shed0
                n_fail = jnp.sum(affected.astype(jnp.float32), axis=1)
                n_shed = jnp.sum(new_shed.astype(jnp.float32), axis=1)
                n_ev = jnp.sum(evicted.astype(jnp.float32), axis=1)
                q_d, b_f, n_o = qdepth, busy_frac, n_open
            else:
                # the control hook's observables over the static lease
                # windows, evaluated on the pre-update carry
                unfin_t = valid & (finish0 >= _BIG / 2)
                q_d = jnp.sum((unfin_t & (start0 >= _BIG / 2)
                               & (ready0c <= t0[:, None]))
                              .astype(jnp.float32), axis=1)
                busy_v = per_vm_sum(runf) > 0.5
                open_v = vm_valid_t & (vm_start + spinup <= t0[:, None]) \
                    & (t0[:, None] < vm_stop)
                n_o = jnp.sum(open_v.astype(jnp.float32), axis=1)
                b_f = (jnp.sum((open_v & busy_v).astype(jnp.float32),
                               axis=1) / jnp.maximum(n_o, 1.0))
                n_fail = n_shed = n_ev = jnp.zeros_like(actf)
            # flat (C * 8) row layout: column j is field j % 8 of row
            # j // 8 (shifts, not a 3-D reshape, which Mosaic refuses)
            ts = st[-1]
            col = jax.lax.broadcasted_iota(jnp.int32, (1, ts.shape[1]), 1)
            row = ((col >> 3) == lane_ep[:, None]).astype(jnp.float32) \
                * actf[:, None]
            vals = jnp.zeros_like(ts)
            for k, v in enumerate((time, q_d, b_f, n_o, actf, n_fail,
                                   n_shed, n_ev)):
                vals = jnp.where((col & 7) == k, v[:, None], vals)
            ts = ts + row * vals
            new = new + (ts,)
        return pack(new)

    st = jax.lax.while_loop(cond, epoch, state)
    # outputs mirror the carry layout minus the call-local epoch counter
    for ref, x in zip(out_refs, st[:8] + st[9:]):
        ref[...] = x


def initial_state(task_len, ready0, is_red, valid, vm_start=None,
                  vm_stop=None, vm_auto=None, trace_capacity=None):
    """The canonical t=0 carry state, built with the exact constants the
    kernel used to initialize in VMEM (so feeding it through the state
    inputs is a bitwise no-op vs the pre-carry kernel).  Layout — every
    leaf 2-D for the BlockSpecs: ``(time (N,1) f32, rem (N,T) f32,
    running (N,T) i32, start (N,T) f32, finish (N,T) f32, ready (N,T)
    f32, maps_left (N,1) i32, n_epochs (N,1) i32)``.

    Passing ``vm_auto`` (with ``vm_start``/``vm_stop``) appends the seven
    control leaves (DESIGN.md §10–11): ``hit (N,T) i32, vm_open (N,V)
    f32, vm_close (N,V) f32, n_scale (N,1) i32, shed (N,T) i32, n_evict
    (N,T) i32, work_lost (N,1) f32`` — reserve VMs start with no realized
    lease (``vm_open = _BIG``) until the control rule opens one, exactly
    the engine's ``_epoch_setup`` initialization.

    ``trace_capacity`` (DESIGN.md §12) appends the per-epoch time-series
    leaf ``ts (N, C*8) f32`` at the end — ``C`` rows of the 8-column
    ``telemetry.TS_COLUMNS`` layout, flattened 2-D for the BlockSpecs."""
    N, T = task_len.shape
    base = (jnp.zeros((N, 1), jnp.float32),
            task_len,
            jnp.zeros((N, T), jnp.int32),
            jnp.full((N, T), _BIG, jnp.float32),
            jnp.full((N, T), _BIG, jnp.float32),
            ready0,
            jnp.sum(((valid != 0) & ~(is_red != 0)).astype(jnp.int32),
                    axis=1, keepdims=True),
            jnp.zeros((N, 1), jnp.int32))
    if vm_auto is not None:
        base = base + (
            jnp.zeros((N, T), jnp.int32),
            jnp.where(vm_auto != 0, jnp.float32(_BIG),
                      vm_start.astype(jnp.float32)),
            vm_stop.astype(jnp.float32),
            jnp.zeros((N, 1), jnp.int32),
            jnp.zeros((N, T), jnp.int32),
            jnp.zeros((N, T), jnp.int32),
            jnp.zeros((N, 1), jnp.float32))
    if trace_capacity is not None:
        base = base + (jnp.zeros((N, int(trace_capacity) * 8),
                                 jnp.float32),)
    return base


def _mr_epoch_impl(task_len, task_vm, ready0, is_red, valid, shuffle,
                   vm_mips, vm_pes, sched_policy=None, vm_start=None,
                   vm_stop=None, spinup=None, prio=None, vm_valid=None,
                   vm_fail=None, vm_restore=None, vm_auto=None,
                   ctl_policy=None, ctl_queue=None, ctl_busy=None,
                   redispatch=None, task_vm2=None, refetch=None,
                   task_deadline=None, dl_policy=None, dl_slack=None,
                   preempt=None, preempt_resume=None, state=None,
                   *, tile: int = 64, max_pes: int = 8,
                   interpret: bool = True, epoch_limit: int | None = None,
                   control: bool = False, trace: bool = False,
                   block_lanes: int | None = None):
    """All args lead with the scenario dim N (padded to a tile multiple).

    task_len/ready0: (N,T) f32; task_vm: (N,T) i32; is_red/valid: (N,T) i32;
    shuffle: (N,1) f32; vm_mips/vm_pes: (N,V) f32; sched_policy: (N,1) i32
    (0 time-shared | 1 space-shared; defaults to all time-shared).
    Elasticity lane data (DESIGN.md §8): vm_start/vm_stop: (N,V) f32 lease
    windows (stop carries the 1e30 +inf stand-in, never ``inf``); spinup:
    (N,1) f32; prio: (N,T) f32 space-shared admission priorities — the
    defaults (static fleet, zero priorities) reproduce the pre-elastic
    schedule bit for bit.

    Control lane data (DESIGN.md §10, required iff the static ``control``
    flag is on): vm_valid/vm_auto: (N,V) i32; vm_fail/vm_restore: (N,V)
    f32 seeded failure/restore instants (_BIG = never); ctl_policy: (N,1)
    i32 policy id; ctl_queue/ctl_busy/redispatch: (N,1) f32 thresholds +
    re-dispatch latency; task_vm2: (N,T) i32 failover binding; refetch:
    (N,T) f32 re-replication fetch toward it.  Graceful degradation
    (DESIGN.md §11, also control-gated): task_deadline: (N,T) f32
    (``_BIG`` = none); dl_policy: (N,1) i32 (NONE/SHED/BOOST);
    dl_slack: (N,1) f32 BOOST window; preempt/preempt_resume: (N,1) i32
    knobs.  ``control=False`` lowerings carry none of this — the
    open-loop kernel is byte-for-byte the pre-control one.

    ``state``/``epoch_limit`` make the kernel *resumable* (DESIGN.md §9):
    ``state`` is a full carry in :func:`initial_state` layout (default —
    the t=0 state; when given, the ``ready0`` argument is superseded by
    ``state[5]``) and ``epoch_limit`` caps how many event epochs this
    call advances (default — the engine bound: ``2T + 2`` open-loop, the
    additive worst case ``7T + V + 3`` under control, i.e. run to
    completion; per-lane realized counts still honor the data-dependent
    ``engine._lane_bound``).  The compacted driver
    (``ops.epoch_schedule_compact``) steps K-epoch chunks over gathered
    active lanes this way.

    ``max_pes`` must be >= the largest per-VM PE count in the batch (it
    bounds the static admission scan); ``tile`` lanes share one early-exit
    epoch loop.  Returns the advanced carry state (same 8-leaf layout;
    15 leaves under control).  ``ready0`` may be ``None`` when ``state``
    is given (the resume path never reads it) — required so the compacted
    driver can donate the state pytree without also holding a live alias
    of its ready leaf in the argument list.

    ``block_lanes`` (static) re-tiles each ``tile``-lane macro tile
    across a second, minor grid dimension of ``tile // block_lanes``
    steps of ``block_lanes`` lanes each.  On real TPU hardware the minor
    grid dimension iterates sequentially per core, so Pallas's pipeline
    emitter double-buffers the HBM→VMEM input streams across consecutive
    blocks — the next block's operands DMA in while the current block's
    event loop runs (the ``flash_attention`` kernel's mechanism).  Lanes
    are independent, so the multi-tile lowering is bitwise-equal to the
    single-tile one (asserted in interpret mode); ``None`` keeps the
    original one-dimensional grid and compiled-shape cache keys.

    ``trace=True`` (static, DESIGN.md §12) appends the per-epoch
    time-series leaf ``ts (N, C*8) f32`` to the carry — one
    ``telemetry.TS_COLUMNS`` row per realized epoch, written by the
    engine recorder's exact one-hot add, so the rows are **bitwise** the
    engine's in interpret mode.  Open-loop traces additionally require
    ``vm_valid`` (the open-VM observable); the event log stays
    engine/refsim scope.
    """
    N, T = task_len.shape
    V = vm_mips.shape[1]
    if sched_policy is None:
        sched_policy = jnp.zeros((N, 1), jnp.int32)
    if vm_start is None:
        vm_start = jnp.zeros((N, V), jnp.float32)
    if vm_stop is None:
        vm_stop = jnp.full((N, V), _BIG, jnp.float32)
    if spinup is None:
        spinup = jnp.zeros((N, 1), jnp.float32)
    if prio is None:
        prio = jnp.zeros((N, T), jnp.float32)
    ctl = (vm_valid, vm_fail, vm_restore, vm_auto, ctl_policy, ctl_queue,
           ctl_busy, redispatch, task_vm2, refetch, task_deadline,
           dl_policy, dl_slack, preempt, preempt_resume)
    if control and any(x is None for x in ctl):
        raise ValueError("mr_epoch: control=True requires all fifteen "
                         "control lane-data arrays (vm_valid .. "
                         "preempt_resume)")
    if trace and vm_valid is None:
        raise ValueError("mr_epoch: trace=True requires vm_valid (the "
                         "open-VM observable needs the real-VM mask)")
    if state is None:
        if ready0 is None:
            raise ValueError("mr_epoch: ready0 is required when no resume "
                             "state is given (it seeds initial_state)")
        state = initial_state(
            task_len, ready0, is_red, valid,
            vm_start=vm_start, vm_stop=vm_stop,
            vm_auto=vm_auto if control else None,
            trace_capacity=(timeseries_capacity(T, V, control)
                            if trace else None))
    if epoch_limit is None:
        epoch_limit = 7 * T + V + 3 if control else 2 * T + 2
    tile = min(tile, N)
    while N % tile:
        tile //= 2
    block = tile
    if block_lanes is not None:
        # minor lane-tile grid dim: pow2 halving mirrors the tile
        # adjustment so any (tile, block_lanes) request lowers cleanly
        block = max(1, min(int(block_lanes), tile))
        while tile % block:
            block //= 2
    if not interpret and block % 8 and block != N:
        # Mosaic tiles the second-minor (lane) dim of every block by 8
        raise ValueError(
            f"mr_epoch: a compiled kernel needs lane blocks that are a "
            f"multiple of 8 or span all N={N} lanes; tile={tile}, "
            f"block_lanes={block_lanes} give blocks of {block} (pad N to "
            f"a multiple of 8)")
    nsub = tile // block
    if block_lanes is None:
        grid = (N // tile,)

        def row(i):
            return (i, 0)
    else:
        # (macro tile, sub-block) grid: the minor dim is sequential on
        # TPU, giving Pallas's pipeline emitter the double-buffering
        # window described in the docstring
        grid = (N // tile, nsub)

        def row(i, j):
            return (i * nsub + j, 0)

    spec_t = pl.BlockSpec((block, T), row)
    spec_1 = pl.BlockSpec((block, 1), row)
    spec_v = pl.BlockSpec((block, V), row)
    data = [task_len, task_vm, state[5], is_red, valid, shuffle,
            vm_mips, vm_pes, sched_policy, vm_start, vm_stop, spinup, prio]
    data_specs = [spec_t, spec_t, spec_t, spec_t, spec_t, spec_1,
                  spec_v, spec_v, spec_1, spec_v, spec_v, spec_1, spec_t]
    if control:
        data += [vm_valid, vm_fail, vm_restore, vm_auto, ctl_policy,
                 ctl_queue, ctl_busy, redispatch, task_vm2, refetch,
                 task_deadline, dl_policy, dl_slack, preempt,
                 preempt_resume]
        data_specs += [spec_v, spec_v, spec_v, spec_v, spec_1, spec_1,
                       spec_1, spec_1, spec_t, spec_t, spec_t, spec_1,
                       spec_1, spec_1, spec_1]
    elif trace:
        data += [vm_valid]
        data_specs += [spec_v]
    state_in = [state[0], state[1], state[2], state[3], state[4],
                state[6], state[7]]
    state_in_specs = [spec_1, spec_t, spec_t, spec_t, spec_t, spec_1,
                      spec_1]
    state_specs = (spec_1, spec_t, spec_t, spec_t, spec_t, spec_t,
                   spec_1, spec_1)
    if control:
        state_in += [state[8], state[9], state[10], state[11], state[12],
                     state[13], state[14]]
        state_in_specs += [spec_t, spec_v, spec_v, spec_1, spec_t,
                           spec_t, spec_1]
        state_specs = state_specs + (spec_t, spec_v, spec_v, spec_1,
                                     spec_t, spec_t, spec_1)
    if trace:
        spec_ts = pl.BlockSpec((block, state[-1].shape[1]), row)
        state_in += [state[-1]]
        state_in_specs += [spec_ts]
        state_specs = state_specs + (spec_ts,)
    state_shapes = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                         for x in state)
    out = pl.pallas_call(
        functools.partial(_kernel, T=T, V=V, max_pes=max_pes,
                          epoch_bound=epoch_limit, control=control,
                          trace=trace),
        grid=grid,
        in_specs=data_specs + state_in_specs,
        out_specs=state_specs,
        out_shape=state_shapes,
        interpret=interpret,
        name="mr_epoch",
    )(*data, *state_in)
    return out


_MR_STATIC = ("tile", "interpret", "max_pes", "epoch_limit", "control",
              "trace", "block_lanes")

mr_epoch = jax.jit(_mr_epoch_impl, static_argnames=_MR_STATIC)
# Resume-path variant that donates the ``state`` carry pytree: the
# output leaves match the input state's shapes exactly, so XLA reuses
# the buffers in place instead of copying the full carry every K-epoch
# chunk.  Callers (``ops.epoch_schedule_compact``) must pass
# ``ready0=None`` and never re-read a donated state object.
mr_epoch_donated = jax.jit(_mr_epoch_impl, static_argnames=_MR_STATIC,
                           donate_argnames="state")
