"""Sweep-throughput benchmark: the TPU adaptation's headline number.

CloudSim runs one scenario per process; the vectorized engine runs a whole
parameter grid per ``pjit`` call.  We measure scenarios/second on the host
CPU (single device) and — because the sweep is embarrassingly parallel with
zero collectives (verified by the dry-run) — the pod-scale figure is
devices × single-device throughput, reported as the derived column.

The measured path is the declarative API end to end:
:func:`~repro.core.sweep.zip_`-ed random axes compiled and executed by
``SweepPlan.run()`` (encode + simulate + labeled readback per call) under
the adaptive execution schedule (DESIGN.md §6 — shape buckets + batch
early exit), so each row also records the *realized* epoch count next to
the worst-case ``2T + 2`` bound the pre-adaptive engine always paid.

Mixed-policy gap: scheduling policies differ in how many event epochs a
scenario intrinsically needs (space-shared admission serializes starts), so
comparing a mixed grid's scen/s against the all-time-shared row conflates
policy mixing with policy *cost*.  The ``unifpol`` row therefore runs the
mixed grid's exact workload as six per-combination uniform plans (summed
wall time) — the relevant baseline for "what does mixing policies inside
one batch cost?".  The recorded gap is mixed vs that.

Locality rows: the ``_locality_b*`` rows re-run the workload with the
storage subsystem on (DESIGN.md §7 — skewed hot-spot placement,
replication 1–3 per lane, LOCALITY binding), timing the placement hash +
candidate-masked binding scan + fetch-delay ops the block store adds to
the encode path; each row records its placement/replication meta.

Elastic rows: the ``_elastic_b*`` rows run the workload as a dynamic
fleet (DESIGN.md §8 — Poisson job arrivals as ``job_submit``, per-VM
lease windows with spinup, priorities per lane, and *mixed* scheduling
policies: priorities and window-gated admission only bite under
space-shared queues), timing the lease-availability masking +
window-gated admission the elastic epoch loop adds.  Because the row
mixes sched policies, its honest comparator is the ``mixedpol`` row
(which pays the same policy-mixing tax, PR 3), NOT the all-time-shared
plain row — the recorded gap is ``elastic_gap_vs_mixedpol``; each row
records its arrival-rate/process/policy-mix meta.

Control rows: the ``_control_b*`` rows run the elastic workload through
the closed-loop lowering (DESIGN.md §10 — per-lane seeded VM
failure/restore streams with failover re-dispatch, plus the AUTOSCALE
per-epoch hook over a reserve-free fleet, so the hook is evaluated every
epoch but never strands work on an unopened reserve), timing the fail
event join + kill/redispatch ops + hook contraction the control loop
adds.  The workload *is* the elastic grid plus control columns, so the
honest comparator is the elastic row — timed min-of-alternating-A/B
(like the compaction pair) and recorded as ``control_gap_vs_elastic``.

Traced row: the ``_traced_b64`` row times the deadline workload at the
engine level with the in-loop trace lowering on (DESIGN.md §12 — one-hot
time-series scatter + bounded event log inside the epoch loop), min-of-
alternating-A/B against the same jitted call with tracing off, recorded
as ``trace_gap_vs_plain``.  The trace-*off* side is bitwise the plain
path (the lowering inserts no ops when off) — ``bench_smoke`` guards
that identity with a tightened budget on the plain b64 row.

``python -m benchmarks.sweep_throughput`` records the rows plus
backend/device metadata (and a small calibration figure that lets CI gate
regressions across machine speeds, see ``benchmarks.bench_smoke``) to
``BENCH_sweep.json`` at the repo root, the perf-trajectory baseline.
"""
from __future__ import annotations

import functools
import json
import multiprocessing
import pathlib
import platform
import time

import jax
import numpy as np

from repro.core import (BindingPolicy, ControlPolicy, Placement,
                        SchedPolicy, control as ctl, costmodel, elasticity,
                        engine, telemetry)
from repro.core.sweep import axis, product, zip_

EPOCH_BOUND = 2 * 21 + 2   # the pre-adaptive engine's static bound at T=21
LOC_PLACEMENT = int(Placement.SKEWED)   # locality rows' placement variant
LOC_REPLICATION = "1-3"                 # … and replication-factor range
ELASTIC_RATE = 0.002                    # elastic rows' Poisson arrival rate
TAIL_MAPS = 40                          # tailheavy rows' uniform map count
TAIL_PAD = TAIL_MAPS + 1                # … and their task padding (T=41)
CONTROL_RATE = 0.0005                   # control rows' per-VM failure rate
CONTROL_REPAIR = 600.0                  # … and repair delay (seconds)


def _random_cols(n, rng, mixed_policies=False, locality=False,
                 elastic=False, tailheavy=False, control=False,
                 deadline=False):
    cols = dict(
        n_maps=rng.integers(1, 21, n).astype(np.int32),
        n_reduces=np.ones(n, np.int32),
        n_vms=rng.integers(1, 10, n).astype(np.int32),
        vm_mips=rng.choice([250.0, 500.0, 1000.0], n).astype(np.float32),
        vm_pes=rng.choice([1.0, 2.0, 4.0], n).astype(np.float32),
        vm_cost=rng.choice([1.0, 2.0, 4.0], n).astype(np.float32),
        job_length=rng.choice([362880.0, 725760.0, 1451520.0], n
                              ).astype(np.float32),
        job_data=rng.choice([2e5, 4e5, 8e5], n).astype(np.float32),
    )
    if mixed_policies:
        cols["sched_policy"] = rng.integers(0, 2, n).astype(np.int32)
        cols["binding_policy"] = rng.integers(0, 3, n).astype(np.int32)
    if locality:
        # the storage-subsystem workload (DESIGN.md §7): block store on,
        # skewed hot-spot placement, LOCALITY bound per lane — the
        # placement hash + candidate-masked binding scan now sit on the
        # encode path this row times
        cols["binding_policy"] = np.full(
            n, int(BindingPolicy.LOCALITY), np.int32)
        cols["storage_enabled"] = np.ones(n, np.float32)
        cols["replication"] = rng.integers(1, 4, n).astype(np.int32)
        cols["placement"] = np.full(n, LOC_PLACEMENT, np.int32)
        cols["block_size_mb"] = rng.choice([8192.0, 32768.0], n
                                           ).astype(np.float32)
        cols["storage_seed"] = rng.integers(0, 1000, n).astype(np.int32)
    if elastic or control or deadline:
        # the dynamic-fleet workload (DESIGN.md §8): Poisson job arrivals
        # against per-VM lease windows with spinup and mixed priorities —
        # the availability masking + window-gated admission now sit on the
        # epoch loop this row times.  Windows are generous (open-ended or
        # arrival + 40k s) so lanes realize full schedules, not strands.
        cols["job_submit"] = elasticity.arrival_times(
            n, rate=ELASTIC_RATE, seed=n)
        start = rng.choice([0.0, 500.0, 2000.0], (n, 9)).astype(np.float32)
        cols["vm_start"] = start
        cols["vm_stop"] = np.where(rng.random((n, 9)) < 0.5, 1e30,
                                   start + cols["job_submit"][:, None]
                                   + 40000.0).astype(np.float32)
        cols["spinup_delay"] = rng.choice([0.0, 60.0], n).astype(np.float32)
        cols["task_prio"] = rng.integers(0, 3, (n, 21)).astype(np.float32)
        cols["sched_policy"] = rng.integers(0, 2, n).astype(np.int32)
    if control or deadline:
        # the closed-loop workload (DESIGN.md §10): the elastic grid plus
        # per-lane seeded failure/restore streams (one flat counter-hash
        # draw resliced per lane — same idiom, distinct instants) and the
        # AUTOSCALE hook over a reserve-free fleet: the fail event joins
        # t_next, kills re-dispatch after a detection delay, and the hook
        # contraction runs every epoch — without opened-reserve dynamics
        # that would strand time-shared lanes and benchmark stranding
        # instead of control cost
        f, r = ctl.failure_times(9 * n, rate=CONTROL_RATE, seed=n,
                                 repair_delay=CONTROL_REPAIR)
        cols["vm_fail"] = np.asarray(f, np.float32).reshape(n, 9)
        cols["vm_restore"] = np.asarray(r, np.float32).reshape(n, 9)
        cols["redispatch_delay"] = rng.choice([0.0, 30.0], n
                                              ).astype(np.float32)
        cols["control_policy"] = np.full(n, int(ControlPolicy.AUTOSCALE),
                                         np.int32)
        cols["ctl_queue"] = rng.choice([2.0, 8.0], n).astype(np.float32)
        cols["ctl_busy"] = np.full(n, 0.5, np.float32)
    if deadline:
        # the graceful-degradation workload (DESIGN.md §11): the control
        # grid plus per-task deadlines with SHED/BOOST lanes and priority
        # preemption armed — the earliest-finish admission predicate, the
        # urgency tier and the per-VM eviction scan now sit on the epoch
        # loop this row times.  Half the deadlines are the _BIG sentinel
        # (absent), the rest clear the job's submit time by construction
        # so the plan validates; slack varies so BOOST lanes fire at
        # different urgencies.
        dl = (cols["job_submit"][:, None]
              + rng.choice([3000.0, 12000.0, 48000.0], (n, 21))
              ).astype(np.float32)
        cols["task_deadline"] = np.where(rng.random((n, 21)) < 0.5,
                                         1e30, dl).astype(np.float32)
        cols["deadline_policy"] = rng.integers(1, 3, n).astype(np.int32)
        cols["deadline_slack"] = rng.choice([0.0, 120.0], n
                                            ).astype(np.float32)
        cols["preempt"] = np.ones(n, np.int32)
        cols["preempt_resume"] = rng.integers(0, 2, n).astype(np.int32)
    if tailheavy:
        # the sparse-compaction workload (DESIGN.md §9): every lane runs
        # the SAME 40-map space-shared shape — one policy combo, one
        # shape, so the static policy/shape bucketing cannot isolate the
        # tail — but ~1/8 of lanes are stragglers stuck on a single 1-PE
        # VM: 40 sequential admissions -> ~2·T realized epochs, while
        # the rest spread their maps over 12-36 PEs and retire within a
        # few epochs.  The tail is *data-dependent inside one compiled
        # bucket*, exactly the regime compaction targets: the dense
        # driver steps all lanes to the last straggler, the compacted
        # driver steps only the pow2-padded survivors.  Lane 0 is always
        # a straggler so every batch size realizes >= 20 epochs (the
        # bench_smoke gate asserts it).
        strag = rng.random(n) < 1.0 / 8.0
        strag[0] = True
        cols["n_maps"] = np.full(n, TAIL_MAPS, np.int32)
        cols["n_vms"] = np.where(strag, 1,
                                 rng.integers(6, 10, n)).astype(np.int32)
        cols["vm_pes"] = np.where(strag, 1.0,
                                  rng.choice([2.0, 4.0], n)
                                  ).astype(np.float32)
        cols["sched_policy"] = np.ones(n, np.int32)
        cols["binding_policy"] = np.zeros(n, np.int32)
    return cols


def _plan_of(cols, pad_tasks=21):
    # one zipped dimension: all columns advance together (a labeled random
    # scenario list, not a cartesian grid)
    plan = product(zip_(*(axis(k, v) for k, v in cols.items())))
    return plan.replace(pad_tasks=pad_tasks, pad_vms=9)


def _random_plan(n, rng, mixed_policies=False, locality=False,
                 elastic=False, tailheavy=False, control=False,
                 deadline=False):
    return _plan_of(_random_cols(n, rng, mixed_policies, locality, elastic,
                                 tailheavy, control, deadline),
                    pad_tasks=TAIL_PAD if tailheavy else 21)


def _time_runs(run, reps=7):
    """(mean_seconds, min_seconds, last_result) over ``reps`` timed calls.

    The mean is the trend-tracking figure; the min is the noise floor the
    CI gate (``bench_smoke``) compares against — gating a local min-of-7
    against a recorded *mean* left no headroom whenever the machine-speed
    calibration drifted between samples.  ``reps=7`` matches the gate's
    min-of-7: this host's noise is bimodal on minute timescales, and a
    recorded min-of-3 regularly missed the fast phase the min-of-15
    calibration catches, skewing the row/calibration ratio the gate
    budgets on."""
    run()                                       # compile + warm caches
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        res = run()
        times.append(time.perf_counter() - t0)
    return sum(times) / reps, min(times), res


def _time_ab(run_a, run_b, reps=7):
    """Min-of-alternating-A/B: interleave the two variants' timed calls so
    this host's bimodal slow phases hit both sides equally — timing A's
    seven reps back-to-back and then B's lets one variant land entirely in
    a fast phase and fabricate a gap.  Returns ``(mean_a, min_a, mean_b,
    min_b)`` in seconds; the mins are the noise floors the recorded
    A-vs-B gaps use."""
    run_a()                                     # compile + warm caches
    run_b()
    times_a, times_b = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_a()
        times_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_b()
        times_b.append(time.perf_counter() - t0)
    return (sum(times_a) / reps, min(times_a),
            sum(times_b) / reps, min(times_b))


def throughput_rows(batch_sizes=(64, 512, 2048), reps=7,
                    mixed_policies=False, locality=False, elastic=False):
    rows = []
    tag = ("_elastic" if elastic else "_locality" if locality
           else "_mixedpol" if mixed_policies else "")
    meta = None
    if locality:
        meta = {"placement": Placement(LOC_PLACEMENT).name.lower(),
                "replication": LOC_REPLICATION, "storage": True}
    elif elastic:
        meta = {"arrival": "poisson", "arrival_rate": ELASTIC_RATE,
                "leases": True, "spinup": "0|60",
                "sched_policy": "mixed"}
    for n in batch_sizes:
        # seed == batch size: every b{n} row draws the same base columns
        # regardless of which batch sizes the call sweeps, so variant rows
        # (plain / mixedpol / locality / elastic) at one n are the *same
        # workload* and their recorded gaps measure the variant, not rng
        # drift
        plan = _random_plan(n, np.random.default_rng(n), mixed_policies,
                            locality, elastic)
        dt, dt_min, res = _time_runs(plan.run, reps)
        rows.append((f"sweep_throughput{tag}_b{n}", dt * 1e6, dt_min * 1e6,
                     f"{n / dt:.0f}_scen/s",
                     int(res["realized_epochs"].max()), meta))
    return rows


def tailheavy_rows(batch_sizes=(64, 2048), reps=7):
    """Dense vs compacted execution on the tail-heavy grid (DESIGN.md §9).

    The pair of rows per batch size is timed min-of-alternating-A/B
    (:func:`_time_ab`): A is the dense bucketed ``run()``, B the same plan
    with ``compact="auto"`` — the auto interval and the bucket boundaries
    both come from the measured cost model.  The compact row's meta
    records its ``compaction_gap_vs_dense`` (min-vs-min; negative =
    compaction is faster) plus the host-chattiness census at the pinned
    ``auto_k`` — full pulls / scalar pulls / dispatches from a
    ``report=True`` replay — which ``bench_smoke`` re-derives and gates
    (the census is deterministic given the grid and the interval, unlike
    the wall times)."""
    rows = []
    for n in batch_sizes:
        plan = _random_plan(n, np.random.default_rng(n), tailheavy=True)
        res = [None]

        def run_compact(plan=plan, res=res):
            res[0] = plan.run(compact="auto")

        dt_a, min_a, dt_b, min_b = _time_ab(plan.run, run_compact, reps)
        realized = int(res[0]["realized_epochs"].max())
        k_auto = costmodel.default_cost_model().compact_interval(n, TAIL_PAD)
        # census replay at the *pinned* interval: machine-independent, so
        # a smoke run on any host can compare its own census 1:1
        _, rep = plan.run(compact=k_auto, report=True)
        tail = f"1/8_stragglers_{TAIL_MAPS}maps_1vm_spaceshared"
        rows.append((f"sweep_throughput_tailheavy_b{n}", dt_a * 1e6,
                     min_a * 1e6, f"{n / dt_a:.0f}_scen/s", realized,
                     {"tail": tail}))
        rows.append((f"sweep_throughput_tailheavy_compact_b{n}",
                     dt_b * 1e6, min_b * 1e6, f"{n / dt_b:.0f}_scen/s",
                     realized,
                     {"tail": tail,
                      "compact": "auto", "auto_k": k_auto,
                      "timing": "min_of_alternating_ab",
                      "compaction_gap_vs_dense": round(min_b / min_a - 1.0,
                                                       4),
                      "census": {"k": k_auto,
                                 "compaction_syncs": rep.compaction_syncs,
                                 "scalar_syncs": rep.scalar_syncs,
                                 "dispatches": rep.dispatches}}))
    return rows


def compact_loop_rows(batch_sizes=(64, 2048), reps=7):
    """The dispatch-lean compact loop vs the legacy per-round-sync loop
    (DESIGN.md §13) at the *engine* level.

    Both sides run :func:`engine.simulate_batch_arrays_compact` on the
    tail-heavy batch at the same measured-cost interval K; the only
    difference is the host loop: A (``legacy=True``) reproduces the
    pre-lean driver — a full activity-mask device->host pull every round,
    host-side argsort-free compaction order, no buffer donation — while B
    is the lean loop — one fused 2-scalar pull per round, the on-device
    active-first permutation materialized only on compacting rounds, and
    carries/stores donated across the stepper and scatter calls.  Timed
    min-of-alternating-A/B; the lean row's meta records
    ``lean_speedup_vs_legacy`` (min-vs-min), both sides' sync/dispatch
    census, and the cost coefficients that picked K."""
    rows = []
    cost = costmodel.default_cost_model()
    for n in batch_sizes:
        batch = _random_plan(n, np.random.default_rng(n),
                             tailheavy=True).arrays()
        k = cost.compact_interval(n, TAIL_PAD)
        realized = [0]

        def run_legacy(batch=batch, k=k):
            out, _ = engine.simulate_batch_arrays_compact(batch, k=k,
                                                          legacy=True)
            jax.block_until_ready(out)

        def run_lean(batch=batch, k=k, realized=realized):
            out, rz = engine.simulate_batch_arrays_compact(batch, k=k)
            jax.block_until_ready(out)
            realized[0] = int(rz)

        dt_a, min_a, dt_b, min_b = _time_ab(run_legacy, run_lean, reps)
        st_legacy, st_lean = {}, {}
        engine.simulate_batch_arrays_compact(batch, k=k, legacy=True,
                                             stats=st_legacy)
        engine.simulate_batch_arrays_compact(batch, k=k, stats=st_lean)
        census = {"k": k,
                  "legacy": {key: st_legacy[key] for key in
                             ("dispatches", "syncs", "scalar_syncs",
                              "compactions")},
                  "lean": {key: st_lean[key] for key in
                           ("dispatches", "syncs", "scalar_syncs",
                            "compactions")}}
        rows.append((f"sweep_throughput_compactloop_legacy_b{n}",
                     dt_a * 1e6, min_a * 1e6, f"{n / dt_a:.0f}_scen/s",
                     realized[0],
                     {"k": k, "loop": "legacy_per_round_sync",
                      "timing": "min_of_alternating_ab"}))
        rows.append((f"sweep_throughput_compactloop_lean_b{n}",
                     dt_b * 1e6, min_b * 1e6, f"{n / dt_b:.0f}_scen/s",
                     realized[0],
                     {"k": k, "loop": "lean_scalar_sync_donated",
                      "donate": True,
                      "timing": "min_of_alternating_ab",
                      "lean_speedup_vs_legacy": round(min_a / min_b, 4),
                      "census": census,
                      "cost_model": {"dispatch_us": cost.dispatch_us,
                                     "sync_us": cost.sync_us,
                                     "epoch_lane_us": cost.epoch_lane_us,
                                     "device": cost.device,
                                     "source": cost.source}}))
    return rows


def control_rows(batch_sizes=(64, 2048), reps=7):
    """Closed-loop control vs the open-loop elastic grid (DESIGN.md §10).

    The pair per batch size is timed min-of-alternating-A/B
    (:func:`_time_ab`): A is the elastic plan (same rng(n) base draw), B
    the same draw with the control columns on — seeded failure/restore
    streams, redispatch, the AUTOSCALE hook.  Only the control row is
    recorded; its meta carries ``control_gap_vs_elastic`` (min-vs-min
    against the alternated A side, so the gap measures the lowering, not
    machine drift)."""
    rows = []
    for n in batch_sizes:
        plan_a = _random_plan(n, np.random.default_rng(n), elastic=True)
        plan_b = _random_plan(n, np.random.default_rng(n), control=True)
        res = [None]

        def run_control(plan_b=plan_b, res=res):
            res[0] = plan_b.run()

        dt_a, min_a, dt_b, min_b = _time_ab(plan_a.run, run_control, reps)
        injected = int(np.asarray(res[0]["failures_injected"]).sum())
        rows.append((f"sweep_throughput_control_b{n}", dt_b * 1e6,
                     min_b * 1e6, f"{n / dt_b:.0f}_scen/s",
                     int(res[0]["realized_epochs"].max()),
                     {"failure_rate": CONTROL_RATE,
                      "repair_delay": CONTROL_REPAIR,
                      "policy": "autoscale_hook_no_reserves",
                      "failures_injected": injected,
                      "timing": "min_of_alternating_ab",
                      "control_gap_vs_elastic": round(min_b / min_a - 1.0,
                                                      4)}))
    return rows


def deadline_rows(batch_sizes=(64, 2048), reps=7):
    """Graceful degradation vs the closed-loop control grid (DESIGN.md §11).

    The pair per batch size is timed min-of-alternating-A/B
    (:func:`_time_ab`): A is the control plan (same rng(n) base draw), B
    the same draw with the deadline columns on — per-task deadlines,
    SHED/BOOST policies, priority preemption with and without
    partial-progress resume.  Only the deadline row is recorded; its meta
    carries ``deadline_gap_vs_control`` (min-vs-min against the alternated
    A side), plus the realized shed/preemption census so the row proves
    the degradation machinery actually fired."""
    rows = []
    for n in batch_sizes:
        plan_a = _random_plan(n, np.random.default_rng(n), control=True)
        plan_b = _random_plan(n, np.random.default_rng(n), deadline=True)
        res = [None]

        def run_deadline(plan_b=plan_b, res=res):
            res[0] = plan_b.run()

        dt_a, min_a, dt_b, min_b = _time_ab(plan_a.run, run_deadline, reps)
        shed = int(np.asarray(res[0]["shed_tasks"]).sum())
        pre = int(np.asarray(res[0]["preemptions"]).sum())
        rows.append((f"sweep_throughput_deadline_b{n}", dt_b * 1e6,
                     min_b * 1e6, f"{n / dt_b:.0f}_scen/s",
                     int(res[0]["realized_epochs"].max()),
                     {"policy_mix": "shed|boost", "preempt": True,
                      "shed_tasks": shed, "preemptions": pre,
                      "timing": "min_of_alternating_ab",
                      "deadline_gap_vs_control": round(min_b / min_a - 1.0,
                                                       4)}))
    return rows


def traced_rows(n=64, reps=7):
    """In-loop tracing vs the plain engine path (DESIGN.md §12).

    The pair is timed min-of-alternating-A/B at the *engine* level — the
    same jitted :func:`engine.simulate_batch_arrays` call on the deadline
    b64 batch (every subsystem lit, so all event kinds can fire) with the
    trace lowering off (A) vs on (B).  Only the traced row is recorded;
    its meta carries ``trace_gap_vs_plain`` (min-vs-min — what the one-hot
    time-series scatter + bounded event log cost *inside* the epoch loop),
    the event census from a warm traced call, and — the observability
    contract of DESIGN.md §12.4 — the run provenance and cost-model
    coefficients (with their measured/cache/fallback ``source``) that the
    report/export paths stamp.  The trace-off side is the identity the
    ``bench_smoke`` plain-path guard protects: with ``trace=False`` the
    lowering inserts no ops at all."""
    batch = _random_plan(n, np.random.default_rng(n), deadline=True).arrays()
    run_plain = jax.jit(functools.partial(
        engine.simulate_batch_arrays, control=True))
    run_traced = jax.jit(functools.partial(
        engine.simulate_batch_arrays, control=True, trace=True))
    res = [None]

    def a():
        jax.block_until_ready(run_plain(batch))

    def b(res=res):
        res[0] = jax.block_until_ready(run_traced(batch))

    dt_a, min_a, dt_b, min_b = _time_ab(a, b, reps)
    out, realized, tb = res[0]
    tr = telemetry.TraceResult(tb, label=f"traced_b{n}")
    counts = tr.counts_by_kind()
    cost = costmodel.default_cost_model()
    return [(f"sweep_throughput_traced_b{n}", dt_b * 1e6, min_b * 1e6,
             f"{n / dt_b:.0f}_scen/s", int(np.asarray(realized).max()),
             {"trace": "timeseries+events",
              "events_logged": int(sum(counts.values())),
              "dropped_events": int(tr.dropped_events.sum()),
              "timing": "min_of_alternating_ab",
              "trace_gap_vs_plain": round(min_b / min_a - 1.0, 4),
              "cost_model": {"dispatch_us": cost.dispatch_us,
                             "sync_us": cost.sync_us,
                             "epoch_lane_us": cost.epoch_lane_us,
                             "device": cost.device, "source": cost.source},
              "provenance": dict(telemetry.provenance())})]


def unifpol_rows(n=2048, reps=7):
    """The mixed grid's workload as six per-policy-combo uniform plans.

    Policy-uniform sub-batches are the fair reference for the mixed row:
    each combo pays only its own realized epoch count, exactly what a user
    running six separate uniform sweeps would see.  Summed wall time over
    the same 2048 scenarios -> directly comparable scen/s.
    """
    # same rng(n) draw as the mixedpol b{n} row -> identical grid
    cols = _random_cols(n, np.random.default_rng(n), mixed_policies=True)
    plans = []
    for sp in SchedPolicy:
        for bp in BindingPolicy:
            pick = np.nonzero((cols["sched_policy"] == int(sp))
                              & (cols["binding_policy"] == int(bp)))[0]
            if len(pick) == 0:      # small n may leave a combo unpopulated
                continue
            sub = {k: v[pick] for k, v in cols.items()
                   if k not in ("sched_policy", "binding_policy")}
            plans.append(_plan_of(sub).replace(
                base=dict(sched_policy=sp, binding_policy=bp)))

    realized = [0]

    def run_all():
        out = [p.run() for p in plans]
        realized[0] = max(int(r["realized_epochs"].max()) for r in out)
        return out

    dt, dt_min, _ = _time_runs(run_all, reps)
    return [(f"sweep_throughput_unifpol_b{n}", dt * 1e6, dt_min * 1e6,
             f"{n / dt:.0f}_scen/s", realized[0], None)]


def calibration_us(reps=15):
    """A fixed miniature sweep (b16 `run()`, min over reps — the noise
    floor, since this feeds a pass/fail gate) timed on this machine and
    stored with the baseline, so CI smoke runs can scale the regression
    gate by relative machine speed.  Deliberately the same code path as
    the gated workload — dispatch + encode + epoch loop + readback — so
    the ratio tracks the real cost profile, which a pure-compute matmul
    calibration would not (the b64 row is dispatch-dominated)."""
    plan = _random_plan(16, np.random.default_rng(123))
    plan.run()                                     # compile + warm caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        plan.run()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def all_rows():
    # mixed-policy row: same grid with random (sched, binding) per scenario —
    # policy diversity is data, so one adaptive schedule serves all scenarios
    # within the batch; the unifpol row is its uniform-execution reference.
    # locality rows: the same workload with the block store on (skewed
    # placement, LOCALITY binding) — what the storage subsystem costs.
    # elastic rows: the same workload as a dynamic fleet (arrivals, lease
    # windows, priorities) — what the elasticity subsystem costs.
    # tailheavy rows: one compiled shape whose 1/8 straggler lanes run
    # ~2T epochs while the rest retire early — dense vs compact="auto"
    # timed alternating-A/B (what sparse compaction buys on the
    # data-dependent tail it targets).
    return (throughput_rows()
            + throughput_rows(batch_sizes=(2048,), mixed_policies=True)
            + unifpol_rows()
            + throughput_rows(batch_sizes=(64, 2048), locality=True)
            + throughput_rows(batch_sizes=(64, 2048), elastic=True)
            + tailheavy_rows()
            + compact_loop_rows()
            + control_rows()
            + deadline_rows()
            + traced_rows())


def main() -> None:
    from repro.core.util import enable_compile_cache
    enable_compile_cache()
    rows = all_rows()
    by_name = {r[0]: r for r in rows}
    mixed = by_name["sweep_throughput_mixedpol_b2048"][1]
    unif = by_name["sweep_throughput_unifpol_b2048"][1]
    plain = by_name["sweep_throughput_b2048"][1]
    loc = by_name["sweep_throughput_locality_b2048"][1]
    # elastic mixes sched policies (priorities/window admission need
    # space-shared lanes), so its comparator is the mixedpol row — the
    # plain all-time-shared row would mostly measure the policy-mixing
    # tax PR 3 already quantifies, not elasticity
    ela = by_name["sweep_throughput_elastic_b2048"][1]
    # compaction gap: noise-floor min vs min on the alternating-A/B pair
    th_dense = by_name["sweep_throughput_tailheavy_b2048"][2]
    th_comp = by_name["sweep_throughput_tailheavy_compact_b2048"][2]
    # lean-loop gain: the engine-level legacy-vs-lean A/B pair (§13)
    lean_speedup = by_name["sweep_throughput_compactloop_lean_b2048"][5][
        "lean_speedup_vs_legacy"]
    # control gap: already min-vs-min from its own alternating-A/B pair
    ctl_gap = by_name["sweep_throughput_control_b2048"][5][
        "control_gap_vs_elastic"]
    # deadline gap: ditto, against the control comparator (DESIGN.md §11)
    dl_gap = by_name["sweep_throughput_deadline_b2048"][5][
        "deadline_gap_vs_control"]
    # trace gap: min-of-A/B at the engine level (DESIGN.md §12) — the cost
    # of turning the in-loop trace lowering ON; the OFF side is bitwise the
    # plain path and is guarded separately by bench_smoke
    tr_meta = by_name["sweep_throughput_traced_b64"][5]
    tr_gap = tr_meta["trace_gap_vs_plain"]
    # the fluid speculative-execution study rides along in the same schema
    from . import speculative_execution
    rows = rows + speculative_execution.bench_rows()
    out = pathlib.Path(__file__).resolve().parent.parent / "BENCH_sweep.json"
    payload = {
        "benchmark": "sweep_throughput (SweepPlan.run end-to-end, "
                     "adaptive schedule)",
        "meta": {
            "backend": jax.default_backend(),
            "device": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
            "cpu_count": multiprocessing.cpu_count(),
            "platform": platform.platform(),
            "epoch_bound": EPOCH_BOUND,
            "calibration_us": round(calibration_us(), 1),
            "mixedpol_gap_vs_unifpol": round(mixed / unif - 1.0, 4),
            "locality_gap_vs_plain": round(loc / plain - 1.0, 4),
            "elastic_gap_vs_mixedpol": round(ela / mixed - 1.0, 4),
            "compaction_gap_vs_dense": round(th_comp / th_dense - 1.0, 4),
            "compaction_speedup_tailheavy_b2048": round(th_dense / th_comp,
                                                        2),
            "compact_lean_speedup_vs_legacy_b2048": lean_speedup,
            "control_gap_vs_elastic": ctl_gap,
            "deadline_gap_vs_control": dl_gap,
            "trace_gap_vs_plain": tr_gap,
            # run provenance + cost-model transparency (DESIGN.md §12.4):
            # which build/device produced this baseline, and whether the
            # bucket-split coefficients were measured here or loaded
            "provenance": tr_meta["provenance"],
            "cost_model": tr_meta["cost_model"],
        },
        "rows": [{"name": n, "us_per_call": round(us, 1),
                  "us_per_call_min": round(us_min, 1), "derived": d,
                  "realized_epochs": ep,
                  **({"meta": m} if m else {})}
                 for n, us, us_min, d, ep, m in rows],
    }
    out.write_text(json.dumps(payload, indent=2) + "\n")
    for r in payload["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']},"
              f"epochs={r['realized_epochs']}/{EPOCH_BOUND}")
    print(f"mixedpol vs unifpol gap: "
          f"{payload['meta']['mixedpol_gap_vs_unifpol']:+.1%}")
    print(f"locality (storage on) vs plain b2048 gap: "
          f"{payload['meta']['locality_gap_vs_plain']:+.1%}")
    print(f"elastic (dynamic fleet) vs mixedpol b2048 gap: "
          f"{payload['meta']['elastic_gap_vs_mixedpol']:+.1%}")
    print(f"compaction vs dense tailheavy b2048 (min-of-A/B): "
          f"{payload['meta']['compaction_speedup_tailheavy_b2048']:.2f}x")
    print(f"lean vs legacy compact loop b2048 (min-of-A/B): "
          f"{payload['meta']['compact_lean_speedup_vs_legacy_b2048']:.2f}x")
    print(f"control (closed-loop) vs elastic b2048 gap (min-of-A/B): "
          f"{payload['meta']['control_gap_vs_elastic']:+.1%}")
    print(f"deadline (graceful degradation) vs control b2048 gap "
          f"(min-of-A/B): {payload['meta']['deadline_gap_vs_control']:+.1%}")
    print(f"trace (in-loop telemetry) vs plain engine b64 gap "
          f"(min-of-A/B): {payload['meta']['trace_gap_vs_plain']:+.1%}")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
