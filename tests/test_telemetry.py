"""Device-side trace & telemetry subsystem (DESIGN.md §12).

The trace layer must be *free when off* and *exact when on*:

* **trace-off / trace-on identity** — with tracing disabled the drivers
  run the pre-§12 lowering (the flag only adds carry leaves, never ops);
  with tracing enabled the ``SimOutput`` stays bitwise identical across
  engine ↔ batched ↔ batched-compact ↔ pallas dense + compact, stranded
  lanes included, and the trace buffers themselves agree bitwise across
  every engine path (the pallas twin carries the time-series rows);
* **oracle event parity** — the refsim calendar mirrors every event the
  engine logs: per-kind counts are integer-exact and timestamps match to
  the f32 tolerance (rtol 2e-4) over seeded failure / shed / preempt /
  autoscale grids.  SHED is counts-only: the engine detects refusal at
  epoch granularity, the oracle at calendar time;
* **overflow semantics** — an undersized event log drops the *newest*
  rows, counts them in ``dropped_events``, and never corrupts earlier
  rows (the one-hot write falls off the end of the buffer);
* **exports** — ``to_chrome_trace()`` is valid trace-event JSON with one
  complete-event span per realized task execution; parquet artifacts
  carry the provenance stamp; ``run(report=True)`` returns a
  :class:`~repro.core.telemetry.RunReport` without changing any metric.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import (ControlPolicy, ControlSpec, DeadlinePolicy, Scenario,
                        SchedPolicy, costmodel, engine, refsim, sweep,
                        telemetry)
from repro.core.config import (JobSpec, NetworkSpec, VM_SMALL, VMSpec,
                               paper_scenario)
from repro.core.elasticity import ElasticitySpec
from repro.core.sweep import axis, product
from repro.core.telemetry import (EV_FINISH, EV_KILL, EV_PREEMPT,
                                  EV_SCALE_CLOSE, EV_SCALE_OPEN, EV_SHED,
                                  EV_START, EVENT_NAMES, TraceResult,
                                  event_capacity, timeseries_capacity)
from repro.kernels.mr_sched import epoch_schedule, epoch_schedule_compact

_BIG = engine._BIG
SCHED_FIELDS = engine.SimOutput._fields


def _assert_same(a, b, fields, msg):
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg}: {f}")


def _overload(dlpol, *, preempt=False, resume=False, slack=0.0,
              sp=SchedPolicy.SPACE_SHARED, spacing=120.0,
              deadlines=(4000.0, 4600.0, 5200.0, 5800.0, 6400.0)):
    """Five staggered jobs on two small VMs: sustained overload."""
    jobs = tuple(JobSpec(f"j{i}", length_mi=362_880.0, data_mb=200_000.0,
                         n_maps=3, n_reduces=1, submit_time=spacing * i,
                         priority=float(i % 3), deadline=deadlines[i])
                 for i in range(5))
    return Scenario(vms=(VM_SMALL,) * 2, jobs=jobs,
                    network=NetworkSpec(enabled=False), sched_policy=sp,
                    control=ControlSpec(deadline_policy=dlpol,
                                        deadline_slack=slack,
                                        preempt=preempt,
                                        preempt_resume=resume))


def _fail_scenario(seed=7, sp=SchedPolicy.SPACE_SHARED):
    sc = paper_scenario(n_maps=6, n_reduces=2, n_vms=4, sched_policy=sp)
    return sc.replace(control=ControlSpec(
        failure_rate=0.002, failure_seed=seed, repair_delay=300.0,
        redispatch_delay=5.0))


def _scale_scenario(sp=SchedPolicy.SPACE_SHARED):
    vms = (VMSpec("base", mips=250.0), VMSpec("base", mips=250.0),
           VMSpec("res", mips=250.0, autoscale=True),
           VMSpec("res", mips=250.0, autoscale=True))
    job = JobSpec("j", length_mi=362_880.0, data_mb=200_000.0,
                  n_maps=12, n_reduces=2)
    return Scenario(vms=vms, jobs=(job,), sched_policy=sp,
                    control=ControlSpec(policy=ControlPolicy.AUTOSCALE,
                                        queue_threshold=2.0,
                                        busy_threshold=0.5))


def _stranded():
    """A lane whose VM leases all close early: tasks never finish, so
    the lane realizes its full epoch bound (the hard trace-capacity
    case)."""
    base = paper_scenario(n_maps=6, n_reduces=2, n_vms=3,
                          sched_policy=SchedPolicy.SPACE_SHARED)
    return base.replace(
        vms=tuple(dataclasses.replace(v, lease_stop=500.0)
                  for v in base.vms),
        elasticity=ElasticitySpec())


def test_capacity_formulas():
    assert timeseries_capacity(10, 4, False) == 2 * 10 + 2
    assert timeseries_capacity(10, 4, True) == 7 * 10 + 4 + 3
    assert event_capacity(10, 4, False) == 2 * 10
    assert event_capacity(10, 4, True) == 11 * 10 + 2 * 4


# ---------------------------------------------------------------------------
# Bitwise identity: trace on/off, all five execution paths
# ---------------------------------------------------------------------------

def test_trace_bitwise_every_path():
    """Traced SimOutput == untraced, and the trace buffers agree bitwise
    across engine per-lane ↔ batched ↔ compact and the pallas twin's
    time series — on a mixed batch that includes failures, autoscale
    and a stranded lane."""
    batch = sweep.stack_scenarios([_fail_scenario(), _scale_scenario(),
                                   _stranded()])
    ref, _ = engine.simulate_batch_arrays(batch, control=True)
    assert (np.asarray(ref.finish[2]) >= _BIG / 2).any(), "no stranded lane"
    out, _, tb = engine.simulate_batch_arrays(batch, control=True,
                                              trace=True)
    _assert_same(ref, out, SCHED_FIELDS, "batched traced")
    # per-lane driver under vmap: outputs and buffers bitwise
    lane_out, lane_tb = jax.vmap(
        lambda sc: engine.simulate_arrays(sc, control=True, trace=True)
    )(batch)
    _assert_same(ref, lane_out, SCHED_FIELDS, "vmapped traced")
    _assert_same(tb, lane_tb, telemetry.TraceBuffers._fields,
                 "vmapped trace buffers")
    for K in (1, 4, "auto"):
        comp, _, ctb = engine.simulate_batch_arrays_compact(
            batch, k=K, control=True, trace=True)
        _assert_same(ref, comp, SCHED_FIELDS, f"compact traced k={K}")
        _assert_same(tb, ctb, telemetry.TraceBuffers._fields,
                     f"compact trace buffers k={K}")
    # pallas twin: time-series rows only, bitwise vs the engine's
    pal, ts = epoch_schedule(batch, control=True, trace=True)
    _assert_same(ref, pal, SCHED_FIELDS, "pallas dense traced")
    np.testing.assert_array_equal(np.asarray(ts), np.asarray(tb.ts),
                                  err_msg="pallas dense ts")
    palc, _, tsc = epoch_schedule_compact(batch, k=2, control=True,
                                          trace=True)
    _assert_same(ref, palc, SCHED_FIELDS, "pallas compact traced")
    np.testing.assert_array_equal(np.asarray(tsc), np.asarray(tb.ts),
                                  err_msg="pallas compact ts")
    tr = TraceResult(telemetry.jax_tree_to_numpy(tb))
    assert (tr.dropped_events == 0).all()


def test_trace_off_open_loop_identity():
    """Open-loop lowering: tracing composes without the control hook and
    stays an identity on the schedule."""
    sc = engine.from_scenario(paper_scenario(n_maps=6, n_reduces=2,
                                             n_vms=3))
    base = engine.simulate_arrays(sc, control=False)
    out, tb = engine.simulate_arrays(sc, control=False, trace=True)
    _assert_same(base, out, SCHED_FIELDS, "open-loop traced")
    tr = TraceResult(telemetry.jax_tree_to_numpy(tb))
    n = int(np.asarray(sc.task_valid).sum())
    c = tr.counts_by_kind(0)
    assert c["start"] == n and c["finish"] == n
    assert sum(c.values()) == 2 * n          # open loop: START/FINISH only


# ---------------------------------------------------------------------------
# Oracle event parity: refsim mirrors the engine's event log
# ---------------------------------------------------------------------------

_PARITY_CASES = [
    ("open-loop", lambda: paper_scenario(n_maps=6, n_reduces=2, n_vms=3),
     False),
    ("shed", lambda: _overload(DeadlinePolicy.SHED), True),
    ("preempt", lambda: _overload(DeadlinePolicy.NONE, preempt=True), True),
    ("shed-preempt", lambda: _overload(DeadlinePolicy.SHED, preempt=True,
                                       resume=True), True),
    ("failures", _fail_scenario, True),
    ("failures-ts", lambda: _fail_scenario(sp=SchedPolicy.TIME_SHARED),
     True),
    ("autoscale", _scale_scenario, True),
    ("autoscale-ts", lambda: _scale_scenario(SchedPolicy.TIME_SHARED),
     True),
]


@pytest.mark.parametrize("name,mk,control", _PARITY_CASES,
                         ids=[n for n, _, _ in _PARITY_CASES])
def test_engine_trace_matches_refsim_events(name, mk, control):
    sc = mk()
    ref = refsim.simulate(sc)
    arrs = engine.from_scenario(sc)
    out, tb = engine.simulate_arrays(arrs, control=control, trace=True)
    tr = TraceResult(telemetry.jax_tree_to_numpy(tb))
    assert int(tr.dropped_events[0]) == 0
    # per-kind counts: integer-exact
    refc: dict[int, int] = {}
    for (_, k, _, _) in ref.events:
        refc[k] = refc.get(k, 0) + 1
    eng = tr.counts_by_kind(0)
    for k, kname in EVENT_NAMES.items():
        assert eng[kname] == refc.get(k, 0), \
            f"{name}: {kname} count {eng[kname]} != refsim {refc.get(k, 0)}"
    ev = tr.events()
    # timestamps per kind to the f32 tolerance (SHED is counts-only:
    # the engine detects refusal at epoch granularity)
    for k in EVENT_NAMES:
        if k == EV_SHED:
            continue
        et = np.sort(ev["t"][ev["kind"] == k])
        rt = np.sort([t for (t, kk, _, _) in ref.events if kk == k])
        np.testing.assert_allclose(et, rt, rtol=2e-4, atol=1e-2,
                                   err_msg=f"{name}: {EVENT_NAMES[k]}")
    # (kind, task, vm) rows are the same multiset
    es = sorted((int(k), int(t), int(v))
                for k, t, v in zip(ev["kind"], ev["task"], ev["vm"])
                if k != EV_SHED)
    rs = sorted((int(k), int(t), int(v)) for (_, k, t, v) in ref.events
                if k != EV_SHED)
    assert es == rs, f"{name}: (kind,task,vm) multiset mismatch"
    # time-series: active rows time-monotone; per-epoch counters sum to
    # the oracle's totals
    ts = tr.ts[0]
    act = ts[:, 4] > 0
    assert (np.diff(ts[act, 0]) >= -1e-6).all()
    assert int(ts[:, 5].sum()) == refc.get(EV_KILL, 0)
    assert int(ts[:, 6].sum()) == refc.get(EV_SHED, 0)
    assert int(ts[:, 7].sum()) == refc.get(EV_PREEMPT, 0)


# ---------------------------------------------------------------------------
# Overflow semantics
# ---------------------------------------------------------------------------

def test_event_overflow_counts_without_corruption():
    sc = engine.from_scenario(_fail_scenario())
    base = engine.simulate_arrays(sc, control=True)
    _, full = engine.simulate_arrays(sc, control=True, trace=True)
    n_ev = int(np.asarray(full.ev_n))
    cap = 4
    assert n_ev > cap, "scenario too quiet to overflow"
    out, tiny = engine.simulate_arrays(sc, control=True, trace=True,
                                       trace_events=cap)
    _assert_same(base, out, SCHED_FIELDS, "overflowed traced")
    tr = TraceResult(telemetry.jax_tree_to_numpy(tiny))
    assert int(tr.dropped_events[0]) == n_ev - cap
    # rows that fit are exactly the first `cap` rows of the full log
    for name, f in (("t", "ev_t"), ("kind", "ev_kind"),
                    ("task", "ev_task"), ("vm", "ev_vm")):
        np.testing.assert_array_equal(
            np.asarray(getattr(tiny, f)),
            np.asarray(getattr(full, f))[:cap],
            err_msg=f"overflow corrupted earlier {name} rows")


# ---------------------------------------------------------------------------
# Exports
# ---------------------------------------------------------------------------

def test_chrome_trace_schema(tmp_path):
    sc = _fail_scenario()
    _, tr = telemetry.trace_scenario(sc, label="failures")
    path = tmp_path / "trace.json"
    tr.to_chrome_trace(path)
    doc = json.loads(path.read_text())          # valid JSON on disk
    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    counts = tr.counts_by_kind(0)
    # one complete-event span per realized task execution: every START
    # opens exactly one span (kills close one and the redispatch START
    # opens the next)
    assert len(spans) == counts["start"]
    assert counts["kill"] > 0, "no failure ever fired"
    kills = [e for e in doc["traceEvents"]
             if e["ph"] == "i" and e["name"] == "kill"]
    redisp = [e for e in doc["traceEvents"]
              if e["ph"] == "i" and e["name"] == "redispatch"]
    assert len(kills) == counts["kill"]
    assert 0 < len(redisp) <= counts["kill"]    # restarts after kills
    for e in spans:
        assert e["dur"] >= 0.0
        assert e["args"]["outcome"] in ("ok", "kill", "preempt",
                                        "unterminated")
    assert doc["otherData"]["jax_version"]
    assert doc["otherData"]["dropped_events"] == 0


def test_timeseries_table_and_parquet(tmp_path):
    pa = pytest.importorskip("pyarrow")
    pq = pytest.importorskip("pyarrow.parquet")
    _, tr = telemetry.trace_scenario(_scale_scenario())
    tab = tr.to_table()
    n = len(tab["epoch"])
    assert n == int((tr.ts[:, :, 4] > 0).sum())
    assert set(telemetry.TS_COLUMNS) < set(tab)
    p = tmp_path / "ts.parquet"
    tr.to_parquet(p)
    meta = pq.read_schema(p).metadata
    prov = json.loads(meta[b"repro_provenance"])
    assert prov["jax_version"] and "device_kind" in prov


# ---------------------------------------------------------------------------
# Sweep-runtime telemetry: run(report=True)
# ---------------------------------------------------------------------------

_PINNED = costmodel.CostModel(dispatch_us=100.0, epoch_lane_us=0.05,
                              sync_us=40.0, device="pinned")


def test_run_report_observational():
    plan = product(axis("n_maps", [2, 3, 8, 12]), axis("n_vms", [2, 4]))
    base = plan.run(cost_model=_PINNED)
    res, rep = plan.run(cost_model=_PINNED, report=True)
    for f in base.metric_names:
        np.testing.assert_array_equal(base[f], res[f], err_msg=f)
    assert rep.n_cells == 8 and rep.n_buckets == len(rep.buckets) >= 1
    assert rep.dispatches == sum(b.dispatches for b in rep.buckets) >= 1
    assert rep.cost_model == {"dispatch_us": 100.0, "epoch_lane_us": 0.05,
                              "sync_us": 40.0, "device": "pinned",
                              "source": "static"}
    assert rep.provenance["jax_version"]
    assert rep.wall_s > 0 and all(b.wall_s > 0 for b in rep.buckets)
    # second identical run hits the fused-runner cache for every bucket
    _, rep2 = plan.run(cost_model=_PINNED, report=True)
    assert rep2.compile_cache_misses == 0
    assert rep2.compile_cache_hits >= rep2.n_buckets
    json.loads(rep.to_json())                   # serializable


def test_run_report_compact_counts_syncs():
    plan = product(axis("n_maps", [2, 4, 6, 9]), n_vms=3)
    base = plan.run(cost_model=_PINNED)
    res, rep = plan.run(cost_model=_PINNED, compact=1, report=True)
    for f in base.metric_names:
        if f == "realized_epochs":
            continue
        np.testing.assert_array_equal(base[f], res[f], err_msg=f)
    # dispatch-lean loop (DESIGN.md §13): every round pulls one fused
    # scalar pair; full mask/permutation pulls happen only on rounds that
    # actually compact — this 4-cell plan never shrinks below the pow2
    # floor, so its full-pull count is exactly zero
    assert rep.scalar_syncs > 0
    assert rep.compaction_syncs == 0
    assert rep.compact == 1
    assert all(b.compact_scalar_syncs > 0 for b in rep.buckets)
    assert all(b.compact_syncs <= b.compact_scalar_syncs
               for b in rep.buckets)


def test_run_report_cost_source_surfaces():
    """The calibration source rides into the report (fallback pinned via
    a CostModel constructed by the fallback path)."""
    cm = costmodel.fallback_cost_model("test-dev")
    _, rep = product(axis("n_maps", [2, 3]), n_vms=2).run(
        cost_model=cm, report=True)
    assert rep.cost_model["source"] == "fallback"
    assert rep.cost_model["device"] == "test-dev"


def test_sweep_parquet_provenance(tmp_path):
    pq = pytest.importorskip("pyarrow.parquet")
    plan = product(axis("n_maps", [2, 3, 4]), n_vms=2)
    res = plan.run(cost_model=_PINNED)
    p1 = tmp_path / "res.parquet"
    res.to_parquet(p1)
    assert b"repro_provenance" in pq.read_schema(p1).metadata
    p2 = tmp_path / "stream.parquet"
    streamed, rep = plan.run(chunk=2, stream_to=p2, cost_model=_PINNED,
                             report=True)
    prov = json.loads(pq.read_schema(p2).metadata[b"repro_provenance"])
    assert prov["repro_version"] and prov["jax_version"]
    assert streamed.n_rows == 3
    assert rep.n_cells == 3 and rep.dispatches >= 2   # >= one per chunk


# ---------------------------------------------------------------------------
# Host spans and transfer / lane-epoch counters of SweepPlan.run
# ---------------------------------------------------------------------------

# The span that each iotsim.* span sits directly inside (DESIGN.md §12.4);
# the first activity poll of a compaction sits in its prepare span.
_SPAN_PARENTS = {
    "iotsim.run": {None},
    "iotsim.plan": {"iotsim.run"},
    "iotsim.assemble": {"iotsim.run"},
    "iotsim.bucket": {"iotsim.run"},
    "iotsim.upload": {"iotsim.bucket"},
    "iotsim.launch": {"iotsim.bucket"},
    "iotsim.readback": {"iotsim.bucket"},
    "iotsim.metrics": {"iotsim.bucket"},
    "iotsim.compact.prepare": {"iotsim.bucket"},
    "iotsim.compact.step": {"iotsim.bucket"},
    "iotsim.compact.regather": {"iotsim.bucket"},
    "iotsim.compact.finish": {"iotsim.bucket"},
    "iotsim.compact.poll": {"iotsim.bucket", "iotsim.compact.prepare"},
}
_DENSE = {"iotsim.run", "iotsim.plan", "iotsim.assemble", "iotsim.bucket",
          "iotsim.upload", "iotsim.launch", "iotsim.readback"}
_COMPACT = (_DENSE - {"iotsim.launch"}) | {
    "iotsim.metrics", "iotsim.compact.prepare", "iotsim.compact.step",
    "iotsim.compact.poll", "iotsim.compact.regather",
    "iotsim.compact.finish"}


def _tail_plan():
    """16 cells, 1 to 24 maps on 1 to 8 VMs: most lanes finish long
    before the last, so a compacting run regathers."""
    return product(axis("n_maps", [1, 2, 3, 24]), axis("n_vms", [1, 2, 4, 8]))


def _traced_run(tmp_path, plan, **kw):
    """``plan.run(report=True, **kw)`` under the profiler, after a plain
    run (which also compiles outside the trace) whose results it must
    match bit for bit; returns the result, the report and the run's
    ``iotsim.*`` spans (:func:`_iotsim_spans`)."""
    base = plan.run(cost_model=_PINNED, **kw)
    with jax.profiler.trace(str(tmp_path)):
        res, rep = plan.run(cost_model=_PINNED, report=True, **kw)
    for name in base.metric_names:
        np.testing.assert_array_equal(base[name], res[name],
                                      err_msg=f"{name} ({kw})")
    return res, rep, _iotsim_spans(tmp_path)


def _host_events(trace_dir, prefix=""):
    """``(start, end, name, attributes)`` of the Python thread's host
    events whose names start with ``prefix``, in the one profiler session
    under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = trace_dir.glob("**/*.xplane.pb")
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines if line.name.startswith("python")
            for e in line.events if e.name.startswith(prefix)]


def _iotsim_spans(trace_dir):
    """``(start, end, name, attributes)`` of the ``iotsim.*`` host spans in
    the one profiler session under ``trace_dir``."""
    return _host_events(trace_dir, "iotsim.")


def _parent(span, spans):
    """Name of the innermost other span that holds ``span``."""
    s, e = span[:2]
    holders = [o for o in spans if o is not span and o[0] <= s
               and e <= o[1] and (o[1] - o[0]) > (e - s)]
    return min(holders, key=lambda o: o[1] - o[0])[2] if holders else None


# Also pins that the profiler and report=True leave every result bit as
# it is (_traced_run).
@pytest.mark.parametrize("kw, names", [
    (dict(), _DENSE),
    (dict(compact=2), _COMPACT),
    (dict(backend="pallas", compact=2), _COMPACT),
    (dict(bucket=False, compact=2), _COMPACT),
], ids=["dense", "xla-compact", "pallas-compact", "one-bucket-compact"])
def test_run_spans_nest_and_match_counters(tmp_path, kw, names):
    plan = _tail_plan()
    res, rep, spans = _traced_run(tmp_path, plan, **kw)
    count = {n: sum(s[2] == n for s in spans) for n in _SPAN_PARENTS}
    assert {n for n, c in count.items() if c} == names
    for span in spans:
        assert _parent(span, spans) in _SPAN_PARENTS[span[2]], span
    (run,) = [s for s in spans if s[2] == "iotsim.run"]
    # the profiler hands back attribute values as it parses them
    assert {k: str(v) for k, v in run[3].items()} == {
        "run": str(run[3]["run"]), "cells": str(plan.size),
        "backend": kw.get("backend", "xla"),
        "compact": str(kw.get("compact"))}
    buckets = [s for s in spans if s[2] == "iotsim.bucket"]
    assert [(b[3]["bucket"], b[3]["cells"], b[3]["pad_tasks"],
             b[3]["pad_vms"], b[3]["run"]) for b in buckets] == [
        (i, b.cells, b.pad_tasks, b.pad_vms, run[3]["run"])
        for i, b in enumerate(rep.buckets)]
    if "compact" in kw:
        assert count["iotsim.compact.poll"] == rep.scalar_syncs
        assert count["iotsim.compact.step"] == rep.dispatches
        assert count["iotsim.compact.regather"] == rep.compaction_syncs > 0
    else:
        assert count["iotsim.launch"] == rep.dispatches == rep.n_buckets
    assert rep.d2h_transfers >= rep.compaction_syncs + rep.scalar_syncs
    assert rep.h2d_transfers > 0 and rep.h2d_bytes > 0
    assert rep.d2h_bytes > 0


@pytest.mark.parametrize("kw", [
    dict(), dict(bucket=False), dict(compact=2),
    dict(backend="pallas", compact=2), dict(backend="pallas"),
    dict(chunk=12), dict(chunk=12, compact=2)],
    ids=["dense", "one-bucket", "xla-compact", "pallas-compact",
         "pallas-dense", "chunk", "chunk-compact"])
def test_run_lane_epoch_counters(kw):
    plan = _tail_plan()
    res, rep = plan.run(cost_model=_PINNED, report=True, **kw)
    n_epochs = res["n_epochs"]
    assert rep.lane_epochs_useful == int(n_epochs.sum())
    assert rep.lane_epochs_useful == sum(b.lane_epochs_useful
                                         for b in rep.buckets)
    assert rep.lane_epochs_allotted >= rep.lane_epochs_useful
    assert rep.lane_epochs_allotted == sum(b.lane_epochs_allotted
                                           for b in rep.buckets)
    if "compact" not in kw and "chunk" not in kw:
        # a dense bucket allots its padded lanes x its realized epochs
        from repro.kernels.mr_sched import ops
        _, tile = ops.resolve_mode(None, None)
        realized = set(res["realized_epochs"].ravel().tolist())
        for b in rep.buckets:
            lanes = b.cells
            if kw.get("backend") == "pallas":
                lanes += ops.lane_pad(b.cells, tile)
            assert b.lane_epochs_allotted in {lanes * r for r in realized}


@pytest.mark.parametrize("legacy", [False, True], ids=["lean", "legacy"])
def test_compact_loop_spans_match_stats(tmp_path, legacy):
    """Both XLA compaction loops: one poll span per scalar pull, one step
    span per chunk launch, one regather span per full pull."""
    batch = sweep.grid_arrays(_tail_plan().params(), pad_tasks=25, pad_vms=8)
    engine.simulate_batch_arrays_compact(batch, k=2, legacy=legacy)
    st = {}
    with jax.profiler.trace(str(tmp_path)):
        engine.simulate_batch_arrays_compact(batch, k=2, legacy=legacy,
                                             stats=st)
    names = [s[2] for s in _iotsim_spans(tmp_path)]
    assert names.count("iotsim.compact.prepare") == 1
    assert names.count("iotsim.compact.finish") == 1
    assert names.count("iotsim.compact.poll") == st["scalar_syncs"]
    assert names.count("iotsim.compact.step") == st["dispatches"]
    assert names.count("iotsim.compact.regather") == st["syncs"]
    assert st["compactions"] > 0
    assert st["d2h_transfers"] >= st["syncs"] + st["scalar_syncs"]
    assert st["lane_epochs_allotted"] > 0


_GLUE_SPANS = ("iotsim.compact.prepare", "iotsim.compact.finish")


@pytest.mark.parametrize("kw", [dict(), dict(control=True), dict(trace=True)],
                         ids=["open-loop", "control", "trace"])
def test_pallas_compact_glue_programs(tmp_path, kw):
    """The Pallas compaction driver's set-up and output glue run as one
    compiled program each: at most two executable launches inside
    ``prepare`` (glue and activity, here fused into one) and two inside
    ``finish`` (the store merge and the output), not one per ``jnp`` op."""
    batch = sweep.grid_arrays(_tail_plan().params(), pad_tasks=25, pad_vms=8)
    epoch_schedule_compact(batch, k=2, tile=8, interpret=True, **kw)
    with jax.profiler.trace(str(tmp_path)):
        epoch_schedule_compact(batch, k=2, tile=8, interpret=True, **kw)
    events = _host_events(tmp_path)
    runs = [e for e in events if e[2] == "PjRtCpuExecutable::Execute"]
    for name in _GLUE_SPANS:
        (span,) = [e for e in events if e[2] == name]
        n = sum(span[0] <= r[0] and r[1] <= span[1] for r in runs)
        assert 1 <= n <= 2, (name, n)


def test_pallas_compact_glue_compiles_once():
    """Another round count on a batch of the same shapes reuses the glue
    programs: what a benchmark window runs after warm-up compiles none."""
    from repro.kernels.mr_sched import ops
    shapes = dict(pad_tasks=25, pad_vms=8)
    batch = sweep.grid_arrays(_tail_plan().params(), **shapes)
    other = sweep.grid_arrays(product(axis("n_maps", [2, 5, 7, 20]),
                                      axis("n_vms", [1, 3, 6, 8])).params(),
                              **shapes)
    first, _ = epoch_schedule_compact(batch, k=2, tile=8, interpret=True)
    sizes = (ops._compact_prepare._cache_size(),
             ops._compact_finish._cache_size())
    again, _ = epoch_schedule_compact(batch, k=3, tile=8, interpret=True)
    epoch_schedule_compact(other, k=5, tile=8, interpret=True)
    assert (ops._compact_prepare._cache_size(),
            ops._compact_finish._cache_size()) == sizes
    for a, b in zip(jax.tree.leaves(first), jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, b)
