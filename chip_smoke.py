"""Chip smoke test: ``SweepPlan.run`` end to end on a TPU at a real sweep size.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # the mesh path over four chips only

One process drives the chip.  The one-chip run sweeps 65,536 scenarios of
three ``benchmarks/sweep_throughput`` families, each drawn from a fixed
seed:

* ``mixed``     — mixed scheduling/binding policies, on both backends;
* ``deadline``  — the closed loop (seeded failures, autoscale hook) with
  deadlines and preemption, ``control`` on, on both backends;
* ``tailheavy`` — 1/8 straggler lanes at T=41, dense and ``compact="auto"``
  on both backends.

It checks that the Pallas kernel ran compiled by Mosaic (not interpreted),
that 32 sampled cells of ``mixed`` and of ``tailheavy`` agree with the
``refsim`` oracle to 1e-3 relative, that Pallas agrees with XLA (integer
outputs equal, float outputs within ``FLOAT_RTOL``), and that compaction
agrees with the dense path.  ``--four-chips`` runs ``run(mesh=...)`` over
``jax.devices()[:4]`` on the ``mixed`` plan and compares it with the
one-chip XLA result.

Earlier lines print one JSON object per phase (wall times around host-side
results, compile counts, the cost model's source, buckets).  The last line
is ``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it.  Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
N_CELLS = 65_536
SEED = 0
N_ORACLE = 32              # sampled cells per family checked against refsim
ORACLE_RTOL = 1e-3         # the documented engine <-> refsim contract
# Pallas vs XLA (and mesh vs one chip) on float metrics: both compile the
# same f32 op sequence, but XLA and Mosaic may round a divide or fuse a
# multiply-add differently on the chip.  The measured difference is printed;
# on a TPU v5e it was 0.0 for every metric of all three families.
FLOAT_RTOL = 1e-4
# metrics that count events: equal across backends, never within a tolerance
INT_METRICS = ("n_epochs", "shed_tasks", "preemptions", "failures_injected",
               "tasks_redispatched", "scale_events")
ORACLE_FIELDS = ("avg_exec", "makespan", "vm_cost", "network_cost")
FAMILIES = {"mixed": dict(mixed_policies=True),
            "deadline": dict(deadline=True),
            "tailheavy": dict(tailheavy=True)}


class CompileCounter:
    """Counts XLA backend compiles and their seconds, from JAX's own
    monitoring events."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1
            self.s += duration

    def snapshot(self):
        return self.n, self.s


def family_plan(name: str, n: int, seed: int = SEED):
    from benchmarks.sweep_throughput import _random_plan
    return _random_plan(n, np.random.default_rng(seed), **FAMILIES[name])


def run_phase(label: str, plan, counter: CompileCounter, **kw):
    """Cold then warm ``plan.run(report=True, **kw)``; prints one line.
    ``run`` returns host numpy arrays, so the wall times include the
    device work and the readback."""
    c0, s0 = counter.snapshot()
    t0 = time.perf_counter()
    _, cold_rep = plan.run(report=True, **kw)
    cold = time.perf_counter() - t0
    c1, s1 = counter.snapshot()
    t0 = time.perf_counter()
    res, rep = plan.run(report=True, **kw)
    warm = time.perf_counter() - t0
    c2, _ = counter.snapshot()
    check_finite(label, res, plan.size)
    print(json.dumps({
        "phase": label, "cells": plan.size, "cold_s": cold, "warm_s": warm,
        "compiles": c1 - c0, "compile_s": s1 - s0,
        "warm_compiles": c2 - c1,
        "runner_compiles": cold_rep.compile_cache_misses,
        "buckets": rep.n_buckets, "dispatches": rep.dispatches,
        "cost_model_source": rep.cost_model["source"],
        "cost_model": {k: rep.cost_model[k] for k in
                       ("dispatch_us", "epoch_lane_us", "sync_us")},
        "realized_epochs_max": int(res["realized_epochs"].max())}),
        flush=True)
    return res


def check_finite(label: str, res, n: int) -> None:
    for name in res.metric_names:
        x = np.asarray(res[name])
        if x.shape[0] != n:
            raise AssertionError(f"{label}: {name} has shape {x.shape}, "
                                 f"expected {n} cells")
        if not np.all(np.isfinite(x)):
            raise AssertionError(f"{label}: {name} has non-finite values")


def compare(label: str, a, b, rtol: float) -> dict:
    """Integer-valued metrics equal, float metrics within ``rtol`` of the
    larger magnitude; returns the largest relative difference per float
    metric (``realized_epochs`` is schedule-dependent by design)."""
    worst = {}
    for name in a.metric_names:
        if name == "realized_epochs":
            continue
        x = np.asarray(a[name], np.float64)
        y = np.asarray(b[name], np.float64)
        if name in INT_METRICS:
            bad = int(np.sum(x != y))
            if bad:
                raise AssertionError(f"{label}: {name} differs in {bad} "
                                     f"cells")
            continue
        scale = np.maximum(np.maximum(np.abs(x), np.abs(y)), 1e-30)
        rel = float(np.max(np.abs(x - y) / scale))
        worst[name] = rel
        if rel > rtol:
            raise AssertionError(f"{label}: {name} differs by {rel:.3e} "
                                 f"relative (limit {rtol:.0e})")
    out = {"compare": label, "max_rel_diff": max(worst.values()),
           "per_metric": {k: v for k, v in worst.items() if v > 0}}
    print(json.dumps(out), flush=True)
    return out


def cell_scenario(cols, i: int):
    """The refsim :class:`Scenario` of one homogeneous plan cell."""
    from repro.core import (BindingPolicy, JobSpec, Scenario, SchedPolicy,
                            VMSpec)
    vm = VMSpec(mips=float(cols["vm_mips"][i]), pes=int(cols["vm_pes"][i]),
                cost_per_sec=float(cols["vm_cost"][i]))
    job = JobSpec(length_mi=float(cols["job_length"][i]),
                  data_mb=float(cols["job_data"][i]),
                  n_maps=int(cols["n_maps"][i]),
                  n_reduces=int(cols["n_reduces"][i]))
    return Scenario(vms=(vm,) * int(cols["n_vms"][i]), jobs=(job,),
                    sched_policy=SchedPolicy(int(cols["sched_policy"][i])),
                    binding_policy=BindingPolicy(
                        int(cols["binding_policy"][i])))


def check_oracle(label: str, plan, results, seed: int = SEED) -> None:
    """``N_ORACLE`` sampled cells of ``plan`` against ``refsim`` for every
    result in ``results`` (backend -> SweepResult)."""
    from repro.core import refsim
    cols = plan.params()
    pick = np.random.default_rng(seed).choice(plan.size, N_ORACLE,
                                              replace=False)
    worst = 0.0
    for i in pick:
        ref = refsim.simulate(cell_scenario(cols, int(i))).job()
        for backend, res in results.items():
            for f in ORACLE_FIELDS:
                want, got = getattr(ref, f), float(res[f][i])
                rel = abs(got - want) / max(abs(want), 1e-30)
                if rel > ORACLE_RTOL and abs(got - want) > 1e-2:
                    raise AssertionError(
                        f"{label}/{backend} cell {i}: {f} = {got} vs "
                        f"refsim {want}")
                worst = max(worst, rel)
    print(json.dumps({"oracle": label, "cells": N_ORACLE,
                      "backends": sorted(results), "max_rel_diff": worst}),
          flush=True)


def check_compiled_kernel(plan) -> None:
    """The Pallas path resolves to Mosaic on this backend, and its
    lowering holds the kernel as a TPU custom call, not an interpreted
    loop."""
    import jax
    from repro.kernels.mr_sched import ops
    interpret, tile = ops.resolve_mode(None, None)
    if interpret:
        raise AssertionError("the Pallas path would run in interpret mode")
    batch = plan.arrays()
    text = jax.jit(lambda b: ops.epoch_schedule(b).finish).lower(
        batch).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("mr_epoch did not lower to a Mosaic kernel")
    print(json.dumps({"pallas": "compiled", "interpret": interpret,
                      "tile": tile}), flush=True)


def one_chip(n: int, counter: CompileCounter) -> None:
    plans = {name: family_plan(name, n) for name in FAMILIES}
    check_compiled_kernel(family_plan("mixed", 64))

    mixed = {b: run_phase(f"mixed/{b}", plans["mixed"], counter, backend=b)
             for b in ("xla", "pallas")}
    compare("mixed pallas vs xla", mixed["xla"], mixed["pallas"], FLOAT_RTOL)
    check_oracle("mixed", plans["mixed"], mixed)

    dl = {b: run_phase(f"deadline/{b}", plans["deadline"], counter,
                       backend=b) for b in ("xla", "pallas")}
    compare("deadline pallas vs xla", dl["xla"], dl["pallas"], FLOAT_RTOL)
    fired = {k: float(dl["xla"][k].sum()) for k in
             ("shed_tasks", "preemptions", "failures_injected")}
    print(json.dumps({"deadline_events": fired}), flush=True)
    if min(fired.values()) == 0:
        raise AssertionError(f"deadline family: a mechanism never fired "
                             f"({fired})")

    tail = {}
    for b in ("xla", "pallas"):
        tail[b] = run_phase(f"tailheavy/{b}", plans["tailheavy"], counter,
                            backend=b)
        tail[b + "+compact"] = run_phase(
            f"tailheavy/{b}+compact", plans["tailheavy"], counter,
            backend=b, compact="auto")
        compare(f"tailheavy {b} compact vs dense", tail[b],
                tail[b + "+compact"], FLOAT_RTOL)
    compare("tailheavy pallas vs xla", tail["xla"], tail["pallas"],
            FLOAT_RTOL)
    check_oracle("tailheavy", plans["tailheavy"],
                 {"xla": tail["xla"], "pallas": tail["pallas"]})


def four_chips(n: int, counter: CompileCounter, devices) -> None:
    import jax
    mesh = jax.sharding.Mesh(np.array(devices), ("pod",))
    plan = family_plan("mixed", n)
    sharded = run_phase("mixed/xla mesh4", plan, counter, mesh=mesh)
    # every chip of the mesh must have held a share of the batch
    used = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    print(json.dumps({"peak_bytes_in_use_per_chip": used}), flush=True)
    if min(used) < 1 << 20:
        raise AssertionError(f"mesh run left a chip idle: {used}")
    one = run_phase("mixed/xla one chip", plan, counter)
    compare("mixed mesh4 vs one chip", one, sharded, FLOAT_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if args.four_chips and len(jax.devices()) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips; JAX found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from repro.core.util import enable_compile_cache
    print(json.dumps({"device_kind": dev.device_kind,
                      "devices": len(jax.devices()),
                      "jax": jax.__version__,
                      "compile_cache": enable_compile_cache()}), flush=True)

    counter = CompileCounter()
    t0 = time.perf_counter()
    if args.four_chips:
        devices = jax.devices()[:4]
        four_chips(N_CELLS, counter, devices)
    else:
        devices = [dev]
        one_chip(N_CELLS, counter)
    print(json.dumps({
        "total_s": time.perf_counter() - t0, "compiles": counter.n,
        "compile_s": counter.s,
        "peak_bytes_in_use": [d.memory_stats()["peak_bytes_in_use"]
                              for d in devices]}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
