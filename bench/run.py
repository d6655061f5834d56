"""Benchmark harness: one cell of ``BENCHMARK.json`` on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A closed loop drives ``SweepPlan.run``: one planner builds a sweep plan,
waits for the host-side ``SweepResult`` and builds the next.  Every sweep
is a fresh plan drawn from ``(seed, index)`` with the cell's axes and
shapes (``bench/generate.py``), so set-up warms every program the window
runs and nothing compiles inside it.

Set-up (``setup_s``) runs from process start to the first timed sweep:
imports, the persistent compile cache (``.jax_cache/`` in the checkout),
and the mix's warm-up sweeps.  The window then runs sweeps for
``--seconds``; every sweep started before the time is up completes.  With
``--trace 1`` the profiler records the first whole sweeps of the window
and the run reports the per-layer metrics instead of the end-to-end ones.

After the window ``bench/check.py`` compares sampled cells with the plain
reference.  Earlier lines of standard output describe the device, the
set-up and the window; the last is the result.  Without a TPU, or with
fewer chips than the cell asks for, the run exits with status 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                  # noqa: E402
import importlib.util                                            # noqa: E402
import json                                                      # noqa: E402
import os                                                        # noqa: E402
import pathlib                                                   # noqa: E402
import shutil                                                    # noqa: E402
import statistics                                                # noqa: E402
import sys                                                       # noqa: E402
import tempfile                                                  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np                                               # noqa: E402

from bench import check, generate                                # noqa: E402
from bench import trace as trace_mod                             # noqa: E402
from bench.compile_counter import CompileCounter                 # noqa: E402
from bench.stallwatch import StallWatch                          # noqa: E402

TRACE_SECONDS = 5.0        # profiled share of a --trace 1 window
NO_CHIP = 2                # exit status: no TPU, or too few chips


class NoChip(RuntimeError):
    pass


def log(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


def _metric_reader(name: str):
    path = generate.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def measure(spec: dict, workload: str, config: dict, mix: dict, seed: int,
            seconds: float, trace: bool, *, require_tpu: bool = True,
            t_start: float = T_START, control_dtype=None) -> dict:
    """Run one cell; returns the result object (the last output line).

    ``control_dtype`` also reads the control: the reference in that
    precision, put in the program's place on the same sampled cells, under
    the result's ``control`` key (``bench/readings.py``; the benchmark's
    own runs never read it)."""
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    chips = int(cell["chips"])
    os.environ["REPRO_COSTMODEL_PATH"] = str(ROOT / ".bench_cache"
                                             / "costmodel.json")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs: none
    t_import = time.perf_counter() - t_start
    import jax
    devices = jax.devices()
    t_devices = time.perf_counter() - t_start
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    devices = devices[:chips]
    from repro.core.util import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(device_kind=devices[0].device_kind, devices=len(jax.devices()),
        chips_used=chips, jax=jax.__version__, compile_cache=cache,
        workload=workload, seed=seed, import_s=t_import,
        jax_devices_s=t_devices - t_import)

    counter = CompileCounter()
    traffic = generate.Traffic(config, mix)
    kw = traffic.run_kwargs(devices)
    warm = []
    for w in range(int(mix.get("warmup_sweeps", 1))):
        tw = time.perf_counter()
        plan = traffic.plan(seed, w, generate.WARMUP)
        out = plan.run(report=(w == 0), **kw)
        warm.append(time.perf_counter() - tw)
        if w == 0:
            res, rep = out
            log(phase="warmup", cells=plan.size, buckets=rep.n_buckets,
                dispatches=rep.dispatches,
                compaction_syncs=rep.compaction_syncs,
                scalar_syncs=rep.scalar_syncs,
                realized_epochs_max=int(np.max(res["realized_epochs"])))
    n_cells = plan.size
    setup_s = time.perf_counter() - t_start
    setup_compiles, setup_compile_s = counter.snapshot()
    log(phase="setup", setup_s=setup_s, compiles=setup_compiles,
        compile_s=setup_compile_s, warmup_s=warm)

    keeper = check.Keeper(seed, n_cells, int(mix["check"]["per_sweep"]))
    reports, lat = [], []
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    profiling = False
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        profiling = True
    c0 = counter.n
    watch = StallWatch()
    t0 = time.perf_counter()
    end, trace_end = t0 + seconds, t0 + min(seconds, TRACE_SECONDS)
    i, te = 0, t0
    while True:
        ts = time.perf_counter()
        if ts >= end:
            break
        if profiling and ts >= trace_end:
            jax.profiler.stop_trace()
            profiling = False
        watch.begin(i)
        with jax.profiler.TraceAnnotation("bench.sweep"):
            with jax.profiler.TraceAnnotation("bench.build_plan"):
                plan = traffic.plan(seed, i)
            with jax.profiler.TraceAnnotation("bench.run"):
                out = plan.run(report=trace, **kw)
            res = out[0] if trace else out
            if trace:
                reports.append(out[1])
            te = time.perf_counter()
            lat.append(watch.end())
            with jax.profiler.TraceAnnotation("bench.keep"):
                keeper.add(i, res)
        i += 1
    if profiling:
        jax.profiler.stop_trace()
    window_s = te - t0
    window_compiles = counter.n - c0
    log(phase="window", sweeps=i, cells=i * n_cells, window_s=window_s,
        window_compiles=window_compiles,
        dispatches=sum(r.dispatches for r in reports) if trace else None,
        sweep_ms_median=1e3 * statistics.median(lat),
        sweep_ms_max=1e3 * max(lat), **watch.close())

    peak = [d.memory_stats().get("peak_bytes_in_use", 0)
            if d.memory_stats() else 0 for d in devices]
    del res, out
    tr = None
    if trace:
        tr = trace_mod.reduce(trace_mod.load(trace_dir), chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(phase="trace", window_s=tr["window_s"], busy_s=tr["busy_s"],
            sweeps=tr["sweeps"], device_planes=tr["device_planes"])

    sample = keeper.sample(int(mix["check"]["cells"]))
    t_ref = time.perf_counter()
    want = check.reference_values(traffic, seed, sample)
    gap = check.widest_gap([v for _, _, v in sample], want)
    limit = float(mix["check"]["limit"])
    bad_sweeps = {s for (s, _, v), w in zip(sample, want)
                  if check.widest_gap([v], [w]) > limit}
    log(phase="check", cells=len(sample),
        reference_s=time.perf_counter() - t_ref)
    control = None
    if control_dtype is not None:
        control = check.widest_gap(check.reference_values(
            traffic, seed, sample, control_dtype), want)

    if trace:
        data = {"sweeps": i, "reports": reports,
                "window_compiles": window_compiles,
                "setup_compile_s": setup_compile_s, "trace": tr}
        metrics = {}
        for m in spec["per_layer"]:
            if not _applies(m, workload):
                continue
            v = _metric_reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"cells_per_s": i * n_cells / window_s,
                  "sweep_p95_ms": 1e3 * _p95(lat), "setup_s": setup_s}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec["end_to_end"] if _applies(m, workload)}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": int(max(peak))}
    result = {"correct": bool(gap <= limit), "attempted": i,
              "failed": len(bad_sweeps), "metrics": metrics,
              "device": device}
    if trace and tr["busy_s"] is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        top = sorted(tr["ops"].items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [list(x) for x in top],
                               "idle_gaps": [list(x)
                                             for x in tr["idle_gaps"]]}
    if control is not None:
        result["control"] = control
    result["checks"] = {"max_rel_gap": {"value": gap, "limit": limit}}
    return result


def _p95(xs) -> float:
    """95th percentile (inclusive quantiles) of every sweep's wall time."""
    if len(xs) < 2:
        return float(xs[0])
    return statistics.quantiles(xs, n=20, method="inclusive")[-1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 1
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    mix = generate.load("traffic", cell["traffic"])
    try:
        result = measure(spec, args.workload, config, mix, args.seed,
                         args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
