"""Record the profiler trace of one timed sweep of a cell, for the tests
of the trace reduction (``bench/tests/data/``).

    python3 bench/record_trace.py --workload <cell> --seed <n> --out <dir>

Set-up is the harness's (``bench/run.py``): the pinned cost model, the
persistent compile cache and the mix's warm-up sweeps.  Then sweep 0 of
the window runs under a ``bench.sweep`` span with the profiler on, as a
``--trace 1`` run records it (Python tracer off, host tracer level 1),
followed by five untraced sweeps for comparison.  Writes
``<out>/sweep.xplane.pb.gz`` and ``<out>/report.json`` (the traced
sweep's ``RunReport``) and prints one line with both reductions of the
trace (``bench/trace.py``, ``bench/spans.py``) and the traced and
untraced sweep times.  Needs a TPU: exit status 2 without one.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import pathlib
import shutil
import statistics
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import generate, run, spans, trace                    # noqa: E402


def record(config: dict, mix: dict, seed: int, out: pathlib.Path, *,
           require_tpu: bool = True) -> dict:
    os.environ["REPRO_COSTMODEL_PATH"] = str(ROOT / ".bench_cache"
                                             / "costmodel.json")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    device = jax.devices()[0]
    if require_tpu and device.platform != "tpu":
        raise run.NoChip(f"needs a TPU; JAX found {device.platform!r}")
    from repro.core.util import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    traffic = generate.Traffic(config, mix)
    kw = traffic.run_kwargs([device])
    for w in range(int(mix.get("warmup_sweeps", 1))):
        traffic.plan(seed, w, generate.WARMUP).run(**kw)

    tmp = tempfile.mkdtemp(prefix="bench_record_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.sweep"):
        plan = traffic.plan(seed, 0)
        _, report = plan.run(report=True, **kw)
    traced_ms = 1e3 * (time.perf_counter() - t0)
    jax.profiler.stop_trace()
    untraced = []
    for i in range(1, 6):
        t0 = time.perf_counter()
        traffic.plan(seed, i).run(**kw)
        untraced.append(1e3 * (time.perf_counter() - t0))

    out.mkdir(parents=True, exist_ok=True)
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    with open(path, "rb") as src, \
            gzip.open(out / "sweep.xplane.pb.gz", "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    (out / "report.json").write_text(report.to_json(indent=1))
    profile = trace.load(str(out))
    tr = trace.reduce(profile, 1)
    return {"device_kind": device.device_kind, "cells": plan.size,
            "traced_sweep_ms": traced_ms,
            "untraced_sweep_ms_median": statistics.median(untraced),
            "window_s": tr["window_s"], "busy_s": tr["busy_s"],
            **spans.reduce(profile),
            "dispatches": report.dispatches,
            "host_syncs": report.compaction_syncs + report.scalar_syncs,
            "h2d_transfers": report.h2d_transfers,
            "d2h_transfers": report.d2h_transfers,
            "lane_epochs_allotted": report.lane_epochs_allotted,
            "lane_epochs_useful": report.lane_epochs_useful}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", type=pathlib.Path, required=True)
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    mix = generate.load("traffic", cell["traffic"])
    try:
        result = record(config, mix, args.seed, args.out)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return run.NO_CHIP
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
