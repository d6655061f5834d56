"""Readings that set a cell's limit: the program's widest gap on many seeds,
and the control's on the same sampled cells.

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds <n> ...

One process sets up once and runs a short window per seed at the cell's
own load.  For each seed it prints the program's widest relative gap to
the float64 reference and the control's: the same reference computed in
bfloat16 (the precision below the float32 the configurations state), put
in the program's place on the same sampled cells.  The limit in the
cell's traffic file lies between the largest program reading and the
smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import ml_dtypes

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generate                                      # noqa: E402
from bench.run import NO_CHIP, NoChip, measure                  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    mix = generate.load("traffic", cell["traffic"])
    rows = []
    for seed in args.seeds:
        try:
            r = measure(spec, args.workload, config, mix, seed, args.seconds,
                        False, t_start=time.perf_counter(),
                        control_dtype=ml_dtypes.bfloat16)
        except NoChip as e:
            print(f"readings: {e}", file=sys.stderr)
            return NO_CHIP
        row = {"reading": args.workload, "seed": seed,
               "program": r["checks"]["max_rel_gap"]["value"],
               "control": r["control"], "sweeps": r["attempted"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"readings": args.workload, "seeds": len(rows),
                      "program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
