"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Prints ``name,us_per_call,derived`` CSV — one section per paper table/figure
(paper_groups), the sweep-throughput adaptation benchmark, the kernel
micro-benchmarks, and the workload/goodput study.  Roofline extraction for
the dry-run lives in ``benchmarks/roofline.py`` (separate entry point:
reads compiled artifacts, writes EXPERIMENTS.md tables).
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.core.util import enable_compile_cache
    enable_compile_cache()
    rows = []
    from . import paper_groups
    rows += paper_groups.all_rows()
    from . import sweep_throughput
    rows += sweep_throughput.all_rows()
    from . import kernel_bench
    rows += kernel_bench.all_rows()
    from . import goodput
    rows += goodput.all_rows()
    from . import speculative_execution
    rows += speculative_execution.all_rows()

    print("name,us_per_call,derived")
    for name, us, derived, *_ in rows:
        print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
