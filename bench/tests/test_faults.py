"""The comparison rejects a broken timed path.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Each test drives a whole run of a cell (set-up, window, comparison) on
the CPU at a small size, skipping only the harness's look for a chip,
with one fault planted underneath the timed path, and sees ``correct``
come out false: an epoch step that returns its state unchanged; half of
the cells left out, their results copied from the rest; an answer
altered where it is produced; and, on a four-chip cell or on the mesh
mix kept for one, the gather of the other chips' shards left out.
"""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generate, run                                  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
# The mix kept for a four-chip cell: its path is tested here whether or
# not BENCHMARK.json has such a cell yet.
MESH_MIX = {"name": "wordcount_capacity_space_mesh4",
            "config": "hibench_wordcount_huge",
            "traffic": "capacity_space_mesh", "chips": 4}
MESH = [w["name"] for w in SPEC["workloads"] if w["chips"] > 1] \
    or [MESH_MIX["name"]]
FAULTS = ("unchanged_step", "half_batch", "altered_answer")


def small(workload: str):
    """The cell's spec, configuration and mix, cut to a CPU test's size:
    12 maps, two fleet sizes, two storage seeds per sweep."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    if workload == MESH_MIX["name"] and \
            all(w["name"] != workload for w in spec["workloads"]):
        spec["workloads"].append(MESH_MIX)
    cell = next(w for w in spec["workloads"] if w["name"] == workload)
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    config.update(n_maps=12, n_reduces=3, data_mb=1536.0,
                  job_length_mi=2786.0)
    mix = generate.load("traffic", cell["traffic"])
    for ax in mix["axes"]:
        if ax[0] == "n_vms":
            ax[1] = ax[1][:2]
        if ax[0] == "storage_seed":
            ax[1] = {"draw": 2}
    mix["warmup_sweeps"] = 1
    return spec, config, mix


def plant(fault: str, monkeypatch) -> None:
    import jax
    from repro.core import engine, sweep
    from repro.kernels.mr_sched import ops
    if fault == "unchanged_step":
        monkeypatch.setattr(engine, "_epoch_step",
                            lambda sc, inv, c, **kw: c)
        for name in ("mr_epoch", "mr_epoch_donated"):
            monkeypatch.setattr(ops, name, lambda *a, state, **kw: state)
    elif fault == "half_batch":
        orig = sweep._run_cells

        def half(cols, n, *a, **kw):
            keep = (n + 1) // 2
            out = orig({k: v[:keep] for k, v in cols.items()}, keep,
                       *a, **kw)
            return jax.tree.map(lambda x: np.concatenate([x, x])[:n], out)
        monkeypatch.setattr(sweep, "_run_cells", half)
    elif fault == "altered_answer":
        orig = sweep.job_metrics

        def altered(sc, out):
            jm = orig(sc, out)
            return jm._replace(makespan=jm.makespan * 1.01)
        monkeypatch.setattr(sweep, "job_metrics", altered)
    elif fault == "exchange_left_out":
        orig = sweep._simulate_full_sharded

        def first_shard_only(batch, mesh, control=False):
            jm, sm = orig(batch, mesh, control)
            n = jm.makespan.shape[0] // mesh.devices.size
            keep = lambda x: x.at[n:].set(0)                # noqa: E731
            return jax.tree.map(keep, jm), jax.tree.map(keep, sm)
        monkeypatch.setattr(sweep, "_simulate_full_sharded",
                            first_shard_only)
    else:
        raise ValueError(fault)


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Programs traced under a planted fault must not outlive the test."""
    import jax
    from repro.core import sweep

    def clear():
        for fn in (sweep._fused_runner, sweep._grid_encoder,
                   sweep._sharded_runner):
            fn.cache_clear()
        jax.clear_caches()
    clear()
    yield
    clear()


def correct(workload: str, seed: int = 2**31 + 11) -> dict:
    spec, config, mix = small(workload)
    import time
    return run.measure(spec, workload, config, mix, seed, 0.5, False,
                       require_tpu=False, t_start=time.perf_counter())


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_run_is_correct(workload):
    assert correct(workload)["correct"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    plant(fault, monkeypatch)
    assert not correct(workload)["correct"]


MESH_SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}, {here!r}]
import pytest, test_faults
mp = pytest.MonkeyPatch()
if {fault!r} != "none":
    test_faults.plant({fault!r}, mp)
r = test_faults.correct({workload!r})
print(json.dumps({{"correct": r["correct"]}}))
"""


@pytest.mark.parametrize("fault", ("none",) + FAULTS + ("exchange_left_out",))
@pytest.mark.parametrize("workload", MESH)
def test_mesh_cell(workload, fault):
    """Four virtual CPU devices, in a process of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = MESH_SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"),
                              here=str(pathlib.Path(__file__).parent),
                              fault=fault, workload=workload)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])["correct"]
    assert got == (fault == "none")
