"""The trace reduction on a trace recorded on the chip.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_trace.py

``data/fleet_tail_trace`` holds the profiler trace of one timed sweep of
``terasort_fleet_tail`` on one TPU v5e chip (504 cells, Pallas
``mr_epoch`` with compaction).  Checked by hand against the trace's own
``XLA Modules`` line: the four ``jit__mr_epoch_impl`` modules sum to
72.33 ms, against 72.31 ms of kernel ops inside them.
"""
import pathlib

import pytest

from test_faults import ROOT  # noqa: F401  (puts the checkout on sys.path)
from bench import trace
from bench.metrics import (device_idle_share, device_ms_per_sweep,
                           mr_epoch_ms_per_sweep)

DATA = pathlib.Path(__file__).parent / "data" / "fleet_tail_trace"


@pytest.fixture(scope="module")
def run():
    return {"trace": trace.reduce(trace.load(str(DATA)), 1)}


def test_window_and_busy(run):
    t = run["trace"]
    assert t["sweeps"] == 1
    assert t["device_planes"] == ["/device:TPU:0"]
    assert t["window_s"] == pytest.approx(0.1904345, rel=1e-9)
    assert t["busy_s"] == pytest.approx(0.083110106, rel=1e-9)


def test_per_layer_metrics(run):
    device = device_ms_per_sweep.read(run)
    kernel = mr_epoch_ms_per_sweep.read(run)
    idle = device_idle_share.read(run)
    assert device == pytest.approx(83.110106, rel=1e-9)
    assert kernel == pytest.approx(72.305319, rel=1e-6)
    assert idle == pytest.approx(100 * (1 - 0.083110106 / 0.1904345),
                                 rel=1e-9)
    assert 0 < kernel < device


def test_idle_gaps_are_named_by_host_spans(run):
    gaps = run["trace"]["idle_gaps"]
    assert gaps[0][0] == "np.asarray(jax.Array)"
    assert gaps[0][1] == pytest.approx(0.012191901, rel=1e-6)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
