"""Device idle time put down to the program's spans (``bench/spans.py``).

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_spans.py

The interval arithmetic on hand-made intervals; the reduction on the
trace recorded before the program had spans, which it must read as all
idle time outside them; and the reduction and the readers on two traces
recorded with the spans on one TPU v5e chip, one timed sweep each
(``python3 bench/record_trace.py --workload <cell> --seed 3000000017
--out bench/tests/data/<dir>``): ``fleet_tail_spans``
(``terasort_fleet_tail``, 504 cells, Pallas with compaction) and
``whatif_spans`` (``wordcount_whatif``, 144 cells, one XLA dispatch).
The expected idle times were checked by a brute-force split of each
window at every span and operation boundary, which agreed to the
nanosecond; the expected program counts are the traces' own ``XLA
Modules`` events.
"""
import json
import pathlib
import types

import numpy as np
import pytest

from test_faults import ROOT  # noqa: F401  (puts the checkout on sys.path)
from bench import spans, trace
from bench.metrics import (compaction_idle_ms_per_sweep,
                           host_transfers_per_sweep, lane_epoch_yield,
                           programs_per_sweep, readback_idle_ms_per_sweep,
                           upload_idle_ms_per_sweep)

DATA = pathlib.Path(__file__).parent / "data"


def _idle(*rows):
    return np.array(rows, np.float64).reshape(-1, 2)


def _check_sum(idle, by_name, outside):
    total = float(np.sum(np.diff(idle, axis=1)))
    assert sum(by_name.values()) + outside == pytest.approx(total, abs=1e-9)


def test_self_segments_of_nested_spans():
    got = spans.self_segments([(0, 100, "run"), (10, 90, "bucket"),
                               (10, 20, "upload"), (70, 90, "readback")])
    assert got == [(0, 10, "run"), (10, 20, "upload"), (20, 70, "bucket"),
                   (70, 90, "readback"), (90, 100, "run")]


def test_nested_spans_take_only_their_self_time():
    idle = _idle((0, 5), (15, 25), (50, 60), (85, 95))
    by_name, outside = spans.idle_by_span(
        idle, [(0, 100, "run"), (10, 90, "bucket"), (10, 20, "upload"),
               (70, 90, "readback")])
    assert by_name == {"run": 10.0, "bucket": 15.0, "upload": 5.0,
                       "readback": 5.0}
    assert outside == 0.0
    _check_sum(idle, by_name, outside)


def test_gap_crossing_two_spans_is_split():
    idle = _idle((5, 15))
    by_name, outside = spans.idle_by_span(idle, [(0, 10, "a"),
                                                 (10, 20, "b")])
    assert by_name == {"a": 5.0, "b": 5.0} and outside == 0.0
    _check_sum(idle, by_name, outside)


def test_gap_outside_every_span():
    idle = _idle((8, 12), (20, 30))
    by_name, outside = spans.idle_by_span(idle, [(0, 10, "a")])
    assert by_name == {"a": 2.0}
    assert outside == 12.0
    _check_sum(idle, by_name, outside)


def test_same_name_spans_add_up_and_no_idle_reads_zero():
    by_name, outside = spans.idle_by_span(
        _idle((0, 1), (4, 6)), [(0, 2, "poll"), (3, 7, "poll"),
                                (8, 9, "step")])
    assert by_name == {"poll": 3.0, "step": 0.0} and outside == 0.0
    assert spans.idle_by_span(_idle(), [(0, 2, "a")]) == ({"a": 0.0}, 0.0)


@pytest.fixture(scope="module")
def old_trace():
    """The trace recorded before the program had spans: one sweep of
    ``terasort_fleet_tail`` (``test_trace.py``)."""
    profile = trace.load(str(DATA / "fleet_tail_trace"))
    return {**trace.reduce(profile, 1), **spans.reduce(profile)}


def test_trace_without_program_spans(old_trace):
    t = old_trace
    assert t["idle_by_span"] == {} and t["programs"] == 0
    assert t["idle_s"] == pytest.approx(t["window_s"] - t["busy_s"],
                                        abs=1e-9)
    assert t["idle_outside_s"] == pytest.approx(t["idle_s"], abs=1e-9)
    for reader in (upload_idle_ms_per_sweep, readback_idle_ms_per_sweep,
                   compaction_idle_ms_per_sweep, programs_per_sweep):
        assert reader.read({"trace": t}) is None


# per cell: the six metrics of one recorded sweep, checked by hand
RECORDED = {
    "fleet_tail_spans": {
        upload_idle_ms_per_sweep: 4.99586,
        readback_idle_ms_per_sweep: 12.969981,
        compaction_idle_ms_per_sweep: (34.774035 + 11.775729 + 6.011033
                                       + 32.841691),
        programs_per_sweep: 189,
        host_transfers_per_sweep: 25 + 38,
        lane_epoch_yield: 100 * 23597 / 43296},
    "whatif_spans": {
        upload_idle_ms_per_sweep: 6.09288,
        readback_idle_ms_per_sweep: 12.782942,
        compaction_idle_ms_per_sweep: None,
        programs_per_sweep: 1,
        host_transfers_per_sweep: 20 + 28,
        lane_epoch_yield: 100 * 2432 / 5472},
}


@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request):
    """``(name, profile, run)``: a recorded sweep reduced as the harness
    reduces a traced window, with the sweep's ``RunReport``."""
    profile = trace.load(str(DATA / request.param))
    report = json.loads((DATA / request.param / "report.json").read_text())
    run = {"trace": {**trace.reduce(profile, 1), **spans.reduce(profile)},
           "reports": [types.SimpleNamespace(**report)]}
    return request.param, profile, run


def test_recorded_idle_adds_up(recorded):
    _, _, run = recorded
    t = run["trace"]
    assert t["sweeps"] == 1
    assert t["idle_s"] == pytest.approx(t["window_s"] - t["busy_s"],
                                        abs=1e-9)
    assert sum(t["idle_by_span"].values()) + t["idle_outside_s"] == \
        pytest.approx(t["idle_s"], abs=1e-6)
    assert set(t["idle_by_span"]) >= {"iotsim.run", "iotsim.bucket",
                                      "iotsim.upload", "iotsim.readback"}


def test_recorded_programs_are_the_chips_modules(recorded):
    _, profile, run = recorded
    (modules,) = [line for plane in profile.planes
                  if plane.name == "/device:TPU:0"
                  for line in plane.lines if line.name == "XLA Modules"]
    assert run["trace"]["programs"] == len(list(modules.events))


def test_recorded_readers(recorded):
    name, _, run = recorded
    for reader, want in RECORDED[name].items():
        got = reader.read(run)
        if want is None:
            assert got is None, reader.__name__
        else:
            assert got == pytest.approx(want, rel=1e-6), reader.__name__
