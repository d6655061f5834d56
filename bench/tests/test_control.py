"""The control fails the comparison that the program passes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_control.py

The control is the plain reference computed in bfloat16, the precision
below the float32 that the configurations state, put in the program's
place on the same sampled cells.  On three seeds of each one-chip cell,
cut to a CPU test's size, the program's widest gap stays within the
cell's limit and the control's does not.
"""
import time

import ml_dtypes
import pytest

from test_faults import ONE_CHIP, run, small


@pytest.mark.parametrize("seed", (3, 2**31 + 5, 2**33 + 9))
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_rejected(workload, seed):
    spec, config, mix = small(workload)
    r = run.measure(spec, workload, config, mix, seed, 0.3, False,
                    require_tpu=False, t_start=time.perf_counter(),
                    control_dtype=ml_dtypes.bfloat16)
    limit = mix["check"]["limit"]
    assert r["checks"]["max_rel_gap"]["value"] <= limit
    assert r["control"] > limit
