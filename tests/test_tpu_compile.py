"""Ahead-of-time compiles for a described TPU v5e, without the chip.

The TPU compiler is installed with JAX, so the main path's kernels can be
compiled here for a ``v5e:2x2`` topology that is described, not attached:
Mosaic refuses what interpret mode accepts (dot_generals it cannot lower,
bool loop carries, unaligned blocks), and these tests catch that without
chip time.  Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Keep these compiles in this one file so that one worker holds it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import sweep
from repro.kernels.mr_sched import megakernel, ops

N, T, V = 2048, 21, 9          # a mixed-policy bucket at the grid pads
T_TAIL = 41                    # the tail-heavy family's task pad
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # pragma: no cover - env
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for an absent chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _lane_data(sh, n, t, control):
    """mr_epoch's positional lane data for ``n`` lanes of ``t`` tasks."""
    s = functools.partial(_spec, sh)
    data = [s((n, t), f32), s((n, t), i32), s((n, t), f32), s((n, t), i32),
            s((n, t), i32), s((n, 1), f32), s((n, V), f32), s((n, V), f32),
            s((n, 1), i32), s((n, V), f32), s((n, V), f32), s((n, 1), f32),
            s((n, t), f32)]
    if control:
        data += [s((n, V), i32), s((n, V), f32), s((n, V), f32),
                 s((n, V), i32), s((n, 1), i32), s((n, 1), f32),
                 s((n, 1), f32), s((n, 1), f32), s((n, t), i32),
                 s((n, t), f32), s((n, t), f32), s((n, 1), i32),
                 s((n, 1), f32), s((n, 1), i32), s((n, 1), i32)]
    return data


def _compile_kernel(sh, *, t=T, control=False, **kw):
    kw.setdefault("tile", ops.COMPILED_TILE)
    compiled = megakernel.mr_epoch.lower(
        *_lane_data(sh, N, t, control), max_pes=4, interpret=False,
        control=control, **kw).compile()
    assert compiled.as_text()
    return compiled


def test_mr_epoch_compiles_open_loop(one_chip):
    _compile_kernel(one_chip)


@pytest.mark.parametrize("t", [T, T_TAIL])
def test_mr_epoch_compiles_control(one_chip, t):
    """At T=41 the control kernel fits VMEM only in compiled-size tiles
    (64 lanes run out of VMEM)."""
    _compile_kernel(one_chip, t=t, control=True)


def test_mr_epoch_compiles_traced_control(one_chip):
    _compile_kernel(one_chip, control=True, trace=True)


def test_mr_epoch_compiles_block_lanes(one_chip):
    _compile_kernel(one_chip, t=T_TAIL, tile=64, block_lanes=8)


def test_mr_epoch_compiles_donated_resume(one_chip):
    """The compacted driver's K-epoch step: state in, state donated."""
    s = functools.partial(_spec, one_chip)
    data = _lane_data(one_chip, N, T_TAIL, control=False)
    state = (s((N, 1), f32), s((N, T_TAIL), f32), s((N, T_TAIL), i32),
             s((N, T_TAIL), f32), s((N, T_TAIL), f32), s((N, T_TAIL), f32),
             s((N, 1), i32), s((N, 1), i32))
    compiled = megakernel.mr_epoch_donated.lower(
        *data[:2], None, *data[3:], state=state, tile=ops.COMPILED_TILE,
        max_pes=4, interpret=False, epoch_limit=8).compile()
    assert compiled.as_text()


def test_mr_epoch_rejects_unaligned_blocks():
    """A compiled block must be a multiple of 8 lanes (or all of them)."""
    args = [jnp.zeros((16, 4), f32), jnp.zeros((16, 4), i32),
            jnp.zeros((16, 4), f32), jnp.zeros((16, 4), i32),
            jnp.zeros((16, 4), i32), jnp.zeros((16, 1), f32),
            jnp.ones((16, 4), f32), jnp.ones((16, 4), f32)]
    with pytest.raises(ValueError, match="multiple of 8"):
        megakernel.mr_epoch(*args, tile=4, interpret=False)


def test_fused_runner_compiles_mixed_bucket(one_chip):
    """The XLA bucket runner at one real mixed-policy bucket: 8,192 cells,
    T=21, V=9, both policies as lane data, its columns in one uint32
    buffer."""
    n = 8192
    names = sorted(("n_maps", "n_reduces", "n_vms", "vm_mips", "vm_pes",
                    "vm_cost", "job_length", "job_data", "sched_policy",
                    "binding_policy"))
    layout = tuple((k, np.dtype(i32 if k in sweep._INT_PARAMS else f32), ())
                   for k in names)
    runner = sweep._fused_runner(layout, T, V, (), "xla", 0, False)
    buf = _spec(one_chip, (n * len(names),), jnp.uint32)
    compiled = runner.jit.lower(buf).compile()
    mem = compiled.memory_analysis()
    # the bucket's temporaries fit a 16 GiB v5e chip many times over
    assert mem.temp_size_in_bytes < (1 << 30), mem
    assert np.isfinite(mem.temp_size_in_bytes)
