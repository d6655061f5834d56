"""One upload and one readback per launch (DESIGN.md §4.3).

Every launch of ``SweepPlan.run`` off the mesh sends its columns to the
device as one uint32 buffer and reads its results back, the realized
epoch count included, as one:

* **transfer counts** — the sweep front end makes one ``telemetry.put``
  and one ``telemetry.pull`` per bucket launch (dense), per chunk
  (chunked), and per compacted execution, beside the compaction loop's
  own index uploads and syncs (XLA and Pallas);
* **bitwise contract** — packing moves bits and computes nothing, so the
  results equal, leaf by leaf, the same programs fed one ``jnp.asarray``
  per column and read back one ``np.asarray`` per leaf, across per-VM and
  per-task columns, static policies, mixed and narrow dtypes, and
  ``jax.Array`` columns handed to ``grid_arrays``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costmodel, engine, sweep, telemetry
from repro.core.sweep import axis, product
from repro.kernels.mr_sched import epoch_schedule

_PINNED = costmodel.CostModel(dispatch_us=100.0, epoch_lane_us=0.05,
                              sync_us=40.0, device="pinned")
T, V = 16, 4


def _plan():
    """24 cells, 1 to 12 maps on 1 to 4 VMs of per-VM speeds: tasks pad
    to several buckets, and most lanes finish long before the last."""
    return product(axis("n_maps", [1, 2, 3, 12]), axis("n_vms", [1, 2, 4]),
                   axis("vm_mips", [[250.0, 500.0, 1000.0, 750.0],
                                    [1000.0, 250.0, 250.0, 500.0]]))


def _front_end(monkeypatch) -> list:
    """The dtypes of the transfers the sweep front end makes itself, in
    order (the compaction drivers call their own ``put``/``pull``)."""
    seen = []
    put, pull = telemetry.put, telemetry.pull

    def put_(x, stats):
        seen.append(("h2d", np.asarray(x).dtype))
        return put(x, stats)

    def pull_(x, stats):
        a = pull(x, stats)
        seen.append(("d2h", a.dtype))
        return a
    monkeypatch.setattr(telemetry, "put", put_)
    monkeypatch.setattr(telemetry, "pull", pull_)
    return seen


@pytest.mark.parametrize("kw", [
    dict(), dict(bucket=False), dict(backend="pallas"), dict(chunk=5),
    dict(compact=2), dict(backend="pallas", compact=2),
    dict(chunk=5, compact=2)],
    ids=["dense", "one-bucket", "pallas-dense", "chunk", "xla-compact",
         "pallas-compact", "chunk-compact"])
def test_one_upload_one_readback_per_launch(monkeypatch, kw):
    plan = _plan()
    base = plan.run(cost_model=_PINNED, **kw)
    seen = _front_end(monkeypatch)
    res, rep = plan.run(cost_model=_PINNED, report=True, **kw)
    for name in base.metric_names:
        np.testing.assert_array_equal(base[name], res[name], err_msg=name)
    assert len(rep.buckets) == (1 if kw.get("bucket") is False else 2)
    launches = [-(-b.cells // kw.get("chunk", b.cells)) for b in rep.buckets]
    # each launch: its column buffer up, then its result buffer down
    assert seen == [("h2d", np.uint32), ("d2h", np.uint32)] * sum(launches)
    for b, n in zip(rep.buckets, launches):
        if "compact" in kw:
            assert b.d2h_transfers == n + b.compact_syncs \
                + b.compact_scalar_syncs, b
            assert b.h2d_transfers >= n, b      # and the loop's own puts
        else:
            assert (b.h2d_transfers, b.d2h_transfers) == (n, n), b
            assert b.dispatches == n


def _columns(case: str, n: int = 24, seed: int = 3):
    """Host columns and statics of one case, at pads ``T`` and ``V``."""
    rng = np.random.default_rng(seed)
    cols = dict(
        n_maps=rng.integers(1, 12, n).astype(np.int32),
        n_reduces=rng.integers(1, 4, n).astype(np.int32),
        n_vms=rng.integers(1, V + 1, n).astype(np.int32),
        vm_mips=rng.choice([250.0, 500.0, 1000.0], n).astype(np.float32),
        vm_pes=rng.choice([1.0, 2.0], n).astype(np.float32),
        vm_cost=rng.choice([1.0, 2.0], n).astype(np.float32),
        job_length=rng.choice([362880.0, 725760.0], n).astype(np.float32),
        job_data=rng.choice([2e5, 4e5], n).astype(np.float32))
    statics = {}
    if case == "per-vm-per-task":
        cols["vm_mips"] = rng.choice([250.0, 500.0, 1000.0], (n, V))
        cols["vm_cost"] = rng.uniform(0.5, 2.0, (n, V)).astype(np.float32)
        cols["task_mult"] = rng.uniform(0.5, 1.5, (n, T))
        cols["task_prio"] = rng.integers(0, 3, (n, T)).astype(np.float32)
    elif case == "static-policies":
        statics = {"sched_policy": 0, "binding_policy": 2}
    elif case == "mixed-dtypes":
        # float64 and int64 as numpy gives them, narrow ints, a bool
        cols["n_maps"] = cols["n_maps"].astype(np.int64)
        cols["n_reduces"] = cols["n_reduces"].astype(np.int8)
        cols["job_length"] = cols["job_length"].astype(np.float64) + 0.1
        cols["net_bw"] = rng.uniform(100.0, 1000.0, n)
        cols["sched_policy"] = rng.integers(0, 2, n).astype(np.int32)
        cols["binding_policy"] = rng.integers(0, 3, n).astype(np.uint8)
        cols["storage_enabled"] = rng.integers(0, 2, n).astype(bool)
    return cols, statics


_CASES = ("per-vm-per-task", "static-policies", "mixed-dtypes")


def _per_leaf_encode(cols, statics):
    """The encoder fed one ``jnp.asarray`` per column."""
    names = sorted(cols)

    def one(*xs):
        return sweep.encode_cell(**dict(zip(names, xs)), **statics,
                                 pad_tasks=T, pad_vms=V)
    return jax.jit(jax.vmap(one))(*(jnp.asarray(cols[k]) for k in names))


def _leaves_equal(got, want, what):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want), what
    for i, (a, b) in enumerate(zip(got, want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, (what, i)
        assert a.tobytes() == b.tobytes(), (what, i)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", _CASES)
def test_dense_launch_bitwise_per_leaf(case, backend):
    """A packed bucket launch == the same program with per-column uploads
    and per-leaf readback."""
    cols, statics = _columns(case)
    max_pes = int(np.ceil(np.max(cols["vm_pes"])))

    def program(*xs):
        batch = jax.vmap(lambda *c: sweep.encode_cell(
            **dict(zip(sorted(cols), c)), **statics, pad_tasks=T,
            pad_vms=V))(*xs)
        if backend == "pallas":
            out = epoch_schedule(batch, max_pes=max_pes)
            realized = jnp.max(out.n_epochs)
        else:
            out, realized = engine.simulate_batch_arrays(batch)
        return sweep._metrics_of(batch, out) + (realized,)

    want = jax.jit(program)(*(jnp.asarray(cols[k]) for k in sorted(cols)))
    want = [np.asarray(x) for x in jax.tree.leaves(want)]
    stats = {}
    jm, sm, realized = sweep._run_cells(cols, 24, T, V, statics or None,
                                        None, None, backend, stats=stats)
    _leaves_equal((jm, sm), want[:-1], case)
    assert (realized == want[-1]).all() and realized.dtype == np.int32
    assert (stats["h2d_transfers"], stats["d2h_transfers"]) == (1, 1)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("case", _CASES)
def test_compacted_launch_bitwise_per_leaf(case, backend):
    """A packed compacted execution == the same encoder, loop and metrics
    pass with per-column uploads and per-leaf readback."""
    from repro.kernels.mr_sched import epoch_schedule_compact
    cols, statics = _columns(case)
    max_pes = int(np.ceil(np.max(cols["vm_pes"])))
    batch = _per_leaf_encode(cols, statics)
    if backend == "pallas":
        out, realized = epoch_schedule_compact(batch, k=2, max_pes=max_pes,
                                               cost_model=_PINNED)
    else:
        out, realized = engine.simulate_batch_arrays_compact(
            batch, k=2, cost_model=_PINNED)
    want = jax.jit(sweep._metrics_of)(batch, out)
    stats = {}
    jm, sm, rz = sweep._run_compact(cols, T, V, statics or None, backend, 2,
                                    _PINNED, max_pes, stats=stats)
    _leaves_equal((jm, sm), want, case)
    assert rz == int(np.asarray(realized)) and isinstance(rz, int)
    assert stats["d2h_transfers"] == 1 + stats["syncs"] \
        + stats["scalar_syncs"]


@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("where", ["host", "device", "both"])
def test_grid_arrays_columns_bitwise(case, where):
    """``grid_arrays`` packs its host columns and passes ``jax.Array``
    columns through: the batch equals the per-column encoder's, and only
    host columns cross, in one upload."""
    cols, statics = _columns(case)
    names = sorted(cols)
    on_device = {"host": (), "device": names, "both": names[::2]}[where]
    mixed = {k: jnp.asarray(v) if k in on_device else v
             for k, v in cols.items()}
    stats = {}
    got = sweep.grid_arrays(mixed, pad_tasks=T, pad_vms=V,
                            static_params=statics or None, stats=stats)
    _leaves_equal(got, _per_leaf_encode(cols, statics), (case, where))
    uploads = 0 if where == "device" else 1
    assert stats.get("h2d_transfers", 0) == uploads


def test_column_packing_round_trip():
    """``_pack_columns`` casts as ``jnp.asarray`` does, and the program's
    unpacking gives every column back bit for bit, odd values included."""
    n = 5
    cols = {
        "a": np.array([1e300, -0.0, np.nan, 1e-320, 3.3]),
        "b": np.array([2**31 + 11, -1, 2**40 + 3, 0, 7], np.int64),
        "c": np.array([True, False, True, True, False]),
        "d": np.arange(n * 3, dtype=np.float16).reshape(n, 3),
        "e": np.array([250, 0, 1, 2, 3], np.uint8),
        "f": np.broadcast_to(np.float32(0.5), (n, 2))}
    layout = sweep._upload_layout(cols, sorted(cols))
    buf = sweep._pack_columns(cols, layout)
    assert buf.dtype == np.uint32
    assert buf.size == n * (1 + 1 + 1 + 3 + 1 + 2)
    back = jax.jit(lambda b: sweep._unpack_columns(b, layout))(
        jnp.asarray(buf))
    for (name, _, _), x in zip(layout, back):
        _leaves_equal(x, jnp.asarray(cols[name]), name)
