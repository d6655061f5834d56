"""Sparse active-lane compaction + measured-cost scheduling (DESIGN.md §9).

The compacted stepping drivers — ``engine.simulate_batch_arrays_compact``
and the Pallas ``epoch_schedule_compact`` — gather still-active lanes into
a pow2-padded batch every K epochs and scatter the carry back.  Because
the epoch body is idempotent for finished lanes, dropping them from the
working set is a **bitwise** no-op; this suite pins that claim:

* compacted == dense ``simulate_batch_arrays``, every ``SimOutput`` field
  and the realized epoch count, across all 6 policy combos, a mixed
  storage grid (LOCALITY + replication/placement skew) and an elastic
  grid with stranded lanes (``finish`` stays at the 1e30 +inf stand-in),
  for K in {1, 4, "auto"};
* ``run(compact=...)`` == ``run()`` across bucketed / chunked / pallas
  execution modes, including same-mode ``realized_epochs`` parity;
* engine <-> batched <-> pallas parity under compaction;
* the shared pow2 padding util matches the retired per-unique-value loop;
* the measured cost model is deterministic given a pinned calibration
  file — equal coefficients, equal bucket partitions, equal intervals.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:              # environment without hypothesis: the
    HAVE_HYPOTHESIS = False      # seeded-rng cases below still run

from repro.core import (BindingPolicy, SchedPolicy, compaction, costmodel,
                        engine, sweep)
from repro.core.engine import _BIG
from repro.core.sweep import axis, product, zip_
from repro.core.util import pow2_pad, pow2_pads
from repro.kernels.mr_sched import epoch_schedule, epoch_schedule_compact

ALL_POLICIES = [(sp, bp) for sp in SchedPolicy for bp in BindingPolicy]
KS = [1, 4, "auto"]

# one pinned calibration shared by every scheduling-determinism test
PINNED = costmodel.CostModel(dispatch_us=800.0, epoch_lane_us=0.05,
                             sync_us=120.0, device="pinned")


def _random_params(n, seed, mixed_policies=True):
    rng = np.random.default_rng(seed)
    params = dict(
        n_maps=rng.integers(1, 21, n).astype(np.int32),
        n_reduces=rng.integers(1, 3, n).astype(np.int32),
        n_vms=rng.integers(1, 10, n).astype(np.int32),
        vm_mips=rng.choice([250.0, 500.0, 1000.0], n).astype(np.float32),
        vm_pes=rng.choice([1.0, 2.0, 4.0], n).astype(np.float32),
        vm_cost=rng.choice([1.0, 2.0], n).astype(np.float32),
        job_length=rng.choice([362880.0, 725760.0], n).astype(np.float32),
        job_data=rng.choice([2e5, 4e5], n).astype(np.float32),
    )
    if mixed_policies:
        params["sched_policy"] = rng.integers(0, 2, n).astype(np.int32)
        params["binding_policy"] = rng.integers(0, 3, n).astype(np.int32)
    return params


def _storage_params(n, seed):
    rng = np.random.default_rng(seed)
    params = _random_params(n, seed)
    params.update(
        binding_policy=rng.integers(0, 4, n).astype(np.int32),
        storage_enabled=rng.integers(0, 2, n).astype(np.float32),
        replication=rng.integers(1, 4, n).astype(np.int32),
        placement=rng.integers(0, 2, n).astype(np.int32),
        block_size_mb=rng.choice([1024.0, 8192.0], n).astype(np.float32),
        storage_seed=rng.integers(0, 100, n).astype(np.int32),
    )
    return params


def _elastic_params(n, seed):
    """Lease windows that close before some tasks become eligible — the
    grid must exercise stranded lanes (asserted below)."""
    rng = np.random.default_rng(seed)
    params = _random_params(n, seed)
    params.update(
        job_submit=rng.choice([0.0, 400.0], n).astype(np.float32),
        spinup_delay=rng.choice([0.0, 120.0], n).astype(np.float32),
        vm_start=rng.choice([0.0, 800.0], (n, 9)).astype(np.float32),
        vm_stop=rng.choice([900.0, 40000.0, _BIG], (n, 9)
                           ).astype(np.float32),
        task_prio=rng.integers(0, 3, (n, 23)).astype(np.float32),
    )
    return params


def _assert_bitwise(a, b, tag):
    for f in a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{f} ({tag})")


# ---------------------------------------------------------------------------
# Engine: compacted vs dense, bitwise (policies x storage x elastic)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sp,bp", ALL_POLICIES,
                         ids=[f"{sp.name}-{bp.name}"
                              for sp, bp in ALL_POLICIES])
def test_engine_compact_bitwise_per_policy(sp, bp):
    n = 24
    params = _random_params(n, seed=10 * int(sp) + int(bp),
                            mixed_policies=False)
    params["sched_policy"] = np.full(n, int(sp), np.int32)
    params["binding_policy"] = np.full(n, int(bp), np.int32)
    batch = sweep.grid_arrays(params, pad_tasks=23, pad_vms=9)
    dense, realized = jax.jit(engine.simulate_batch_arrays)(batch)
    for k in KS:
        comp, rz = engine.simulate_batch_arrays_compact(batch, k=k)
        _assert_bitwise(dense, comp, f"{sp.name}/{bp.name} k={k}")
        assert int(rz) == int(realized), (sp, bp, k)


@pytest.mark.parametrize("k", KS, ids=[f"k{k}" for k in KS])
def test_engine_compact_bitwise_storage_grid(k):
    batch = sweep.grid_arrays(_storage_params(48, seed=11),
                              pad_tasks=23, pad_vms=9)
    dense, realized = jax.jit(engine.simulate_batch_arrays)(batch)
    comp, rz = engine.simulate_batch_arrays_compact(batch, k=k)
    _assert_bitwise(dense, comp, f"storage k={k}")
    assert int(rz) == int(realized)


@pytest.mark.parametrize("k", KS, ids=[f"k{k}" for k in KS])
def test_engine_compact_bitwise_elastic_stranded(k):
    batch = sweep.grid_arrays(_elastic_params(48, seed=23),
                              pad_tasks=23, pad_vms=9)
    dense, realized = jax.jit(engine.simulate_batch_arrays)(batch)
    stranded = np.asarray(batch.task_valid) & (np.asarray(dense.finish)
                                               >= _BIG / 2)
    assert stranded.any(), "grid should exercise stranding"
    comp, rz = engine.simulate_batch_arrays_compact(batch, k=k)
    _assert_bitwise(dense, comp, f"elastic k={k}")
    assert int(rz) == int(realized)
    # stranded lanes never leave the working set, so they realize the
    # full epoch budget in both drivers
    np.testing.assert_array_equal(
        np.asarray(dense.finish) >= _BIG / 2,
        np.asarray(comp.finish) >= _BIG / 2)


def test_engine_compact_rejects_bad_k():
    batch = sweep.grid_arrays(_random_params(8, seed=1),
                              pad_tasks=23, pad_vms=9)
    with pytest.raises(ValueError, match="k"):
        engine.simulate_batch_arrays_compact(batch, k=0)


# ---------------------------------------------------------------------------
# Pallas: compacted vs dense megakernel vs engine (three-way, bitwise)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", KS, ids=[f"k{k}" for k in KS])
def test_pallas_compact_three_way_bitwise(k):
    params = _random_params(48, seed=7)
    batch = sweep.grid_arrays(params, pad_tasks=23, pad_vms=9)
    eng, _ = jax.jit(engine.simulate_batch_arrays)(batch)
    dense = epoch_schedule(batch, tile=8, interpret=True)
    comp, rz = epoch_schedule_compact(batch, k=k, tile=8, interpret=True)
    _assert_bitwise(eng, dense, "engine vs dense pallas")
    _assert_bitwise(dense, comp, f"dense vs compact pallas k={k}")
    assert int(rz) == int(np.asarray(dense.n_epochs).max())


def test_pallas_compact_elastic_stranded_bitwise():
    batch = sweep.grid_arrays(_elastic_params(32, seed=23),
                              pad_tasks=23, pad_vms=9)
    eng, _ = jax.jit(engine.simulate_batch_arrays)(batch)
    comp, _ = epoch_schedule_compact(batch, k=4, tile=8, interpret=True)
    stranded = np.asarray(batch.task_valid) & (np.asarray(eng.finish)
                                               >= _BIG / 2)
    assert stranded.any(), "grid should exercise stranding"
    _assert_bitwise(eng, comp, "engine vs compact pallas (stranded)")


# ---------------------------------------------------------------------------
# run(compact=...): bit-identity across execution modes
# ---------------------------------------------------------------------------

def _mixed_plan(n=96, seed=5):
    params = _random_params(n, seed)
    plan = product(zip_(*(axis(k, v) for k, v in params.items())))
    return plan.replace(pad_tasks=23, pad_vms=9)


def test_run_compact_bit_identical_all_modes():
    plan = _mixed_plan()
    base = plan.run(bucket=False)
    variants = {
        "compact": plan.run(compact="auto"),
        "compact-k1": plan.run(compact=1),
        "nobucket+compact": plan.run(bucket=False, compact=4),
        "chunk+compact": plan.run(chunk=17, compact=4),
        "pallas+compact": plan.run(backend="pallas", compact=4),
        "pallas+chunk+compact": plan.run(backend="pallas", chunk=17,
                                         compact="auto"),
    }
    for tag, res in variants.items():
        for name in base.metric_names:
            if name == "realized_epochs":   # schedule-dependent by design
                continue
            np.testing.assert_array_equal(base[name], res[name],
                                          err_msg=f"{name} ({tag})")


def test_run_compact_realized_parity_same_mode():
    """Same execution mode, compaction on vs off: even realized_epochs —
    the schedule-dependent metric — must agree, because a compacted
    global epoch executes iff some lane is active, exactly like dense."""
    plan = _mixed_plan(n=64, seed=3)
    for kw in (dict(bucket=False), dict(bucket=False, backend="pallas")):
        dense = plan.run(**kw)
        comp = plan.run(compact=1, **kw)
        for name in dense.metric_names:
            np.testing.assert_array_equal(dense[name], comp[name],
                                          err_msg=f"{name} ({kw})")


def test_run_compact_rejects_bad_values():
    plan = product(axis("n_maps", (1, 2)))
    with pytest.raises(ValueError, match="compact"):
        plan.run(compact=0)
    with pytest.raises(ValueError, match="compact"):
        plan.run(compact="always")


def test_run_compact_mesh_ignored():
    """The mesh path shards per-lane epoch loops (no dense tail to trim):
    compact is accepted and ignored, results unchanged."""
    plan = _mixed_plan(n=32, seed=9)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("pod",))
    base = plan.run(mesh=mesh)
    comp = plan.run(mesh=mesh, compact=4)
    for name in base.metric_names:
        np.testing.assert_array_equal(base[name], comp[name], err_msg=name)


# ---------------------------------------------------------------------------
# pow2 padding util (hoisted from sweep; vectorized)
# ---------------------------------------------------------------------------

def test_pow2_pad_matches_reference_loop():
    def ref(need, cap, floor=4):        # the retired scalar loop
        b = floor
        while b < need:
            b *= 2
        return min(b, cap)

    rng = np.random.default_rng(0)
    need = rng.integers(0, 70, 500)
    for cap in (8, 21, 23, 64, 100):
        for floor in (4, 8):
            want = np.array([ref(int(v), cap, floor) for v in need])
            np.testing.assert_array_equal(pow2_pads(need, cap, floor), want)
            for v in (0, 1, 4, 5, 8, 63, 64, 65):
                assert pow2_pad(v, cap, floor) == ref(v, cap, floor)


def test_pow2_pads_vectorized_properties():
    need = np.array([1, 3, 4, 5, 9, 40, 1000])
    pads = pow2_pads(need, cap=64, floor=4)
    assert (pads >= np.minimum(need, 64)).all()
    assert (pads <= 64).all()
    # every pad is floor * 2**j or the cap
    assert all(p == 64 or (p % 4 == 0 and (p // 4) & (p // 4 - 1) == 0)
               for p in pads.tolist())


# ---------------------------------------------------------------------------
# Cost model: pinned-calibration determinism
# ---------------------------------------------------------------------------

def test_cost_model_roundtrip_and_determinism(tmp_path):
    path = tmp_path / "costmodel.json"
    costmodel.save_cost_model(PINNED, path)
    m1 = costmodel.load_cost_model(path, device="pinned")
    m2 = costmodel.load_cost_model(path)        # single-entry form
    assert m1 == m2 == PINNED
    # file contents are plain JSON: schema version + the coefficients
    data = json.loads(path.read_text())
    assert data == {"schema": costmodel.SCHEMA_VERSION,
                    "models": {"pinned": {"dispatch_us": 800.0,
                                          "epoch_lane_us": 0.05,
                                          "sync_us": 120.0}}}


def test_cost_model_stale_schema_invalidated(tmp_path):
    """Pre-schema / mismatched caches raise on load and are discarded on
    save instead of feeding drifted coefficients to the schedulers."""
    path = tmp_path / "costmodel.json"
    # the pre-schema format: a bare device -> coefficients mapping
    path.write_text(json.dumps(
        {"old-dev": {"dispatch_us": 1.0, "epoch_lane_us": 9.9}}))
    with pytest.raises(ValueError, match="schema"):
        costmodel.load_cost_model(path, device="old-dev")
    # a future schema version is equally stale
    path.write_text(json.dumps(
        {"schema": costmodel.SCHEMA_VERSION + 1,
         "models": {"d": {"dispatch_us": 1.0, "epoch_lane_us": 1.0}}}))
    with pytest.raises(ValueError, match="schema"):
        costmodel.load_cost_model(path)
    # saving over a stale cache drops its entries entirely
    costmodel.save_cost_model(PINNED, path)
    data = json.loads(path.read_text())
    assert data["schema"] == costmodel.SCHEMA_VERSION
    assert list(data["models"]) == ["pinned"]


def test_cost_model_scoring_is_deterministic():
    params = _random_params(300, seed=11)
    g1 = sweep._bucket_groups(params, 23, 9, "auto", cost=PINNED)
    g2 = sweep._bucket_groups(params, 23, 9, "auto", cost=PINNED)
    assert len(g1) == len(g2)
    for a, b in zip(g1, g2):
        np.testing.assert_array_equal(a[0], b[0])
        assert a[2:] == b[2:]
    # intervals derive from the same two coefficients
    assert PINNED.compact_interval(2048, 21) \
        == PINNED.compact_interval(2048, 21)
    assert PINNED.compact_interval(8, 8) >= 1


def test_bucket_groups_partition_under_pinned_cost():
    """The measured-cost scorer still yields a valid ordered partition
    with correct per-bucket pads (the old suite's invariants)."""
    params = _random_params(300, seed=11)
    groups = sweep._bucket_groups(params, 23, 9, "auto", cost=PINNED)
    seen = np.concatenate([g[0] for g in groups])
    assert len(seen) == 300 and len(np.unique(seen)) == 300
    for idx, gcols, statics, tb, vb in groups:
        assert (np.diff(idx) > 0).all()
        need_t = gcols["n_maps"] + gcols["n_reduces"]
        assert int(need_t.max()) <= tb <= 23
        assert int(gcols["n_vms"].max()) <= vb <= 9


def test_bucket_split_follows_dispatch_cost():
    """Cheaper dispatch => more buckets (splits amortize sooner); a huge
    dispatch cost collapses the grid into one bucket per policy combo."""
    params = _random_params(300, seed=11, mixed_policies=False)
    cheap = costmodel.CostModel(dispatch_us=10.0, epoch_lane_us=0.05,
                                device="cheap")
    pricey = costmodel.CostModel(dispatch_us=1e9, epoch_lane_us=0.05,
                                 device="pricey")
    n_cheap = len(sweep._bucket_groups(params, 23, 9, "auto", cost=cheap))
    n_pricey = len(sweep._bucket_groups(params, 23, 9, "auto", cost=pricey))
    assert n_pricey == 1
    assert n_cheap > n_pricey


def test_run_results_independent_of_cost_model():
    """Scheduling decisions change with the calibration; results may not."""
    plan = _mixed_plan(n=96, seed=5)
    cheap = costmodel.CostModel(dispatch_us=10.0, epoch_lane_us=0.05,
                                device="cheap")
    a = plan.run(cost_model=PINNED, compact="auto")
    b = plan.run(cost_model=cheap, compact="auto")
    base = plan.run(bucket=False)
    for name in base.metric_names:
        if name == "realized_epochs":
            continue
        np.testing.assert_array_equal(base[name], a[name], err_msg=name)
        np.testing.assert_array_equal(base[name], b[name], err_msg=name)


def test_default_cost_model_prefers_pinned_file(tmp_path, monkeypatch):
    """REPRO_COSTMODEL_PATH + a pinned file skips measurement entirely."""
    path = tmp_path / "cal.json"
    key = costmodel.device_key()
    costmodel.save_cost_model(
        costmodel.CostModel(dispatch_us=123.0, epoch_lane_us=0.01,
                            device=key), path)
    monkeypatch.setenv(costmodel.ENV_PATH, str(path))
    monkeypatch.setattr(costmodel, "_CACHE", {})
    got = costmodel.default_cost_model()
    assert got.dispatch_us == 123.0 and got.epoch_lane_us == 0.01


def test_default_cost_model_failed_measurement_raises(tmp_path, monkeypatch):
    """A failed measurement is an error, never the built-in constants;
    those serve only ``allow_measure=False`` with no cached file."""
    def broken():
        raise RuntimeError("probe failed")

    monkeypatch.setenv(costmodel.ENV_PATH, str(tmp_path / "none.json"))
    monkeypatch.setattr(costmodel, "_CACHE", {})
    monkeypatch.setattr(costmodel, "measure", broken)
    with pytest.raises(RuntimeError, match="probe failed"):
        costmodel.default_cost_model()
    got = costmodel.default_cost_model(allow_measure=False)
    assert got.source == "fallback"


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_enable_compile_cache_path(tmp_path, monkeypatch, env):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache sits at a fixed ``.jax_cache/`` in the checkout."""
    from repro.core import util
    set_to = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(util._CHECKOUT / ".jax_cache")
        assert util.enable_compile_cache() == want
        assert set_to == [("jax_compilation_cache_dir", want)]
        assert (util._CHECKOUT / "src" / "repro" / "core" /
                "util.py").is_file()
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
        assert util.enable_compile_cache() == str(tmp_path / env)
        assert set_to == []


# ---------------------------------------------------------------------------
# Floor validation (ISSUE 10): nonsensical pow2 floors fail loudly
# ---------------------------------------------------------------------------

BAD_FLOORS = [0, -1, -8, 3, 6, 12]


@pytest.mark.parametrize("floor", BAD_FLOORS)
def test_pow2_pad_rejects_bad_floor(floor):
    with pytest.raises(ValueError, match="floor"):
        pow2_pad(5, cap=64, floor=floor)
    with pytest.raises(ValueError, match="floor"):
        pow2_pads(np.array([5, 9]), cap=64, floor=floor)


@pytest.mark.parametrize("floor", [0, -4, 6])
def test_compact_drivers_reject_bad_floor(floor):
    batch = sweep.grid_arrays(_random_params(8, seed=1),
                              pad_tasks=23, pad_vms=9)
    with pytest.raises(ValueError, match="floor"):
        engine.simulate_batch_arrays_compact(batch, k=2, floor=floor)
    with pytest.raises(ValueError, match="floor"):
        epoch_schedule_compact(batch, k=2, tile=8, interpret=True,
                               floor=floor)


# ---------------------------------------------------------------------------
# Compact-interval clamp: named constants, pinned (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def test_compact_interval_clamp_constants_pinned():
    """The K* re-derivation (sync_us + dispatch_us round pricing) must not
    silently change the clamp the pre-split formula used."""
    assert costmodel.COMPACT_INTERVAL_MIN == 1
    assert costmodel.COMPACT_INTERVAL_MAX == 64
    huge = costmodel.CostModel(dispatch_us=1e12, epoch_lane_us=0.05,
                               sync_us=1e12, device="huge")
    assert huge.compact_interval(2048, 21) == costmodel.COMPACT_INTERVAL_MAX
    tiny = costmodel.CostModel(dispatch_us=1e-9, epoch_lane_us=1e9,
                               sync_us=1e-9, device="tiny")
    assert tiny.compact_interval(2048, 21) == costmodel.COMPACT_INTERVAL_MIN
    for n, t in ((8, 8), (64, 21), (2048, 23)):
        k = PINNED.compact_interval(n, t)
        assert costmodel.COMPACT_INTERVAL_MIN <= k \
            <= costmodel.COMPACT_INTERVAL_MAX


def test_compact_interval_prices_sync_plus_dispatch():
    """A round costs one scalar pull plus one chunk launch: moving cost
    between the two coefficients leaves K* unchanged."""
    a = costmodel.CostModel(dispatch_us=900.0, epoch_lane_us=0.05,
                            sync_us=100.0, device="a")
    b = costmodel.CostModel(dispatch_us=100.0, epoch_lane_us=0.05,
                            sync_us=900.0, device="b")
    for n, t in ((64, 8), (512, 21), (2048, 23)):
        assert a.compact_interval(n, t) == b.compact_interval(n, t)
    # and a pricier sync alone pushes the interval up (fewer checks)
    cheap_sync = costmodel.CostModel(dispatch_us=800.0, epoch_lane_us=0.05,
                                     sync_us=1.0, device="c")
    dear_sync = costmodel.CostModel(dispatch_us=800.0, epoch_lane_us=0.05,
                                    sync_us=80000.0, device="d")
    assert dear_sync.compact_interval(512, 21) \
        > cheap_sync.compact_interval(512, 21)


# ---------------------------------------------------------------------------
# _take_lanes/_put_lanes round-trip: permutation identity (property)
# ---------------------------------------------------------------------------

def _check_take_put_roundtrip(seed: int):
    """Gathering any lane subset and scattering it straight back is the
    identity, for arbitrary carry-shaped pytrees including ``None``
    trace/control leaves (the static-off lowerings' pytree form)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 33))
    m = int(rng.integers(1, n + 1))
    tree = {
        "f32": jnp.asarray(rng.normal(size=(n, int(rng.integers(1, 5))))
                           .astype(np.float32)),
        "i32": (jnp.asarray(rng.integers(-5, 9, size=(n,))
                            .astype(np.int32)), None),
        "bool": jnp.asarray(rng.integers(0, 2, size=(n, 3)) != 0),
        "trace_off": None,
    }
    idx = jnp.asarray(rng.permutation(n)[:m])
    sub = compaction._take_lanes(tree, idx)
    back = compaction._put_lanes(tree, idx, sub)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, back)
    # distinct-index scatter of gathered rows is exact, so double
    # application changes nothing either
    again = compaction._put_lanes(back, idx, compaction._take_lanes(back, idx))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, again)


@pytest.mark.parametrize("seed", range(8))
def test_take_put_roundtrip_identity(seed):
    _check_take_put_roundtrip(seed)


if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(hst.integers(min_value=0, max_value=2**32 - 1))
    def test_take_put_roundtrip_identity_hypothesis(seed):
        _check_take_put_roundtrip(seed)


def test_take_put_roundtrip_real_carry():
    """The property on the engine's actual carry pytree (trace leaves off
    -> None leaves ride the tree.map exactly like the synthetic case)."""
    batch = sweep.grid_arrays(_elastic_params(12, seed=2),
                              pad_tasks=23, pad_vms=9)
    _, c0 = engine._setup_batch(batch)
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.permutation(12)[:8])
    back = compaction._put_lanes(c0, idx, compaction._take_lanes(c0, idx))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), c0, back)


# ---------------------------------------------------------------------------
# Donation safety: no use-after-donate on any mode (ISSUE 10)
# ---------------------------------------------------------------------------

def test_engine_compact_donation_safe_and_bitwise():
    """The donating loop must consume only loop-internal buffers: results
    match the dense driver bitwise, every output fully materializes, and
    a second run over the SAME batch arrays (shared, never donated) is
    identical — a use-after-donate anywhere raises."""
    batch = sweep.grid_arrays(_elastic_params(48, seed=23),
                              pad_tasks=23, pad_vms=9)
    comp, r1 = engine.simulate_batch_arrays_compact(batch, k=2)
    dense, r2 = jax.jit(engine.simulate_batch_arrays)(batch)
    again, r3 = engine.simulate_batch_arrays_compact(batch, k=2)
    _assert_bitwise(comp, dense, "compact vs dense driver")
    _assert_bitwise(comp, again, "repeat over shared batch")
    assert int(r1) == int(r2) == int(r3)


def test_engine_compact_donation_safe_traced():
    """The trace leaves ride the donated carry; the buffers the host
    finally reads must never have been donated."""
    batch = sweep.grid_arrays(_random_params(24, seed=6),
                              pad_tasks=23, pad_vms=9)
    out_a, rz_a, tr_a = engine.simulate_batch_arrays_compact(
        batch, k=2, trace=True)
    out_b, rz_b, tr_b = engine.simulate_batch_arrays(batch, trace=True)
    _assert_bitwise(out_a, out_b, "traced compact vs dense driver")
    assert int(rz_a) == int(rz_b)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tr_a, tr_b)


def test_pallas_compact_donation_safe_and_bitwise():
    batch = sweep.grid_arrays(_random_params(48, seed=7),
                              pad_tasks=23, pad_vms=9)
    comp, r1 = epoch_schedule_compact(batch, k=2, tile=8, interpret=True)
    dense = epoch_schedule(batch, tile=8, interpret=True)
    again, r3 = epoch_schedule_compact(batch, k=2, tile=8, interpret=True)
    _assert_bitwise(comp, dense, "pallas compact vs dense kernel")
    _assert_bitwise(comp, again, "pallas repeat over shared batch")
    assert int(r1) == int(r3) == int(np.asarray(dense.n_epochs).max())


def test_run_modes_survive_repeat_with_donation():
    """run() encodes grids through an lru cache, so the compact drivers
    must never donate encoder-owned arrays: every compacted mode must
    produce identical results when run twice back to back."""
    plan = _mixed_plan(n=48, seed=13)
    for kw in (dict(compact=1), dict(chunk=17, compact=2),
               dict(backend="pallas", compact=2)):
        first = plan.run(**kw)
        second = plan.run(**kw)
        for name in first.metric_names:
            np.testing.assert_array_equal(first[name], second[name],
                                          err_msg=f"{name} ({kw})")


# ---------------------------------------------------------------------------
# Host chattiness: the compaction loop's sync census
# ---------------------------------------------------------------------------

_COMPACT_DRIVERS = {
    "xla": lambda batch, **kw: engine.simulate_batch_arrays_compact(batch,
                                                                    **kw),
    "pallas": lambda batch, **kw: epoch_schedule_compact(
        batch, tile=8, interpret=True, **kw),
}


@pytest.mark.parametrize("backend", sorted(_COMPACT_DRIVERS))
def test_lean_loop_sync_census(backend):
    """Full permutation pulls happen only on compaction rounds; every
    round pays exactly one scalar pull, plus the initial poll."""
    batch = sweep.grid_arrays(_random_params(64, seed=7),
                              pad_tasks=23, pad_vms=9)
    st = {}
    _COMPACT_DRIVERS[backend](batch, k=1, stats=st)
    assert st["compactions"] > 0, "grid must actually compact"
    assert st["syncs"] == st["compactions"]
    assert st["scalar_syncs"] == st["dispatches"] + 1


# ---------------------------------------------------------------------------
# Multi-tile mr_epoch: bitwise across the compact tile-sweep shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [4, 8, 16])
def test_mr_epoch_multitile_bitwise(block):
    batch = sweep.grid_arrays(_random_params(48, seed=7),
                              pad_tasks=23, pad_vms=9)
    ref = epoch_schedule(batch, tile=16, interpret=True)
    mt = epoch_schedule(batch, tile=16, interpret=True, block_lanes=block)
    _assert_bitwise(ref, mt, f"multi-tile block={block}")


def test_pallas_compact_multitile_bitwise():
    """Compacted pow2 working sets re-tile across the minor grid dim and
    stay bitwise-equal to the engine across the tile-sweep shapes."""
    batch = sweep.grid_arrays(_random_params(48, seed=7),
                              pad_tasks=23, pad_vms=9)
    eng, _ = jax.jit(engine.simulate_batch_arrays)(batch)
    for tile, block in ((8, 4), (16, 8), (32, 8)):
        comp, rz = epoch_schedule_compact(batch, k=4, tile=tile,
                                          interpret=True,
                                          block_lanes=block)
        _assert_bitwise(eng, comp, f"compact tile={tile} block={block}")


def test_mr_epoch_multitile_elastic_stranded_bitwise():
    batch = sweep.grid_arrays(_elastic_params(32, seed=23),
                              pad_tasks=23, pad_vms=9)
    eng, _ = jax.jit(engine.simulate_batch_arrays)(batch)
    comp, _ = epoch_schedule_compact(batch, k=4, tile=8, interpret=True,
                                     block_lanes=4)
    _assert_bitwise(eng, comp, "multi-tile compact (stranded)")
