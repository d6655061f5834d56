"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                     # seeded fallback, same test surface
    from _hypothesis_fallback import given, settings
    from _hypothesis_fallback import strategies as st

from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rwkv6 import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

FA_SHAPES = [
    # (B, S, T, Hq, Hkv, Dh, causal, window)
    (2, 128, 128, 4, 2, 32, True, None),       # GQA causal
    (1, 256, 256, 8, 8, 16, True, 64),         # MHA sliding window
    (2, 64, 64, 4, 1, 32, False, None),        # encoder (MQA)
    (1, 128, 128, 2, 2, 64, True, None),       # head_dim 64
    (1, 96, 96, 2, 1, 8, True, 32),            # non-pow2 seq
]


@pytest.mark.parametrize("shape", FA_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(shape, dtype):
    B, S, T, Hq, Hkv, Dh, causal, window = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, Hkv, Dh), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=32)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    ref = attention_ref(tr(q), tr(k), tr(v), causal=causal,
                        window=window).transpose(0, 2, 1, 3)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@settings(max_examples=10, deadline=None)
@given(st.sampled_from([16, 32, 64]), st.sampled_from([1, 2, 4]),
       st.sampled_from([8, 16, 32]), st.booleans())
def test_flash_attention_property(S, G, Dh, causal):
    """Random block sizes & GQA groups against the oracle."""
    Hkv = 2
    q = jax.random.normal(jax.random.PRNGKey(S), (1, S, Hkv * G, Dh))
    k = jax.random.normal(jax.random.PRNGKey(S + 1), (1, S, Hkv, Dh))
    v = jax.random.normal(jax.random.PRNGKey(S + 2), (1, S, Hkv, Dh))
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    ref = attention_ref(tr(q), tr(k), tr(v), causal=causal) \
        .transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_flash_vs_chunked_vs_dense_model_paths():
    """The three attention impls inside the model agree."""
    from repro.models import ArchConfig
    from repro.models.attention import (_gqa_scores_mask, chunked_sdpa,
                                        sdpa)
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=16, window=48)
    B, S, Dh = 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, 4, Dh))
    k = jax.random.normal(ks[1], (B, S, 2, Dh))
    v = jax.random.normal(ks[2], (B, S, 2, Dh))
    pos = jnp.arange(S)
    dense = sdpa(cfg, q, k, v, _gqa_scores_mask(cfg, pos, pos))
    chunked = chunked_sdpa(cfg, q, k, v, block_q=32, block_k=32)
    flash = flash_attention(q, k, v, causal=True, window=48,
                            block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(chunked),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------

WKV_SHAPES = [
    (2, 3, 96, 16, 32),
    (1, 2, 64, 8, 64),
    (2, 1, 40, 4, 16),
    (1, 4, 128, 32, 32),
]


@pytest.mark.parametrize("shape", WKV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_matches_ref(shape, dtype):
    B, H, T, hs, bt = shape
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    r, k, v = (0.5 * jax.random.normal(ks[i], (B, T, H, hs), dtype)
               for i in range(3))
    w = (jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hs))) * 0.5
         + 0.45).astype(dtype)
    u = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (H, hs), dtype)
    got = wkv6(r, k, v, w, u, block_t=bt)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    ref = wkv6_ref(tr(r), tr(k), tr(v), tr(w), u).transpose(0, 2, 1, 3)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_wkv6_block_size_invariance():
    B, H, T, hs = 1, 2, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    r, k, v = (jax.random.normal(ks[i], (B, T, H, hs)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hs))) * 0.4 + 0.5
    u = jax.random.normal(jax.random.PRNGKey(5), (H, hs)) * 0.1
    outs = [np.asarray(wkv6(r, k, v, w, u, block_t=bt))
            for bt in (8, 16, 64)]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)
    np.testing.assert_allclose(outs[0], outs[2], atol=1e-5)


# ---------------------------------------------------------------------------
# mr_sched
# ---------------------------------------------------------------------------

def _random_batch(n, seed=0, mixed_policies=False):
    from repro.core import sweep
    rng = np.random.default_rng(seed)
    params = dict(
        n_maps=rng.integers(1, 21, n).astype(np.int32),
        n_reduces=rng.integers(1, 3, n).astype(np.int32),
        n_vms=rng.integers(1, 10, n).astype(np.int32),
        vm_mips=rng.choice([250.0, 500.0, 1000.0], n).astype(np.float32),
        vm_pes=rng.choice([1.0, 2.0, 4.0], n).astype(np.float32),
        vm_cost=np.ones(n, np.float32),
        job_length=rng.choice([362880.0, 725760.0], n).astype(np.float32),
        job_data=rng.choice([2e5, 4e5], n).astype(np.float32),
    )
    if mixed_policies:
        params["sched_policy"] = rng.integers(0, 2, n).astype(np.int32)
        params["binding_policy"] = rng.integers(0, 3, n).astype(np.int32)
    return sweep.grid_arrays(params, pad_tasks=23, pad_vms=9)


def _assert_schedule_matches(batch, tile):
    from repro.core import engine
    from repro.kernels.mr_sched import epoch_schedule
    ref = jax.vmap(engine.simulate_arrays)(batch)
    got = epoch_schedule(batch, tile=tile)
    s_ref, f_ref, s_got, f_got = ref.start, ref.finish, got.start, got.finish
    valid = np.asarray(batch.task_valid)
    np.testing.assert_allclose(np.where(valid, s_got, 0),
                               np.where(valid, np.asarray(s_ref), 0),
                               rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.where(valid, f_got, 0),
                               np.where(valid, np.asarray(f_ref), 0),
                               rtol=1e-4, atol=1e-2)


@pytest.mark.parametrize("tile", [8, 32])
def test_mr_sched_matches_engine(tile):
    _assert_schedule_matches(_random_batch(32, seed=tile), tile)


@pytest.mark.parametrize("tile", [8, 32])
def test_mr_sched_matches_engine_mixed_policies(tile):
    """One tile mixing sched/binding policies matches the engine oracle."""
    _assert_schedule_matches(
        _random_batch(32, seed=100 + tile, mixed_policies=True), tile)


def test_mr_sched_reproduces_paper_metrics():
    """Kernel schedule -> paper Table IV numbers end to end."""
    from repro.core import sweep
    from repro.kernels.mr_sched import epoch_schedule
    batch = sweep.product(sweep.axis("n_maps", range(1, 11))).arrays()
    out = epoch_schedule(batch, tile=8)
    s, f = out.start, out.finish
    # delay time for M1R1: last map start + reduce start - last map finish
    valid = np.asarray(batch.task_valid)
    for i, m in enumerate(range(1, 11)):
        is_red = np.asarray(batch.task_is_reduce)[i] & valid[i]
        is_map = ~np.asarray(batch.task_is_reduce)[i] & valid[i]
        delay = (np.max(np.asarray(s)[i][is_map])
                 + np.max(np.asarray(s)[i][is_red])
                 - np.max(np.asarray(f)[i][is_map]))
        assert delay == pytest.approx(4250.0 / (m + 1), rel=1e-4)
