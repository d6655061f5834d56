"""WKV6 recurrence kernel: VMEM-resident state, time-block streaming.

The RWKV6 recurrence
    S_t = diag(w_t) S_{t-1} + k_tᵀ v_t ,   y_t = r_t (S_{t-1} + diag(u) k_tᵀ v_t)
is O(1)-state but strictly sequential in time.  The jnp reference
(``repro.models.ssm._wkv_scan``) round-trips the (hs × hs) state through
HBM every step; on TPU that recurrence is purely memory-bound.  This
kernel keeps the state in a VMEM scratch tile across the whole sequence
and streams (r, k, v, w) in time blocks:

* grid = (batch, heads, T / block_t), time axis minor (sequential), so the
  state scratch persists across time blocks;
* per block, one VMEM-resident fori over block_t steps of rank-1 updates —
  HBM traffic drops from O(T · hs²) to O(T · hs) (the factor-hs win that
  makes the ``long_500k`` decode shape stream-bound instead of
  state-bound);
* head_size 64 keeps the (64, 64) state on one 8×128 VREG tile boundary.

Adaptation note (DESIGN.md): the official CUDA kernel exploits warp-level
shuffles for the rank-1 update; TPU has no warp analogue — the VMEM
scratch + VPU vector update is the TPU-idiomatic equivalent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *, bt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)                     # (hs,)

    def step(t, _):
        # size-1 slices, not int indices: interpret-mode discharge rejects
        # raw python ints in ref index tuples
        idx = (pl.ds(0, 1), pl.ds(0, 1), pl.ds(t, 1), slice(None))
        r_t = r_ref[idx][0, 0, 0].astype(jnp.float32)   # (hs,)
        k_t = k_ref[idx][0, 0, 0].astype(jnp.float32)
        v_t = v_ref[idx][0, 0, 0].astype(jnp.float32)
        w_t = w_ref[idx][0, 0, 0].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]                 # (hs, hs)
        s = s_ref[...]
        y = jnp.sum(r_t[:, None] * (s + u[:, None] * kv), axis=0)
        y_ref[idx] = y.astype(y_ref.dtype)[None, None, None]
        s_ref[...] = w_t[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, bt, step, 0)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "interpret"))
def wkv6_bhts(r, k, v, w, u, *, block_t: int = 64, interpret: bool = True):
    """r/k/v/w: (B, H, T, hs); u: (H, hs) -> y: (B, H, T, hs)."""
    B, H, T, hs = r.shape
    bt = min(block_t, T)
    while T % bt:
        bt //= 2
    nt = T // bt
    spec = pl.BlockSpec((1, 1, bt, hs), lambda b, h, ti: (b, h, ti, 0))
    return pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=(B, H, nt),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, hs), lambda b, h, ti: (h, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, hs), r.dtype),
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u)
