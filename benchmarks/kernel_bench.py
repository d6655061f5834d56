"""Kernel micro-benchmarks (interpret-mode timings are NOT TPU numbers —
the derived column carries the jnp-reference comparison + the structural
quantity that matters on TPU: HBM-traffic reduction / FLOP parity).

``python -m benchmarks.kernel_bench`` additionally sweeps ``mr_epoch``
megakernel tile sizes and records the winners + device metadata to
``BENCH_kernel.json`` at the repo root (interpret-mode numbers rank tile
shapes by the work the schedule actually does — epoch-loop trips × lanes —
which is the quantity the TPU path tiles for; re-run on real hardware to
re-rank).
"""
from __future__ import annotations

import json
import multiprocessing
import pathlib
import platform
import time

import jax
import jax.numpy as jnp


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn(*args).block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def flash_rows():
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    B, S, Hq, Hkv, Dh = 1, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    us = _time(lambda a, b, c: flash_attention(a, b, c, causal=True,
                                               block_q=64, block_k=64),
               q, k, v)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    ref = attention_ref(tr(q), tr(k), tr(v), causal=True).transpose(0, 2, 1, 3)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    err = float(jnp.abs(got - ref).max())
    # structural: score-matrix HBM bytes avoided per layer at 32k prefill
    avoided = 32 * 32768 * 32768 * 4 / 2**30
    return [("kernel_flash_attn_interp", us, f"err={err:.1e}"),
            ("kernel_flash_attn_32k_score_GiB_avoided", us,
             f"{avoided:.0f}")]


def wkv_rows():
    from repro.kernels.rwkv6 import wkv6
    from repro.kernels.rwkv6.ref import wkv6_ref
    B, H, T, hs = 1, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    r, k, v = (0.5 * jax.random.normal(ks[i], (B, T, H, hs))
               for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, hs))) * 0.5 + 0.45
    u = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (H, hs))
    us = _time(lambda *a: wkv6(*a, block_t=32), r, k, v, w, u)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    err = float(jnp.abs(wkv6(r, k, v, w, u, block_t=32)
                        - wkv6_ref(tr(r), tr(k), tr(v), tr(w), u)
                        .transpose(0, 2, 1, 3)).max())
    # structural: HBM state traffic, scan (O(T·hs^2)) vs kernel (O(T·hs))
    ratio = hs
    return [("kernel_wkv6_interp", us, f"err={err:.1e}"),
            ("kernel_wkv6_state_traffic_reduction", us, f"{ratio}x")]


def _mr_batch(m_range=range(1, 21)):
    from repro.core import sweep
    return sweep.product(sweep.axis("n_maps", m_range)).arrays()


def mr_sched_rows():
    import numpy as np

    from repro.kernels.mr_sched import epoch_schedule, schedule
    from repro.kernels.mr_sched.ref import schedule_ref
    batch = _mr_batch()
    us_k = _time(lambda b: schedule(b, tile=8)[1], batch)
    us_e = _time(lambda b: epoch_schedule(b, tile=8).finish, batch)
    us_r = _time(lambda b: schedule_ref(b)[1], batch)
    s_r, f_r = schedule_ref(batch)
    valid = np.asarray(batch.task_valid)

    def err(f_k):
        return float(np.abs(np.where(valid,
                                     np.asarray(f_k) - np.asarray(f_r),
                                     0)).max())

    return [("kernel_mr_sched_interp", us_k, f"err={err(schedule(batch, tile=8)[1]):.1e}"),
            ("kernel_mr_epoch_interp", us_e,
             f"err={err(epoch_schedule(batch, tile=8).finish):.1e}"),
            ("kernel_mr_sched_xla_engine_ref", us_r, "baseline")]


def mr_epoch_tile_rows(tiles=(8, 16, 32, 64, 128), n=256, reps=3):
    """Sweep ``mr_epoch`` tile sizes over a mixed-policy random batch.

    A bigger tile amortizes grid steps but couples more lanes to one
    early-exit predicate (the tile runs to its slowest lane); the sweep
    measures that trade-off on this backend.  Returns one row per tile
    plus a winner row.
    """
    from repro.kernels.mr_sched import epoch_schedule
    batch = _mr_tile_batch(n)
    rows, timings = [], {}
    for tile in tiles:
        us = _time(lambda b, t=tile: epoch_schedule(b, tile=t).finish,
                   batch, reps=reps)
        timings[tile] = us
        rows.append((f"kernel_mr_epoch_tile{tile}", us,
                     f"{n / us * 1e6:.0f}_scen/s"))
    best = min(timings, key=timings.get)
    rows.append(("kernel_mr_epoch_best_tile", timings[best], str(best)))
    return rows, best


def mr_epoch_block_rows(blocks=(8, 16, 32), tile=32, n=256, reps=3):
    """Sweep the multi-tile ``block_lanes`` sub-blocking of ``mr_epoch``
    at a fixed lane tile (DESIGN.md §13).

    ``block_lanes=b`` splits each ``tile``-lane grid step into
    ``tile // b`` minor-dimension steps; on TPU the minor grid dimension
    is sequential, so the Pallas pipeline emitter double-buffers the
    ``b``-lane block fetches — HBM->VMEM streaming of the next block
    overlaps the current block's epoch loop.  Each candidate is asserted
    bitwise-equal to the single-tile lowering before it is timed (the
    sub-blocking must be pure pipelining, never a semantic change); the
    winner row records the block the TPU path should use at this tile.
    Interpret-mode numbers rank by work, not TPU wall time — re-run on
    real hardware to re-rank.
    """
    import numpy as np

    from repro.kernels.mr_sched import epoch_schedule
    batch = _mr_tile_batch(n)
    ref = epoch_schedule(batch, tile=tile)
    rows, timings = [], {}
    for block in blocks:
        got = epoch_schedule(batch, tile=tile, block_lanes=block)
        for f in ref._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(got, f)),
                err_msg=f"mr_epoch block_lanes={block} diverges from "
                        f"single-tile on {f}")
        us = _time(lambda b, blk=block: epoch_schedule(
            b, tile=tile, block_lanes=blk).finish, batch, reps=reps)
        timings[block] = us
        rows.append((f"kernel_mr_epoch_t{tile}_block{block}", us,
                     f"{n / us * 1e6:.0f}_scen/s"))
    best = min(timings, key=timings.get)
    rows.append(("kernel_mr_epoch_best_block_lanes", timings[best],
                 str(best)))
    return rows, best


def _mr_tile_batch(n):
    """The mixed-policy random batch the tile/block sweeps share."""
    import numpy as np

    from repro.core import sweep
    rng = np.random.default_rng(0)
    params = dict(
        n_maps=rng.integers(1, 21, n).astype(np.int32),
        n_reduces=rng.integers(1, 3, n).astype(np.int32),
        n_vms=rng.integers(1, 10, n).astype(np.int32),
        vm_mips=rng.choice([250.0, 500.0, 1000.0], n).astype(np.float32),
        vm_pes=rng.choice([1.0, 2.0, 4.0], n).astype(np.float32),
        vm_cost=np.ones(n, np.float32),
        job_length=rng.choice([362880.0, 725760.0], n).astype(np.float32),
        job_data=rng.choice([2e5, 4e5], n).astype(np.float32),
        sched_policy=rng.integers(0, 2, n).astype(np.int32),
        binding_policy=rng.integers(0, 3, n).astype(np.int32),
    )
    return sweep.grid_arrays(params, pad_tasks=23, pad_vms=9)


def mr_epoch_compact_tile_rows(tiles=(8, 16, 32, 64), n=64, reps=3):
    """Sweep ``mr_epoch`` tiles over the compacted batch shapes the sparse
    host loop actually dispatches (DESIGN.md §9).

    The workload is the tail-heavy grid's straggler residue: ``n`` lanes
    at T=41 whose 1/8 stragglers run ~2·T epochs — the pow2 shape the
    compacted driver re-tiles and re-dispatches after each gather.  The
    timing drives :func:`epoch_schedule_compact` end to end (host loop,
    gather/scatter and chunked kernel included), so the winner is the
    tile the compact path should use at this lane count.  On CPU these
    are interpret-mode numbers (rank, not TPU wall time).  On a TPU the
    ``interpret=None`` default compiles the kernel with Mosaic; the
    tile ranking there is not measured yet (``chip_smoke.py`` runs the
    sweep path compiled at the 8-lane default tile).
    """
    import numpy as np

    from repro.core import sweep
    from repro.kernels.mr_sched import epoch_schedule_compact
    rng = np.random.default_rng(1)
    strag = rng.random(n) < 1.0 / 8.0
    strag[0] = True
    params = dict(
        n_maps=np.full(n, 40, np.int32),
        n_reduces=np.ones(n, np.int32),
        n_vms=np.where(strag, 1, rng.integers(6, 10, n)).astype(np.int32),
        vm_mips=rng.choice([250.0, 500.0, 1000.0], n).astype(np.float32),
        vm_pes=np.where(strag, 1.0,
                        rng.choice([2.0, 4.0], n)).astype(np.float32),
        vm_cost=np.ones(n, np.float32),
        job_length=rng.choice([362880.0, 725760.0], n).astype(np.float32),
        job_data=rng.choice([2e5, 4e5], n).astype(np.float32),
        sched_policy=np.ones(n, np.int32),
        binding_policy=np.zeros(n, np.int32),
    )
    batch = sweep.grid_arrays(params, pad_tasks=41, pad_vms=9)
    rows, timings = [], {}
    for tile in tiles:
        def run(b, t=tile):
            out, _ = epoch_schedule_compact(b, k=8, tile=t)
            return out.finish
        us = _time(run, batch, reps=reps)
        timings[tile] = us
        rows.append((f"kernel_mr_epoch_compact_tile{tile}", us,
                     f"{n / us * 1e6:.0f}_scen/s"))
    best = min(timings, key=timings.get)
    rows.append(("kernel_mr_epoch_compact_best_tile", timings[best],
                 str(best)))
    return rows, best


def all_rows():
    return flash_rows() + wkv_rows() + mr_sched_rows()


def main() -> None:
    from repro.core.util import enable_compile_cache
    enable_compile_cache()
    tile_rows, best_tile = mr_epoch_tile_rows()
    block_rows, best_block = mr_epoch_block_rows()
    compact_rows, best_tile_compact = mr_epoch_compact_tile_rows()
    rows = mr_sched_rows() + tile_rows + block_rows + compact_rows
    out = pathlib.Path(__file__).resolve().parent.parent / "BENCH_kernel.json"
    payload = {
        "benchmark": "mr_sched/mr_epoch kernel micro-benchmarks",
        "meta": {
            "backend": jax.default_backend(),
            "device": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
            "cpu_count": multiprocessing.cpu_count(),
            "platform": platform.platform(),
            "interpret": jax.default_backend() != "tpu",
            "best_tile": best_tile,
            "best_block_lanes": best_block,
            "best_tile_compact": best_tile_compact,
        },
        "rows": [{"name": n, "us_per_call": round(us, 1), "derived": d}
                 for n, us, d in rows],
    }
    out.write_text(json.dumps(payload, indent=2) + "\n")
    for r in payload["rows"]:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
