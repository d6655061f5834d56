"""Plain reference for the benchmark's MapReduce cells.

One job on a homogeneous fleet, with its input in an HDFS-style block
store, simulated event by event.  It follows the semantics the
configuration files state and imports nothing of the simulator:

* the input of ``job_data`` MB splits into ``block_size_mb`` blocks (the
  last holds the remainder); map ``m`` reads block ``m mod n_blocks``;
* each block has ``min(replication, n_vms)`` replicas on consecutive VMs
  from a start VM hashed from ``(seed, job 0, block)`` (lowbias32 in
  uint32); SKEWED placement squares a uniform draw first;
* binding in submission order (maps, then reduces): ROUND_ROBIN is task
  ``k`` to VM ``k mod V``; LEAST_LOADED takes the first VM of least
  float32 load ``sum(length) / (mips * pes)``; LOCALITY does the same
  among the replica holders of a map's block;
* maps are ready at ``kappa_in * S / ((M + 1) * BW)``, plus
  ``kappa_in * block_mb / BW`` when bound off their block's replicas;
  reduces are ready ``kappa_shuffle * S / ((M + 1) * BW)`` after the last
  map finishes;
* time-shared VMs run every ready task at ``mips * min(1, pes / n)``;
  space-shared VMs run at most ``pes`` at ``mips`` and queue the rest by
  (ready time, task id).

Placement and the binding load are integer and float32 arithmetic by the
semantics' own definition.  Every time, rate and metric is computed in
``dtype``: float64 for the reference; a lower precision gives the control
that the comparison has to reject.
"""
from __future__ import annotations

import numpy as np

SPACE_SHARED = 1
ROUND_ROBIN, LEAST_LOADED, LOCALITY = 0, 1, 3
SKEWED = 1
_EPS = 1e-9

_M1, _M2 = np.uint32(0x7FEB352D), np.uint32(0x846CA68B)
_C1, _C3 = np.uint32(0x9E3779B9), np.uint32(0xC2B2AE35)

# Metrics compared with the program, by the program's names.
METRICS = ("avg_exec", "max_exec", "min_exec", "makespan", "delay_time",
           "vm_cost", "network_cost", "map_avg_exec", "reduce_avg_exec",
           "utilization", "locality_fraction", "transfer_bytes",
           "queue_wait")


def _mix32(h):
    h = (h ^ (h >> np.uint32(16))) * _M1
    h = (h ^ (h >> np.uint32(15))) * _M2
    return h ^ (h >> np.uint32(16))


def placement(n_maps, n_vms, data_mb, block_mb, replication, skewed, seed):
    """``(replicas bool[M, V], block size f32[M])`` of every map's input."""
    f32 = np.float32
    bs = max(f32(block_mb), f32(1e-6))
    n_blocks = max(int(np.ceil(f32(data_mb) / bs)), 1)
    block = np.arange(n_maps) % n_blocks
    last = f32(data_mb) - f32(n_blocks - 1) * bs
    size = np.where(block == n_blocks - 1, last, bs).astype(f32)
    with np.errstate(over="ignore"):
        h = _mix32(block.astype(np.uint32) * _C1
                   + np.uint32(seed % (1 << 32)) * _C3)
    if skewed:
        u = (h >> np.uint32(8)).astype(f32) * f32(1.0 / (1 << 24))
        start = np.minimum((u * u * f32(n_vms)).astype(np.int64), n_vms - 1)
    else:
        start = (h % np.uint32(n_vms)).astype(np.int64)
    reps = min(max(int(replication), 1), n_vms)
    holds = np.zeros((n_maps, n_vms), bool)
    for r in range(reps):
        holds[np.arange(n_maps), (start + r) % n_vms] = True
    return holds, size


def bind(binding, n_maps, n_reduces, n_vms, mips, pes, map_len, red_len,
         holds):
    """Task -> VM, maps first; loads in float32 as the semantics define."""
    f32 = np.float32
    n = n_maps + n_reduces
    if binding == ROUND_ROBIN:
        return np.arange(n) % n_vms
    load = np.zeros(n_vms, f32)
    cap = f32(mips) * f32(pes)
    vm = np.empty(n, np.int64)
    for k in range(n):
        is_map = k < n_maps
        masked = load
        if binding == LOCALITY and is_map:
            masked = np.where(holds[k], load, f32(1e30))
        v = int(np.argmin(masked))
        vm[k] = v
        load[v] = load[v] + (f32(map_len) if is_map else f32(red_len)) / cap
    return vm


def simulate(cell: dict, dtype=np.float64) -> dict:
    """Simulate one cell; returns :data:`METRICS` as Python floats."""
    d = np.dtype(dtype)
    c = lambda x: np.asarray(x, d)                        # noqa: E731
    M, R, V = int(cell["n_maps"]), int(cell["n_reduces"]), int(cell["n_vms"])
    T = M + R
    mips, pes, cost = c(cell["vm_mips"]), int(cell["vm_pes"]), c(cell["vm_cost"])
    L, S, rf = c(cell["job_length"]), c(cell["job_data"]), c(cell["reduce_factor"])
    bw, k_in, k_sh = c(cell["net_bw"]), c(cell["kappa_in"]), c(cell["kappa_shuffle"])
    space = int(cell["sched_policy"]) == SPACE_SHARED

    f32 = np.float32
    holds, size = placement(M, V, cell["job_data"], cell["block_size_mb"],
                            cell["replication"],
                            int(cell["placement"]) == SKEWED,
                            int(cell["storage_seed"]))
    map_len32 = f32(cell["job_length"]) / f32(M)
    red_len32 = f32(cell["reduce_factor"]) * f32(cell["job_length"]) / f32(R)
    vm = bind(int(cell["binding_policy"]), M, R, V, cell["vm_mips"], pes,
              map_len32, red_len32, holds)
    local = holds[np.arange(M), vm[:M]]

    is_map = np.arange(T) < M
    length = np.where(is_map, L / c(M), rf * L / c(R)).astype(d)
    stage_in = k_in * S / ((c(M) + c(1)) * bw)
    fetch = np.where(local, c(0), k_in * size.astype(d) / bw).astype(d)
    shuffle = k_sh * S / ((c(M) + c(1)) * bw)

    inf = c(np.inf)
    ready = np.full(T, inf, d)
    ready[:M] = stage_in + fetch
    start = np.full(T, inf, d)
    finish = np.full(T, inf, d)
    remaining = length.copy()
    arrived = np.zeros(T, bool)      # handed to its VM (running or queued)
    running = np.zeros(T, bool)
    maps_left = M
    now = c(0)
    slots = np.full(V, pes)

    def admit(vms):
        # space-shared: fill free PE slots in (ready, id) order
        for v in np.unique(vms):
            waiting = np.flatnonzero(arrived & ~running & (finish == inf)
                                     & (start == inf) & (vm == v))
            free = slots[v] - int(np.sum(running & (vm == v)))
            if free <= 0 or waiting.size == 0:
                continue
            order = waiting[np.lexsort((waiting, ready[waiting]))][:free]
            start[order] = now
            running[order] = True

    while True:
        pending = ~arrived & (ready < inf)
        if not running.any() and not pending.any():
            break
        n_on = np.bincount(vm[running], minlength=V)
        rate = mips * np.minimum(c(1), c(pes) / np.maximum(n_on[vm], 1)
                                 .astype(d)).astype(d)
        eta = np.where(running, now + remaining / rate, inf).astype(d)
        t_comp = eta.min()
        t_evt = ready[pending].min() if pending.any() else inf
        t_next = min(t_comp, t_evt)
        remaining = np.where(running, remaining - (t_next - now) * rate,
                             remaining).astype(d)
        now = c(t_next)
        if t_comp <= t_evt:
            done = running & (eta <= t_comp + c(_EPS))
            finish[done] = now
            remaining[done] = c(0)
            running &= ~done
            maps_left -= int(np.sum(done & is_map))
            if maps_left == 0 and np.any(done & is_map):
                ready[M:] = now + shuffle
            if space:
                admit(vm[done])
        else:
            new = pending & (ready <= now + c(_EPS))
            arrived |= new
            if space:
                admit(vm[new])
            else:
                start[new] = now
                running |= new

    exec_t = (finish - start).astype(d)
    m_ex, r_ex = exec_t[:M], exec_t[M:]
    m_avg, r_avg = m_ex.sum(dtype=d) / c(M), r_ex.sum(dtype=d) / c(R)
    delay = start[:M].max() + start[M:].max() - finish[:M].max()
    fin = finish.max()
    total_mi = length.sum(dtype=d)
    out = dict(
        avg_exec=m_avg + r_avg,
        max_exec=m_ex.max() + r_ex.max(),
        min_exec=m_ex.min() + r_ex.min(),
        makespan=finish[M:].max(),
        delay_time=delay,
        vm_cost=(exec_t * cost).sum(dtype=d),
        network_cost=delay * c(cell["net_cost_per_unit"]),
        map_avg_exec=m_avg,
        reduce_avg_exec=r_avg,
        utilization=total_mi / (c(V) * mips * c(pes) * fin),
        locality_fraction=c(np.sum(local)) / c(M),
        transfer_bytes=size[~local].astype(d).sum(dtype=d) * c(1e6),
        queue_wait=(start - ready).sum(dtype=d) / c(T),
    )
    return {k: float(v) for k, v in out.items()}
