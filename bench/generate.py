"""Traffic generator: one sweep plan from a configuration, a traffic mix
and ``(seed, index)``.

A configuration file (``bench/configs/<name>.json``) gives the deployment:
the job, its input and block store, the network and the VM types.  A
traffic file (``bench/traffic/<name>.json``) gives what a user sweeps over
it:

* ``axes`` — ``[name, values]`` pairs in grid order.  ``vm_type`` takes
  names from the configuration's ``vm_types``; every other name is a
  ``SweepPlan`` axis.  Values ``{"draw": n}`` are drawn afresh for every
  sweep: ``n`` storage seeds from ``(seed, index)``;
* ``pin`` — parameters held fixed over the grid (``vm_type`` allowed);
* ``run`` — the options handed to ``SweepPlan.run``: ``backend``,
  ``compact``, ``mesh`` (shard over the cell's chips) and a pinned
  ``cost_model`` calibration, so the schedule is the same on every host;
* ``warmup_sweeps`` — sweeps run in set-up, with their own draws;
* ``check`` — ``per_sweep`` cells of each timed sweep kept as
  candidates, and ``cells`` of them compared with the reference.

Every sweep of a mix has the same axes and the same grid shape; only the
drawn values differ.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent

# configuration keys -> SweepPlan parameters pinned for every cell
_CONFIG_PARAMS = {
    "n_maps": "n_maps", "n_reduces": "n_reduces",
    "job_length_mi": "job_length", "data_mb": "job_data",
    "reduce_factor": "reduce_factor", "net_bw": "net_bw",
    "kappa_in": "kappa_in", "kappa_shuffle": "kappa_shuffle",
    "net_cost_per_unit": "net_cost_per_unit",
    "block_size_mb": "block_size_mb",
}
WINDOW, WARMUP, CHECK = 0, 1, 2      # random streams per (seed, index)


def load(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def rng(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream,
                                  int(index) % (1 << 64)])


class Traffic:
    """Builds the sweep plans of one cell."""

    def __init__(self, config: dict, mix: dict):
        self.config, self.mix = config, mix
        self.vm_types = config["vm_types"]

    def _vm_axis(self, names):
        from repro.core import sweep
        t = [self.vm_types[n] for n in names]
        return sweep.zip_(
            sweep.axis("vm_mips", [float(v["mips"]) for v in t]),
            sweep.axis("vm_pes", [float(v["pes"]) for v in t]),
            sweep.axis("vm_cost", [float(v["cost_per_sec"]) for v in t]))

    def plan(self, seed: int, index: int, stream: int = WINDOW):
        """The sweep of ``index`` in ``stream`` for ``seed``."""
        from repro.core import sweep
        draw = rng(seed, stream, index)
        dims = []
        for name, values in self.mix["axes"]:
            if isinstance(values, dict):
                values = draw.integers(0, 2**31 - 1,
                                       size=values["draw"]).tolist()
            dims.append(self._vm_axis(values) if name == "vm_type"
                        else sweep.axis(name, values))
        base = {p: self.config[k] for k, p in _CONFIG_PARAMS.items()}
        base.update(storage_enabled=1.0, net_enabled=1.0)
        for name, value in self.mix.get("pin", {}).items():
            if name == "vm_type":
                v = self.vm_types[value]
                base.update(vm_mips=float(v["mips"]), vm_pes=float(v["pes"]),
                            vm_cost=float(v["cost_per_sec"]))
            else:
                base[name] = value
        return sweep.product(*dims, **base)

    def run_kwargs(self, devices) -> dict:
        """Keyword arguments of ``SweepPlan.run`` for this mix."""
        from repro.core import costmodel
        opts = self.mix["run"]
        kw = {"backend": opts.get("backend", "xla"),
              "compact": opts.get("compact")}
        cm = opts["cost_model"]
        kw["cost_model"] = costmodel.CostModel(
            dispatch_us=float(cm["dispatch_us"]),
            epoch_lane_us=float(cm["epoch_lane_us"]),
            sync_us=float(cm["sync_us"]), device=costmodel.device_key(),
            source="static")
        if opts.get("mesh"):
            import jax
            kw["mesh"] = jax.sharding.Mesh(np.array(devices), ("cells",))
        return kw

    def reference_cell(self, cols: dict, i: int) -> dict:
        """One grid cell's parameters, as the reference takes them."""
        keys = ("n_maps", "n_reduces", "n_vms", "vm_mips", "vm_pes",
                "vm_cost", "job_length", "job_data", "reduce_factor",
                "net_bw", "kappa_in", "kappa_shuffle", "net_cost_per_unit",
                "sched_policy", "binding_policy", "block_size_mb",
                "replication", "placement", "storage_seed")
        return {k: np.asarray(cols[k])[i].item() for k in keys}
