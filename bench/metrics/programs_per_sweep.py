"""Device programs launched per traced sweep inside ``SweepPlan.run``:
the host's ``PJRT_LoadedExecutable_Execute`` events within the
``iotsim.run`` spans (``bench/spans.py``).  Every eager ``jnp`` op of
the host loop is a program of its own; ``dispatches_per_sweep`` counts
only the planned launches."""


def read(run: dict):
    t = run["trace"]
    if t is None or "iotsim.run" not in t.get("idle_by_span", {}):
        return None
    return t["programs"] / t["sweeps"]
