"""Milliseconds per traced sweep in which the chip sat idle inside the
compaction host loop: the device-idle time inside every
``iotsim.compact.*`` span (``bench/spans.py``)."""

PREFIX = "iotsim.compact."


def read(run: dict):
    t = run["trace"]
    spans = {} if t is None else t.get("idle_by_span", {})
    idle = [s for name, s in spans.items() if name.startswith(PREFIX)]
    if not idle:
        return None
    return 1e3 * sum(idle) / t["sweeps"]
