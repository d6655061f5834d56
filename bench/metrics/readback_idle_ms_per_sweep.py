"""Milliseconds per traced sweep in which the chip sat idle while the
program pulled results to the host: the device-idle time inside the
``iotsim.readback`` spans (``bench/spans.py``)."""

SPAN = "iotsim.readback"


def read(run: dict):
    t = run["trace"]
    if t is None or SPAN not in t.get("idle_by_span", {}):
        return None
    return 1e3 * t["idle_by_span"][SPAN] / t["sweeps"]
