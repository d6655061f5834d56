"""Milliseconds per traced sweep in which the chip sat idle while the
program uploaded a bucket's columns: the device-idle time inside the
``iotsim.upload`` spans (``bench/spans.py``)."""

SPAN = "iotsim.upload"


def read(run: dict):
    t = run["trace"]
    if t is None or SPAN not in t.get("idle_by_span", {}):
        return None
    return 1e3 * t["idle_by_span"][SPAN] / t["sweeps"]
