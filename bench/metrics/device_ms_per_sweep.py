"""Milliseconds per traced sweep in which an operation ran on the chip:
the union of the device's operation intervals, averaged over chips."""


def read(run: dict):
    t = run["trace"]
    if t is None or not t["busy_s"]:
        return None
    return 1e3 * t["busy_s"] / t["sweeps"]
