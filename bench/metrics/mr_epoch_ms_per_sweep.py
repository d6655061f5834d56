"""Device milliseconds per traced sweep in the ``mr_epoch`` kernel.  It is
the only Pallas kernel on the cell's path and its ``pallas_call`` has no
``name=``, so its events are the trace's Mosaic custom calls
(``custom_call_target="tpu_custom_call"``)."""

KIND = "[tpu_custom_call]"


def read(run: dict):
    t = run["trace"]
    if t is None:
        return None
    ms = 1e3 * sum(s for name, s in t["ops"].items() if name.endswith(KIND))
    return ms / t["sweeps"] if ms > 0 else None
