"""Explicit host-device transfers per timed sweep:
``RunReport.h2d_transfers + d2h_transfers`` (every column or index
uploaded, every result leaf and scalar pulled)."""


def read(run: dict):
    reports = run["reports"]
    if not reports or not hasattr(reports[0], "h2d_transfers"):
        return None
    return sum(r.h2d_transfers + r.d2h_transfers
               for r in reports) / len(reports)
