"""Device dispatches per timed sweep, from ``RunReport.dispatches``
(``SweepPlan.run`` front end: one per bucket, one per chunk step under
compaction)."""


def read(run: dict):
    reports = run["reports"]
    if not reports:
        return None
    return sum(r.dispatches for r in reports) / len(reports)
