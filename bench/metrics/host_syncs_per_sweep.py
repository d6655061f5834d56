"""Blocking device-to-host pulls of the compaction host loop per timed
sweep: ``RunReport.compaction_syncs + scalar_syncs``."""


def read(run: dict):
    reports = run["reports"]
    if not reports:
        return None
    return sum(r.compaction_syncs + r.scalar_syncs
               for r in reports) / len(reports)
