"""Share of the lane-epochs the sweep's launches allotted that real cells
needed: ``RunReport.lane_epochs_useful / lane_epochs_allotted`` over the
timed sweeps, in percent.  A dense bucket allots its padded lanes times
its realized epochs, a compaction chunk its launched lanes times its
epoch limit."""


def read(run: dict):
    reports = run["reports"]
    if not reports or not hasattr(reports[0], "lane_epochs_allotted"):
        return None
    allotted = sum(r.lane_epochs_allotted for r in reports)
    if not allotted:
        return None
    return 100.0 * sum(r.lane_epochs_useful for r in reports) / allotted
