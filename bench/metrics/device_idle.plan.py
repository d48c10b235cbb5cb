"""Share of the traced window of a planner cell in which no operation ran
on the device (``TraceSummary.idle_percent``)."""


def read(run):
    return None if run.trace is None else run.trace.idle_percent()
