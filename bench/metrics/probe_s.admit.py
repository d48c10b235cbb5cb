"""Host seconds per decision inside ``JobSetController.estimated_iter_time``,
the simulator probes, from the span the runner puts around each call."""


def read(run):
    if "probe" not in run.spans or not run.units:
        return None
    return run.spans["probe"] / run.units
