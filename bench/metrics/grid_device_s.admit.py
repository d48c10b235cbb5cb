"""Device seconds of the planner's grid kernel per decision: the
``ChainKernel`` grid programs (jitted from ``_one_ladder``) in the traced
window, by their names on the device's module line."""

PROGRAM = "_one_ladder"


def read(run):
    if run.trace is None or not run.traced_units:
        return None
    seconds = run.trace.program_s(PROGRAM)
    if seconds <= 0.0:
        return None
    return seconds / run.traced_units
