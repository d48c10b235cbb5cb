"""Device milliseconds per step of the gradient all-reduce's ring
exchanges (collective-permute operations) on the first chip, in the traced
steps."""


def read(run):
    if run.trace is None or not run.traced_units:
        return None
    if run.trace.collective_permute_s <= 0.0:
        return None
    return 1e3 * run.trace.collective_permute_s / run.traced_units
