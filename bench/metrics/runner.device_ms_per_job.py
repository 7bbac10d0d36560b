"""Device-busy milliseconds of the traced window per job traced."""


def value(run):
    if run.trace is None or not run.trace.jobs:
        return None
    return 1e3 * run.trace.busy_s / run.trace.jobs
