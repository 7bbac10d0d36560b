"""Growth of the scheduler's dispatch counter (``SCHED_STATS``) in the
window, per job."""


def value(run):
    if not run.jobs:
        return None
    return run.counters["sched.dispatches"] / len(run.jobs)
