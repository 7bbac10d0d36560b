"""Growth of the scheduler's upload counter (``SCHED_STATS
["upload_bytes"]``: bytes of payload stacks put on the device, on
payload-cache misses) in the window, per job. Reported from the traced run
with the other per-layer metrics; a program without the counter reads
nothing."""


def value(run):
    n = run.counters.get("sched.upload_bytes")
    if run.trace is None or n is None or not run.jobs:
        return None
    return n / len(run.jobs)
