"""95th percentile of every job's host latency in the window, from the
hand-off of its inputs to the entry point until its reads and meters are on
the host (inclusive-method quantile of all jobs)."""

import statistics


def value(run):
    lat = [(t1 - t0) * 1e3 for t0, t1 in run.jobs]
    if len(lat) < 20:
        return None
    return statistics.quantiles(lat, n=20, method="inclusive")[-1]
