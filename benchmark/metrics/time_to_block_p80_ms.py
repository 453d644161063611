"""80th percentile of the time from a job's submit to its answer at the
client, in ms, over every job of the window that answered: the highest
percentile with ten or more of a window's jobs beyond it."""

import statistics


def read(run):
    lat = [1e3 * (r["t_done"] - r["t_submit"])
           for r in run.records if r["answer"] is not None]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=100, method="inclusive")[79]
