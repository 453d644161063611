"""Median time from a job's submit to its answer at the client, in ms,
over every job of the window that answered."""

import statistics


def read(run):
    lat = [1e3 * (r["t_done"] - r["t_submit"])
           for r in run.records if r["answer"] is not None]
    return statistics.median(lat) if lat else None
