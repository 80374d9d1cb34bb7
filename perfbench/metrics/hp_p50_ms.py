"""Median of (finish - due) over every HP job due in the window: the HP
response time, timed from the due time. A job that never finished counts
with at least the time to the run's end."""
import numpy as np


def read(run):
    lat = [(r.finish if r.finish is not None else run.end_ms) - r.due
           for r in run.reqs if r.prio == "hp"]
    return float(np.percentile(lat, 50)) if lat else None
