"""Scheduler: 95th percentile of (finish - due) over every LP release due
in the window. A refused release is never served, and like a job that
never finished it counts with at least the time to the run's end, so the
tail is that of all LP requests and always has something to read."""
import numpy as np


def read(run):
    lat = [(r.finish if r.finish is not None else run.end_ms) - r.due
           for r in run.reqs if r.prio == "lp"]
    return float(np.percentile(lat, 95)) if lat else None
