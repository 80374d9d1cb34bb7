"""Engine loop: 95th percentile of how late the loop took each release
(observed release - due) over the admitted releases due in the window.
A refused release carries no observed time."""
import numpy as np


def read(run):
    lag = [r.release - r.due for r in run.reqs if r.release is not None]
    return float(np.percentile(lag, 95)) if lag else None
