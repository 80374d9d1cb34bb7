"""Process start to the window's first instant: imports, weights, the
stage programs' compile or cache load and calibration, and the warm-up
load that fills the scheduler's MRET windows."""


def read(run):
    return run.setup_s
