"""Device: share of the traced segment in which no program ran on the
chip, from the profiler's trace, averaged over the cell's chips, in
percent."""


def read(run):
    t = run.trace
    if t is None or t.device["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t.device["busy_s"] / t.device["window_s"])
