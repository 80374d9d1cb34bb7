"""Jobs of either priority due in the window that finished by their due
time plus deadline, per second of window. Refused, late and unfinished
jobs do not count."""


def read(run):
    return sum(r.met for r in run.reqs) / run.window_s
