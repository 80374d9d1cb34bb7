"""Scheduler: LP releases admitted (Eq. 11-12) over LP releases due in
the window, in percent."""


def read(run):
    lp = [r for r in run.reqs if r.prio == "lp"]
    return 100.0 * sum(r.admitted for r in lp) / len(lp) if lp else None
