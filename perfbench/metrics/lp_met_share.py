"""Share of LP releases due in the window that finished by due time plus
deadline, in percent: the LP service that fits beside HP, timed from the
due time. A refused, cancelled or unfinished release counts as missed."""


def read(run):
    lp = [r for r in run.reqs if r.prio == "lp"]
    return 100.0 * sum(r.met for r in lp) / len(lp) if lp else None
