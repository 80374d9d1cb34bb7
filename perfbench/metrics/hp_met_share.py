"""Share of HP releases due in the window that finished by due time plus
deadline, in percent: the paper's guarantee, timed from the due time."""


def read(run):
    hp = [r for r in run.reqs if r.prio == "hp"]
    return 100.0 * sum(r.met for r in hp) / len(hp) if hp else None
