"""Backend: mean time from one stage's end to the next stage's launch
within a job, on the host clock of a traced run's hooks on the engine
thread: a stage ends at its launch plus the execution time its worker
reported, so the gap is harvest, dispatch and the worker pool's hand-off.
Over the consecutive stage pairs that lay wholly in the window."""


def read(run):
    by_job = {}
    for job, _, stage, t0, t1 in run.stages:
        by_job.setdefault(job, {})[stage] = (t0, t1)
    gaps = [st[j + 1][0] - st[j][1] for st in by_job.values()
            for j in st if j + 1 in st]
    return sum(gaps) / len(gaps) if gaps else None
