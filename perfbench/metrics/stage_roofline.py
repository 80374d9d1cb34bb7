"""Stage programs: the least time the chip could take for the stages that
ran wholly inside the traced segment, each max(FLOPs / peak FLOP/s,
bytes / peak bandwidth) from the benchmark's count of its work, over the
segment's device-busy time, in percent. PERF.md says which of the two
bounds each stage."""


def read(run):
    t = run.trace
    if t is None or not t.stages or not run.peaks:
        return None
    busy = t.device["busy_s"] * run.chips
    if busy <= 0:
        return None
    least = sum(max(run.costs[st]["flops"] / run.peaks["flops_per_s"],
                    run.costs[st]["bytes"] / run.peaks["hbm_bytes_per_s"])
                for _, _, st, _, _ in t.stages)
    return 100.0 * least / busy
