"""Stage programs, whole step: FLOPs of the stages that ran wholly inside
the window, from the benchmark's count of their work, over the window's
length times the cell's chips times the chip's peak FLOP/s, in percent."""


def read(run):
    if not run.stages or not run.peaks:
        return None
    flops = sum(run.costs[st]["flops"] for _, _, st, _, _ in run.stages)
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["flops_per_s"])
