"""Model stage: the FLOPs the window's completed answers needed (their
experts and Eq. 2, ``bench/experts/<kind>.flops_per_event``), per second
of the window, as a share of the chip's peak."""


def read(run):
    if not run.completed:
        return None
    chips = run.cell.chips
    return 100.0 * run.flops / run.seconds / (chips
                                              * run.peaks["flops_per_s"])
