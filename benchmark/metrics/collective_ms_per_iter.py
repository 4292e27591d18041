"""collective_ms_per_iter: device milliseconds of cross-chip collectives
(all-reduce, collective-permute, all-gather, ...) per CG iteration, mean
over the cell's chips."""


def read(run):
    iters = sum(o.niterations for o in run.outcomes if o.ok)
    if run.trace is None or iters == 0 or run.trace.mean_collective_s <= 0:
        return None
    return 1e3 * run.trace.mean_collective_s / iters
