"""cg_iter_roofline: percent of the HBM roofline that one CG iteration
reaches.  The floor is the least bytes an iteration moves, counted from
the configuration's grid (benchmark/counts.py), at the device's published
bandwidth (benchmark/peaks.py); it is set against device-busy seconds per
iteration in the traced window."""

from benchmark.counts import cg_iteration_min_bytes
from benchmark.peaks import peaks_for


def read(run):
    iters = sum(o.niterations for o in run.outcomes if o.ok)
    if run.trace is None or iters == 0 or run.trace.mean_busy_s <= 0:
        return None
    floor_s = (cg_iteration_min_bytes(run.config)
               / peaks_for(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * floor_s * iters / run.trace.mean_busy_s
