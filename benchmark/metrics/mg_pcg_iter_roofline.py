"""mg_pcg_iter_roofline: percent of the HBM roofline that one
MG-preconditioned CG iteration reaches.  The floor is the least bytes
such an iteration moves, counted from the configuration's grid and the
traffic's ``mg_levels`` (benchmark/counts_mg.py), at the device's
published bandwidth (benchmark/peaks.py); it is set against device-busy
seconds per iteration in the traced window."""

from benchmark.counts_mg import mg_pcg_iteration_min_bytes
from benchmark.peaks import peaks_for


def read(run):
    iters = sum(o.niterations for o in run.outcomes if o.ok)
    if run.trace is None or iters == 0 or run.trace.mean_busy_s <= 0:
        return None
    levels = int(run.traffic["options"]["mg_levels"])
    floor_s = (mg_pcg_iteration_min_bytes(run.config, levels)
               / peaks_for(run.device_kind)["hbm_bytes_per_s"])
    return 100.0 * floor_s * iters / run.trace.mean_busy_s
