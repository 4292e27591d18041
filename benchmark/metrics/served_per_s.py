"""served_per_s: requests answered within the measured window, per
second of the window.  Above the rate the service sustains, the queue
never empties, so this is the service's capacity on the cell's mix."""


def read(run):
    t0, t1 = run.window
    done = sum(1 for o in run.outcomes if o.ok and o.end <= t1)
    return done / (t1 - t0) if done else None
