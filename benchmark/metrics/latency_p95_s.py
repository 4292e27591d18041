"""latency_p95_s: 95th percentile, over every request due in the window,
of its answer time less its due time; a failed or shed request counts as
missing it (an infinite latency).  Above the sustained rate the queue
grows through the window and this tail grows with it."""

import math

from benchmark.traffic import percentile


def read(run):
    lat = [o.end - o.due if o.ok else math.inf for o in run.outcomes]
    p = percentile(lat, 95)
    return p if math.isfinite(p) else None
