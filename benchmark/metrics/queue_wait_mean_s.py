"""queue_wait_mean_s: mean of the service's own queue wait per request
(``ServeResponse.queue_wait``, submit to dispatch)."""


def read(run):
    waits = [o.extra["queue_wait"] for o in run.outcomes
             if "queue_wait" in o.extra]
    return sum(waits) / len(waits) if waits else None
