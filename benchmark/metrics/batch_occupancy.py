"""batch_occupancy: percent of the service's ``max_batch`` that the
batch carrying a request held (``ServeResponse.batch_size``), mean over
requests."""


def read(run):
    sizes = [o.extra["batch_size"] for o in run.outcomes
             if o.extra.get("batch_size")]
    if not sizes:
        return None
    return 100.0 * sum(sizes) / len(sizes) / run.traffic["service"]["max_batch"]
