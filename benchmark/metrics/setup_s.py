"""setup_s: seconds from process start to the opening of the measured
window: JAX start-up, operator generation, preparation and upload,
compilation or loading from the cache, right-hand sides and warm-up."""


def read(run):
    return run.setup_s
