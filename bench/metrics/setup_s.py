"""Process start to the first timed unit: JAX start, building the cluster
and jobs from the seed, the fill, and every device program compiled or
loaded from the persistent cache."""


def read(run):
    return run.setup_s
