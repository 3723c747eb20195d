"""Device idle share of the serve window: 1 - (union of device operation
intervals) / (traced window), in percent (device trace)."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
