"""A percentile of one of the run's host-clock series."""
from chipbench import yardstick


def read(run, series, q):
    return yardstick.percentile(run["facts"].get(series) or [], q)
