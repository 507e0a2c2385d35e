"""The mean of one of the run's per-step series."""


def read(run, series):
    values = run["facts"].get(series)
    return sum(values) / len(values) if values else None
