"""One number the runner read from the program or the device."""


def read(run, key, scale=1.0):
    value = run["facts"].get(key)
    return None if value is None else value * scale
