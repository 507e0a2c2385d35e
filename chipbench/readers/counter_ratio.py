"""One of the program's counters over another, both over the window."""


def read(run, num, den):
    facts = run["facts"]
    if not facts.get(den):
        return None
    return facts[num] / facts[den]
