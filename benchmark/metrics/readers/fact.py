"""One fact as it stands (a count)."""


def read(facts, of):
    v = facts.get(of)
    return None if v is None else float(v)
