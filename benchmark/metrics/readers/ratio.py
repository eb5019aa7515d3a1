"""num / den of two facts (each a key, or a list of keys that are summed),
times `scale`; with `one_minus`, scale x (1 - num / den). Nothing to read
(a missing fact, a zero denominator) gives nothing."""


def _sum(facts, keys):
    keys = [keys] if isinstance(keys, str) else keys
    if any(facts.get(k) is None for k in keys):
        return None
    return sum(facts[k] for k in keys)


def read(facts, num, den, scale=1.0, one_minus=False):
    n, d = _sum(facts, num), _sum(facts, den)
    if n is None or not d:
        return None
    r = n / d
    return scale * (1.0 - r if one_minus else r)
