"""The q-th percentile of a list of samples in the facts, times `scale`."""

import numpy as np


def read(facts, of, q, scale=1.0):
    samples = facts.get(of)
    if samples is None or len(samples) == 0:
        return None
    return float(np.percentile(np.asarray(samples), q)) * scale
