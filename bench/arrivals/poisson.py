"""Poisson arrivals at ``rate_per_s``. The set of gaps comes from the
mix's fixed ``arrival_seed`` and only its order from the run's seed, so
every seed offers the same load in another order."""
import math

import numpy as np


def gaps(arrivals, seconds, rng):
    rate = float(arrivals["rate_per_s"])
    n = int(math.ceil(rate * seconds * 1.25)) + 64
    fixed = np.random.default_rng(arrivals["arrival_seed"])
    return rng.permutation(fixed.exponential(1.0 / rate, size=n))
