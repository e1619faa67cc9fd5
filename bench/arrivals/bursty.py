"""On/off bursts: a Poisson process at ``base_rate`` req/s in quiet
phases and ``burst_rate`` in bursts, with exponential phase lengths of
mean ``mean_on_s`` and ``mean_off_s`` (a copy of
``benchmarks/serve_latency.py:bursty_arrivals``). The whole schedule
comes from the mix's fixed ``arrival_seed``: the bursts are the load, so
every run seed gets the same one, and the seed draws only the inputs."""
import numpy as np


def gaps(arrivals, seconds, rng):
    fixed = np.random.default_rng(arrivals["arrival_seed"])
    base, burst = float(arrivals["base_rate"]), float(arrivals["burst_rate"])
    on, off = float(arrivals["mean_on_s"]), float(arrivals["mean_off_s"])
    times = []
    t, in_burst = 0.0, False
    phase_end = fixed.exponential(off)
    while t < seconds * 1.25 + 1.0:
        t += fixed.exponential(1.0 / (burst if in_burst else base))
        while t >= phase_end:             # cross into the next phase(s)
            in_burst = not in_burst
            phase_end += fixed.exponential(on if in_burst else off)
        times.append(t)
    return np.diff(np.asarray(times), prepend=0.0)
