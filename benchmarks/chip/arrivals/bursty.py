"""Open loop with bursts: Poisson arrivals at ``rate_hz``, and every
``every_ms`` a burst of ``burst_ms`` in which they come at ``burst_hz``
instead.  Drawn from the mix's ``arrival_seed`` (never the run's seed).
Mix keys: ``rate_hz``, ``burst_hz``, ``burst_ms``, ``every_ms``,
``arrival_seed``."""
import numpy as np


def due_times(mix: dict, seconds: float) -> np.ndarray:
    g = np.random.default_rng([mix["arrival_seed"], 3])
    every, burst = mix["every_ms"] / 1e3, mix["burst_ms"] / 1e3
    if not 0 < burst < every:
        raise ValueError("a burst must be shorter than its period")
    out, t = [], 0.0
    while t < seconds:                   # one period: burst, then quiet
        for a, b, hz in ((t, t + burst, mix["burst_hz"]),
                         (t + burst, t + every, mix["rate_hz"])):
            n = g.poisson(hz * (b - a))
            out.append(np.sort(g.uniform(a, b, n)))
        t += every
    due = np.concatenate(out)
    return due[due < seconds]


def drive(loop, mix: dict) -> list:
    return loop.open(due_times(mix, loop.seconds))
