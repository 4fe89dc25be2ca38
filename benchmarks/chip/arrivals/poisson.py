"""Open loop with independent users: exponential gaps at ``rate_hz``,
drawn from the mix's ``arrival_seed`` (never the run's seed, so every run
offers the same arrivals).  Mix keys: ``rate_hz``, ``arrival_seed``."""
import numpy as np


def due_times(mix: dict, seconds: float) -> np.ndarray:
    g = np.random.default_rng([mix["arrival_seed"], 2])
    n = int(mix["rate_hz"] * seconds * 1.5) + 64
    due = np.cumsum(g.exponential(1.0 / mix["rate_hz"], n))
    while due[-1] < seconds:             # a long window: draw on
        due = np.concatenate([due, due[-1] + np.cumsum(
            g.exponential(1.0 / mix["rate_hz"], n))])
    return due[due < seconds]


def drive(loop, mix: dict) -> list:
    return loop.open(due_times(mix, loop.seconds))
