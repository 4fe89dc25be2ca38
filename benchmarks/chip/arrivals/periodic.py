"""Paced streams, as sensors send frames: stream ``s`` sends a frame at
``phases_ms[s] + n * 1000 / rate_hz``, whatever the server does.  Mix
keys: ``streams``, ``rate_hz``, ``phases_ms`` (one per stream, each
within one period)."""
import numpy as np


def due_times(mix: dict, seconds: float) -> np.ndarray:
    """Sorted due times, in seconds from the window's start, of every
    frame of every stream that falls inside the window."""
    period = 1.0 / mix["rate_hz"]
    phases = np.asarray(mix["phases_ms"], float) / 1e3
    if len(phases) != mix["streams"]:
        raise ValueError(f"{mix['streams']} streams but "
                         f"{len(phases)} phases")
    if (phases < 0).any() or (phases >= period).any():
        raise ValueError("each phase must lie within one period")
    n = int(np.ceil(seconds / period)) + 1
    due = (phases[:, None] + period * np.arange(n)[None, :]).ravel()
    return np.sort(due[due < seconds])


def drive(loop, mix: dict) -> list:
    return loop.open(due_times(mix, loop.seconds))
