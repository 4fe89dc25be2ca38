"""The one traffic generator: inputs, request order and arrivals from a
mix's data file.

A mix (``traffic/<name>.json``) is parameters only:

* ``"pool"``: how many distinct inputs to make per task.  Inputs follow
  the task's ``inputs`` in its configuration (shape and fill of each named
  input) and are drawn from the seed in one call per input;
* ``"tasks"`` (optional, for a configuration that serves several): the
  share of requests each task gets, ``{"<task>": weight}``;
* ``"arrivals"``: the name of the arrival process, a module
  ``arrivals/<name>.py`` found by name, with its own parameters beside
  it in the mix (``closed``: ``clients``; ``periodic``: ``streams``,
  ``rate_hz``, ``phases_ms``; ``poisson``: ``rate_hz``; ``bursty``:
  ``rate_hz``, ``burst_hz``, ``burst_ms``, ``every_ms``).

The run's seed picks the inputs and which pool entry each request
carries.  It never changes the arrival times, the task of each request or
their count: those come from the mix alone (``arrival_seed`` where they
are random), so every seed offers the same work in another order.
"""
from __future__ import annotations

import numpy as np

POOL_STREAM, ORDER_STREAM, SAMPLE_STREAM = 1, 2, 3


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per purpose, from one run seed."""
    return np.random.default_rng([seed, *stream])


def make_pool(inputs: dict, size: int, seed: int, task_i: int = 0) -> dict:
    """``{name: array (size, *shape)}`` per a task's inputs."""
    g = rng(seed, POOL_STREAM, task_i)
    out = {}
    for name, spec in inputs.items():
        shape = (size, *spec["shape"])
        if spec["fill"] == "normal":
            out[name] = g.standard_normal(shape, dtype=np.float32)
        elif spec["fill"] == "ones":
            out[name] = np.ones(shape, np.float32)
        else:
            raise ValueError(f"input {name!r}: unknown fill "
                             f"{spec['fill']!r}")
    return out


def task_sequence(mix: dict, tasks: list[str], count: int) -> np.ndarray:
    """The task index of each request in sending order: from the mix's
    shares and its ``arrival_seed``, never from the run's seed."""
    shares = mix.get("tasks")
    if not shares:
        if len(tasks) != 1:
            raise ValueError(f"a mix for tasks {tasks} needs 'tasks' shares")
        return np.zeros(count, np.int64)
    unknown = set(shares) - set(tasks)
    if unknown:
        raise ValueError(f"mix names unknown tasks {sorted(unknown)}")
    w = np.asarray([shares.get(t, 0.0) for t in tasks], float)
    g = np.random.default_rng([mix.get("arrival_seed", 0), 1])
    return g.choice(len(tasks), size=count, p=w / w.sum())


def pool_order(size: int, count: int, seed: int) -> np.ndarray:
    """The pool entry of each request in sending order."""
    return rng(seed, ORDER_STREAM).integers(0, size, count)


def sample(count: int, size: int, seed: int, task_i: int = 0) -> np.ndarray:
    """Indices of the answered requests whose answers are compared."""
    return np.sort(rng(seed, SAMPLE_STREAM, task_i).choice(
        count, min(size, count), replace=False))
