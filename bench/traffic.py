"""The one traffic generator: arrivals and request contents from a seed.

Everything a traffic mix can vary is a parameter in its cell file under
``bench/workloads/``; nothing here knows a cell by name."""
from __future__ import annotations

import numpy as np


def arrivals(rng: np.random.Generator, spec: dict, horizon_s: float
             ) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop Poisson
    schedule at ``rate_per_s`` that never waits for the system."""
    if spec["process"] != "poisson":
        raise ValueError(f"arrival process {spec['process']!r}")
    rate = float(spec["rate_per_s"])
    t = np.cumsum(rng.exponential(1.0 / rate, int(rate * horizon_s) + 64))
    while t[-1] < horizon_s:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, int(rate) + 64))])
    return t[t < horizon_s]


def placement_states(rng: np.random.Generator, n: int, executors: int,
                     machines: int, spouts: int, load_sigma: float):
    """Request contents, as the program's ``serve_control.synthetic_requests``
    draws them (a copy, vectorized): a uniformly random feasible
    assignment (machine index per executor) and lognormal spout loads
    normalized by the base rate.  Returns (machine index [n, executors]
    int8, normalized load [n, spouts] float32)."""
    X = rng.integers(0, machines, (n, executors)).astype(np.int8)
    w = np.exp(rng.normal(0.0, load_sigma, (n, spouts))).astype(np.float32)
    return X, w


def state_vector(X_idx: np.ndarray, w: np.ndarray, machines: int
                 ) -> np.ndarray:
    """The state vector a cluster sends: its one-hot assignment, flattened,
    then its normalized spout loads."""
    onehot = np.eye(machines, dtype=np.float32)[X_idx]
    return np.concatenate([onehot.reshape(*X_idx.shape[:-1], -1), w], -1)
