"""Seeded traffic, copied from the program's ``runtime/workload.py``
(``sample_works``, ``sample_packets``, ``poisson_arrivals``,
``bursty_arrivals``, ``diurnal_arrivals``, ``make_workload``) and its
slot quantisation (``to_slots``), as they stood when the benchmark was
defined; ``sort=False`` only skips the last sort, for counting. Same
random stream, draw for draw: a scenario seed gives the
same tasks here as in the program, which ``bench/tests`` checks."""

from __future__ import annotations

import numpy as np


def sample_works(m: int, dist: str, mean: float, rng) -> np.ndarray:
    if dist == "uniform":
        return rng.uniform(1.0, 2.0 * mean - 1.0, size=m)
    if dist == "poisson":
        return 1.0 + rng.poisson(mean - 1.0, size=m).astype(np.float64)
    raise ValueError(f"unknown work distribution {dist!r}")


def sample_packets(m: int, mean: float, rng) -> np.ndarray:
    return 1.0 + rng.poisson(mean, size=m).astype(np.float64)


def poisson_arrivals(horizon: float, rng, *, rate: float = 1.0,
                     sort: bool = True):
    m = rng.poisson(rate * horizon)
    t = rng.uniform(0.0, horizon, size=m)
    return np.sort(t) if sort else t


def bursty_arrivals(horizon: float, rng, *, rate_lo: float = 0.2,
                    rate_hi: float = 5.0, sojourn_lo: float = 20.0,
                    sojourn_hi: float = 4.0, sort: bool = True):
    """MMPP-2: exponential sojourns alternate a low and a high rate."""
    times = []
    t, hi = 0.0, False
    while t < horizon:
        sojourn = rng.exponential(sojourn_hi if hi else sojourn_lo)
        end = min(t + sojourn, horizon)
        rate = rate_hi if hi else rate_lo
        k = rng.poisson(rate * (end - t))
        if k:
            times.append(rng.uniform(t, end, size=k))
        t, hi = end, not hi
    if not times:
        return np.zeros(0, dtype=np.float64)
    t = np.concatenate(times)
    return np.sort(t) if sort else t


def diurnal_arrivals(horizon: float, rng, *, rate_mean: float = 1.0,
                     amplitude: float = 0.8, period: float = 100.0):
    """Inhomogeneous Poisson with a sinusoidal rate, by thinning."""
    peak = rate_mean * (1.0 + amplitude)
    cand = poisson_arrivals(horizon, rng, rate=peak)
    lam = rate_mean * (1.0 + amplitude * np.sin(2.0 * np.pi * cand / period))
    keep = rng.uniform(0.0, peak, size=cand.shape[0]) < lam
    return cand[keep]


ARRIVALS = {"poisson": poisson_arrivals, "bursty": bursty_arrivals,
            "diurnal": diurnal_arrivals}


def make_workload(process: str, *, horizon: float, work_dist: str,
                  work_mean: float, packet_mean: float, seed: int,
                  **params):
    """``(t_arrive, works)`` of one scenario, both float64."""
    rng = np.random.default_rng(seed)
    t = ARRIVALS[process](horizon, rng, **params)
    works = sample_works(t.shape[0], work_dist, work_mean, rng)
    sample_packets(t.shape[0], packet_mean, rng)   # keeps the stream whole
    return t, works


def arrivals(process: str, *, horizon: float, seed: int, **params):
    """Arrival times alone: the first draws of ``make_workload``'s stream."""
    return ARRIVALS[process](horizon, np.random.default_rng(seed), **params)


def to_slots(t_arrive: np.ndarray, dt: float, n_slots: int) -> np.ndarray:
    """Slot index of each arrival before the horizon."""
    keep = t_arrive < dt * n_slots
    return np.floor(t_arrive[keep] / dt).astype(np.int64)


def cluster_powers(n: int, low: int, high: int, seed: int) -> np.ndarray:
    """Integer node powers in ``low..high`` drawn from ``seed``, as the
    program's ``lab.ClusterSpec.resolve_powers`` draws them."""
    rng = np.random.default_rng(seed)
    return rng.integers(low, high + 1, size=n).astype(np.float64)


def slot_counts(process: str, *, horizon: float, seed: int, dt: float,
                n_slots: int, **params) -> np.ndarray:
    """(n_slots,) arrivals per slot, from the same draws as ``arrivals``;
    a count needs no order, so a process that sorts last skips the sort."""
    if process in ("poisson", "bursty"):
        params = dict(params, sort=False)
    t = ARRIVALS[process](horizon, np.random.default_rng(seed), **params)
    return np.bincount(to_slots(t, dt, n_slots), minlength=n_slots)
