"""Workload generators: staggered arrival processes over the paper's work
distributions.

The paper staggers 4000 tasks over time with work units and packet counts
drawn from uniform / Poisson distributions (section 5); this module keeps
those marginals and adds the arrival processes a production cluster sees:

* ``poisson``  — memoryless arrivals at a constant rate,
* ``bursty``   — a 2-state Markov-modulated Poisson process (MMPP-2):
                 exponential sojourns alternate a low and a high rate,
* ``diurnal``  — inhomogeneous Poisson with a sinusoidal rate (thinning),
* ``trace``    — replay of explicit arrival timestamps.

``slot_rows`` quantises scenarios' arrivals onto the fixed-shape tensors the
vectorized backend consumes (slot index per task, padded to a common task
count with zero-work sentinels); ``to_slots``/``batch_slots`` apply it to
``Workload`` objects. The batched sweep lowers synthetic scenarios from
``draw_tasks``, the part of ``make_workload``'s draws that a slot grid reads.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

__all__ = [
    "Workload",
    "sample_works",
    "sample_packets",
    "poisson_arrivals",
    "bursty_arrivals",
    "diurnal_arrivals",
    "trace_arrivals",
    "ARRIVAL_PROCESSES",
    "draw_tasks",
    "make_workload",
    "load_trace_csv",
    "slot_rows",
    "to_slots",
    "batch_slots",
]


@dataclass(frozen=True)
class Workload:
    """Tasks sorted by arrival time. ``works`` = beta_i (work units),
    ``packets`` = mu_i (migration transfer size)."""

    t_arrive: np.ndarray  # (m,) float64, nondecreasing
    works: np.ndarray     # (m,) float64, > 0
    packets: np.ndarray   # (m,) float64, > 0

    def __post_init__(self):
        t = np.asarray(self.t_arrive, dtype=np.float64)
        if t.size and (np.diff(t) < 0).any():
            raise ValueError("arrival times must be sorted")
        object.__setattr__(self, "t_arrive", t)
        object.__setattr__(self, "works",
                           np.asarray(self.works, dtype=np.float64))
        object.__setattr__(self, "packets",
                           np.asarray(self.packets, dtype=np.float64))

    @property
    def m(self) -> int:
        return int(self.t_arrive.shape[0])

    @property
    def horizon(self) -> float:
        return float(self.t_arrive[-1]) if self.m else 0.0


def sample_works(m: int, dist: str, mean: float,
                 rng: np.random.Generator) -> np.ndarray:
    """The paper's two work-unit distributions (section 5)."""
    if dist == "uniform":
        return rng.uniform(1.0, 2.0 * mean - 1.0, size=m)
    if dist == "poisson":
        return 1.0 + rng.poisson(mean - 1.0, size=m).astype(np.float64)
    raise ValueError(f"unknown work distribution {dist!r}")


def sample_packets(m: int, mean: float,
                   rng: np.random.Generator) -> np.ndarray:
    return 1.0 + rng.poisson(mean, size=m).astype(np.float64)


# ---------------------------------------------------------------------------
# Arrival processes
# ---------------------------------------------------------------------------

def poisson_arrivals(horizon: float, rng: np.random.Generator, *,
                     rate: float = 1.0) -> np.ndarray:
    """Homogeneous Poisson process on [0, horizon)."""
    m = rng.poisson(rate * horizon)
    return np.sort(rng.uniform(0.0, horizon, size=m))


def bursty_arrivals(horizon: float, rng: np.random.Generator, *,
                    rate_lo: float = 0.2, rate_hi: float = 5.0,
                    sojourn_lo: float = 20.0,
                    sojourn_hi: float = 4.0) -> np.ndarray:
    """MMPP-2: alternate exponential sojourns in a low-rate and a high-rate
    state; within each sojourn arrivals are Poisson at that state's rate."""
    times: list[np.ndarray] = []
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
    return np.sort(np.concatenate(times))


def diurnal_arrivals(horizon: float, rng: np.random.Generator, *,
                     rate_mean: float = 1.0, amplitude: float = 0.8,
                     period: float = 100.0) -> np.ndarray:
    """Inhomogeneous Poisson with rate ``mean * (1 + A sin(2 pi t / T))``,
    sampled by thinning against the peak rate."""
    if not 0.0 <= amplitude <= 1.0:
        raise ValueError("amplitude must be in [0, 1]")
    peak = rate_mean * (1.0 + amplitude)
    cand = poisson_arrivals(horizon, rng, rate=peak)
    lam = rate_mean * (1.0 + amplitude * np.sin(2.0 * np.pi * cand / period))
    keep = rng.uniform(0.0, peak, size=cand.shape[0]) < lam
    return cand[keep]


def trace_arrivals(horizon: float, rng: np.random.Generator, *,
                   times=()) -> np.ndarray:
    """Replay explicit timestamps (clipped to the horizon)."""
    t = np.sort(np.asarray(list(times), dtype=np.float64))
    return t[t < horizon]


ARRIVAL_PROCESSES = {
    "poisson": poisson_arrivals,
    "bursty": bursty_arrivals,
    "diurnal": diurnal_arrivals,
    "trace": trace_arrivals,
}


def draw_tasks(process: str = "poisson", *, horizon: float = 100.0,
               work_dist: str = "uniform", work_mean: float = 4.0,
               seed: int = 0, **process_kwargs):
    """The first draws of one scenario's stream: ``(rng, t_arrive, works)``,
    the arrival times (sorted, float64) and then their work units, with the
    generator left where a further draw continues the stream."""
    if process not in ARRIVAL_PROCESSES:
        raise ValueError(f"unknown arrival process {process!r}; "
                         f"have {sorted(ARRIVAL_PROCESSES)}")
    rng = np.random.default_rng(seed)
    t = ARRIVAL_PROCESSES[process](horizon, rng, **process_kwargs)
    return rng, t, sample_works(t.shape[0], work_dist, work_mean, rng)


def make_workload(process: str = "poisson", *, horizon: float = 100.0,
                  work_dist: str = "uniform", work_mean: float = 4.0,
                  packet_mean: float = 8.0, seed: int = 0,
                  **process_kwargs) -> Workload:
    """One scenario: arrival process x paper work/packet marginals; the
    packets are drawn after ``draw_tasks``'s arrivals and works."""
    rng, t, works = draw_tasks(process, horizon=horizon, work_dist=work_dist,
                               work_mean=work_mean, seed=seed,
                               **process_kwargs)
    return Workload(t_arrive=t, works=works,
                    packets=sample_packets(t.shape[0], packet_mean, rng))


def load_trace_csv(path, *, horizon: float | None = None) -> Workload:
    """Load a cluster trace from CSV rows of ``t_arrive, work, packets``.

    The minimal interchange format for real cluster traces (first step toward
    Google cluster-data / Azure Packing Trace replay): one task per row, ``#``
    comments and blank lines ignored, rows in any order (sorted by arrival
    here). ``horizon`` clips tasks arriving at or after it, matching the
    ``trace`` arrival process.
    """
    rows = np.loadtxt(path, delimiter=",", comments="#", ndmin=2,
                      dtype=np.float64)
    if rows.size == 0:
        rows = rows.reshape(0, 3)
    if rows.shape[1] != 3:
        raise ValueError(
            f"trace {path!r}: expected 3 columns (t_arrive, work, packets), "
            f"got {rows.shape[1]}")
    order = np.argsort(rows[:, 0], kind="stable")
    t, works, packets = rows[order].T
    if horizon is not None:
        keep = t < horizon
        t, works, packets = t[keep], works[keep], packets[keep]
    if (works <= 0).any() or (packets <= 0).any():
        raise ValueError(f"trace {path!r}: work and packets must be > 0")
    return Workload(t_arrive=t, works=works, packets=packets)


# ---------------------------------------------------------------------------
# Slotted views for the vectorized backend
# ---------------------------------------------------------------------------

def slot_rows(draws, dt: float, n_slots: int):
    """Quantise scenarios onto one slot grid in one fill.

    ``draws`` is a sequence of each scenario's ``(t_arrive, works)``,
    ``t_arrive`` sorted. Returns ``(slot (B, M) int32, works (B, M)
    float64, count (B,) int64)``: row b holds its ``count[b]`` tasks
    before the grid's end ``dt * n_slots``, at slot ``floor(t / dt)``,
    then padding with ``slot == n_slots`` (an out-of-range sentinel the
    backend drops) and zero work, up to M, the largest count.
    """
    end = dt * n_slots
    counts = np.array([np.searchsorted(t, end) for t, _ in draws],
                      dtype=np.int64)
    slot = np.full((len(draws), counts.max(initial=0)), n_slots,
                   dtype=np.int32)
    works = np.zeros(slot.shape, dtype=np.float64)
    for row, ((t, w), c) in enumerate(zip(draws, counts)):
        slot[row, :c] = np.floor(t[:c] / dt)
        works[row, :c] = w[:c]
    return slot, works, counts


def to_slots(wl: Workload, dt: float, n_slots: int):
    """One workload's ``slot_rows``: ``(arrive_slot, works, count)``."""
    slot, works, count = slot_rows([(wl.t_arrive, wl.works)], dt, n_slots)
    return slot[0], works[0], int(count[0])


def batch_slots(workloads, dt: float, n_slots: int):
    """Stack scenarios into ``(B, M)`` tensors with a common task capacity."""
    return slot_rows([(wl.t_arrive, wl.works) for wl in workloads], dt,
                     n_slots)
