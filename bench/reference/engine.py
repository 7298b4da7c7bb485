"""The plain reference of the batched sweep: one scenario at a time, in
numpy, copied from the program's ``runtime/vector_backend.simulate_scalar``
(with ``_owner_np``) and ``runtime/metrics.nearest_rank`` as they stood
when the benchmark was defined.

Semantics: time advances in slots of ``dt``; each slot's arrivals are
placed by the paper's positional rule over deficit intervals (each task's
work midpoint within its slot's stream picks the interval it falls in);
the crossover trigger
redistributes queued work to fair shares when the imbalance exceeds
max(crossover, floor); each node then drains ``power * dt``.

``dtype`` is the arithmetic's precision: float64 is the reference; a lower
one (bfloat16 from ``ml_dtypes``) is the control that shows the comparison
can fail."""

from __future__ import annotations

import math

import numpy as np

_TINY = 1e-12


def nearest_rank(values: np.ndarray, pct: float) -> float:
    """The ceil(pct/100 * n)-th smallest value."""
    values = np.sort(np.asarray(values, dtype=np.float64))
    n = values.shape[0]
    if n == 0:
        return float("nan")
    k = min(max(int(math.ceil(pct / 100.0 * n)), 1), n)
    return float(values[k - 1])


def _below(x):
    """The largest value of x's type below x (x > 0)."""
    return np.nextafter(x, np.zeros_like(x))


def owner(src: np.ndarray, frac: np.ndarray) -> np.ndarray:
    """Interval of each ``frac`` in [0, 1) when the stream is cut into
    intervals proportional to ``src``; a zero-width interval is never
    chosen."""
    edges = np.cumsum(src)
    x = np.minimum(frac * edges[-1], _below(edges[-1]))
    return np.searchsorted(edges, x, side="right")


def simulate(slot: np.ndarray, works: np.ndarray, powers: np.ndarray, *,
             n_slots: int, dt: float, rebalance: bool, floor: float,
             p: float, q: float, t_task: float, packets_per_step: float,
             packets_per_unit: float, dtype=np.float64) -> dict:
    """One scenario's metrics. ``slot``: (M,) arrival slot per task;
    ``works``: (M,) work units; ``powers``: (n,) node powers."""
    f = np.dtype(dtype).type
    slot = np.asarray(slot)
    works = np.asarray(works, dtype=np.float64).astype(dtype)
    pw = np.asarray(powers, dtype=np.float64).astype(dtype)
    T, n = n_slots, pw.shape[0]
    valid = slot < T
    tot = np.bincount(slot[valid], weights=works[valid].astype(np.float64),
                      minlength=T).astype(dtype)
    cnt = np.bincount(slot[valid], minlength=T)
    order = np.argsort(slot, kind="stable")
    first = np.concatenate([[0], np.cumsum(cnt)])
    scan_steps = 2 * (n - 1)
    pi = pw.sum()
    tiny = f(_TINY)

    queue = np.zeros(n, dtype)
    resp = np.zeros(works.shape[0], dtype)
    fires, moved, seen = 0, f(0.0), 0.0
    backlog = np.zeros(T, dtype)
    for t in range(T):
        idx = order[first[t]:first[t + 1]]
        if tot[t] > 0.0:
            w = works[idx]
            fair = pw / pi * (queue.sum() + tot[t])
            deficit = np.maximum(fair - queue, f(0.0))
            src = deficit if deficit.sum() > 0.0 else pw
            frac = (np.cumsum(w) - w + f(0.5) * w) / tot[t]
            own = owner(src, frac)
            resp[idx] = (queue[own] + w) / np.maximum(pw[own], tiny)
            np.add.at(queue, own, w)
            seen += cnt[t]
        if rebalance:
            w = queue.sum()
            t_bal = w / pi if pi > 0.0 else f(0.0)
            ratio = np.where(pw > 0.0, queue / np.maximum(pw, tiny),
                             np.where(queue > tiny, np.inf, 0.0)).astype(dtype)
            imb = ratio.max() / max(t_bal, tiny) - f(1.0)
            fair_q = pw / max(pi, tiny) * w
            excess = np.maximum(queue - fair_q, f(0.0)).sum()
            overhead = (f(scan_steps * (p + q)) + f(seen / n * t_task)
                        + excess * f(packets_per_unit / packets_per_step * p))
            cross = overhead / max(t_bal, tiny)
            if t_bal > tiny and imb > max(cross, f(floor)):
                queue = fair_q.astype(dtype)
                moved = moved + excess
                fires += 1
        backlog[t] = queue.sum()
        queue = np.maximum(queue - pw * f(dt), f(0.0))

    count = float(cnt.sum())
    drained = np.flatnonzero(backlog > tiny)
    return {
        "mean_response": float(resp.sum() / f(count)) if count
        else float("nan"),
        "p99_response": nearest_rank(resp[np.flatnonzero(valid)], 99.0),
        "makespan": float((drained[-1] + 1) * dt) if drained.size else 0.0,
        "trigger_fires": float(fires),
        "moved_units": float(moved),
        "completed": count,
    }
