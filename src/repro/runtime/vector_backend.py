"""Vectorized batched-scenario backend: hundreds of runtime seeds as one
``lax.scan``.

Parameter sweeps (cluster size, arrival rate, trigger constants, failure
patterns) need many scenario seeds; looping the event engine in Python is the
bottleneck. This backend runs B scenarios as one batched time-sliced
simulation on the accelerator:

* time advances in fixed ``dt`` slots; each node drains ``tau_i * dt`` work
  units per slot (fluid FIFO service),
* arrivals are placed by the paper's positional rule over deficit intervals —
  tasks are laid out one row of K per (scenario, slot), and each arrival's
  work position within its slot's stream comes from ONE batched exclusive
  prefix scan over those rows (``kernels.prefix_scan``, the paper's core
  operator); each scan step touches only its own slot's arrivals,
* an optional crossover trigger fires per scenario and slot exactly as in
  ``core.trigger``: imbalance above max(crossover, floor) redistributes
  queued work to fair shares and books the migrated volume.

``simulate_scalar`` is the numpy float64 reference with the same semantics
and operation order; ``simulate_batch`` must match it per seed (tested, with
the tolerance and its reason stated per test), which pins the backend's
meaning to something checkable. The event engine (``runtime.py``) remains
the full-fidelity discrete-task model; this backend is its fluid,
fixed-step counterpart for sweeps.

The batched engine runs in float32 on every platform (TPUs have no float64
units and Mosaic no 64-bit types). A position sums only its own slot's
earlier arrivals, never the difference of two sums over the whole task
stream: every sum the step takes spans at most one slot's arrivals or one
cluster.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..kernels import ops
from .metrics import nearest_rank
from .workload import draw_tasks, slot_rows

__all__ = ["VectorConfig", "BatchMetrics", "simulate_batch",
           "simulate_scalar", "sweep_seeds"]

_TINY = 1e-12


@dataclass(frozen=True)
class VectorConfig:
    """Static scenario parameters (hashable: used as a jit static arg)."""

    n_nodes: int
    n_slots: int
    dt: float = 1.0
    rebalance: bool = True          # crossover-trigger redistribution
    floor: float = 0.1              # trigger hysteresis floor
    p: float = 1e-3                 # comm step cost
    q: float = 1e-4                 # scan-add step cost
    t_task: float = 1e-4            # per-task placement cost
    packets_per_step: float = 64.0
    packets_per_unit: float = 2.0   # migration packets per work unit
    # FIFO-refined dispatch responses: a task's response also counts the
    # work of earlier same-slot arrivals routed to the same node (its own
    # dispatch wave's backlog), computed by the fused Pallas dispatch
    # kernel (``kernels.psts_dispatch``). Off by default — the plain fluid
    # response ignores intra-slot ordering entirely
    fifo_dispatch: bool = False
    # telemetry: emit per-slot probe series (queue snapshot, imbalance,
    # crossover, fire flag) as extra scan carry-outs. Static, so the
    # disabled variant compiles the probe outputs away entirely
    probe: bool = False

    @property
    def scan_steps(self) -> int:
        """1-D grid step count 2(n-1) (paper eq. 11) for the overhead term."""
        return 2 * (self.n_nodes - 1)


@dataclass(frozen=True)
class BatchMetrics:
    """Per-scenario metrics, shape (B,)."""

    mean_response: np.ndarray
    p99_response: np.ndarray
    makespan: np.ndarray
    trigger_fires: np.ndarray
    moved_units: np.ndarray
    completed: np.ndarray
    # probe series (cfg.probe only, else None): sampled once per slot at
    # the backlog point — after arrivals and the trigger's redistribution,
    # before service. Imbalance/crossover are the values the trigger
    # evaluated (pre-redistribution); an idle slot reads imbalance -1
    probe_queue: np.ndarray | None = None       # (B, T, n)
    probe_imbalance: np.ndarray | None = None   # (B, T)
    probe_crossover: np.ndarray | None = None   # (B, T)
    probe_fires: np.ndarray | None = None       # (B, T) bool


# ---------------------------------------------------------------------------
# The positional rule's owner lookup (same formula in both engines)
# ---------------------------------------------------------------------------

def _owner_np(src, frac):
    """Interval index of each ``frac`` in [0, 1) when the unit stream is cut
    into intervals proportional to ``src`` (the paper's positional rule).
    The cut points are the inclusive running sums of ``src`` and the query
    stays strictly below the last one, so a zero-width interval (a node
    with no deficit or no power) is never chosen, however the sums round."""
    edges = np.cumsum(src)
    x = np.minimum(frac * edges[-1], np.nextafter(edges[-1], 0.0))
    return np.searchsorted(edges, x, side="right")


# ---------------------------------------------------------------------------
# Scalar reference engine (numpy, one scenario)
# ---------------------------------------------------------------------------

def simulate_scalar(slot: np.ndarray, works: np.ndarray, powers: np.ndarray,
                    cfg: VectorConfig,
                    power_scale: np.ndarray | None = None) -> dict:
    """One scenario with the exact semantics of ``simulate_batch``.

    ``slot``: (M,) arrival slot per task (``n_slots`` = padding sentinel);
    ``works``: (M,) work units; ``powers``: (n,) node powers;
    ``power_scale``: optional (T, n) multiplier (0 = node down that slot).
    """
    slot = np.asarray(slot)
    works = np.asarray(works, dtype=np.float64)
    powers = np.asarray(powers, dtype=np.float64)
    T, n = cfg.n_slots, cfg.n_nodes
    scale = (np.ones((T, n)) if power_scale is None
             else np.asarray(power_scale, dtype=np.float64))
    valid = slot < T
    tot = np.bincount(slot[valid], weights=works[valid], minlength=T)
    cnt = np.bincount(slot[valid], minlength=T).astype(np.float64)

    queue = np.zeros(n)
    resp = np.zeros(works.shape[0])
    fires, moved, seen = 0, 0.0, 0.0
    backlog = np.zeros(T)
    probe_q = np.zeros((T, n)) if cfg.probe else None
    probe_imb = np.zeros(T) if cfg.probe else None
    probe_cross = np.zeros(T) if cfg.probe else None
    probe_fire = np.zeros(T, dtype=bool) if cfg.probe else None
    for t in range(T):
        idx = np.flatnonzero(slot == t)
        pw = powers * scale[t]
        pi = pw.sum()
        # -- arrivals: positional rule over deficit intervals
        if tot[t] > 0.0:
            w = works[idx]
            fair = pw / pi * (queue.sum() + tot[t])
            deficit = np.maximum(fair - queue, 0.0)
            src = deficit if deficit.sum() > 0.0 else pw
            # each task's midpoint in its slot's work stream, in [0, 1)
            frac = (np.cumsum(w) - w + 0.5 * w) / tot[t]
            owner = _owner_np(src, frac)
            backlog_ahead = 0.0
            if cfg.fifo_dispatch:
                # exclusive same-owner work prefix within the slot (the
                # FIFO backlog this dispatch wave builds in front of each
                # task) — reference semantics for the Pallas dispatch
                # kernel the batched path uses
                backlog_ahead = np.zeros(idx.size)
                acc = np.zeros(n)
                for i, k in enumerate(owner):
                    backlog_ahead[i] = acc[k]
                    acc[k] += w[i]
            resp[idx] = ((queue[owner] + backlog_ahead + w)
                         / np.maximum(pw[owner], _TINY))
            np.add.at(queue, owner, w)
            seen += cnt[t]
        # -- crossover trigger (fluid redistribution of queued work); the
        # probe reads the same formulas, so the trigger signal it exports
        # is exactly what the decision saw (the guarded max(., _TINY)
        # denominators are identical to the old t_bal > _TINY branch
        # whenever that branch ran)
        if cfg.rebalance or cfg.probe:
            w = queue.sum()
            t_bal = w / pi if pi > 0.0 else 0.0
            with np.errstate(divide="ignore", invalid="ignore"):
                ratio = np.where(pw > 0.0, queue / np.maximum(pw, _TINY),
                                 np.where(queue > _TINY, np.inf, 0.0))
            imb = ratio.max() / max(t_bal, _TINY) - 1.0
            fair_q = pw / max(pi, _TINY) * w
            excess = np.maximum(queue - fair_q, 0.0).sum()
            overhead = (cfg.scan_steps * (cfg.p + cfg.q)
                        + seen / n * cfg.t_task
                        + excess * cfg.packets_per_unit
                        / cfg.packets_per_step * cfg.p)
            cross = overhead / max(t_bal, _TINY)
            fire = (cfg.rebalance and t_bal > _TINY
                    and imb > max(cross, cfg.floor))
            if fire:
                queue = fair_q
                moved += excess
                fires += 1
            if cfg.probe:
                probe_q[t] = queue
                probe_imb[t] = imb
                probe_cross[t] = cross
                probe_fire[t] = fire
        # -- service (backlog sampled before draining, so a slot that both
        # receives and finishes work still counts as busy)
        backlog[t] = queue.sum()
        queue = np.maximum(queue - pw * cfg.dt, 0.0)

    count = float(cnt.sum())
    drained = np.flatnonzero(backlog > _TINY)
    out = {
        "mean_response": float(resp.sum() / count) if count else float("nan"),
        "p99_response": nearest_rank(resp[valid], 99.0),
        "makespan": float((drained[-1] + 1) * cfg.dt) if drained.size else 0.0,
        "trigger_fires": float(fires),
        "moved_units": float(moved),
        "completed": count,
    }
    if cfg.probe:
        out.update(probe_queue=probe_q, probe_imbalance=probe_imb,
                   probe_crossover=probe_cross, probe_fires=probe_fire)
    return out


def reference_gaps(got: dict, ref: dict, n_slots: int) -> dict:
    """Relative gaps of one batched scenario's metrics (``got``) to
    ``simulate_scalar``'s (``ref``); raises ``AssertionError`` naming every
    metric outside the float32 engine's tolerance:

    * ``completed`` and ``makespan`` equal: counts stay exact in float32
      (below 2**24) and the makespan is a slot index.
    * ``mean_response`` within 1e-2 and ``p99_response`` within 2e-2: a
      task's owner flips to the neighbouring interval where its midpoint
      lies within rounding of a cut (``_owner``), and the queues then drift
      apart by a task's work here and there.
    * ``trigger_fires``: where the imbalance sits near the crossover, one
      flipped decision changes the queues the next ones see, so after the
      first flip the two fire series are as alike as two independent runs
      (the float64 reference behaves the same under a one-ulp change of its
      inputs). Allowed: 2.5 standard deviations of the difference of two
      binomial counts, 2.5 * sqrt(2 T p (1 - p)) with p the reference's
      fire rate: exact when the trigger fires every slot or never.
    * moved units per fire within 2e-2: each fire moves the excess over
      fair shares, which float32 carries to a few ulps of the backlog.
    """
    gaps = {k: abs(got[k] - ref[k]) / max(abs(ref[k]), 1e-30)
            for k in ("mean_response", "p99_response")}
    per_fire = [d["moved_units"] / max(d["trigger_fires"], 1.0)
                for d in (got, ref)]
    gaps["moved_per_fire"] = (abs(per_fire[0] - per_fire[1])
                              / max(per_fire[1], 1e-30))
    bad = [k for k, tol in (("mean_response", 1e-2), ("p99_response", 2e-2),
                            ("moved_per_fire", 2e-2)) if gaps[k] > tol]
    bad += [k for k in ("completed", "makespan") if got[k] != ref[k]]
    p = ref["trigger_fires"] / n_slots
    if (abs(got["trigger_fires"] - ref["trigger_fires"])
            > 2.5 * np.sqrt(2.0 * n_slots * p * (1.0 - p))):
        bad.append("trigger_fires")
    if bad:
        raise AssertionError(f"batched vs simulate_scalar out of tolerance "
                             f"in {bad}: got={got} ref={ref}")
    return gaps


# ---------------------------------------------------------------------------
# Batched JAX engine
# ---------------------------------------------------------------------------

def _cut_points(src, frac):
    """Each row's cut points ``edges`` (B, n) and its queries ``x`` (B, M),
    as in ``_owner_np``."""
    edges = jnp.cumsum(src, axis=1)
    last = edges[:, -1:]
    return edges, jnp.minimum(frac * last, jnp.nextafter(last, 0.0))


def _owner_search(edges, x):
    """Binary search: ``searchsorted(side="right")`` of each row's queries."""
    return jax.vmap(lambda e, v: jnp.searchsorted(e, v, side="right"))(
        edges, x)


def _owner_count(edges, x):
    """The same owners as ``_owner_search``, as the number of cut points at
    or below each query: one fused compare and sum over the node axis,
    with no gather and no loop. Both are non-negative and finite here, so
    the plain ``<=`` orders them as ``searchsorted``'s comparator does.

    The TPU's reduction runs through the (8, 128) tiles of its output four
    at a time where their number allows, else two or one at a time: on a
    v5e, 8 rows of 2,944 queries (23 tiles) took five times as long as 8
    of 3,072 (24 tiles). So the queries are padded to a multiple of four
    tiles, and the padding's counts dropped."""
    rows, k = x.shape
    row_tiles = -(-rows // 8)
    lanes = 128 * (4 // math.gcd(4, row_tiles))
    xp = jnp.pad(x, ((0, 0), (0, -k % lanes)))
    count = jnp.sum(edges[:, :, None] <= xp[:, None, :], axis=1,
                    dtype=jnp.int32)
    return count[:, :k]


def _owner(src, frac):
    """``_owner_np`` batched over rows: src (B, n), frac (B, M). Rows
    without arrivals this slot are clipped into range (and masked later).

    The method follows the platform the program is lowered for; both give
    the same integers. A binary search does O(M log n) work in ceil(log2
    (n + 1)) rounds, each a gather of one cut point per query. On the CPU
    that is cheap and cache-friendly: there the count ran 20 to 60 times
    slower. On a TPU a gathered element costs about as much as 10**4
    elementwise compares, so the search is bound by its gathers; counting
    does O(M n) compares but keeps them on the vector units, fused, and
    nothing of size (B, M, n) reaches memory. On a v5e, 256 lookups of 32
    rows of 640 queries among 4,000 nodes took 654 ms by search and 34 ms
    by count."""
    edges, x = _cut_points(src, frac)
    owner = jax.lax.platform_dependent(edges, x, tpu=_owner_count,
                                       default=_owner_search)
    return jnp.clip(owner, 0, src.shape[1] - 1)


def _per_slot(slot: np.ndarray, works: np.ndarray, n_slots: int):
    """(B, M) task rows sorted by slot (``workload.slot_rows``) ->
    ``(works (B, T, K), cnt (B, T))``: slot t's arrivals of row b in
    ``works[b, t, :cnt[b, t]]``, in stream order, zero-padded. K is the
    largest slot's task count rounded up to the 128-lane width."""
    slot = np.asarray(slot)
    B = slot.shape[0]
    b, m = np.nonzero(slot < n_slots)
    s = slot[b, m]
    cnt = np.bincount(b * n_slots + s, minlength=B * n_slots).reshape(
        B, n_slots)
    K = -(-max(int(cnt.max(initial=0)), 1) // 128) * 128
    first = np.cumsum(cnt, axis=1) - cnt          # row index of each slot
    out = np.zeros((B, n_slots, K), np.float32)
    out[b, s, m - first[b, s]] = np.asarray(works)[b, m]
    return out, cnt.astype(np.int32)


@partial(jax.jit, static_argnames=("cfg",))
def _simulate_batch_jax(works, cnt, powers, scale, cfg: VectorConfig):
    B, T, K = works.shape
    n = cfg.n_nodes

    rows = jnp.arange(B)[:, None]
    # each task's work position within its slot's arrival stream: one
    # batched exclusive scan over all (scenario, slot) rows — the paper's
    # core operator, the Pallas prefix-scan kernel. Every row holds one
    # slot's arrivals, so no position is a difference of two long sums
    with jax.named_scope("prefix_scan"):
        mid = (ops.prefix_scan(works.reshape(B * T, K)).reshape(B, T, K)
               + 0.5 * works)
    tot = works.sum(axis=2)                               # (B, T)
    lanes = jnp.arange(K)[None, :]

    def step(carry, xs):
        queue, fires, moved, seen = carry
        t, work, mid_t, cnt_t = xs                        # (B, K), (B,)
        mask = lanes < cnt_t[:, None]                     # (B, K)
        # -- arrivals (the positional rule of _owner_np, batched)
        with jax.named_scope("deficit"):
            pw = powers * scale[t]                        # (B, n)
            pi = pw.sum(axis=1, keepdims=True)
            tot_t = tot[:, t][:, None]                    # (B, 1)
            fair = pw / pi * (queue.sum(axis=1, keepdims=True) + tot_t)
            deficit = jnp.maximum(fair - queue, 0.0)
            src = jnp.where(deficit.sum(axis=1, keepdims=True) > 0.0,
                            deficit, pw)
        with jax.named_scope("owner_lookup"):
            owner = _owner(src, mid_t / jnp.where(tot_t > 0.0, tot_t, 1.0))
        with jax.named_scope("owner_gather"):
            q_own = jnp.take_along_axis(queue, owner, axis=1)
            pw_own = jnp.take_along_axis(pw, owner, axis=1)
        backlog_ahead = 0.0
        if cfg.fifo_dispatch:
            # fused dispatch kernel: exclusive same-owner work prefix of
            # this slot's dispatch wave, all B scenarios in one grid
            with jax.named_scope("dispatch"):
                backlog_ahead, _ = ops.dispatch_work_prefix(
                    jnp.where(mask, owner, -1).astype(jnp.int32),
                    jnp.where(mask, work, 0.0), n_experts=n)
        with jax.named_scope("owner_gather"):
            resp = jnp.where(mask, (q_own + backlog_ahead + work)
                             / jnp.maximum(pw_own, _TINY), 0.0)
        with jax.named_scope("scatter_add"):
            queue = queue.at[rows, owner].add(jnp.where(mask, work, 0.0))
        seen = seen + cnt_t
        # -- crossover trigger (and/or the probe's trigger signal — same
        # formulas as simulate_scalar, see the note there)
        with jax.named_scope("trigger"):
            if cfg.rebalance or cfg.probe:
                w = queue.sum(axis=1, keepdims=True)
                t_bal = jnp.where(pi > 0.0, w / jnp.maximum(pi, _TINY), 0.0)
                ratio = jnp.where(pw > 0.0, queue / jnp.maximum(pw, _TINY),
                                  jnp.where(queue > _TINY, jnp.inf, 0.0))
                imb = ratio.max(axis=1, keepdims=True) \
                    / jnp.maximum(t_bal, _TINY) - 1.0
                fair_q = pw / jnp.maximum(pi, _TINY) * w
                excess = jnp.maximum(queue - fair_q, 0.0).sum(
                    axis=1, keepdims=True)
                overhead = (cfg.scan_steps * (cfg.p + cfg.q)
                            + seen[:, None] / n * cfg.t_task
                            + excess * cfg.packets_per_unit
                            / cfg.packets_per_step * cfg.p)
                cross = overhead / jnp.maximum(t_bal, _TINY)
                fire = (t_bal > _TINY) & (imb > jnp.maximum(cross, cfg.floor))
                if cfg.rebalance:
                    queue = jnp.where(fire, fair_q, queue)
                    moved = moved + jnp.where(fire[:, 0], excess[:, 0], 0.0)
                    fires = fires + fire[:, 0].astype(jnp.float32)
                else:
                    fire = jnp.zeros_like(fire)
        # -- service (backlog sampled before draining, as in simulate_scalar)
        with jax.named_scope("service"):
            busy = queue.sum(axis=1)
            queue_next = jnp.maximum(queue - pw * cfg.dt, 0.0)
        if cfg.probe:
            ys = (busy, resp, queue, imb[:, 0], cross[:, 0], fire[:, 0])
        else:
            ys = (busy, resp)
        return (queue_next, fires, moved, seen), ys

    carry0 = (jnp.zeros((B, n)), jnp.zeros(B), jnp.zeros(B), jnp.zeros(B))
    xs = (jnp.arange(T), works.transpose(1, 0, 2), mid.transpose(1, 0, 2),
          cnt.T.astype(jnp.float32))
    (_, fires, moved, _), ys = jax.lax.scan(step, carry0, xs)
    backlog, resp = ys[:2]                      # (T, B), (T, B, K)
    if cfg.probe:
        probe_queue, probe_imb, probe_cross, probe_fire = ys[2:]

    count = cnt.sum(axis=1).astype(jnp.float32)
    resp = resp.transpose(1, 0, 2)                          # (B, T, K)
    with jax.named_scope("summary"):
        mean = jnp.where(count > 0, resp.sum(axis=(1, 2))
                         / jnp.maximum(count, 1.0), jnp.nan)
    # nearest-rank p99 with padding pushed to +inf
    with jax.named_scope("p99_sort"):
        valid = lanes[None] < cnt[:, :, None]
        s = jnp.sort(jnp.where(valid, resp, jnp.inf).reshape(B, T * K),
                     axis=1)
        k = jnp.clip(jnp.ceil(0.99 * count).astype(jnp.int32), 1,
                     jnp.maximum(count.astype(jnp.int32), 1))
        p99 = jnp.where(
            count > 0, jnp.take_along_axis(s, (k - 1)[:, None], axis=1)[:, 0],
            jnp.nan)
    # makespan: last slot with backlog, +1 slot, in time units
    with jax.named_scope("summary"):
        busy = (backlog > _TINY).astype(jnp.int32)          # (T, B)
        last = (jnp.arange(T)[:, None] + 1) * busy
        makespan = last.max(axis=0).astype(jnp.float32) * cfg.dt
    out = (mean, p99, makespan, fires, moved, count)
    if cfg.probe:
        # scan stacks along the leading (time) axis; hand back batch-major
        out = out + (probe_queue.transpose(1, 0, 2),
                     probe_imb.T, probe_cross.T, probe_fire.T)
    return out


def device_args(slot: np.ndarray, works: np.ndarray, powers: np.ndarray,
                cfg: VectorConfig, power_scale: np.ndarray | None = None):
    """The batched program's operands ``(works (B, T, K), cnt (B, T),
    powers (B, n), scale (T, n))`` for (B, M) slot-sorted task rows, as
    ``simulate_batch`` passes them.

    Profiler spans: ``repro.vector.layout`` (the per-slot layout; its
    ``tasks`` are the arrivals laid out, ``lanes`` the B * T * K
    positions that hold them, ``K`` the lanes a slot) and
    ``repro.vector.transfer`` (the copies to the device)."""
    with jax.profiler.TraceAnnotation("repro.vector.layout") as span:
        works, cnt = _per_slot(slot, works, cfg.n_slots)
        span.set_metadata(tasks=int(cnt.sum()), lanes=works.size,
                          K=works.shape[2])
    powers = np.asarray(powers, dtype=np.float32)
    if powers.ndim == 1:
        powers = np.broadcast_to(powers, (works.shape[0], powers.shape[0]))
    scale = (np.ones((cfg.n_slots, cfg.n_nodes), np.float32)
             if power_scale is None else power_scale)
    with jax.profiler.TraceAnnotation("repro.vector.transfer"):
        return (jnp.asarray(works), jnp.asarray(cnt), jnp.asarray(powers),
                jnp.asarray(scale, dtype=jnp.float32))


def simulate_batch(slot: np.ndarray, works: np.ndarray, powers: np.ndarray,
                   cfg: VectorConfig,
                   power_scale: np.ndarray | None = None) -> BatchMetrics:
    """Run B scenarios in one batched call.

    ``slot``/``works``: (B, M); ``powers``: (n,) or (B, n);
    ``power_scale``: optional (T, n) shared up/down schedule.

    Profiler spans: ``repro.vector.run`` (the program's dispatch) and
    ``repro.vector.fetch`` (the wait for the device and the copy of the
    results to the host); ``device_args`` marks layout and transfer.
    """
    args = device_args(slot, works, powers, cfg, power_scale)
    with jax.profiler.TraceAnnotation("repro.vector.run"):
        out = _simulate_batch_jax(*args, cfg)
    with jax.profiler.TraceAnnotation("repro.vector.fetch"):
        out = tuple(map(np.asarray, out))
    mean, p99, makespan, fires, moved, count = out[:6]
    probes = (dict(zip(("probe_queue", "probe_imbalance",
                        "probe_crossover", "probe_fires"), out[6:]))
              if cfg.probe else {})
    return BatchMetrics(mean_response=mean, p99_response=p99,
                        makespan=makespan, trigger_fires=fires,
                        moved_units=moved, completed=count, **probes)


def sweep_seeds(process: str, seeds, powers, cfg: VectorConfig, *,
                power_scale: np.ndarray | None = None,
                **workload_kwargs) -> BatchMetrics:
    """Draw each seed's arrivals and works (``workload.draw_tasks``, which
    takes ``workload_kwargs``) and run the whole sweep in one batched call
    — the on-accelerator replacement for a Python loop over scenarios."""
    horizon = cfg.n_slots * cfg.dt
    draws = [draw_tasks(process, horizon=horizon, seed=int(s),
                        **workload_kwargs)[1:] for s in seeds]
    slot, works, _ = slot_rows(draws, cfg.dt, cfg.n_slots)
    return simulate_batch(slot, works, powers, cfg, power_scale=power_scale)
