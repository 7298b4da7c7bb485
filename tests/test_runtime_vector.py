"""Vectorized batched-scenario backend ≡ scalar reference engine: one
batched lax.scan over >= 100 seeds, in float32, against the float64 numpy
reference ``simulate_scalar``.

Tolerance on the small clusters below (8-16 nodes, <= 10^3 tasks per seed):
``RTOL``. Float32 rounds each operation by at most 6e-8; every metric is a
mean, a sum or one task's value over a few roundings per task, so the
arithmetic alone stays within a few ulps (measured: <= 3e-7). The discrete
decisions could move a metric by far more: one task's owner flipping to
the neighbouring interval moves the mean response by ~1e-3 here, one flipped
trigger fire moves ``trigger_fires`` by one. An owner flips only when its
midpoint lies within rounding of one of the n interval cuts, which is rare
at these n (``test_owner_rule_float32_flips`` counts it), so ``RTOL`` also
asserts that no decision flipped in these seeds. At deployment size flips
do happen; ``test_vector_matches_scalar_deployment_size`` holds the engine
to ``vector_backend.reference_gaps`` there.
"""

import numpy as np
import pytest

from repro.runtime import (
    VectorConfig,
    batch_slots,
    make_workload,
    simulate_batch,
    simulate_scalar,
    sweep_seeds,
)
from repro.runtime.vector_backend import (
    _cut_points,
    _owner,
    _owner_count,
    _owner_np,
    reference_gaps,
)

POWERS = np.array([3.0, 1.0, 7.0, 2.0, 5.0, 9.0, 4.0, 6.0,
                   2.0, 8.0, 1.0, 5.0, 3.0, 6.0, 4.0, 7.0])

FIELDS = ["mean_response", "p99_response", "makespan", "trigger_fires",
          "moved_units", "completed"]
RTOL = 1e-6  # see the module docstring


def _batch(process, n_seeds, cfg, **kw):
    wls = [make_workload(process, horizon=cfg.n_slots * cfg.dt, seed=s, **kw)
           for s in range(n_seeds)]
    return batch_slots(wls, cfg.dt, cfg.n_slots)


@pytest.mark.slow
def test_vector_matches_scalar_100_seeds():
    """>= 100 seeds in ONE batched call, each matching the scalar engine."""
    cfg = VectorConfig(n_nodes=16, n_slots=120, dt=1.0, rebalance=True,
                       floor=0.1)
    slot, works, counts = _batch("poisson", 112, cfg, rate=6.0)
    assert works.shape[0] == 112
    bm = simulate_batch(slot, works, POWERS, cfg)
    for i in range(works.shape[0]):
        sm = simulate_scalar(slot[i], works[i], POWERS, cfg)
        for k in FIELDS:
            np.testing.assert_allclose(getattr(bm, k)[i], sm[k], rtol=RTOL,
                                       err_msg=f"seed {i}, {k}")


def test_vector_matches_scalar_with_failures():
    cfg = VectorConfig(n_nodes=16, n_slots=80, dt=1.0, rebalance=True,
                       floor=0.1)
    slot, works, _ = _batch("bursty", 16, cfg, rate_hi=8.0)
    scale = np.ones((cfg.n_slots, cfg.n_nodes))
    scale[20:50, 3] = 0.0   # node 3 down, then rejoining
    scale[35:60, 9] = 0.0
    bm = simulate_batch(slot, works, POWERS, cfg, power_scale=scale)
    for i in range(0, 16, 3):
        sm = simulate_scalar(slot[i], works[i], POWERS, cfg,
                             power_scale=scale)
        for k in FIELDS:
            np.testing.assert_allclose(getattr(bm, k)[i], sm[k], rtol=RTOL,
                                       err_msg=f"seed {i}, {k}")


def test_vector_matches_scalar_no_rebalance():
    cfg = VectorConfig(n_nodes=8, n_slots=60, dt=0.5, rebalance=False)
    slot, works, _ = _batch("diurnal", 8, cfg, rate_mean=4.0)
    bm = simulate_batch(slot, works, POWERS[:8], cfg)
    assert (bm.trigger_fires == 0).all()
    assert (bm.moved_units == 0).all()
    for i in range(8):
        sm = simulate_scalar(slot[i], works[i], POWERS[:8], cfg)
        for k in FIELDS:
            np.testing.assert_allclose(getattr(bm, k)[i], sm[k], rtol=RTOL)


def test_vector_matches_scalar_fifo_dispatch():
    """The fused dispatch kernel's FIFO response refinement (same-slot
    same-owner work prefix) matches the scalar reference per seed — and
    actually changes the response metrics it refines."""
    cfg = VectorConfig(n_nodes=8, n_slots=60, dt=1.0, fifo_dispatch=True)
    slot, works, _ = _batch("poisson", 12, cfg, rate=6.0)
    bm = simulate_batch(slot, works, POWERS[:8], cfg)
    for i in range(12):
        sm = simulate_scalar(slot[i], works[i], POWERS[:8], cfg)
        for k in FIELDS:
            np.testing.assert_allclose(getattr(bm, k)[i], sm[k], rtol=RTOL,
                                       err_msg=f"seed {i}, {k}")
    plain = simulate_batch(
        slot, works, POWERS[:8],
        VectorConfig(n_nodes=8, n_slots=60, dt=1.0))
    # FIFO refinement only ever adds backlog in front of a task
    assert (bm.mean_response >= plain.mean_response - 1e-12).all()
    assert (bm.mean_response > plain.mean_response).any()
    # queue evolution is untouched: the flag refines responses only
    np.testing.assert_allclose(bm.makespan, plain.makespan)
    np.testing.assert_allclose(bm.moved_units, plain.moved_units)


def test_trigger_floor_hysteresis_in_vector_backend():
    """Same hysteresis law as the event engine: fires monotone in floor."""
    base = dict(n_nodes=16, n_slots=100, dt=1.0, rebalance=True,
                p=1e-6, q=1e-7, t_task=1e-7)
    slot, works, _ = _batch("bursty",
                            4, VectorConfig(floor=0.0, **base), rate_hi=8.0)
    fires = {}
    for floor in [0.0, 0.5, 1e9]:
        bm = simulate_batch(slot, works, POWERS,
                            VectorConfig(floor=floor, **base))
        fires[floor] = bm.trigger_fires.sum()
    assert fires[0.0] > 0
    assert fires[1e9] == 0
    assert fires[0.0] >= fires[0.5] >= fires[1e9]


def test_sweep_seeds_one_call():
    cfg = VectorConfig(n_nodes=16, n_slots=60, dt=1.0)
    bm = sweep_seeds("poisson", range(32), POWERS, cfg, rate=4.0)
    assert bm.mean_response.shape == (32,)
    assert np.isfinite(bm.mean_response).all()
    assert (bm.completed > 0).all()
    # distinct seeds give distinct scenarios
    assert len(np.unique(bm.mean_response)) > 16


def test_rebalance_rescues_stranded_work():
    """In the fluid model the trigger's clearest win is failures: a dead
    node's backlog is stranded (infinite imbalance, as in core.trigger)
    until a rebalance redistributes it. Without rebalancing the backlog
    never drains and the makespan is censored at the horizon."""
    base = dict(n_nodes=16, n_slots=150, dt=1.0, floor=0.1)
    # heavy bursts, arrivals stop at slot 60; slots 60..150 are pure drain
    wls = [make_workload("bursty", horizon=60.0, seed=s, rate_lo=2.0,
                         rate_hi=25.0, sojourn_lo=10.0, sojourn_hi=8.0,
                         work_mean=6.0)
           for s in range(12)]
    slot, works, _ = batch_slots(wls, 1.0, 150)
    scale = np.ones((150, 16))
    scale[30:, 5] = 0.0   # a fast node dies at slot 30 and never returns
    on = simulate_batch(slot, works, POWERS,
                        VectorConfig(rebalance=True, **base),
                        power_scale=scale)
    off = simulate_batch(slot, works, POWERS,
                         VectorConfig(rebalance=False, **base),
                         power_scale=scale)
    # most seeds have backlog stranded on node 3 at the horizon
    assert (off.makespan >= 149.0).mean() >= 0.5, off.makespan
    assert on.makespan.mean() < off.makespan.mean() - 10.0
    assert (on.trigger_fires >= 1).all()


@pytest.mark.parametrize("n,max_rate", [(16, 1e-4), (4096, 1e-3)])
def test_owner_rule_float32_flips(n, max_rate):
    """How often float32 moves a task to another node than float64 does,
    on identical inputs: only where its midpoint lies within rounding of
    an interval cut (measured 0 at 16 nodes, 8e-5 at 4,096), and then only
    to the neighbouring interval of nonzero width. A zero-width interval (a
    node with no deficit or no power) is never chosen."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(n)
    rows, tasks = 64, 3000
    pw = rng.integers(1, 11, size=(rows, n)).astype(np.float32)
    # deficit-like interval widths, about a third of them exactly zero
    src = np.maximum(pw * (3.0 - rng.exponential(3.0, size=(rows, n))),
                     0.0).astype(np.float32)
    w = rng.uniform(0.0, 12.0, size=(rows, tasks))
    frac = ((np.cumsum(w, 1) - 0.5 * w) / w.sum(1, keepdims=True)
            ).astype(np.float32)
    got = np.asarray(jax.jit(_owner)(jnp.asarray(src), jnp.asarray(frac)))
    want = np.stack([_owner_np(src[r].astype(np.float64),
                               frac[r].astype(np.float64))
                     for r in range(rows)])
    assert (src[np.arange(rows)[:, None], got] > 0).all()
    assert (src[np.arange(rows)[:, None], want] > 0).all()
    flips = np.argwhere(got != want)
    assert len(flips) <= max_rate * got.size, len(flips)
    for r, i in flips:
        lo, hi = sorted((got[r, i], want[r, i]))
        assert (src[r, lo:hi + 1] > 0).sum() == 2  # neighbours


def _owner_case(name):
    """(edges, x) of one row batch, built to tell a count of cut points
    from a binary search wherever the two could part."""
    import jax.numpy as jnp
    rng = np.random.default_rng(sum(map(ord, name)))

    def midpoints(rows, k):
        w = rng.uniform(0.0, 12.0, size=(rows, k))
        return ((np.cumsum(w, 1) - 0.5 * w) / w.sum(1, keepdims=True)
                ).astype(np.float32)

    def widths(rows, n, zeros=0.4):
        src = rng.uniform(0.0, 5.0, size=(rows, n))
        return np.where(rng.uniform(size=(rows, n)) < zeros, 0.0,
                        src).astype(np.float32)

    if name == "on_cut":
        # integer widths: the cut points are exact, and so are the queries
        # placed on them and one ulp either side
        src = rng.integers(0, 4, size=(4, 300)).astype(np.float32)
        edges = np.cumsum(src, axis=1)
        x = edges[:, rng.integers(0, 300, size=256)]
        x = np.concatenate([x, np.nextafter(x, np.float32(0.0)),
                            np.nextafter(x, np.float32(np.inf))], axis=1)
        return jnp.asarray(edges), jnp.asarray(x)
    if name == "zero_runs":
        src = widths(4, 4000)
        src[:, :37] = src[:, -41:] = src[:, 1000:1200] = 0.0
        frac = midpoints(4, 640)
        frac[:, 0], frac[:, -1] = 0.0, 1.0
    elif name == "no_arrivals":
        # a slot without arrivals puts every query at 0 (mid 0 over 1);
        # at frac 1 the query sits one ulp below the top cut point
        src = widths(4, 4000)
        frac = np.repeat(np.array([[0.0], [1.0], [0.0], [1.0]], np.float32),
                         128, axis=1)
    elif name == "padding":
        # padding lanes sit at frac 1, past the row's last task
        src = widths(4, 4000)
        frac = np.concatenate([midpoints(4, 100), np.ones((4, 156))],
                              axis=1).astype(np.float32)
    elif name == "all_down":
        # pi = 0: the widths fall back to the powers, all zero
        src = np.zeros((4, 4000), np.float32)
        frac = midpoints(4, 640)
    elif name == "n1":
        src = np.array([[2.5], [0.0], [1.0], [7.0]], np.float32)
        frac = midpoints(4, 128)
    else:                                   # "n130": not a lane multiple
        src = widths(4, 130)
        frac = midpoints(4, 384)
    return _cut_points(jnp.asarray(src), jnp.asarray(frac))


@pytest.mark.parametrize("case", ["on_cut", "zero_runs", "no_arrivals",
                                  "padding", "all_down", "n1", "n130"])
def test_owner_count_matches_binary_search(case):
    """The TPU's owner lookup, a count of cut points at or below each
    query, gives ``searchsorted(side="right")``'s integers element for
    element."""
    import jax
    import jax.numpy as jnp
    edges, x = _owner_case(case)
    want = np.asarray(jax.vmap(lambda e, v: jnp.searchsorted(
        e, v, side="right", method="scan"))(edges, x))
    got = np.asarray(jax.jit(_owner_count)(edges, x))
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_vector_matches_scalar_deployment_size():
    """4,096 nodes (integer powers 1-10) at 0.8 load over 256 slots, about
    7.7e5 tasks per seed — the chip smoke test's sweep, here on two seeds.
    Owner flips happen at this size, so the engine is held to
    ``reference_gaps`` (its docstring gives each bound's reason); the
    measured gaps on the CPU are <= 1e-4 (mean), <= 5e-4 (p99), <= 5e-5
    (moved per fire), with makespan, completions and fires exact."""
    from repro import lab
    cluster = lab.ClusterSpec(n_nodes=4096, power_low=1, power_high=10)
    rate = 0.8 * float(cluster.resolve_powers().sum()) / 6.0
    base = lab.Scenario(
        cluster=cluster,
        workload=lab.WorkloadSpec(process="poisson", horizon=256.0,
                                  work_mean=6.0, params={"rate": rate}),
        policy=lab.PolicySpec(name="psts"))
    scs = lab.expand_grid(base, {"seed": range(2)})
    slot, works, powers, cfg, scale = lab.get_backend("batched").compile(
        scs, 1.0)
    assert works.shape[1] > 7e5
    bm = simulate_batch(slot, works, powers, cfg, power_scale=scale)
    for i in range(2):
        ref = simulate_scalar(slot[i], works[i], powers, cfg,
                              power_scale=scale)
        reference_gaps({k: float(getattr(bm, k)[i]) for k in FIELDS}, ref,
                       cfg.n_slots)
