"""PSTS MoE dispatch: capacity invariants, paper-semantics, and the headline
claim — rebalancing beats dropping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.sched.moe_dispatch import dispatch, router_aux_loss


def _logits(t, e, seed=0, skew=0.0):
    """skew > 0 concentrates routing on expert 0 (hot-expert regime)."""
    base = jax.random.normal(jax.random.key(seed), (t, e))
    hot = jnp.zeros((e,)).at[0].set(skew)
    return base + hot[None, :]


def _slot_matrix(res):
    """(E, C) occupancy count from the index form."""
    e = res.n_experts
    occ = np.zeros((e, res.capacity), dtype=int)
    ei = np.asarray(res.expert_idx)
    si = np.asarray(res.slot_idx)
    kp = np.asarray(res.keep)
    for t in range(ei.shape[0]):
        for s in range(ei.shape[1]):
            if kp[t, s]:
                occ[ei[t, s], si[t, s]] += 1
    return occ


@pytest.mark.parametrize("rebalance", [False, True])
def test_capacity_never_exceeded_and_slots_unique(rebalance):
    res = dispatch(_logits(64, 4, skew=3.0), k=2, capacity=16,
                   rebalance=rebalance)
    occ = _slot_matrix(res)
    assert occ.max() <= 1, "two tokens share one expert slot"
    assert occ.sum(axis=1).max() <= 16


def test_rebalance_eliminates_drops_when_capacity_suffices():
    """Total capacity >= total demand: PSTS re-routes every overflow token
    (the paper's receivers absorb the senders' excess); plain routing drops."""
    logits = _logits(64, 4, skew=4.0)
    plain = dispatch(logits, k=2, capacity=32, rebalance=False)
    psts = dispatch(logits, k=2, capacity=32, rebalance=True)
    assert int(plain.aux["dropped"]) > 0
    assert int(psts.aux["dropped"]) == 0
    assert int(psts.aux["rebalanced"]) == int(plain.aux["dropped"])


def test_rebalanced_tokens_go_to_underloaded_experts():
    logits = _logits(32, 4, skew=5.0)
    res = dispatch(logits, k=1, capacity=16, rebalance=True)
    occ = _slot_matrix(res).sum(axis=1)
    # expert 0 saturated; the overflow spread into the others' free slots
    assert occ[0] == 16
    assert occ.sum() == 32


def test_weights_normalised_and_from_probs():
    logits = _logits(16, 4, seed=2)
    res = dispatch(logits, k=2, capacity=16, rebalance=True)
    w = np.asarray(res.weight * res.keep)
    sums = w.sum(axis=1)
    np.testing.assert_allclose(sums[sums > 0], 1.0, rtol=1e-5)


def test_slot_to_token_roundtrip():
    logits = _logits(24, 4, seed=3)
    res = dispatch(logits, k=2, capacity=16)
    tok, valid = res.slot_to_token()
    ei = np.asarray(res.expert_idx)
    si = np.asarray(res.slot_idx)
    kp = np.asarray(res.keep)
    for t in range(24):
        for s in range(2):
            if kp[t, s]:
                assert valid[ei[t, s], si[t, s]]
                assert tok[ei[t, s], si[t, s]] == t


def test_dense_tensors_match_index_form():
    logits = _logits(24, 4, seed=4)
    res = dispatch(logits, k=2, capacity=16)
    d, c = res.dense()
    assert d.shape == (24, 4, 16)
    # each kept (t,e,c) triple appears exactly once
    occ = _slot_matrix(res)
    np.testing.assert_array_equal(np.asarray(d.sum(axis=0)), occ)
    # combine sums to the per-token normalised weight mass
    np.testing.assert_allclose(np.asarray(c.sum(axis=(1, 2))),
                               np.asarray((res.weight * res.keep).sum(1)),
                               rtol=1e-5)


def test_paper_mapping_positional_stream():
    """With k=1 and every token on expert 0, the overflow stream fills the
    receivers' intervals in exclusive-scan order — Table 5's rule."""
    t = 12
    logits = jnp.full((t, 3), -10.0).at[:, 0].set(10.0)
    res = dispatch(logits, k=1, capacity=4, rebalance=True)
    ei = np.asarray(res.expert_idx[:, 0])
    # first 4 tokens keep expert 0; next 4 go to expert 1; last 4 to expert 2
    assert list(ei) == [0] * 4 + [1] * 4 + [2] * 4
    si = np.asarray(res.slot_idx[:, 0])
    assert list(si) == [0, 1, 2, 3] * 3


@given(st.integers(1, 64), st.integers(2, 8), st.integers(1, 2),
       st.integers(0, 100))
@settings(max_examples=40, deadline=None)
def test_dispatch_invariants(t, e, k, seed):
    cap = max(2, (t * k) // e)
    res = dispatch(_logits(t, e, seed=seed), k=k, capacity=cap)
    occ = _slot_matrix(res)
    assert occ.max() <= 1
    kp = np.asarray(res.keep)
    total_kept = kp.sum()
    assert total_kept <= e * cap
    # conservation: kept + dropped == t*k
    assert total_kept + int(res.aux["dropped"]) == t * k
    # expert indices in range
    assert np.asarray(res.expert_idx).max() < e


def test_router_aux_loss_prefers_balance():
    t, e = 256, 8
    balanced = jax.random.normal(jax.random.key(0), (t, e)) * 0.01
    skewed = jnp.zeros((t, e)).at[:, 0].set(8.0)
    assert float(router_aux_loss(balanced, 2)) < \
        float(router_aux_loss(skewed, 2))


def test_dispatch_jits_and_differentiates():
    logits = _logits(32, 4, seed=9)

    @jax.jit
    def f(lg):
        res = dispatch(lg, k=2, capacity=16)
        return (res.weight * res.keep).sum()

    g = jax.grad(f)(logits)
    assert jnp.isfinite(g).all()
    assert float(jnp.abs(g).sum()) > 0
