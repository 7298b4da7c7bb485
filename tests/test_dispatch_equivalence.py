"""Property test: the sort-based dispatch lowering is semantically identical
to the paper-faithful scan lowering (same keeps, same kept positions, same
weights) — the §Perf optimization changes traffic, never routing."""

import jax
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.sched.moe_dispatch import dispatch


@given(
    st.integers(min_value=1, max_value=96),    # tokens
    st.integers(min_value=2, max_value=16),    # experts
    st.integers(min_value=1, max_value=4),     # k
    st.integers(min_value=0, max_value=6),     # skew
    st.integers(min_value=0, max_value=1000),  # seed
)
@settings(max_examples=30, deadline=None)
def test_sort_equals_scan(t, e, k, skew, seed):
    k = min(k, e)
    cap = max(2, (t * k) // e)
    logits = jax.random.normal(jax.random.key(seed), (t, e))
    logits = logits.at[:, 0].add(float(skew))
    a = dispatch(logits, k=k, capacity=cap, position_method="scan")
    b = dispatch(logits, k=k, capacity=cap, position_method="sort")
    np.testing.assert_array_equal(np.asarray(a.keep), np.asarray(b.keep))
    np.testing.assert_array_equal(np.asarray(a.expert_idx),
                                  np.asarray(b.expert_idx))
    # kept positions identical (overflow positions may differ — they are
    # re-routed or dropped identically either way)
    keep = np.asarray(a.keep)
    np.testing.assert_array_equal(np.asarray(a.slot_idx)[keep],
                                  np.asarray(b.slot_idx)[keep])
    np.testing.assert_allclose(np.asarray(a.weight), np.asarray(b.weight),
                               rtol=1e-6)
    assert int(a.aux["dropped"]) == int(b.aux["dropped"])
    assert int(a.aux["rebalanced"]) == int(b.aux["rebalanced"])
