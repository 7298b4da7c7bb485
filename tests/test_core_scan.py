"""Scan primitives: host, in-core JAX, and the cross-device ladder."""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import exclusive_scan, exclusive_scan_np, inclusive_scan_np


def test_exclusive_scan_np_definition():
    # paper Def. 3.1: (+, A) returns {0, a0, a0+a1, ...}
    a = np.array([5.0, 3.0, 1.0, 7.0])
    assert np.array_equal(exclusive_scan_np(a), [0, 5, 8, 9])


def test_exclusive_scan_np_2d_axis():
    a = np.arange(6, dtype=float).reshape(2, 3)
    out = exclusive_scan_np(a, axis=1)
    assert np.array_equal(out, [[0, 0, 1], [0, 3, 7]])
    out0 = exclusive_scan_np(a, axis=0)
    assert np.array_equal(out0, [[0, 0, 0], [0, 1, 2]])


def test_jax_matches_numpy():
    a = np.random.default_rng(0).uniform(size=(4, 9))
    np.testing.assert_allclose(
        np.asarray(exclusive_scan(jnp.asarray(a), axis=1)),
        exclusive_scan_np(a, axis=1), rtol=1e-6)


@given(st.lists(st.integers(min_value=0, max_value=1000), min_size=1,
                max_size=200))
@settings(max_examples=50, deadline=None)
def test_scan_properties(xs):
    a = np.array(xs, dtype=np.float64)
    exc = exclusive_scan_np(a)
    inc = inclusive_scan_np(a)
    # shift relation, first element zero, total preserved
    assert exc[0] == 0
    assert np.array_equal(exc + a, inc)
    assert inc[-1] == a.sum()
    # monotone for non-negative inputs
    assert (np.diff(exc) >= 0).all()


def test_axis_scan_ladder_multi_device():
    """The ppermute ladder needs >1 device; run it under 8 fake CPU devices
    in a subprocess so the main test process keeps a single device."""
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.scan import axis_exclusive_scan

mesh = jax.make_mesh((8,), ("x",))
vals = np.arange(1.0, 9.0)  # one value per device

def f(x):
    exc, tot = axis_exclusive_scan(x, "x", 8)
    return exc, tot

exc, tot = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                             out_specs=(P("x"), P("x"))))(vals)
want = np.concatenate([[0.0], np.cumsum(vals)[:-1]])
assert np.allclose(np.asarray(exc), want), (exc, want)
assert np.allclose(np.asarray(tot), vals.sum())
print("OK")
"""
    env = dict(**__import__("os").environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=__import__("os").path.dirname(
                              __import__("os").path.dirname(
                                  __import__("os").path.abspath(__file__))),
                          env=env, timeout=240)
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout
