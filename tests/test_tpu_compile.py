"""Ahead-of-time compiles for one TPU v5e chip, on a host without one.

The TPU compiler ships with libtpu and compiles for a chip that is only
described. These tests compile the sweep's kernels and the whole batched
program at the shapes of ``chip_smoke.py`` and check that each Pallas
kernel lowered to a Mosaic ``tpu_custom_call`` (not the interpreter), that
the program fits the chip's 16 GiB, and that its owner lookup gathers and
loops nothing. The topology is described inside a fixture, never at
import: only one process may hold libtpu, and test workers import every
test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.runtime.vector_backend import VectorConfig, _simulate_batch_jax

HBM_BYTES = 16 * 2**30
# chip_smoke.py: 32 seeds x 256 slots; 4,096 nodes with up to 3,328 tasks
# per slot (padded), and 128 nodes with up to 256 for fifo_dispatch
SEEDS, SLOTS = 32, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled.as_text()


@pytest.mark.parametrize("name,fn,shapes", [
    ("prefix_scan", ops.prefix_scan,
     [((SEEDS * SLOTS, 3328), jnp.float32)]),
    ("dispatch_work_prefix",
     lambda e, w: ops.dispatch_work_prefix(e, w, n_experts=128),
     [((SEEDS, 256), jnp.int32), ((SEEDS, 256), jnp.float32)]),
    ("dispatch_positions",
     lambda e, b: ops.dispatch_positions(e, b, n_experts=128),
     [((SEEDS * SLOTS,), jnp.int32), ((128,), jnp.int32)]),
])
def test_kernel_compiles_for_v5e(one_chip, name, fn, shapes):
    text = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text, name


@pytest.mark.parametrize("n_nodes,per_slot,fifo,kernels", [
    (4096, 3328, False, 1),  # prefix scan
    (128, 256, True, 2),     # prefix scan + dispatch work prefix
])
def test_sweep_program_compiles_for_v5e(one_chip, n_nodes, per_slot, fifo,
                                        kernels):
    cfg = VectorConfig(n_nodes=n_nodes, n_slots=SLOTS, fifo_dispatch=fifo)
    text = _compile(
        lambda w, c, p, s: _simulate_batch_jax(w, c, p, s, cfg), one_chip,
        ((SEEDS, SLOTS, per_slot), jnp.float32), ((SEEDS, SLOTS), jnp.int32),
        ((SEEDS, n_nodes), jnp.float32), ((SLOTS, n_nodes), jnp.float32))
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    lookup = [line for line in text.splitlines()
              if re.search(r'op_name="[^"]*/owner_lookup/', line)]
    assert lookup
    assert not [line for line in lookup
                if re.search(r"\s(gather|while)\(", line)]
