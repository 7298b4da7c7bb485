"""Sharding plan and dry-run machinery tests.

The multi-device pieces run in subprocesses with placeholder devices so the
main pytest process keeps a single CPU device (the production 512-device
sweep is exercised by launch/dryrun.py itself; here we validate the same
code paths at 4x2)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # subprocess model compiles; tier-1 fast subset skips

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 560):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    """The same smoke train step, sharded over a 4x2 mesh vs one device,
    produces the same loss (sharding must not change numerics)."""
    out = _run(r"""
import contextlib
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import REGISTRY
from repro.models import LM
from repro.models.common import logical_axis_rules
from repro.optim import AdamW, constant
from repro.train import init_state, make_train_step

cfg = REGISTRY['olmo-1b'].smoke()
lm = LM(cfg)
opt = AdamW()
step = make_train_step(lm, opt, constant(1e-3), remat=False)
state = init_state(lm, opt, jax.random.key(0))
tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
batch = {'tokens': tokens, 'labels': tokens}

# single device
s1, m1 = jax.jit(step)(state, batch)

# sharded
from jax.sharding import AxisType
mesh = jax.make_mesh((4, 2), ('data', 'model'),
                     axis_types=(AxisType.Auto,) * 2)
from repro.launch.shardings import (activation_rules, batch_pspecs,
                                    state_pspecs, named)
from repro.configs.base import SHAPES
rules = activation_rules(cfg, mesh)
state_shapes = jax.eval_shape(lambda: init_state(lm, opt, jax.random.key(0)))
st_sh = named(mesh, state_pspecs(state_shapes, cfg, mesh))
with jax.set_mesh(mesh), logical_axis_rules(rules):
    s2, m2 = jax.jit(step, in_shardings=(st_sh, None),
                     out_shardings=(st_sh, None))(state, batch)
d1 = float(m1['loss']); d2 = float(m2['loss'])
assert abs(d1 - d2) < 1e-3, (d1, d2)
g1 = float(m1['grad_norm']); g2 = float(m2['grad_norm'])
assert abs(g1 - g2) / g1 < 1e-2, (g1, g2)
print('OK', d1, d2)
""")
    assert "OK" in out


def test_dryrun_cell_records_roofline():
    """lower_cell on a smoke config over a small mesh yields a coherent
    record (memory, corrected counts, roofline terms)."""
    out = _run(r"""
import os
import jax, json
# patch the production mesh to the small test mesh
import repro.launch.mesh as mesh_mod
def small_mesh(*, multi_pod=False, ep=None):
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ('pod', 'data', 'model') if multi_pod else ('data', 'model')
    return mesh_mod._mk(shape, axes)
mesh_mod.make_production_mesh = small_mesh
import repro.launch.dryrun as dr
dr.make_production_mesh = small_mesh
import dataclasses
from repro.configs import REGISTRY, SHAPES
cfg = dataclasses.replace(REGISTRY['olmo-1b'].smoke(), n_layers=4)
shape = dataclasses.replace(SHAPES['train_4k'], seq_len=64, global_batch=8)
import repro.configs as C
SHAPES_backup = dict(SHAPES)
SHAPES['train_4k'] = shape
rec = dr.lower_cell('olmo-1b', 'train_4k', False, cfg=cfg)
r = rec['roofline']
assert rec['cost']['flops'] > 0
assert rec['corrected']['flops'] >= rec['cost']['flops'] * 0.9
assert r['compute_s'] > 0 and r['memory_s'] > 0
assert r['dominant'] in ('compute', 'memory', 'collective')
assert 0 < r['useful_compute_ratio'] < 10
print('OK', json.dumps(r['dominant']))
""")
    assert "OK" in out


def test_multi_pod_smoke_cell():
    out = _run(r"""
import jax, dataclasses
import repro.launch.mesh as mesh_mod
def small_mesh(*, multi_pod=False, ep=None):
    shape = (2, 2, 2) if multi_pod else (4, 2)
    axes = ('pod', 'data', 'model') if multi_pod else ('data', 'model')
    return mesh_mod._mk(shape, axes)
mesh_mod.make_production_mesh = small_mesh
import repro.launch.dryrun as dr
dr.make_production_mesh = small_mesh
from repro.configs import REGISTRY, SHAPES
cfg = dataclasses.replace(REGISTRY['granite-moe-1b-a400m'].smoke(),
                          n_layers=2)
SHAPES['decode_32k'] = dataclasses.replace(SHAPES['decode_32k'],
                                           seq_len=128, global_batch=8)
rec = dr.lower_cell('granite-moe-1b-a400m', 'decode_32k', True, cfg=cfg)
assert rec['mesh'] == '2x16x16' or rec['n_devices'] == 8
print('OK')
""")
    assert "OK" in out


def test_elastic_mesh_factorisation():
    from repro.launch.mesh import elastic_mesh  # noqa: F401 — import only
    # pure shape logic, no devices needed beyond 1: compute expected shapes
    code = r"""
from repro.launch.mesh import elastic_mesh
m = elastic_mesh(8, model_parallel=2)
assert m.devices.shape == (4, 2), m.devices.shape
m2 = elastic_mesh(6, model_parallel=2)
assert m2.devices.shape == (3, 2)
m3 = elastic_mesh(1, model_parallel=2)
assert m3.devices.size == 1
print('OK')
"""
    out = _run(code)
    assert "OK" in out
