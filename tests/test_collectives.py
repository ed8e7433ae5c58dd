"""Overlapped-collective-matmul tests.

These need >1 device, and the XLA device count is locked at first jax
init — so each test runs a small script in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the conftest-free
pattern the brief requires: smoke tests see 1 device, only these scripts
see 8)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_script(body: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    script = (
        "import jax, numpy as np, jax.numpy as jnp\n"
        "from jax.sharding import NamedSharding, PartitionSpec as P\n"
        "from repro.parallel import collectives as C\n"
        "mesh = jax.make_mesh((2, 4), ('data', 'model'),\n"
        "    axis_types=(jax.sharding.AxisType.Auto,) * 2)\n"
        + body)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_ag_matmul_matches_dense():
    out = run_script("""
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
xs = jax.device_put(x, NamedSharding(mesh, P(None, 'model')))
ws = jax.device_put(w, NamedSharding(mesh, P(None, 'model')))
y = C.ag_matmul(xs, ws, mesh=mesh, axis='model')
np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                           rtol=1e-5, atol=1e-5)
print('ag ok', y.shape)
""")
    assert "ag ok" in out


@pytest.mark.slow
def test_matmul_rs_matches_dense():
    out = run_script("""
rng = np.random.default_rng(1)
x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
xs = jax.device_put(x, NamedSharding(mesh, P(None, 'model')))
ws = jax.device_put(w, NamedSharding(mesh, P('model', None)))
y = C.matmul_rs(xs, ws, mesh=mesh, axis='model')
np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                           rtol=1e-5, atol=1e-5)
print('rs ok', y.shape)
""")
    assert "rs ok" in out


@pytest.mark.slow
def test_overlap_hlo_has_permutes_not_allgather():
    """The point of the decomposition: the compiled HLO contains
    collective-permute ring hops interleaved with per-panel dots, not a
    monolithic all-gather before one big dot."""
    out = run_script("""
xs = jax.ShapeDtypeStruct((16, 64), jnp.float32)
ws = jax.ShapeDtypeStruct((64, 32), jnp.float32)
f = jax.jit(lambda x, w: C.ag_matmul(x, w, mesh=mesh, axis='model'),
            in_shardings=(NamedSharding(mesh, P(None, 'model')),
                          NamedSharding(mesh, P(None, 'model'))))
txt = f.lower(xs, ws).compile().as_text()
assert 'collective-permute' in txt, 'no ring hops found'
print('n_permute_lines', sum('collective-permute(' in l
                             for l in txt.splitlines()))
""")
    assert "n_permute_lines" in out


@pytest.mark.slow
def test_sharded_train_step_runs_on_8_devices():
    """End-to-end SPMD integration: one real train step on a 2x4 mesh
    with FSDP+TP shardings actually executing (not just lowering)."""
    out = run_script("""
from repro.models import model_zoo
from repro.configs.base import TrainConfig
from repro.runtime import train_loop
cfg = model_zoo.reduced_config(model_zoo.get_config('deepseek-7b'))
import dataclasses
cfg = dataclasses.replace(cfg, d_model=64, num_heads=4, num_kv_heads=2,
                          head_dim=16, d_ff=128, vocab_size=128)
tc = TrainConfig(steps=1, warmup_steps=0, learning_rate=1e-3)
step = train_loop.make_train_step(cfg, tc, mesh, donate=False)
state = jax.device_put(train_loop.init_state(cfg, tc),
                       train_loop.state_shardings(
                           train_loop.abstract_state(cfg, tc), mesh))
rng = np.random.default_rng(0)
batch = {'inputs': jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32),
         'labels': jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32)}
new_state, metrics = step(state, batch)
assert np.isfinite(float(metrics['loss']))
print('spmd step ok', float(metrics['loss']))
""")
    assert "spmd step ok" in out
